"""The fold-stacked C grid of ``GridSearchCV``: over a resident X split by
``KFold``, every (fold, C) model is one block of ONE stacked L-BFGS program
over the one design (fold ids, no fold copies), scored by ONE program.
Held here against a plain per-(fold, C) float64 reference that fits real
fold copies by Newton's method: every block's coefficients, every test
score (but for rows at the decision boundary), the winner; then the paths
a search takes and says it took, its spans, and the out-of-memory rule."""

import numpy as np
import pytest

from dask_ml_tpu import config
from dask_ml_tpu import observability as obs
from dask_ml_tpu.datasets import make_classification
from dask_ml_tpu.linear_model import LogisticRegression
from dask_ml_tpu.model_selection import GridSearchCV, KFold, ShuffleSplit
from dask_ml_tpu.model_selection import _search
from dask_ml_tpu.parallel.sharded import ShardedArray

CS = [1e-3, 1e-1, 1.0, 1e2]
TOL = 1e-6
NEAR = 1e-3          # |eta| under this: either label is right


@pytest.fixture(scope="module")
def data():
    X, y = make_classification(n_samples=6000, n_features=16, n_informative=8,
                               flip_y=0.05, random_state=0)
    return X, y, X.to_numpy().astype(np.float64), y.to_numpy()


def _newton(X, y, lam, steps=30):
    """Plain float64 Newton on mean NLL + lam / 2 ||coef||^2 (intercept
    unpenalised, last)."""
    X1 = np.c_[X, np.ones(len(X))]
    reg = np.r_[np.full(X.shape[1], lam), 0.0]
    b = np.zeros(X1.shape[1])
    for _ in range(steps):
        p = 1.0 / (1.0 + np.exp(-X1 @ b))
        g = X1.T @ (p - y) / len(y) + reg * b
        H = (X1 * (p * (1 - p))[:, None]).T @ X1 / len(y) + np.diag(reg)
        b = b - np.linalg.solve(H, g)
    return b


def _reference(Xh, yh, splits, Cs):
    """(betas (K, F, d + 1), test hits (K, F), near-tie rows (K, F)) from
    real fold copies."""
    K, F = len(Cs), len(splits)
    betas = np.zeros((K, F, Xh.shape[1] + 1))
    hits = np.zeros((K, F), np.int64)
    near = np.zeros((K, F), np.int64)
    for f, (tr, te) in enumerate(splits):
        for c, C in enumerate(Cs):
            b = _newton(Xh[tr], yh[tr], 1.0 / (C * len(tr)))
            betas[c, f] = b
            eta = Xh[te] @ b[:-1] + b[-1]
            hits[c, f] = np.sum((eta > 0) == (yh[te] > 0.5))
            near[c, f] = np.sum(np.abs(eta) < NEAR)
    return betas, hits, near


def _search_of(cv=None, **kw):
    return GridSearchCV(LogisticRegression(solver="lbfgs", tol=TOL,
                                           max_iter=300),
                        {"C": CS}, cv=cv, **kw)


@pytest.mark.parametrize("shuffle", [False, True],
                         ids=["contiguous", "shuffled"])
def test_fold_ids_match_real_fold_copies(data, shuffle):
    X, y, Xh, yh = data
    cv = KFold(5, shuffle=shuffle, random_state=7) if shuffle else None
    s = _search_of(cv).fit(X, y)
    info = s.search_info_
    assert info["path"] == "stacked-folds" and info["fold_copies"] == 0
    assert info["n_models"] == 20 and info["intercept"] == "scalar"
    splits = list((cv or KFold(5)).split(Xh))
    betas, hits, near = _reference(Xh, yh, splits, CS)
    # every block at the reference's optimum, to the solver's tolerance
    np.testing.assert_allclose(info["betas"], betas, atol=2e-3)
    n_test = np.asarray([len(te) for _, te in splits])
    got = np.stack([s.cv_results_[f"split{f}_test_score"]
                    for f in range(5)], axis=1) * n_test
    assert np.all(np.abs(np.rint(got) - hits) <= near)
    ref_means = (hits / n_test).mean(axis=1)
    ties = np.flatnonzero(ref_means >= ref_means.max() - s.tie_tol)
    assert s.best_params_ == {"C": CS[ties[0]]}


def test_one_program_one_root_and_the_refit_inside(data):
    X, y, _, _ = data
    obs.reset_recent_spans()
    before = {r["program"]: r["calls"] for r in obs.programs_snapshot()}
    seen = []
    obs.add_span_observer(seen.append)
    try:
        with config.set(obs_programs=True):
            s = _search_of().fit(X, y)
    finally:
        obs.remove_span_observer(seen.append)
    ran = {r["program"]: r["calls"] - before.get(r["program"], 0)
           for r in obs.programs_snapshot()}
    assert ran["glm.lbfgs_lam_grid"] == 1 and ran["glm.grid_score"] == 1
    assert ran["search.fold_ids"] == 1 and ran["glm.lbfgs"] == 1
    # the grid's label scan (and its cast of X, where the design is
    # bf16: not on the CPU), the refit's one prepare
    cast = s.search_info_["fit_dtype"] == "bfloat16"
    assert ran["glm.prepare"] == 2 + cast
    ring = obs.recent_spans()
    (root,) = [r for r in ring if r["parent_id"] is None]
    assert root["span"] == "fit" and root["component"] == "GridSearchCV"
    assert (root["n_models"], root["fold_copies"], root["path"]) == (
        20, 0, "stacked-folds")
    kids = [r for r in ring if r["parent_id"] == root["span_id"]]
    assert [r["span"] for r in kids] == [
        "fit.validate", "fit.folds", "fit.prepare", "fit.solve", "fit.score",
        "fit.refit", "fit.finish"]
    assert len(ring) == 8                       # flat: nothing deeper
    solve = kids[3]
    assert solve["n_models"] == 20 and solve["n_evals"] > solve["n_iter"]
    assert solve["n_iter_min"] <= solve["n_iter_max"] == solve["n_iter"]
    assert kids[1]["fold_copies"] == 0 and kids[1]["fold_id_bytes"] > 0
    nested = {r["span"]: r for r in kids[5]["nested"]}
    assert set(nested) == {"fit", "fit.validate", "fit.prepare", "fit.solve",
                           "fit.finish"}
    assert nested["fit"]["component"] == "LogisticRegression"
    assert nested["fit.solve"]["n_evals"] == s.best_estimator_.solver_info_[
        "n_evals"]
    # observers see the refit's records as their own, under fit.refit
    (fit,) = [r for r in seen if r.get("component") == "LogisticRegression"]
    assert fit["parent_id"] == kids[5]["span_id"]
    assert fit["root_id"] == root["span_id"]
    assert len(seen) == 8 + len(nested)
    # the refit's ledger counts in its phase and in the root
    assert kids[5]["dispatches"] == 2
    assert root["dispatches"] == sum(ran.values())
    # (the row mask's program runs once per mesh and row count)
    assert sum(v for k, v in ran.items() if k != "sharded.row_mask") \
        == 6 + cast
    obs.reset_recent_spans()


def test_more_than_two_classes_stop_before_the_cast(data, monkeypatch):
    """The stacked path learns a target is multiclass from the label scan
    alone, and leaves X uncast for the one-vs-rest arm's fold copies."""
    from dask_ml_tpu.models import glm

    X, y, _, yh = data
    y3 = ShardedArray.from_array(
        (yh + (np.arange(len(yh)) % 3 == 0)).astype(np.float32), mesh=X.mesh)
    seen = []
    real = glm._prepare_fit

    def recorder(Xd, *a, **k):
        seen.append(Xd is None)
        return real(Xd, *a, **k)

    monkeypatch.setattr(glm, "_prepare_fit", recorder)
    prep = LogisticRegression(solver="lbfgs")._grid_prepare(
        X, y3, binary_only=True)
    assert prep.multiclass and prep.data is None and seen == [True]
    s = _search_of().fit(X, y3)
    assert s.search_info_["path"] != "stacked-folds"
    assert s.search_info_["why"] == "more than two classes"


def test_shuffle_split_keeps_fold_copies(data):
    X, y, _, _ = data
    s = _search_of(ShuffleSplit(3, test_size=0.2, random_state=0)).fit(X, y)
    info = s.search_info_
    assert info["path"] == "fold-copies" and info["fold_copies"] == 6
    assert "ShuffleSplit" in info["why"]
    assert np.isfinite(s.best_score_)


def test_other_scorers_score_one_gathered_fold_at_a_time(data):
    X, y, _, _ = data
    kw = dict(scoring="roc_auc", return_train_score=True)
    fast = _search_of(**kw).fit(X, y)
    assert fast.search_info_["path"] == "stacked-folds"
    assert fast.search_info_["scored"] == "scorer"
    assert fast.search_info_["fold_copies"] == 10   # test + train, 5 folds
    slow = GridSearchCV(LogisticRegression(solver="lbfgs", tol=TOL,
                                           max_iter=300),
                        {"C": CS, "intercept_scaling": [1.0]}, **kw).fit(X, y)
    assert slow.search_info_["path"] == "general"
    for key in ("mean_test_score", "mean_train_score"):
        np.testing.assert_allclose(fast.cv_results_[key],
                                   slow.cv_results_[key], atol=2e-4)


def test_train_scores_from_the_one_program(data):
    X, y, _, _ = data
    s = _search_of(return_train_score=True).fit(X, y)
    assert s.search_info_["scored"] == "program"
    splits = list(KFold(5).split(X.to_numpy()))
    yh = y.to_numpy()
    for f, (tr, _) in enumerate(splits):
        for c, C in enumerate(CS):
            b = s.search_info_["betas"][c, f]
            eta = X.to_numpy()[tr].astype(np.float64) @ b[:-1] + b[-1]
            acc = np.mean((eta > 0) == (yh[tr] > 0.5))
            near = np.mean(np.abs(eta) < NEAR)
            assert abs(s.cv_results_[f"split{f}_train_score"][c] - acc) \
                <= near + 1e-12


def test_out_of_memory_raises_with_the_fold_bytes(data, monkeypatch):
    """Running out of device memory in a stacked path is not a reason to
    rerun the grid through a path that needs more of it."""
    from dask_ml_tpu.models.glm import _GLMBase

    X, y, _, _ = data

    def exhausted(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying "
                           "to allocate 8.00GiB")

    monkeypatch.setattr(_GLMBase, "_grid_blocks", exhausted)
    with pytest.raises(MemoryError, match=r"0 fold copies .*0 bytes"):
        _search_of().fit(X, y)
    with pytest.raises(MemoryError, match=r"2 fold copies .* bytes"):
        _search_of(ShuffleSplit(3, test_size=0.2, random_state=0)).fit(X, y)


def test_any_other_failure_falls_back_on_record(data, monkeypatch):
    from dask_ml_tpu.models.glm import _GLMBase

    X, y, _, _ = data

    def broken(*a, **k):
        raise FloatingPointError("a block diverged")

    monkeypatch.setattr(_GLMBase, "_grid_blocks", broken)
    with pytest.warns(RuntimeWarning, match="a block diverged"):
        s = _search_of().fit(X, y)
    info = s.search_info_
    assert info["path"] == "general" and info["fold_copies"] == 10
    assert info["fallbacks"] == ["FloatingPointError: a block diverged"] * 2
    assert np.isfinite(s.best_score_)


def test_contiguous_fold_ids_are_the_split_s(data):
    X, _, Xh, _ = data
    for cv in (KFold(5), KFold(7, shuffle=True, random_state=3)):
        folds = _search._FoldIds(cv, X)
        ids = np.asarray(folds.ids)[: X.n_rows]
        for f, (tr, te) in enumerate(cv.split(Xh)):
            assert np.array_equal(np.flatnonzero(ids == f), np.sort(te))
            got_tr, got_te = folds.rows(f)
            assert np.array_equal(got_te, te) and np.array_equal(got_tr, tr)
        assert folds.n_test == [len(te) for _, te in cv.split(Xh)]
