"""``HyperbandSearchCV`` over a RESIDENT, row-sharded table: the resident
cohort plane (``model_selection/_incremental.py::_ResidentCohortPlane``)
against the benchmark's plain reference (``benchmark/references/
hyperband.py``), and what the plane promises — ``grid_partition`` blocks,
one grid and one held-out block a fit, one tracked scan a group, nothing of
X fetched by the post-fit methods, a refused gate recorded, one root span
with four flat children. One search serves every test that only reads its
results."""

import json

import jax
import numpy as np
import pytest

from benchmark.references import hyperband as ref
from benchmark.tolerances_sgd import distance
from dask_ml_tpu import config, observability as obs
from dask_ml_tpu.linear_model import SGDClassifier
from dask_ml_tpu.model_selection import HyperbandSearchCV
from dask_ml_tpu.parallel import as_sharded
from dask_ml_tpu.parallel.sharded import ShardedArray

N, D, MAX_ITER, ETA, SEED, TEST_SIZE = 4096, 16, 27, 3, 11, 0.125
PARAMETERS = {"alpha": np.logspace(-4, 0, 1000),
              "eta0": np.logspace(-3, 0, 1000)}
HYPER = dict(loss="log_loss", l2=1.0, l1=0.0, power_t=0.25,
             schedule="invscaling", fit_intercept=True)


def _data(n=N, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, D).astype(np.float32)
    beta = rng.randn(D) / np.sqrt(D)
    y = (rng.rand(n) < 1 / (1 + np.exp(-2 * X @ beta))).astype(np.float32)
    return X, y


def _search(max_iter=MAX_ITER, **kw):
    return HyperbandSearchCV(
        SGDClassifier(loss="log_loss", penalty="l2", fit_dtype="bfloat16"),
        PARAMETERS, max_iter=max_iter, aggressiveness=ETA,
        test_size=TEST_SIZE, random_state=SEED, **kw)


def _programs():
    return {r["program"]: int(r["calls"]) for r in obs.programs_snapshot()}


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    """One whole search on the suite's 8-device mesh with the flight
    recorder on and a JSONL sink: (search, host X, host y, resident X,
    resident y, its spans, the programs it ran, its JSONL lines, the count
    of ``take_rows`` calls inside it)."""
    from dask_ml_tpu.model_selection import _split
    from dask_ml_tpu.parallel import sharded

    X, y = _data()
    Xs, ys = as_sharded(X), as_sharded(y)
    path = str(tmp_path_factory.mktemp("hb") / "search.jsonl")
    taken = []
    orig = sharded.take_rows

    def counted(x, idx):
        taken.append(len(idx))
        return orig(x, idx)

    obs.reset_recent_spans()
    with pytest.MonkeyPatch.context() as mp, \
            config.set(obs_programs=True, metrics_path=path):
        mp.setattr(sharded, "take_rows", counted)
        mp.setattr(_split, "take_rows", counted)
        before = _programs()
        search = _search().fit(Xs, ys, classes=[0, 1])
        # (a row mask is a tracked program too, where its cache has none)
        ran = {k: v - before.get(k, 0) for k, v in _programs().items()
               if v - before.get(k, 0) and k != "sharded.row_mask"}
    spans = obs.recent_spans()
    obs.reset_recent_spans()
    with open(path) as f:
        lines = [json.loads(ln) for ln in f]
    return {"search": search, "X": X, "y": y, "Xs": Xs, "ys": ys,
            "spans": spans, "programs": ran, "lines": lines, "taken": taken}


@pytest.fixture(scope="module")
def problem(fitted):
    return ref.Problem(
        fitted["Xs"].data, fitted["ys"].data, N, len(jax.devices()),
        parameters=PARAMETERS, max_iter=MAX_ITER, eta=ETA,
        test_size=TEST_SIZE, random_state=SEED, hyper=HYPER)


def test_the_whole_search_is_the_references(fitted, problem):
    """(a) the reference's own whole search at the stated precision: the
    same draw, the same survivors at every rung, every final score, and the
    winner's weights to 1e-5 of ||w||."""
    s = fitted["search"]
    own = ref.search(problem, design_dtype="bfloat16")
    meta = ref.metadata(MAX_ITER, ETA)
    assert s.metadata() == meta
    assert {k: s.metadata_[k] for k in meta} == meta
    assert list(s.cv_results_["params"]) == problem.params
    assert list(s.cv_results_["bracket"]) == problem.bracket_of
    calls = {m: int(c) for m, c in enumerate(
        s.cv_results_["partial_fit_calls"])}
    assert calls == own["calls"]
    went_on = {}
    for r in s.history_:
        went_on.setdefault((r["bracket"], r["partial_fit_calls"]),
                           set()).add(r["model_id"])
    for cut in own["cuts"]:
        assert went_on[(cut["bracket"], cut["calls"])] == set(cut["scores"])
        nxt = min(c for (b, c) in went_on
                  if b == cut["bracket"] and c > cut["calls"])
        assert sorted(went_on[(cut["bracket"], nxt)]) == cut["kept"]
    got = {r["model_id"]: r["score"] for r in s.history_}   # the last wins
    assert got == pytest.approx(own["score"], abs=1e-6)
    assert s.best_score_ == pytest.approx(max(own["score"].values()),
                                          abs=1e-6)
    w = np.r_[np.ravel(s.best_estimator_.coef_),
              np.ravel(s.best_estimator_.intercept_)].astype(np.float32)
    assert distance(w, own["W"][s.best_index_]) < 1e-5
    # ... and beside the float32 replay of the same model, as a bf16
    # design sits beside an exact one
    f32 = ref.replay(problem, {s.best_index_: calls[s.best_index_]})
    assert 1e-6 < distance(w, f32[s.best_index_]) < 2e-2


def test_the_partition_is_incrementals(fitted, problem):
    """(b) ``grid_partition`` of the training rows: the blocks
    ``Incremental`` and ``SGDClassifier.fit`` cut of the same rows, and the
    reference's own."""
    from dask_ml_tpu.models.sgd import fused_blocks
    from dask_ml_tpu.parallel.sharded import take_rows

    info = fitted["search"].search_info_
    train = take_rows(fitted["Xs"], problem.train_idx)
    assert (info["blocks"], info["block_rows"]) == fused_blocks(train) \
        == (problem.B, problem.S)
    assert info["plane"] == "grid" and info["gate"]["fits"] is True
    assert info["fit_dtype"] == "bfloat16"


def test_one_grid_a_fit_one_scan_a_group(fitted):
    """(c) the grid and split programs run once, no ``take_rows`` a block,
    the tracked cohort program once a group, the score program once a
    round; ``search_info_`` says the same."""
    info, ran = fitted["search"].search_info_, fitted["programs"]
    groups = [g for r in info["rounds"] for g in r["groups"]]
    assert fitted["taken"] == []
    # (the raw labels are split only for a trial that leaves the cohort)
    assert ran == {"search.split_x": 1, "search.split_y": 1,
                   "sgd.cohort_scan": len(groups),
                   "sgd.cohort_score": info["n_rounds"]}
    assert info["dispatches"] == len(groups) + info["n_rounds"]
    assert all(g["path"] == "cohort_scan" and g["dispatches"] == 1
               and g["program"] == "sgd.cohort_scan" for g in groups)
    assert info["groups"] == len(groups)
    assert info["model_steps"] == ref.metadata(MAX_ITER, ETA)[
        "partial_fit_calls"] == fitted["search"].metadata_[
        "partial_fit_calls"]
    assert info["scan_steps"] == sum(g["steps"] for g in groups)
    d1 = D + 1
    assert info["grid_bytes"] >= 2 * (N * D) + 4 * N   # bf16 X, f32 labels
    assert fitted["search"].best_estimator_._w.shape == (d1,)


def test_post_fit_methods_fetch_no_x(fitted, monkeypatch):
    """(d) ``predict`` / ``predict_proba`` / ``decision_function`` /
    ``score`` hand a resident X to ``best_estimator_`` as it is: no row of
    X comes to the host, the decision says ``link="device"``, and the
    answers are the host-input ones."""
    s, X, y, Xs, ys = (fitted[k] for k in ("search", "X", "y", "Xs", "ys"))
    fetched = []
    orig = ShardedArray.to_numpy

    def spy(self):
        fetched.append(self.data.shape)
        return orig(self)

    monkeypatch.setattr(ShardedArray, "to_numpy", spy)
    obs.reset_recent_spans()
    with config.set(obs_programs=True):
        got = {"predict": s.predict(Xs), "proba": s.predict_proba(Xs),
               "decision": s.decision_function(Xs)}
        spans = obs.recent_spans()
        score = s.score(Xs, ys)
    obs.reset_recent_spans()
    assert not [sh for sh in fetched if len(sh) == 2], fetched
    links = [r["link"] for r in spans if r["span"] == "predict.decision"]
    assert links and set(links) == {"device"}
    assert [r["span"] for r in spans if r["parent_id"] is None] \
        == ["predict"] * len(links)
    best = s.best_estimator_
    np.testing.assert_array_equal(got["predict"], best.predict(X))
    np.testing.assert_allclose(got["proba"], best.predict_proba(X),
                               atol=1e-6)
    np.testing.assert_allclose(got["decision"], best.decision_function(X),
                               atol=1e-5)
    assert score == pytest.approx(np.mean(best.predict(X) == y))


def test_a_refused_gate_is_recorded(monkeypatch):
    """(e) a device that reports too little free memory for the grid: the
    search keeps the partition and gathers block by block, and
    ``search_info_`` says so — the plane, the gate's reading, every group's
    path."""
    X, y = _data(2048, seed=3)
    Xs, ys = as_sharded(X), as_sharded(y)
    want = _search(max_iter=9).fit(Xs, ys, classes=[0, 1])
    monkeypatch.setattr(type(jax.devices()[0]), "memory_stats", lambda dev: {
        "bytes_limit": 1 << 20, "bytes_in_use": (1 << 20) - 4096})
    got = _search(max_iter=9).fit(Xs, ys, classes=[0, 1])
    a, b = want.search_info_, got.search_info_
    assert a["plane"] == "grid" and a["gate"]["free"] is None
    assert b["plane"] == "blocks" and b["grid_bytes"] == 0
    assert b["gate"]["fits"] is False and b["gate"]["free"] == 4096
    assert b["gate"]["needed"] == a["gate"]["needed"] > 0
    assert (b["blocks"], b["block_rows"]) == (a["blocks"], a["block_rows"])
    paths = {g["path"] for r in b["rounds"] for g in r["groups"]}
    assert paths and paths <= {"blocks_scan", "step_loop", "solo"}
    assert {g["path"] for r in a["rounds"] for g in r["groups"]} \
        == {"cohort_scan"}
    # the same minibatches either way: the same search
    assert got.metadata_["partial_fit_calls"] \
        == want.metadata_["partial_fit_calls"]
    assert got.best_params_ == want.best_params_
    assert got.best_score_ == pytest.approx(want.best_score_, abs=2e-2)


def test_the_span_tree(fitted):
    """(f) one root, four flat children and nothing else in the ring; the
    solve's sums add up; a round is a record in the JSONL, not a span."""
    s, spans = fitted["search"], fitted["spans"]
    info = s.search_info_
    roots = [r for r in spans if r["parent_id"] is None]
    assert [r["span"] for r in roots] == ["fit"]
    root = roots[0]
    kids = [r for r in spans if r["parent_id"] == root["span_id"]]
    assert [r["span"] for r in kids] == ["fit.validate", "fit.prepare",
                                         "fit.solve", "fit.finish"]
    assert len(spans) == 5
    assert root["component"] == "HyperbandSearchCV"
    assert (root["n_iter"], root["n_models"], root["partial_fit_calls"]) \
        == (info["n_rounds"], s.metadata_["n_models"],
            s.metadata_["partial_fit_calls"])
    walls = sum(r["wall_s"] for r in kids)
    assert walls <= root["wall_s"] + 1e-5
    assert root["wall_s"] - walls <= 0.02 * root["wall_s"] + 2e-3
    solve = kids[2]
    for k in ("rounds", "groups", "dispatches", "scan_steps", "model_steps"):
        assert solve[k] == info[k if k != "rounds" else "n_rounds"]
    parts = sum(solve[k] for k in ("train_s", "score_s", "publish_s",
                                   "control_s"))
    assert parts == pytest.approx(solve["wall_s"], rel=0.02, abs=2e-3)
    assert 0 < solve["sync_s"] <= solve["train_s"] + solve["score_s"] + 1e-3
    assert kids[1]["data_plane"] == "grid" \
        and kids[1]["grid_bytes"] == info["grid_bytes"]
    events = [r for r in fitted["lines"]
              if r.get("event") == "search.round"]
    assert [e["round"] for e in events] == list(range(info["n_rounds"]))
    for e, rec in zip(events, info["rounds"]):
        assert (e["n_trials"], e["n_calls"]) == (rec["n_trials"],
                                                 rec["n_calls"])
        assert e["wall_s"] == pytest.approx(rec["wall_s"])
    assert not [r for r in fitted["lines"]
                if r.get("span") == "search.round"]


def test_ties_keep_the_lower_model_id():
    from dask_ml_tpu.model_selection._incremental import top_scores

    scores = {70: 0.5, 3: 0.5, 100: 0.75, 41: 0.5, 8: 0.25}
    assert top_scores(scores, 3) == ref.keep(scores, 3) == [100, 3, 41]
