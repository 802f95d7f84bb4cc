"""Checkpoint/resume + observability + config subsystems (SURVEY.md §5:
built beyond the reference — dask-ml restarts searches from scratch)."""

import json
import os

import numpy as np
import pytest


def test_pytree_roundtrip(tmp_path):
    import jax.numpy as jnp

    from dask_ml_tpu.utils import checkpoint as ckpt

    tree = {
        "beta": jnp.arange(6, dtype=jnp.float32),
        "it": jnp.asarray(3),
        "nested": {"m": jnp.ones((2, 2))},
    }
    path = os.path.join(tmp_path, "state")
    ckpt.save_pytree(path, tree)
    got = ckpt.restore_pytree(path, like=tree)
    np.testing.assert_allclose(np.asarray(got["beta"]), np.arange(6))
    assert int(got["it"]) == 3
    np.testing.assert_allclose(np.asarray(got["nested"]["m"]), 1.0)


def test_host_roundtrip(tmp_path):
    from sklearn.linear_model import SGDClassifier

    from dask_ml_tpu.utils import checkpoint as ckpt

    rng = np.random.RandomState(0)
    X = rng.randn(50, 4)
    y = (X[:, 0] > 0).astype(int)
    est = SGDClassifier(random_state=0).fit(X, y)
    p = os.path.join(tmp_path, "est.pkl")
    ckpt.save_host(p, est)
    got = ckpt.restore_host(p)
    np.testing.assert_array_equal(got.predict(X), est.predict(X))


def test_search_checkpoint_roundtrip(tmp_path):
    from dask_ml_tpu.utils.checkpoint import SearchCheckpoint

    sc = SearchCheckpoint(os.path.join(tmp_path, "search"))
    assert sc.load() is None
    history = [{"model_id": 0, "score": 0.5}]
    meta = {0: {"partial_fit_calls": 3}}
    sc.save_round(2, history, meta, models={0: "modelblob"})
    state = sc.load()
    assert state["round"] == 2
    assert state["history"] == history
    assert state["meta"] == meta
    assert state["models"][0] == "modelblob"


def test_metrics_logger_jsonl(tmp_path):
    from dask_ml_tpu.utils.observability import MetricsLogger

    p = os.path.join(tmp_path, "metrics.jsonl")
    with MetricsLogger(p, extra={"run": "t1"}) as log:
        log.log(step=0, loss=1.5)
        log.log(step=1, loss=0.7, samples_per_sec=123.0)
    lines = [json.loads(l) for l in open(p)]
    assert len(lines) == 2
    assert lines[0]["run"] == "t1" and lines[0]["step"] == 0
    assert lines[1]["samples_per_sec"] == 123.0
    assert all("time" in rec for rec in lines)


def test_timed():
    from dask_ml_tpu.utils.observability import timed

    out, secs = timed(lambda a, b: a + b, 2, b=3)
    assert out == 5 and secs >= 0.0


def test_config_set_overrides_and_env():
    from dask_ml_tpu import config

    base = config.get_config()
    assert base.dtype in ("auto", "float32", "bfloat16")
    with config.set(stream_block_rows=4096, dtype="bfloat16"):
        cfg = config.get_config()
        assert cfg.stream_block_rows == 4096
        assert cfg.dtype == "bfloat16"
        with config.set(dtype="float32"):
            assert config.get_config().dtype == "float32"
            assert config.get_config().stream_block_rows == 4096
    assert config.get_config().stream_block_rows == base.stream_block_rows


def test_config_rejects_unknown_key():
    from dask_ml_tpu import config

    with pytest.raises(TypeError):
        with config.set(not_a_field=1):
            pass


class _FlakyClassifier:
    """sklearn-compatible partial_fit classifier that raises after a set
    number of partial_fit calls across ALL instances — fault injection for
    the controller (SURVEY.md §5 failure row)."""

    CALLS = {"n": 0, "fail_at": None}

    def __init__(self, alpha=1e-4):
        from sklearn.linear_model import SGDClassifier

        self.alpha = alpha
        self._est = SGDClassifier(alpha=alpha, tol=1e-3, random_state=0)

    def get_params(self, deep=True):
        return {"alpha": self.alpha}

    def set_params(self, **p):
        self.__init__(**{**self.get_params(), **p})
        return self

    def partial_fit(self, X, y, classes=None, **kw):
        c = _FlakyClassifier.CALLS
        c["n"] += 1
        if c["fail_at"] is not None and c["n"] > c["fail_at"]:
            raise RuntimeError("injected failure")
        self._est.partial_fit(X, y, classes=classes)
        return self

    def predict(self, X):
        return self._est.predict(X)

    def score(self, X, y):
        return self._est.score(X, y)


def test_incremental_search_checkpoint_resume(tmp_path):
    """A KILLED adaptive search resumes from its last round; a COMPLETED
    one clears its checkpoint (SURVEY.md §5: beyond the reference, whose
    killed searches restart from scratch)."""
    from sklearn.datasets import make_classification

    from dask_ml_tpu import config
    from dask_ml_tpu.model_selection import IncrementalSearchCV
    from dask_ml_tpu.utils.checkpoint import SearchCheckpoint

    X, y = make_classification(n_samples=400, n_features=8, random_state=0)
    params = {"alpha": list(np.logspace(-4, -1, 8))}
    ckpt_dir = os.path.join(tmp_path, "ck")

    def make_search():
        return IncrementalSearchCV(
            _FlakyClassifier(), params,
            n_initial_parameters=4, max_iter=6, random_state=0,
        )

    # run 1: injected failure mid-search -> checkpoint survives
    _FlakyClassifier.CALLS.update(n=0, fail_at=8)
    with config.set(checkpoint_dir=ckpt_dir):
        import pytest as _pytest

        with _pytest.raises(RuntimeError, match="injected"):
            make_search().fit(X, y, classes=[0, 1])
    # per-search subdirectory is keyed by the identity token
    subs = os.listdir(ckpt_dir)
    assert len(subs) == 1 and subs[0].startswith("IncrementalSearchCV-")
    sub = os.path.join(ckpt_dir, subs[0])
    state = SearchCheckpoint(sub).load()
    assert state is not None and state["round"] >= 1
    calls_before_crash = sum(
        m["partial_fit_calls"] for m in state["meta"].values()
    )
    assert calls_before_crash >= 4

    # run 2: same search resumes from the checkpoint and completes;
    # the completed run clears the checkpoint
    _FlakyClassifier.CALLS.update(n=0, fail_at=None)
    with config.set(checkpoint_dir=ckpt_dir):
        s2 = make_search().fit(X, y, classes=[0, 1])
    new_calls = _FlakyClassifier.CALLS["n"]
    assert hasattr(s2, "best_params_") and s2.best_score_ > 0.5
    # resumed run re-used the checkpointed work: only the remaining calls
    # were executed on fresh estimators
    total_after = int(s2.cv_results_["partial_fit_calls"].sum())
    assert new_calls == total_after - calls_before_crash
    assert SearchCheckpoint(sub).load() is None  # cleared on completion


def test_checkpoint_different_search_isolated(tmp_path):
    """A DIFFERENT search (other budget) gets its own checkpoint dir: it
    starts fresh AND leaves the interrupted search's state resumable."""
    from sklearn.datasets import make_classification

    from dask_ml_tpu import config
    from dask_ml_tpu.model_selection import IncrementalSearchCV
    from dask_ml_tpu.utils.checkpoint import SearchCheckpoint

    X, y = make_classification(n_samples=300, n_features=6, random_state=0)
    ckpt_dir = os.path.join(tmp_path, "ck2")

    _FlakyClassifier.CALLS.update(n=0, fail_at=6)
    with config.set(checkpoint_dir=ckpt_dir):
        import pytest as _pytest

        with _pytest.raises(RuntimeError):
            IncrementalSearchCV(
                _FlakyClassifier(), {"alpha": [1e-4, 1e-3, 1e-2, 1e-1]},
                n_initial_parameters=4, max_iter=6, random_state=0,
            ).fit(X, y, classes=[0, 1])
    sub_a = os.path.join(ckpt_dir, os.listdir(ckpt_dir)[0])
    assert SearchCheckpoint(sub_a).load() is not None

    # different search (different max_iter): own subdir, fresh run
    _FlakyClassifier.CALLS.update(n=0, fail_at=None)
    with config.set(checkpoint_dir=ckpt_dir):
        s = IncrementalSearchCV(
            _FlakyClassifier(), {"alpha": [1e-4, 1e-3, 1e-2, 1e-1]},
            n_initial_parameters=4, max_iter=3, random_state=0,
        ).fit(X, y, classes=[0, 1])
    assert int(s.cv_results_["partial_fit_calls"].max()) <= 3
    assert _FlakyClassifier.CALLS["n"] == int(
        s.cv_results_["partial_fit_calls"].sum()
    )
    # the interrupted search's checkpoint is untouched and still resumable
    assert SearchCheckpoint(sub_a).load() is not None


def test_checkpoint_resume_disabled_without_random_state(tmp_path):
    """random_state=None draws a fresh split per run — resume must be
    disabled (a resumed model would be scored on rows it trained on)."""
    from sklearn.datasets import make_classification

    from dask_ml_tpu import config
    from dask_ml_tpu.model_selection import IncrementalSearchCV

    X, y = make_classification(n_samples=300, n_features=6, random_state=0)
    ckpt_dir = os.path.join(tmp_path, "ck3")
    _FlakyClassifier.CALLS.update(n=0, fail_at=6)
    with config.set(checkpoint_dir=ckpt_dir):
        import pytest as _pytest

        with _pytest.raises(RuntimeError):
            IncrementalSearchCV(
                _FlakyClassifier(), {"alpha": [1e-4, 1e-3, 1e-2, 1e-1]},
                n_initial_parameters=4, max_iter=6, random_state=None,
            ).fit(X, y, classes=[0, 1])
    # ADVICE r1 #2: no checkpoint state is written AT ALL — resume is
    # impossible, so writes would be pure overhead and a shared-dir hazard
    assert not os.path.exists(ckpt_dir) or os.listdir(ckpt_dir) == []

    # rerun completes from scratch (no resume), using its own full budget
    _FlakyClassifier.CALLS.update(n=0, fail_at=None)
    with config.set(checkpoint_dir=ckpt_dir):
        s = IncrementalSearchCV(
            _FlakyClassifier(), {"alpha": [1e-4, 1e-3, 1e-2, 1e-1]},
            n_initial_parameters=4, max_iter=6, random_state=None,
        ).fit(X, y, classes=[0, 1])
    assert _FlakyClassifier.CALLS["n"] == int(
        s.cv_results_["partial_fit_calls"].sum()
    )


def _read_jsonl(path):
    return [json.loads(line) for line in open(path)]


def _read_steps(path):
    """Per-step metric records only: fits also append one span record
    each (the observability package's trace layer) — step-count
    assertions exclude those."""
    return [r for r in _read_jsonl(path) if "span" not in r]


def test_resident_glm_per_step_metrics(tmp_path):
    """config.metrics_path wires per-iteration JSONL OUT OF the jitted
    while_loop solvers via debug callbacks (VERDICT r2 #3)."""
    from dask_ml_tpu import config
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.parallel import as_sharded

    rng = np.random.RandomState(0)
    X = rng.randn(400, 6).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    Xs, ys = as_sharded(X), as_sharded(y)
    path = str(tmp_path / "glm.jsonl")
    with config.set(metrics_path=path):
        clf = LogisticRegression(solver="lbfgs", max_iter=20).fit(Xs, ys)
    recs = _read_steps(path)
    assert len(recs) == clf.n_iter_
    for r in recs:
        assert r["component"] == "LogisticRegression"
        assert r["solver"] == "lbfgs"
        assert "loss" in r and "grad_norm" in r and "step" in r
    # steps are the solver's own iteration counter
    assert [r["step"] for r in recs] == list(range(clf.n_iter_))
    # silent path: no file grows without the knob
    clf2 = LogisticRegression(solver="lbfgs", max_iter=5).fit(Xs, ys)
    assert len(_read_steps(path)) == len(recs)


@pytest.mark.parametrize("solver,keys", [
    ("newton", ("loss", "grad_norm")),
    ("gradient_descent", ("loss", "grad_norm")),
    ("proximal_grad", ("loss", "opt_residual")),
    ("admm", ("primal_residual", "dual_residual")),
])
def test_all_resident_solvers_emit_metrics(tmp_path, solver, keys):
    from dask_ml_tpu import config
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.parallel import as_sharded

    rng = np.random.RandomState(1)
    X = rng.randn(300, 5).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    path = str(tmp_path / f"{solver}.jsonl")
    with config.set(metrics_path=path):
        LogisticRegression(solver=solver, max_iter=5).fit(
            as_sharded(X), as_sharded(y)
        )
    recs = _read_steps(path)
    assert recs, solver
    for k in keys:
        assert all(k in r for r in recs), (solver, k, recs[0])


def test_kmeans_per_iteration_metrics(tmp_path):
    from dask_ml_tpu import config
    from dask_ml_tpu.cluster import KMeans
    from dask_ml_tpu.parallel import as_sharded

    rng = np.random.RandomState(2)
    X = np.concatenate([
        rng.randn(200, 4).astype(np.float32) + 4 * i for i in range(3)
    ])
    path = str(tmp_path / "km.jsonl")
    with config.set(metrics_path=path):
        km = KMeans(n_clusters=3, init="random", random_state=0,
                    max_iter=20).fit(as_sharded(X))
    recs = _read_steps(path)
    assert len(recs) == km.n_iter_
    for r in recs:
        assert r["component"] == "KMeans"
        assert "center_shift2" in r and "step" in r


def test_adaptive_search_metrics(tmp_path):
    from dask_ml_tpu import config
    from dask_ml_tpu.model_selection import IncrementalSearchCV
    from dask_ml_tpu.models.sgd import SGDClassifier

    rng = np.random.RandomState(3)
    X = rng.randn(400, 5).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    path = str(tmp_path / "search.jsonl")
    with config.set(metrics_path=path):
        search = IncrementalSearchCV(
            SGDClassifier(random_state=0),
            {"alpha": [1e-4, 1e-3, 1e-2]},
            n_initial_parameters=3, max_iter=5, random_state=0,
        )
        search.fit(X, y, classes=[0.0, 1.0])
    logged = [r for r in _read_steps(path)
              if r.get("component") == "adaptive_search"]
    # one line a scored trial, and one ``search.round`` event a round
    recs = [r for r in logged if "model_id" in r]
    rounds = [r for r in logged if r.get("event") == "search.round"]
    assert len(recs) + len(rounds) == len(logged)
    assert len(rounds) == search.search_info_["n_rounds"]
    assert len(recs) == len(search.history_)
    for r in recs:
        assert "model_id" in r and "score" in r and "batch_size" in r


def test_checkpoint_data_fingerprint_isolates(tmp_path):
    """ADVICE r1 #1: same shape, same params, DIFFERENT data content must
    not resume the stale search — the identity token carries a content
    fingerprint."""
    from sklearn.datasets import make_classification

    from dask_ml_tpu import config
    from dask_ml_tpu.model_selection import IncrementalSearchCV

    X, y = make_classification(n_samples=300, n_features=6, random_state=0)
    X2, y2 = make_classification(n_samples=300, n_features=6,
                                 random_state=99)  # same shape, new data
    ckpt_dir = os.path.join(tmp_path, "ckfp")
    params = {"alpha": [1e-4, 1e-3, 1e-2, 1e-1]}

    def search():
        return IncrementalSearchCV(
            _FlakyClassifier(), params,
            n_initial_parameters=4, max_iter=6, random_state=0,
        )

    _FlakyClassifier.CALLS.update(n=0, fail_at=6)
    with config.set(checkpoint_dir=ckpt_dir):
        with pytest.raises(RuntimeError, match="injected"):
            search().fit(X, y, classes=[0, 1])
    assert len(os.listdir(ckpt_dir)) == 1

    # same-shape different data: must get its OWN token directory and run
    # from scratch, not resume the stale models
    _FlakyClassifier.CALLS.update(n=0, fail_at=None)
    with config.set(checkpoint_dir=ckpt_dir):
        s = search().fit(X2, y2, classes=[0, 1])
    assert len(os.listdir(ckpt_dir)) == 2  # distinct token dirs
    # fresh run executed its entire own budget (nothing resumed)
    assert _FlakyClassifier.CALLS["n"] == int(
        s.cv_results_["partial_fit_calls"].sum()
    )
