"""The handoff ledger (PR 34): every tracked dispatch and every blocking
device-to-host read is recorded where it happens and charged to the span
that caused it (``observability/_spans.py``, ``_programs.py::track_program``,
``base.to_host``, ``solvers._fetch``) — ``dispatches``, ``dispatch_s``,
``host_operands``, ``host_operand_bytes``, ``fetches``, ``fetch_bytes``,
``fetch_s``, inclusive as ``wall_s`` is, and ``host_gap_s``, the time inside
the span in which this thread had nothing in flight. XLA:CPU gives the
counts; a time here is only ever compared with the span's own wall."""

import functools
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dask_ml_tpu import config, datasets, observability as obs
from dask_ml_tpu.base import to_host
from dask_ml_tpu.observability import _spans
from dask_ml_tpu.observability._spans import LEDGER_KEYS, span

EIGHT = (*LEDGER_KEYS, "host_gap_s")


@pytest.fixture(autouse=True)
def _clean_ring():
    obs.reset_recent_spans()
    yield
    obs.reset_recent_spans()


def _program_calls():
    return {r["program"]: int(r["calls"]) for r in obs.programs_snapshot()}


def _delta(before):
    return {k: v - before.get(k, 0) for k, v in _program_calls().items()
            if v - before.get(k, 0)}


def _calls(ring, name):
    """[(root record, {child name: record})] of the roots named ``name``."""
    roots = [r for r in ring if r["span"] == name and r["parent_id"] is None]
    return [(root, {r["span"]: r for r in ring
                    if r["root_id"] == root["span_id"]
                    and r["parent_id"] is not None}) for root in roots]


def _xy(n=1024, d=8, seed=0):
    return datasets.make_classification(n_samples=n, n_features=d,
                                        random_state=seed)


# -- the no-op path ----------------------------------------------------------

def test_tracing_off_a_fetch_is_plain_asarray_and_nothing_is_recorded():
    x = jnp.arange(6.0).reshape(2, 3)
    assert obs.current_span() is _spans.NOOP_SPAN
    out = to_host(x)
    assert type(out) is np.ndarray and np.array_equal(out, np.asarray(x))
    host = np.arange(3.0)
    assert to_host(host) is host                 # np.asarray of an ndarray
    with span("fit") as sp:                      # no sink, no obs_programs
        assert sp is _spans.NOOP_SPAN and not sp.ledger
        assert sp.fetch(np.asarray, x).tolist() == out.tolist()
    assert obs.recent_spans() == []


def test_tracing_off_a_tracked_call_hands_its_arguments_on_untouched():
    seen = []

    def fn(*args, **kwargs):
        seen.append((args, kwargs))
        return "out"

    tracked = obs.track_program("test.untouched")(fn)
    a, b = np.ones(3), object()
    before = _program_calls()
    assert tracked(a, 2, k=b) == "out"
    (args, kwargs), = seen
    assert args[0] is a and args[1] == 2 and kwargs["k"] is b
    assert _delta(before) == {} and obs.recent_spans() == []


def test_a_sink_alone_records_spans_without_a_ledger(tmp_path):
    """``trace_dir`` arms a span; the ledger is ``obs_programs``' alone."""
    with config.set(trace_dir=str(tmp_path)):
        with span("fit") as sp:
            assert not sp.ledger
            to_host(jnp.ones(4))
    (rec,) = obs.recent_spans()
    assert rec["span"] == "fit" and not set(EIGHT) & set(rec)


# -- known counts a call -----------------------------------------------------

def test_logreg_fit_is_two_programs_two_fetches_seven_host_operands():
    from dask_ml_tpu.linear_model import LogisticRegression

    X, y = _xy()
    with config.set(obs_programs=True):
        LogisticRegression(solver="lbfgs", max_iter=30).fit(X, y)  # warm
        obs.reset_recent_spans()
        before = _program_calls()
        clf = LogisticRegression(solver="lbfgs", max_iter=30).fit(X, y)
        ran = _delta(before)
        ring = obs.recent_spans()
    ((root, kids),) = _calls(ring, "fit")
    # the row mask of 1,024 rows comes from its result cache
    assert ran == {"glm.prepare": 1, "glm.lbfgs": 1}
    assert root["dispatches"] == 2 == sum(ran.values())
    assert root["fetches"] == 2       # prep's three scalars, the result
    # n_rows, beta0, lam, pmask, l1_ratio, stop_it, tol
    assert root["host_operands"] == 7
    assert kids["fit.solve"]["host_operands"] == 7
    assert kids["fit.prepare"]["host_operands"] == 0
    assert kids["fit.prepare"]["fetches"] == kids["fit.solve"]["fetches"] == 1
    assert kids["fit.prepare"]["fetch_bytes"] == 3 * 4
    d1 = X.shape[1] + 1
    assert kids["fit.solve"]["fetch_bytes"] == 4 * (d1 + 3)
    assert kids["fit.finish"]["fetches"] == 0    # beta came with the result
    # beta0 and pmask (d + 1 floats each), five 4-byte scalars
    assert root["host_operand_bytes"] == 2 * 4 * d1 + 5 * 4
    assert 0 < root["dispatch_s"] <= root["wall_s"]
    assert 0 < root["host_gap_s"] <= root["wall_s"] + 1e-6
    assert clf.n_iter_ == root["n_iter"]


def test_a_predict_is_one_dispatch_and_one_fetch_of_the_result_s_bytes():
    from dask_ml_tpu.linear_model import LogisticRegression

    X, y = _xy()
    clf = LogisticRegression(solver="lbfgs", max_iter=30).fit(X, y)
    with config.set(obs_programs=True):
        before = _program_calls()
        proba = clf.predict_proba(X)
        ran = _delta(before)
        ring = obs.recent_spans()
    ((root, kids),) = _calls(ring, "predict")
    assert ran == {"glm.decision": 1}
    assert root["dispatches"] == 1 and root["fetches"] == 1
    # 1,024 rows pad to nothing on a mesh of eight: what crossed is what
    # the caller holds
    assert root["fetch_bytes"] == proba.nbytes == 1024 * 2 * 4
    assert kids["predict.decision"]["fetch_bytes"] == root["fetch_bytes"]
    assert kids["predict.host"]["fetches"] == 0
    assert root["host_operands"] == 1            # beta
    assert root["fetch_s"] <= root["wall_s"]


@pytest.mark.parametrize("hit", [False, True], ids=["miss", "hit"])
def test_an_incremental_pass_counts_what_its_record_counts(hit):
    """A pass that builds the X half of its grid is three dispatches; one
    that reads the grid kept from the pass before (the same device array)
    makes two: no ``sgd.grid_x``."""
    from dask_ml_tpu.linear_model import SGDClassifier
    from dask_ml_tpu.parallel import as_sharded
    from dask_ml_tpu.wrappers import Incremental

    X, y = _xy(4096)
    with config.set(obs_programs=True):
        Xs, ys = as_sharded(X), as_sharded(y.astype(np.float32))
        inc = Incremental(SGDClassifier(), random_state=3)
        inc.fit(Xs, ys, classes=[0, 1])
        if not hit:
            inc._epoch_grid.clear()
        obs.reset_recent_spans()
        before = _program_calls()
        inc.partial_fit(Xs, ys)
        ran = _delta(before)
        ring = obs.recent_spans()
    ((root, kids),) = _calls(ring, "partial_fit")
    assert inc.pass_info_["path"] == "fused_epoch"
    assert inc.pass_info_["grid_hit"] is kids["pass.grid"]["grid_hit"] is hit
    assert ran == {"sgd.grid_y": 1, "sgd.fused_epoch": 1,
                   **({} if hit else {"sgd.grid_x": 1})}
    # the registry's delta, the pass record and the span's own ledger agree
    assert root["dispatches"] == inc.pass_info_["dispatches"] \
        == (2 if hit else 3) == sum(ran.values())
    assert kids["pass.grid"]["dispatches"] == (1 if hit else 2)
    assert kids["pass.solve"]["dispatches"] == 1
    # the label check's one scalar, the weights
    assert root["fetches"] == 2
    assert kids["pass.validate"]["fetches"] == 1
    assert kids["pass.validate"]["fetch_bytes"] == 1
    assert kids["pass.solve"]["fetch_bytes"] == 4 * (X.shape[1] + 1)
    assert root["host_operands"] == kids["pass.solve"]["host_operands"] == 8


def test_a_search_fit_counts_the_registry_s_delta():
    from dask_ml_tpu.linear_model import SGDClassifier
    from dask_ml_tpu.model_selection import HyperbandSearchCV
    from dask_ml_tpu.parallel import as_sharded

    X, y = _xy(2048)
    params = {"alpha": np.logspace(-4, 0, 50), "eta0": np.logspace(-3, 0, 50)}
    with config.set(obs_programs=True):
        Xs, ys = as_sharded(X), as_sharded(y.astype(np.float32))
        before = _program_calls()
        search = HyperbandSearchCV(
            SGDClassifier(loss="log_loss"), params, max_iter=9,
            aggressiveness=3, test_size=0.125, random_state=5,
        ).fit(Xs, ys, classes=[0, 1])
        ran = _delta(before)
        ring = obs.recent_spans()
    ((root, kids),) = _calls(ring, "fit")
    info = search.search_info_
    assert info["plane"] == "grid"
    assert kids["fit.solve"]["dispatches"] == info["dispatches"] \
        == info["groups"] + info["n_rounds"]
    # the two splits, and a row mask where its result cache had none
    masks = ran.get("sharded.row_mask", 0)
    assert ran["search.split_x"] == ran["search.split_y"] == 1
    assert root["dispatches"] == 2 + masks + info["dispatches"] \
        == sum(ran.values())
    # a round's scores, once a round; the stack, once a fit
    assert kids["fit.solve"]["fetches"] == info["n_rounds"]
    assert kids["fit.finish"]["fetches"] == 1
    for key in LEDGER_KEYS:
        assert root[key] == pytest.approx(
            sum(k[key] for k in kids.values()), abs=1e-5)


def test_a_pca_fit_makes_five_fetches():
    from dask_ml_tpu.decomposition import PCA

    X = np.random.RandomState(0).randn(1024, 24).astype(np.float32)
    with config.set(obs_programs=True):
        before = _program_calls()
        PCA(n_components=4, svd_solver="randomized", random_state=0).fit(X)
        ran = _delta(before)
        ring = obs.recent_spans()
    ((root, kids),) = _calls(ring, "fit")
    # s, vt, the mean, the variance, the fallback count
    assert root["fetches"] == kids["fit.solve"]["fetches"] == 5
    assert ran.get("pca.center") == ran.get("pca.rsvd") == 1
    assert root["dispatches"] == sum(ran.values())


@pytest.mark.parametrize("tol, scale_calls, host_operands",
                         [(0.0, 0, 0), (1e-4, 1, 2)])
def test_a_kmeans_fit_counts_its_fetches_and_its_programs(
        tol, scale_calls, host_operands):
    from dask_ml_tpu.cluster import KMeans

    X = np.random.RandomState(0).randn(2048, 8).astype(np.float32)
    init = X[:4].copy()
    with config.set(obs_programs=True):
        before = _program_calls()
        KMeans(n_clusters=4, init=init, max_iter=5, tol=tol).fit(X)
        ran = _delta(before)
        ring = obs.recent_spans()
    ((root, kids),) = _calls(ring, "fit")
    assert root["dispatches"] == sum(ran.values())
    # tol == 0: no scale is computed, X is not read for one; else ONE
    # program, and n_rows and tol, four bytes each, ride in with its
    # dispatch. Nothing else of a fit comes from the host
    assert ran.get("kmeans.tol_scale", 0) == scale_calls
    assert kids["fit.tol_scale"]["dispatches"] == scale_calls
    assert kids["fit.tol_scale"]["host_operands"] == host_operands
    assert root["host_operands"] == host_operands
    assert root["host_operand_bytes"] == 4 * host_operands
    # n_iter; the two finite checks, the centres, the inertia
    assert kids["fit.solve"]["fetches"] == 1
    assert kids["fit.finish"]["fetches"] == 4
    assert root["fetches"] == 5


# -- the mechanism -----------------------------------------------------------

def test_static_arguments_and_device_arrays_are_not_host_operands():
    @obs.track_program("test.operands")
    @functools.partial(jax.jit, static_argnames=("mode", "k"),
                       static_argnums=(0,))
    def f(n, x, w, scale, shift=0.0, pair=(0.0, 0.0), mode="a", k=3):
        return x * scale + w.sum() + shift + pair[0] + n + k

    x = jnp.ones(4)
    with config.set(obs_programs=True):
        with span("fit"):
            # n: static by position; mode, k: static by name; x: a device
            # array. Host operands: w (numpy, 12 bytes), scale (a Python
            # float), shift (np.float32), the pair's two leaves
            f(2, x, np.ones(3, np.float32), 2.0, shift=np.float32(1.0),
              pair=(1.0, np.float64(2.0)), mode="b", k=4)
    (rec,) = obs.recent_spans()
    assert rec["dispatches"] == 1
    assert rec["host_operands"] == 5
    assert rec["host_operand_bytes"] == 12 + 4 + 4 + 4 + 8


def test_a_value_already_on_the_host_counts_nothing():
    with config.set(obs_programs=True):
        with span("fit"):
            to_host(np.ones(5))
            to_host([1.0, 2.0])
            to_host(jnp.ones(5))
    (rec,) = obs.recent_spans()
    assert rec["fetches"] == 1 and rec["fetch_bytes"] == 20


def test_totals_are_inclusive_root_is_own_plus_children():
    read = lambda v: np.zeros(v, np.uint8)  # noqa: E731
    with config.set(obs_programs=True):
        with span("a") as a:
            a.dispatched(0.5, time.perf_counter(), 2, 16)
            a.fetch(read, 10)
            with span("b") as b:
                b.dispatched(0.25, time.perf_counter(), 1, 4)
                with span("c") as c:
                    c.fetch(read, 7)
                    c.fetch(read, 1)
            with span("d") as d:
                d.dispatched(0.125, time.perf_counter(), 0, 0)
    by = {r["span"]: r for r in obs.recent_spans()}
    assert [by["c"][k] for k in ("dispatches", "fetches", "fetch_bytes")] \
        == [0, 2, 8]
    assert [by["b"][k] for k in ("dispatches", "host_operands", "fetches",
                                 "fetch_bytes")] == [1, 1, 2, 8]
    assert by["d"]["dispatches"] == 1 and by["d"]["fetches"] == 0
    want = {"dispatches": 3, "dispatch_s": 0.875, "host_operands": 3,
            "host_operand_bytes": 20, "fetches": 3, "fetch_bytes": 18}
    assert {k: by["a"][k] for k in want} == want
    assert by["a"]["fetch_s"] >= by["b"]["fetch_s"] == by["c"]["fetch_s"] > 0
    # and the ring holds the four spans alone: a dispatch and a fetch are
    # bare annotations, not records
    assert sorted(by) == ["a", "b", "c", "d"] and len(obs.recent_spans()) == 4


def _stepped_clock(monkeypatch):
    """Every read of ``perf_counter`` inside ``_spans`` is one second."""
    ticks = iter(range(1, 10_000))
    fake = types.SimpleNamespace(
        perf_counter=lambda: float(next(ticks)), time=time.time,
        time_ns=time.time_ns)
    monkeypatch.setattr(_spans, "time", fake)
    return fake


def test_host_gap_plus_time_in_flight_is_the_wall(monkeypatch):
    clock = _stepped_clock(monkeypatch)
    with config.set(obs_programs=True):
        with span("fit") as root:                        # opens at 1
            root.dispatched(1.0, clock.perf_counter(), 0, 0)   # in flight: 2
            root.sync(np.ones(2))                        # reads 3, 4: drained
            with span("fit.solve") as sp:                # opens at 5
                sp.dispatched(1.0, clock.perf_counter(), 0, 0)   # 6
                sp.dispatched(1.0, clock.perf_counter(), 0, 0)   # 7: still
                sp.fetch(np.asarray, np.ones(2))         # 8, 9: drained
            # the child closes at 10; the root at 11
    child, rec = obs.recent_spans()
    in_flight_child, in_flight_root = 9 - 6, (4 - 2) + (9 - 6)
    assert child["wall_s"] == 5.0
    assert child["host_gap_s"] == 5.0 - in_flight_child == 2.0
    assert rec["wall_s"] == 10.0
    assert rec["host_gap_s"] == 10.0 - in_flight_root == 5.0
    assert rec["sync_s"] == 1.0 and rec["fetch_s"] == 1.0
    assert rec["dispatch_s"] == 3.0 and rec["dispatches"] == 3


def test_a_root_with_nothing_dispatched_is_all_gap(monkeypatch):
    _stepped_clock(monkeypatch)
    with config.set(obs_programs=True):
        with span("predict"):
            pass
    (rec,) = obs.recent_spans()
    assert rec["host_gap_s"] == rec["wall_s"] == 1.0


def test_two_threads_keep_separate_flags():
    barrier = threading.Barrier(2, timeout=30)
    errors = []

    def run(name, dispatch):
        try:
            with config.set(obs_programs=True):
                with span(name) as sp:
                    if dispatch:
                        sp.dispatched(0.0, time.perf_counter(), 0, 0)
                    barrier.wait()       # both are open, one is in flight
                    time.sleep(0.05)
                    barrier.wait()
        except Exception as e:           # a thread's failure must be seen
            errors.append(e)

    threads = [threading.Thread(target=run, args=("busy", True)),
               threading.Thread(target=run, args=("starved", False))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors and not any(t.is_alive() for t in threads)
    by = {r["span"]: r for r in obs.recent_spans()}
    assert by["busy"]["wall_s"] >= 0.05 and by["starved"]["wall_s"] >= 0.05
    # the other thread's dispatch did not feed this one, nor starve that
    assert by["starved"]["host_gap_s"] == pytest.approx(
        by["starved"]["wall_s"], abs=2e-6)
    assert by["busy"]["host_gap_s"] < 0.04
    assert by["busy"]["dispatches"] == 1 and by["starved"]["dispatches"] == 0


def test_an_abandoned_span_leaves_the_flag_sane(monkeypatch):
    clock = _stepped_clock(monkeypatch)
    with config.set(obs_programs=True):
        with span("fit"):
            lost = span("fit.solve")
            lost.__enter__()                      # never closed
            lost.dispatched(1.0, clock.perf_counter(), 3, 12)   # in flight
        with span("fit"):
            pass
    first, second = obs.recent_spans()
    assert first["dispatches"] == 0               # the lost span's are lost
    assert second["parent_id"] is None and second["depth"] == 0
    # a root's open clears the flag: what the lost dispatch left in flight
    # is not this call's
    assert second["host_gap_s"] == second["wall_s"] == 1.0
    assert obs.current_span() is _spans.NOOP_SPAN
