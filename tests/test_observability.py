"""Observability subsystem (ISSUE 1): hierarchical span tracing, the
runtime counter registry, the run-report CLI, back-compat re-exports,
and the zero-overhead guarantee (no callback traced into jitted code
when metrics are disabled)."""

import io
import json
import os
import threading

import numpy as np
import pytest

from dask_ml_tpu import config, observability as obs


def _read_jsonl(path):
    return [json.loads(line) for line in open(path)]


# -- spans ------------------------------------------------------------------

def test_span_nesting_parent_ids_and_attrs(tmp_path):
    trace = str(tmp_path / "t")
    with config.set(trace_dir=trace):
        with obs.span("outer", component="X", n_rows=100) as sp_o:
            assert obs.current_span_id() is not None
            with obs.span("inner") as sp_i:
                sp_i.add(detail=7)
            sp_o.add(n_iter=3)
        assert obs.current_span_id() is None
    recs = _read_jsonl(os.path.join(trace, "trace.jsonl"))
    assert [r["span"] for r in recs] == ["inner", "outer"]  # close order
    inner, outer = recs
    assert inner["parent_id"] == outer["span_id"]
    assert inner["depth"] == 1 and outer["depth"] == 0
    assert outer["parent_id"] is None
    assert inner["detail"] == 7
    assert outer["n_iter"] == 3 and outer["n_rows"] == 100
    assert outer["wall_s"] >= inner["wall_s"] >= 0.0
    assert "sync_s" in outer


def test_span_noop_when_disabled(tmp_path):
    with config.set(trace_dir="", metrics_path=""):
        with obs.span("nothing", a=1) as sp:
            assert sp is obs.NOOP_SPAN
            assert obs.current_span_id() is None
            assert sp.sync(5) == 5  # passthrough
    assert list(tmp_path.iterdir()) == []


def test_span_sync_accumulates(tmp_path):
    import jax.numpy as jnp

    trace = str(tmp_path / "t")
    with config.set(trace_dir=trace):
        with obs.span("s") as sp:
            out = sp.sync(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    assert float(out[0, 0]) == 8.0
    rec = _read_jsonl(os.path.join(trace, "trace.jsonl"))[-1]
    assert rec["sync_s"] >= 0.0


def test_span_records_error_and_unwinds_stack(tmp_path):
    trace = str(tmp_path / "t")
    with config.set(trace_dir=trace):
        with pytest.raises(ValueError):
            with obs.span("boom"):
                raise ValueError("x")
        assert obs.current_span_id() is None
    rec = _read_jsonl(os.path.join(trace, "trace.jsonl"))[-1]
    assert rec["span"] == "boom" and rec["error"] == "ValueError"


def test_span_prefers_active_logger_sink(tmp_path):
    p = str(tmp_path / "m.jsonl")
    with obs.MetricsLogger(p, extra={"run": "r1"}) as lg, \
            obs.active_logger(lg):
        with obs.span("inside"):
            pass
    recs = _read_jsonl(p)
    assert recs and recs[0]["span"] == "inside"
    assert recs[0]["run"] == "r1"  # went through the bound logger


# -- the in-memory ring and the profiler's timeline --------------------------

def test_ring_keeps_closed_spans_with_root_and_clock(tmp_path):
    """Every span that has a sink also lands in the ring, newest last: the
    JSONL record plus ``root_id`` (shared by every span of the outermost
    one) and ``t_start_ns`` / ``t_end_ns``; children lie inside their root,
    starts and ends are ordered."""
    obs.reset_recent_spans()
    with config.set(trace_dir=str(tmp_path / "t")):
        for _ in range(2):
            with obs.span("call", component="X") as root:
                assert obs.current_span() is root
                with obs.span("call.a"):
                    with obs.span("call.a.deep") as deep:
                        assert obs.current_span() is deep
                with obs.span("call.b"):
                    pass
        assert obs.current_span() is obs.NOOP_SPAN
    ring = obs.recent_spans()
    assert [r["span"] for r in ring] == [
        "call.a.deep", "call.a", "call.b", "call"] * 2      # close order
    # what the sink got, and nothing else (the file sink adds its "time")
    assert ring == [{k: v for k, v in r.items() if k != "time"}
                    for r in _read_jsonl(tmp_path / "t" / "trace.jsonl")]
    first, second = ring[:4], ring[4:]
    for call in (first, second):
        root = call[-1]
        assert root["parent_id"] is None
        assert root["root_id"] == root["span_id"]
        assert {r["root_id"] for r in call} == {root["span_id"]}
        for r in call:
            assert root["t_start_ns"] <= r["t_start_ns"] <= r["t_end_ns"] \
                <= root["t_end_ns"]
            assert abs((r["t_end_ns"] - r["t_start_ns"]) * 1e-9
                       - r["wall_s"]) < 1e-3
        a, b = call[1], call[2]
        assert a["t_end_ns"] <= b["t_start_ns"]               # siblings
        assert call[0]["parent_id"] == a["span_id"]
    assert first[-1]["root_id"] != second[-1]["root_id"]
    assert first[-1]["t_end_ns"] <= second[-1]["t_start_ns"]
    obs.reset_recent_spans()
    assert obs.recent_spans() == []


def test_ring_is_bounded(monkeypatch):
    import collections

    from dask_ml_tpu.observability import _spans

    assert _spans._ring.maxlen == _spans.RING_SIZE == 4096
    monkeypatch.setattr(_spans, "_ring", collections.deque(maxlen=8))
    with config.set(obs_programs=True):
        for i in range(20):
            with obs.span("s", i=i):
                pass
    assert [r["i"] for r in obs.recent_spans()] == list(range(12, 20))


def test_obs_programs_alone_arms_ring_and_annotations(tmp_path):
    """No sink, ``obs_programs`` on: spans record into the ring and onto the
    profiler's timeline (``dmt.<name>``), write no file, and do not count as
    ``recording`` (the stream's readiness syncs stay off)."""
    import jax

    from benchmark import trace_reduce

    obs.reset_recent_spans()
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        with config.set(obs_programs=True, trace_dir="", metrics_path=""):
            with obs.span("armed", component="X") as sp:
                assert sp is not obs.NOOP_SPAN and not sp.recording
                assert sp.sync(5) == 5
                with obs.span("armed.child"):
                    pass
    finally:
        jax.profiler.stop_trace()
    assert [r["span"] for r in obs.recent_spans()] == ["armed.child", "armed"]
    assert obs.recent_spans()[1]["component"] == "X"
    assert [p.name for p in tmp_path.iterdir()] == ["prof"]   # no JSONL
    table = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path / "prof")),
                              host_prefix="dmt.")
    events = {n: (s, s + d) for p in table["planes"] for line in p["lines"]
              for n, s, d in line["events"]}
    assert set(events) == {"dmt.armed", "dmt.armed.child"}
    outer, inner = events["dmt.armed"], events["dmt.armed.child"]
    assert outer[0] <= inner[0] <= inner[1] <= outer[1]
    obs.reset_recent_spans()


def test_new_call_sites_are_noop_when_nothing_listens(monkeypatch):
    """No sink, ``obs_programs`` off (the benchmark's untraced runs): every
    span the resident fit/predict paths open resolves to ``NOOP_SPAN``, the
    ring stays empty, and the natural syncs pass straight through."""
    from dask_ml_tpu.cluster import KMeans
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.observability import _spans
    from dask_ml_tpu.parallel import as_sharded

    opened = []
    enter = _spans.span.__enter__

    def spy(self):
        got = enter(self)
        opened.append((self.name, got))
        return got

    monkeypatch.setattr(_spans.span, "__enter__", spy)
    obs.reset_recent_spans()
    rng = np.random.RandomState(0)
    X = rng.randn(300, 5).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    Xs = as_sharded(X)
    with config.set(obs_programs=False, trace_dir="", metrics_path=""):
        clf = LogisticRegression(solver="lbfgs", max_iter=10).fit(
            Xs, as_sharded(y))
        clf.predict_proba(Xs)
        km = KMeans(n_clusters=2, init="random", random_state=0,
                    max_iter=3).fit(Xs)
        km.predict(Xs)
    assert {n for n, _ in opened} == {
        "fit", "fit.validate", "fit.prepare", "fit.init", "fit.tol_scale",
        "fit.solve", "fit.finish", "predict", "predict.decision",
        "predict.host"}
    assert all(got is obs.NOOP_SPAN for _, got in opened)
    assert obs.recent_spans() == []
    assert clf.solver_info_["n_evals"] >= clf.n_iter_ + 1   # always counted


def test_resident_fit_and_predict_phase_spans():
    """``obs_programs`` on: one root per call, the phases as its children,
    the solver's counts on ``fit.solve``, and the phases' walls sum to the
    root's (what lies between them is span bookkeeping)."""
    from dask_ml_tpu.cluster import KMeans
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.parallel import as_sharded

    rng = np.random.RandomState(0)
    X = rng.randn(300, 5).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    Xs = as_sharded(X)
    obs.reset_recent_spans()
    with config.set(obs_programs=True):
        clf = LogisticRegression(solver="lbfgs", max_iter=10).fit(
            Xs, as_sharded(y))
        clf.predict_proba(Xs)
        km = KMeans(n_clusters=2, init="random", random_state=0,
                    max_iter=3).fit(Xs)
        km.predict(Xs)
    ring = obs.recent_spans()
    obs.reset_recent_spans()
    roots = [r for r in ring if r["parent_id"] is None]
    assert [(r["span"], r["component"]) for r in roots] == [
        ("fit", "LogisticRegression"), ("predict", "LogisticRegression"),
        ("fit", "KMeans"), ("predict", "KMeans")]
    kids = [[r["span"] for r in ring if r["parent_id"] == root["span_id"]]
            for root in roots]
    assert kids == [
        ["fit.validate", "fit.prepare", "fit.solve", "fit.finish"],
        ["predict.decision", "predict.host"],
        ["fit.validate", "fit.init", "fit.tol_scale", "fit.solve",
         "fit.finish"], []]
    assert all(r["root_id"] in {x["span_id"] for x in roots} for r in ring)
    assert all(r["n_rows"] == 300 for r in roots)
    glm_solve = next(r for r in ring if r["span"] == "fit.solve")
    assert glm_solve["n_iter"] == clf.n_iter_ == roots[0]["n_iter"]
    assert glm_solve["n_evals"] == clf.solver_info_["n_evals"]
    assert glm_solve["fused"] is False
    km_solve = [r for r in ring if r["span"] == "fit.solve"][1]
    assert km_solve["n_iter"] == km.n_iter_ and km_solve["fused"] is False
    for root in (roots[0], roots[2]):
        walls = sum(r["wall_s"] for r in ring
                    if r["parent_id"] == root["span_id"])
        assert walls <= root["wall_s"]
        assert root["wall_s"] - walls < 2e-3 + 0.02 * root["wall_s"]


# -- counters ---------------------------------------------------------------

def test_counter_snapshot_and_reset():
    obs.counters_reset()
    obs.counter_add("widgets", 2)
    obs.counter_add("widgets", 3)
    snap = obs.counters_snapshot()
    assert snap["widgets"] == 5
    snap["widgets"] = 99  # snapshot is a copy
    assert obs.counters_snapshot()["widgets"] == 5
    obs.counters_reset()
    assert obs.counters_snapshot() == {}


def test_record_transfer_gated_by_config():
    obs.counters_reset()
    with config.set(obs_counters=False):
        obs.record_transfer(1024)
    assert "h2d_bytes" not in obs.counters_snapshot()
    with config.set(obs_counters=True):
        obs.record_transfer(1024)
        obs.record_donation(512)
    snap = obs.counters_snapshot()
    assert snap["h2d_bytes"] == 1024 and snap["h2d_transfers"] == 1
    assert snap["donated_bytes_reused"] == 512


def test_recompile_counter_increments_on_fresh_compile():
    import jax

    obs.counters_reset()
    with config.set(obs_counters=True):
        # a jit of a brand-new Python lambda can't hit any cache
        jax.jit(lambda x: x * 3 + 1)(np.float32(2.0))
    snap = obs.counters_snapshot()
    assert snap.get("recompiles", 0) >= 1
    assert snap.get("compile_secs", 0) > 0


def test_stream_h2d_bytes_counted():
    from dask_ml_tpu.parallel.streaming import BlockStream

    X = np.random.RandomState(0).rand(512, 4).astype(np.float32)
    obs.counters_reset()
    with config.set(obs_counters=True):
        for blk in BlockStream((X,), block_rows=128):
            pass
    snap = obs.counters_snapshot()
    # every block: X slab + its row mask, all float32
    assert snap["h2d_bytes"] == X.nbytes + 4 * 512
    assert snap["h2d_transfers"] == 4


def test_span_emits_counter_deltas(tmp_path):
    trace = str(tmp_path / "t")
    obs.counters_reset()
    with config.set(trace_dir=trace, obs_counters=True):
        obs.counter_add("pre_existing", 100)
        with obs.span("work"):
            obs.record_transfer(2048)
    rec = _read_jsonl(os.path.join(trace, "trace.jsonl"))[-1]
    assert rec["ctr_h2d_bytes"] == 2048
    assert "ctr_pre_existing" not in rec  # only deltas, not totals


def test_device_memory_gauges_shape():
    gauges = obs.device_memory_gauges()
    assert isinstance(gauges, dict)  # empty on CPU; keyed dev<i>_* on TPU
    for v in gauges.values():
        assert isinstance(v, int)


def test_log_counters_record(tmp_path):
    p = str(tmp_path / "c.jsonl")
    obs.counters_reset()
    obs.counter_add("recompiles", 4)
    with obs.MetricsLogger(p) as lg:
        snap = obs.log_counters(lg, phase="end")
    rec = _read_jsonl(p)[-1]
    assert rec["counters"] is True and rec["recompiles"] == 4
    assert rec["phase"] == "end"
    assert snap["recompiles"] == 4


# -- ambient logger under concurrency --------------------------------------

def test_active_logger_non_lifo_and_concurrent(tmp_path):
    """Two fits binding/unbinding out of LIFO order must each remove
    exactly their own sink entry; the innermost surviving binding keeps
    receiving jit-step callbacks."""
    from dask_ml_tpu.observability._metrics import _active_loggers, _jit_step_cb

    a = obs.MetricsLogger(str(tmp_path / "a.jsonl"), extra={"who": "a"})
    b = obs.MetricsLogger(str(tmp_path / "b.jsonl"), extra={"who": "b"})
    cm_a = obs.active_logger(a)
    cm_b = obs.active_logger(b)
    cm_a.__enter__()
    cm_b.__enter__()
    cm_a.__exit__(None, None, None)  # non-LIFO exit
    assert _active_loggers == [b]
    _jit_step_cb(0, ("loss",), 1.5)
    cm_b.__exit__(None, None, None)
    assert _active_loggers == []
    recs = _read_jsonl(str(tmp_path / "b.jsonl"))
    assert recs and recs[0]["who"] == "b" and recs[0]["loss"] == 1.5
    assert not os.path.exists(str(tmp_path / "a.jsonl"))


def test_concurrent_fits_span_trees_are_threadlocal(tmp_path):
    """Parallel trial threads trace independent span trees: no thread
    ever parents its span under another thread's open span."""
    trace = str(tmp_path / "t")
    errs = []

    def worker(tag):
        try:
            # config.set is thread-local (like dask.config): each trial
            # thread binds its own override, exactly as the controller's
            # worker threads would
            with config.set(trace_dir=trace):
                with obs.span("outer", tag=tag):
                    with obs.span("inner", tag=tag):
                        pass
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    recs = _read_jsonl(os.path.join(trace, "trace.jsonl"))
    outer = {r["tag"]: r for r in recs if r["span"] == "outer"}
    inner = {r["tag"]: r for r in recs if r["span"] == "inner"}
    assert set(outer) == set(inner) == {0, 1, 2, 3}
    for tag, r in inner.items():
        assert r["parent_id"] == outer[tag]["span_id"]
    for r in outer.values():
        assert r["parent_id"] is None


# -- zero overhead ----------------------------------------------------------

def test_no_debug_callback_in_solver_jaxpr_when_disabled():
    """With metrics disabled the solver trace must contain NO host
    callback — the acceptance criterion that the silent path stays at
    hardware speed."""
    import jax
    import jax.numpy as jnp

    from dask_ml_tpu.models.solvers.solvers import _gd_run

    X = jnp.ones((16, 3))
    y = jnp.zeros(16)
    mask = jnp.ones(16)

    def run(log):
        return jax.make_jaxpr(
            lambda X_, y_, m_, b_: _gd_run(
                X_, y_, m_, 16.0, b_, jnp.float32(0.0), jnp.ones(3), 0.5,
                jnp.asarray(3), jnp.float32(1e-6), 1.0, "logistic", "none",
                log=log,
            )
        )(X, y, mask, jnp.zeros(3))

    assert "debug_callback" not in str(run(False))
    assert "debug_callback" in str(run(True))


def test_program_registry_and_watchdog_add_nothing_when_disabled():
    """ISSUE 4 extension of the zero-overhead contract: with
    obs_programs/watchdog_timeout_s at their defaults, the tracked
    solver entry points trace to the IDENTICAL jaxpr (the tracker lives
    outside jit and must stay there), the program registry stays empty,
    and no watchdog thread exists."""
    import jax
    import jax.numpy as jnp

    from dask_ml_tpu.models.solvers.solvers import _gd_run

    X = jnp.ones((16, 3))
    y = jnp.zeros(16)
    mask = jnp.ones(16)

    def jaxpr():
        return str(jax.make_jaxpr(
            lambda X_, y_, m_, b_: _gd_run(
                X_, y_, m_, 16.0, b_, jnp.float32(0.0), jnp.ones(3), 0.5,
                jnp.asarray(3), jnp.float32(1e-6), 1.0, "logistic",
                "none", log=False,
            )
        )(X, y, mask, jnp.zeros(3)))

    obs.programs_reset()
    with config.set(obs_programs=False, watchdog_timeout_s=0.0):
        baseline = jaxpr()
        assert "debug_callback" not in baseline
        assert obs.programs_snapshot() == []   # tracker never recorded
        assert not obs.watchdog_active()       # no thread armed
        from dask_ml_tpu.observability import watchdog

        with watchdog() as wd:                 # config-gated: a no-op
            assert wd is None
            assert jaxpr() == baseline         # nothing entered the trace
        assert not obs.watchdog_active()
    # the tracker is transparent: the jit object stays reachable and the
    # raw body unwrap (used by super-block reducers) still lands on the
    # plain function
    assert hasattr(_gd_run, "__wrapped_jit__")
    assert not hasattr(_gd_run.__wrapped__, "__wrapped__")


def test_live_plane_adds_nothing_when_port_unset():
    """ISSUE 5 extension of the zero-overhead contract: with
    obs_http_port at its 0 default the live telemetry plane is inert —
    no exporter thread, no span observer, every publish call a bool
    check, the gauge/histogram registry untouched by a streamed SGD
    pass, and the streamed scan kernel's jaxpr byte-identical whether
    or not a server ever existed in the process."""
    import jax
    import jax.numpy as jnp

    from dask_ml_tpu.models.sgd import (SGDClassifier,
                                        _sgd_stream_program)
    from dask_ml_tpu.observability import live
    from dask_ml_tpu.observability._programs import unwrap
    from dask_ml_tpu.observability._spans import _span_observers

    def scan_jaxpr():
        body = unwrap(_sgd_stream_program(None, "xla", "hinge", False))
        K, S, d = 2, 8, 3
        return str(jax.make_jaxpr(
            lambda W, Xs, ys, c, lrs: body(
                W, (Xs,), ys, c, lrs, 1e-4, 1.0, 0.0, 1.0
            )
        )(jnp.zeros(d + 1), jnp.zeros((K, S, d)), jnp.zeros((K, S)),
          jnp.zeros(K, jnp.int32), jnp.zeros(K)))

    assert live.telemetry_server() is None
    assert not live.live_publishing()
    baseline = scan_jaxpr()
    live.metrics_reset()
    rng = np.random.RandomState(0)
    X = rng.randn(4096, 6).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    with config.set(stream_block_rows=512):
        SGDClassifier(max_iter=2, random_state=0).fit(X, y)
    # the fit registered nothing with the live plane...
    from dask_ml_tpu.observability import _spans

    assert live.gauges_snapshot() == {}
    assert live.histograms_snapshot() == {}
    assert _span_observers == [] and _spans._armed_trackers == 0
    assert live.telemetry_server() is None
    # ...and a server's life cycle leaves the traced program unchanged
    # (the plane lives entirely outside jit)
    with obs.TelemetryServer(port=0):
        assert scan_jaxpr() == baseline
    assert scan_jaxpr() == baseline
    live.metrics_reset()


def test_drift_plane_adds_nothing_when_disabled():
    """ISSUE 7 extension of the zero-overhead contract: with
    ``obs_drift`` off, a streamed SGD fit allocates NO sketch, attaches
    no profile, arms no monitor thread, registers nothing with the
    drift engine — and the streamed scan kernel's jaxpr is
    byte-identical (trivially guaranteed: the quality plane is host
    numpy that never imports jax, but the assertion pins it)."""
    import jax
    import jax.numpy as jnp

    from dask_ml_tpu.models.sgd import (SGDClassifier,
                                        _sgd_stream_program)
    from dask_ml_tpu.observability import drift
    from dask_ml_tpu.observability._programs import unwrap

    def scan_jaxpr():
        body = unwrap(_sgd_stream_program(None, "xla", "hinge", False))
        K, S, d = 2, 8, 3
        return str(jax.make_jaxpr(
            lambda W, Xs, ys, c, lrs: body(
                W, (Xs,), ys, c, lrs, 1e-4, 1.0, 0.0, 1.0
            )
        )(jnp.zeros(d + 1), jnp.zeros((K, S, d)), jnp.zeros((K, S)),
          jnp.zeros(K, jnp.int32), jnp.zeros(K)))

    drift.reset()
    baseline = scan_jaxpr()
    rng = np.random.RandomState(0)
    X = rng.randn(4096, 6).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    with config.set(stream_block_rows=512, obs_drift=False):
        est = SGDClassifier(max_iter=2, random_state=0).fit(X, y)
        assert est.training_profile_ is None
        assert scan_jaxpr() == baseline
    assert not drift.monitor_active()
    assert drift.status_block() == {
        "scores": [], "canaries": [], "serving_sketches": [],
        "training_profiles": [],
    }
    # with the default (on), the profile is host-side only: the traced
    # program STILL cannot change — sketch.py/drift.py never import jax
    with config.set(stream_block_rows=512):
        SGDClassifier(max_iter=1, random_state=0).fit(X, y)
        assert scan_jaxpr() == baseline
    drift.reset()


def test_trace_plane_adds_nothing_when_disabled():
    """ISSUE 16 extension of the zero-overhead contract: the request
    trace plane is pure host bookkeeping — a full traced server
    lifecycle (sample=1.0) and an untraced one (the 0 default) leave
    the serving entry point's jaxpr byte-identical, and with the plane
    off no trace is ever allocated and no sampler state moves."""
    import jax
    import jax.numpy as jnp

    from dask_ml_tpu.observability import _requests as rtrace
    from dask_ml_tpu.serving import BucketLadder, ModelServer
    from dask_ml_tpu.wrappers import _linear_core

    def serve_jaxpr():
        core = _linear_core("classify", multi=False)
        p = {"W": jnp.zeros((1, 6)), "b": jnp.zeros(1)}
        return str(jax.make_jaxpr(core)(p, jnp.zeros((8, 6))))

    from dask_ml_tpu.datasets import make_classification
    from dask_ml_tpu.linear_model import LogisticRegression

    X, y = make_classification(
        n_samples=300, n_features=6, n_informative=4, random_state=0
    )
    clf = LogisticRegression(solver="lbfgs", max_iter=20).fit(X, y)
    Xh = X.to_numpy().astype(np.float32)

    rtrace.traces_reset()
    assert not rtrace.tracing_enabled()
    baseline = serve_jaxpr()
    ladder = BucketLadder(8, 64, 2.0)
    # traced lifecycle: the plane records on the host, the program
    # can't see it
    with config.set(obs_trace_sample=1.0):
        assert rtrace.tracing_enabled()
        with ModelServer(clf, ladder=ladder) as srv:
            srv.warmup()
            srv.submit(Xh[:4]).result(10)
            assert serve_jaxpr() == baseline
    assert rtrace.traces_data()["counts"]["completed"] == 1
    rtrace.traces_reset()
    # untraced lifecycle: nothing allocated, nothing counted, same
    # program
    with ModelServer(clf, ladder=ladder) as srv:
        assert srv._trace_on is False
        srv.warmup()
        f = srv.submit(Xh[:4])
        # the queue entry never grew a trace
        f.result(10)
        assert serve_jaxpr() == baseline
    d = rtrace.traces_data()
    assert d["counts"] == {"started": 0, "completed": 0, "sampled": 0,
                           "captured": 0}
    assert d["traces"] == [] and d["stage_histograms"] == {}
    assert serve_jaxpr() == baseline


def test_fleet_plane_adds_nothing_when_disabled():
    """ISSUE 19 extension of the zero-overhead contract: the fleet
    observability plane (trace propagation + metrics federation) is
    host-side bookkeeping riding threads the federation already owns —
    a federated lifecycle with federation ON leaves the serving entry
    point's jaxpr byte-identical and compiles nothing new, and the
    default (federation OFF) builds no federator, registers no
    provider, and spawns no extra thread."""
    import threading

    import jax
    import jax.numpy as jnp

    from dask_ml_tpu.observability import live
    from dask_ml_tpu.serving import (
        BucketLadder,
        FederatedFleet,
        FleetServer,
        LocalEndpoint,
    )
    from dask_ml_tpu.wrappers import _linear_core

    def serve_jaxpr():
        core = _linear_core("classify", multi=False)
        p = {"W": jnp.zeros((1, 6)), "b": jnp.zeros(1)}
        return str(jax.make_jaxpr(core)(p, jnp.zeros((8, 6))))

    from dask_ml_tpu.datasets import make_classification
    from dask_ml_tpu.linear_model import LogisticRegression

    X, y = make_classification(
        n_samples=300, n_features=6, n_informative=4, random_state=0
    )
    clf = LogisticRegression(solver="lbfgs", max_iter=20).fit(X, y)
    Xh = X.to_numpy().astype(np.float32)

    baseline = serve_jaxpr()
    ladder = BucketLadder(8, 64, 2.0)
    fleet = FleetServer(clf, name="zf", replicas=1, ladder=ladder,
                        batch_window_ms=1.0).warmup().start()
    try:
        before = obs.counters_snapshot().get("recompiles", 0)
        # federation + propagation ON: everything stays on the host
        with config.set(obs_fleet_federate=True, obs_trace_sample=1.0):
            with FederatedFleet([LocalEndpoint(fleet, "p0")],
                                name="zf", ladder=ladder) as fed:
                assert fed._federator is not None
                fed._poll_once()
                fed.predict(Xh[:8])
                assert serve_jaxpr() == baseline
        assert obs.counters_snapshot().get("recompiles", 0) == before
        # the default: no federator object, no provider registration,
        # no fleet_ families on /metrics, and no thread beyond the
        # poller + submit pool the federation owns anyway
        names_before = {t.name for t in threading.enumerate()}
        with FederatedFleet([LocalEndpoint(fleet, "p0")],
                            name="zf", ladder=ladder) as fed:
            assert fed._federator is None
            assert not live._fleet_providers
            assert "dask_ml_tpu_fleet_" not in live.render_prometheus()
            new = {t.name for t in threading.enumerate()} - names_before
            assert all(n.startswith(("fed-poller", "fed-submit"))
                       for n in new), new
        assert serve_jaxpr() == baseline
    finally:
        fleet.stop(drain=False)
        from dask_ml_tpu.observability import _requests as rtrace

        rtrace.traces_reset()


# -- back-compat shim -------------------------------------------------------

def test_utils_observability_reexports_same_objects():
    from dask_ml_tpu.observability import _metrics
    from dask_ml_tpu.utils import observability as legacy

    assert legacy.MetricsLogger is obs.MetricsLogger
    assert legacy.active_logger is obs.active_logger
    assert legacy.emit_jit_step is obs.emit_jit_step
    assert legacy.fit_logger is obs.fit_logger
    assert legacy.timed is obs.timed
    # the mutable sink registry must be the SAME list object: the shim
    # and streaming.py bind it through different import paths
    assert legacy._active_loggers is _metrics._active_loggers


# -- report CLI -------------------------------------------------------------

@pytest.fixture
def canned_run(tmp_path):
    """A canned JSONL run: two fit spans, stream passes, step records,
    and a final counters snapshot."""
    p = str(tmp_path / "run.jsonl")
    recs = [
        {"time": 0.1, "span": "fit", "span_id": 1, "parent_id": None,
         "depth": 0, "wall_s": 2.0, "sync_s": 0.5,
         "component": "KMeans", "n_rows": 10000, "n_iter": 7},
        {"time": 0.2, "span": "stream.pass", "span_id": 3, "parent_id": 2,
         "depth": 1, "wall_s": 0.5, "sync_s": 0.0},
        {"time": 0.3, "span": "fit", "span_id": 2, "parent_id": None,
         "depth": 0, "wall_s": 1.0, "sync_s": 0.1,
         "component": "LogisticRegression", "n_rows": 5000},
        {"time": 0.4, "component": "KMeans", "step": 0,
         "center_shift2": 9.0},
        {"time": 0.5, "component": "KMeans", "step": 1,
         "center_shift2": 0.25},
        {"time": 0.6, "component": "LogisticRegression", "step": 0,
         "loss": 0.693, "grad_norm": 1.0},
        {"time": 0.7, "component": "LogisticRegression", "step": 1,
         "loss": 0.21, "grad_norm": 0.05},
        {"time": 0.8, "stream_pass": 1, "host_s": 0.2, "put_s": 0.1,
         "wait_s": 0.01, "consume_s": 0.4, "pass_s": 0.71, "n_blocks": 8,
         "block_rows": 1250},
        {"time": 0.9, "counters": True, "recompiles": 12,
         "h2d_bytes": 40960000, "h2d_transfers": 8},
    ]
    with open(p, "w") as fh:
        fh.write("\n".join(json.dumps(r) for r in recs) + "\n")
        fh.write("{corrupt trailing line")  # must be skipped, not fatal
    return p


def test_report_build(canned_run):
    from dask_ml_tpu.observability.report import build_report, load_records

    records = load_records(canned_run)
    assert len(records) == 9  # corrupt line skipped
    out = build_report(records, path=canned_run)
    assert "KMeans.fit" in out
    assert "LogisticRegression.fit" in out
    assert "5,000" in out  # 5000 rows / 1.0s
    assert "center_shift2: 9 -> 0.25" in out
    assert "loss: 0.693 -> 0.21" in out
    assert "recompiles" in out and "12" in out
    assert "39.1MiB" in out  # h2d_bytes rendered human-readable
    assert "streaming overlap" in out


def test_report_cli_main(canned_run, capsys):
    from dask_ml_tpu.observability import report

    rc = report.main([canned_run])
    assert rc == 0
    out = capsys.readouterr().out
    assert "KMeans.fit" in out and "recompiles" in out


def test_report_cli_missing_file(tmp_path, capsys):
    from dask_ml_tpu.observability import report

    rc = report.main([str(tmp_path / "nope.jsonl")])
    assert rc == 1
    assert "cannot read" in capsys.readouterr().err


def test_report_cli_runs_as_module(canned_run):
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "dask_ml_tpu.observability.report",
         canned_run],
        capture_output=True, text=True, cwd=repo,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "KMeans.fit" in proc.stdout


# -- end-to-end: spans from a real fit --------------------------------------

def test_fit_emits_span_with_samples_per_sec(tmp_path):
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.parallel import as_sharded

    rng = np.random.RandomState(0)
    X = rng.randn(300, 5).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    p = str(tmp_path / "fit.jsonl")
    with config.set(metrics_path=p):
        LogisticRegression(solver="lbfgs", max_iter=10).fit(
            as_sharded(X), as_sharded(y)
        )
    spans = [r for r in _read_jsonl(p) if r.get("span") == "fit"]
    assert len(spans) == 1
    rec = spans[0]
    assert rec["component"] == "LogisticRegression"
    assert rec["n_rows"] == 300 and rec["wall_s"] > 0
    assert rec["n_iter"] >= 1


def test_streamed_fit_nests_pass_spans_under_fit(tmp_path):
    from dask_ml_tpu.linear_model import LinearRegression

    rng = np.random.RandomState(1)
    X = rng.randn(600, 4).astype(np.float32)
    y = (X @ rng.randn(4)).astype(np.float32)
    p = str(tmp_path / "stream.jsonl")
    with config.set(metrics_path=p, stream_block_rows=150):
        LinearRegression(solver="gradient_descent", max_iter=3).fit(X, y)
    recs = _read_jsonl(p)
    fits = [r for r in recs if r.get("span") == "fit"]
    # per-block passes trace stream.pass; super-block passes (the
    # default when K > 1) trace streaming.superblock — both are
    # stream_pass-keyed pass records nested under the fit
    passes = [r for r in recs
              if r.get("span") in ("stream.pass", "streaming.superblock")]
    assert len(fits) == 1 and fits[0]["streamed"] is True
    assert passes, "streamed fit must trace stream pass spans"
    assert all(r["parent_id"] == fits[0]["span_id"] for r in passes)
    assert all("stream_pass" in r for r in passes)


def test_search_round_spans_and_trial_tags(tmp_path):
    from dask_ml_tpu.model_selection import HyperbandSearchCV
    from dask_ml_tpu.models.sgd import SGDClassifier

    rng = np.random.RandomState(3)
    X = rng.randn(300, 5).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    p = str(tmp_path / "hb.jsonl")
    with config.set(metrics_path=p):
        search = HyperbandSearchCV(
            SGDClassifier(random_state=0),
            {"alpha": [1e-4, 1e-3, 1e-2]},
            max_iter=4, random_state=0,
        ).fit(X, y, classes=[0.0, 1.0])
    recs = _read_jsonl(p)
    # a round is a RECORD, not a span: one ``search.round`` event line in
    # the fit's logger with the fields the span had and its wall and sync
    # time, and the same entry in ``search_info_["rounds"]``
    assert not [r for r in recs if r.get("span") == "search.round"]
    rounds = [r for r in recs if r.get("event") == "search.round"]
    info = search.search_info_
    assert len(rounds) == info["n_rounds"] == len(info["rounds"]) >= 2
    for line, rec in zip(rounds, info["rounds"]):
        assert line["component"] == "adaptive_search"
        for k in ("round", "n_trials", "n_calls", "wall_s", "sync_s"):
            assert line[k] == pytest.approx(rec[k])
        assert rec["groups"] and sum(
            g["model_steps"] for g in rec["groups"]) == rec["n_calls"]
    # the search's spans: one root, four flat children, no other
    spans = [r for r in recs if "span_id" in r and r.get("span", "")
             .startswith("fit")]
    root = [r for r in spans if r["span"] == "fit"]
    assert len(root) == 1 and root[0]["component"] == "HyperbandSearchCV"
    assert root[0]["n_iter"] == info["n_rounds"]
    assert sorted(r["span"] for r in spans if r["parent_id"]
                  == root[0]["span_id"]) == [
        "fit.finish", "fit.prepare", "fit.solve", "fit.validate"]
    trials = [r for r in recs
              if r.get("component") == "adaptive_search"
              and "model_id" in r]
    assert trials
    for r in trials:
        assert "bracket" in r and "partial_fit_calls" in r and "score" in r
