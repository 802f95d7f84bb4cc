"""Flight recorder (ISSUE 4): compiled-program registry (cost/memory
attribution + measured MFU), stall watchdog, Perfetto export, and the
report CLI's --json / programs / double-count fixes."""

import json
import os
import threading
import time

import numpy as np
import pytest

from dask_ml_tpu import config, observability as obs
from dask_ml_tpu.observability.report import (build_report, final_counters,
                                              load_records, report_data,
                                              summarize_spans)


def _read_jsonl(path):
    return [json.loads(line) for line in open(path)]


@pytest.fixture(autouse=True)
def _clean_registry():
    obs.programs_reset()
    obs.counters_reset()
    yield
    obs.programs_reset()


# -- program registry --------------------------------------------------------

def _tracked_matmul(name="test.matmul"):
    import jax

    @obs.track_program(name)
    @jax.jit
    def mm(a, b):
        return a @ b

    return mm


def test_track_program_records_compile_cost_and_calls():
    mm = _tracked_matmul()
    a = np.ones((16, 8), np.float32)
    with config.set(obs_programs=True):
        mm(a, a.T)
        mm(a, a.T)   # warm call: no new compile
    snap = obs.programs_snapshot()
    assert len(snap) == 1
    p = snap[0]
    assert p["program"] == "test.matmul"
    assert p["compiles"] == 1 and p["calls"] == 2
    assert p["compile_s"] > 0
    # XLA's measured cost: 2*16*8*16 FLOPs for the (16,8)x(8,16) matmul
    assert p["flops_per_call"] == pytest.approx(2 * 16 * 8 * 16)
    assert p["flops_total"] == pytest.approx(2 * p["flops_per_call"])
    assert p["hbm_peak_bytes"] and p["hbm_peak_bytes"] > 0
    assert p["exec_s"] > 0


def test_track_program_disabled_is_passthrough_and_records_nothing():
    mm = _tracked_matmul("test.disabled")
    a = np.ones((4, 4), np.float32)
    with config.set(obs_programs=False):
        out = mm(a, a)
    assert np.allclose(np.asarray(out), a @ a)
    assert obs.programs_snapshot() == []


def test_track_program_new_shape_is_new_compile():
    mm = _tracked_matmul("test.shapes")
    with config.set(obs_programs=True):
        mm(np.ones((4, 4), np.float32), np.ones((4, 4), np.float32))
        mm(np.ones((8, 4), np.float32), np.ones((4, 4), np.float32))
    p = obs.programs_snapshot()[0]
    assert p["compiles"] == 2 and p["calls"] == 2


def test_track_program_credits_each_shape_its_own_flops():
    """One program name spans many specializations (the serving bucket
    ladder): each call must be credited ITS shape's FLOPs, not the
    latest-compiled shape's, and a compiling call's wall (trace +
    compile) must not pollute exec_s."""
    mm = _tracked_matmul("test.buckets")
    small = np.ones((8, 4), np.float32)    # 2*8*4*8  = 512 F
    big = np.ones((64, 4), np.float32)     # 2*64*4*64 = 32768 F
    with config.set(obs_programs=True):
        mm(small, small.T)
        mm(big, big.T)      # latest compile is the BIG shape
        mm(small, small.T)  # must still be credited 512, not 32768
    p = obs.programs_snapshot()[0]
    assert p["compiles"] == 2 and p["calls"] == 3
    assert p["flops_total"] == pytest.approx(512 * 2 + 32768)
    assert "_by_shape" not in p  # internals stay out of snapshots


def test_track_program_preserves_raw_body_unwrap():
    """Super-block reducers lift block-kernel BODIES into their scans
    via ``.__wrapped__`` — the tracker must keep that unwrap landing on
    the raw Python function, with the jit still reachable."""
    from dask_ml_tpu.models.solvers.streamed import _block_val_grad

    raw = _block_val_grad.__wrapped__
    assert not hasattr(raw, "__wrapped__")       # the plain function
    assert callable(_block_val_grad.__wrapped_jit__)
    assert hasattr(_block_val_grad, "_cache_size")


def test_program_flops_counter_feeds_span_deltas(tmp_path):
    """A span enclosing tracked-program calls carries the
    ctr_program_flops delta — the raw material of per-span MFU."""
    mm = _tracked_matmul("test.span_flops")
    a = np.ones((16, 8), np.float32)
    trace = str(tmp_path / "t")
    with config.set(trace_dir=trace, obs_programs=True,
                    obs_counters=True):
        mm(a, a.T)  # compile + analyze OUTSIDE the span
        with obs.span("work"):
            mm(a, a.T)
            mm(a, a.T)
    rec = [r for r in _read_jsonl(os.path.join(trace, "trace.jsonl"))
           if r.get("span") == "work"][-1]
    assert rec["ctr_program_flops"] == pytest.approx(2 * 2 * 16 * 8 * 16)


def test_solver_fit_populates_registry():
    from dask_ml_tpu.linear_model import LogisticRegression

    rng = np.random.RandomState(0)
    X = rng.randn(200, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    with config.set(obs_programs=True):
        LogisticRegression(solver="lbfgs", max_iter=5).fit(X, y)
    names = {p["program"] for p in obs.programs_snapshot()}
    assert "glm.lbfgs" in names
    p = [p for p in obs.programs_snapshot()
         if p["program"] == "glm.lbfgs"][0]
    assert p["compiles"] >= 1 and p["flops_per_call"]


# -- peak table ---------------------------------------------------------------

def test_peak_table_exact_kinds_and_unknown_device_raises():
    """Peaks are keyed by the exact device_kind, carry their source,
    and a device that is not in the table (this CPU included) is an
    error — never a matmul timed on the spot."""
    from dask_ml_tpu.observability._peak import (
        UnknownDeviceError, mfu_fields, peak_for, resolve_peak,
    )

    peak = peak_for("TPU v5 lite")
    assert peak["flops"] == 197e12 and "TPU v5e" in peak["source"]
    # half the peak's worth of work in 1s -> mfu 0.5 exactly
    f = mfu_fields(peak["flops"] / 2.0, 1.0, 1, peak)
    assert f["mfu"] == pytest.approx(0.5, rel=1e-3)
    for kind in ("cpu", "TPU v5", "v5 lite", "tpu v5 lite"):
        with pytest.raises(UnknownDeviceError, match="no published peak"):
            peak_for(kind)
    with pytest.raises(UnknownDeviceError):
        resolve_peak()  # tier-1 runs on the CPU backend


# -- watchdog -----------------------------------------------------------------

def test_watchdog_dumps_stalled_span_and_fit_completes(tmp_path):
    """The acceptance fixture: a span sleeping past watchdog_timeout_s
    produces a watchdog record with thread tracebacks + memory gauges
    while the enclosing work completes normally."""
    trace = str(tmp_path / "t")
    stalls = []
    with config.set(trace_dir=trace, watchdog_timeout_s=0.2):
        with obs.watchdog(on_stall=stalls.append, poll_s=0.05):
            with obs.span("stalled.fixture", n_rows=7) as sp:
                time.sleep(0.7)
                sp.add(done=True)
        finished = True
    assert finished and stalls  # the "fit" was never killed
    recs = _read_jsonl(os.path.join(trace, "trace.jsonl"))
    wd = [r for r in recs if r.get("watchdog")]
    assert len(wd) == 1  # reported once, not once per poll
    r = wd[0]
    assert r["span"] == "stalled.fixture"
    assert r["age_s"] >= 0.2 and r["timeout_s"] == 0.2
    # all-thread tracebacks, including the sleeping one; the stalled
    # thread's OWN stack is resolved by ident (same-named threads must
    # not shadow it)
    assert r["stacks"] and any(
        "time.sleep" in "\n".join(st) for st in r["stacks"].values()
    )
    assert "time.sleep" in "\n".join(r["stalled_stack"])
    # the open-span stack names the stalled span
    assert any(s["span"] == "stalled.fixture" for s in r["open_spans"])
    # memory gauges rode along (empty dict -> no dev* keys on CPU; the
    # call itself must not have been skipped: gauge keys are dev<i>_*)
    assert isinstance(obs.device_memory_gauges(), dict)
    # ...and the span itself closed normally afterwards
    closed = [x for x in recs if x.get("span") == "stalled.fixture"
              and "wall_s" in x]
    assert closed and closed[0]["done"] is True


def test_watchdog_catches_sinkless_spans():
    """The hung-run scenario: NO metrics_path/trace_dir configured
    (bench's timed fits), watchdog armed — a stalled span must still
    reach the on_stall callback. Sinkless tracked spans emit no record
    and, once the watchdog disarms, spans revert to the no-op."""
    stalls = []
    with config.set(trace_dir="", metrics_path="",
                    watchdog_timeout_s=0.15):
        with obs.watchdog(on_stall=stalls.append, poll_s=0.03):
            with obs.span("sinkless.stall") as sp:
                assert sp is not obs.NOOP_SPAN  # tracked for the watchdog
                time.sleep(0.5)
        assert stalls and stalls[0]["span"] == "sinkless.stall"
        # disarmed again: back to the zero-cost no-op
        with obs.span("after") as sp:
            assert sp is obs.NOOP_SPAN
        assert obs.open_spans_snapshot() == []


def test_stream_wait_measure_not_flipped_by_sinkless_watchdog():
    """A watchdog-tracked (sinkless) pass span must NOT switch on the
    per-block readiness syncs — that would perturb the timed runs the
    watchdog observes. wait_s stays unmeasured (0.0) without a sink."""
    from dask_ml_tpu.parallel.streaming import BlockStream

    X = np.random.RandomState(0).rand(512, 4).astype(np.float32)
    with config.set(trace_dir="", metrics_path="",
                    watchdog_timeout_s=30.0):
        with obs.watchdog(poll_s=0.05):
            s = BlockStream((X,), block_rows=128)
            for _ in s:
                pass
    assert s.stats["wait_s"] == 0.0


def test_export_counters_top_level_spans_only():
    """Nested ctr_* deltas are already contained in their parent's —
    the cumulative counter track must not sum both."""
    from dask_ml_tpu.observability.export import to_chrome_trace

    recs = [
        {"span": "pass", "span_id": 2, "parent_id": 1, "t_unix": 10.1,
         "wall_s": 0.1, "thread": "m", "ctr_h2d_bytes": 512},
        {"span": "fit", "span_id": 1, "parent_id": None, "t_unix": 10.2,
         "wall_s": 0.3, "thread": "m", "ctr_h2d_bytes": 512},
    ]
    events = to_chrome_trace(recs)["traceEvents"]
    tracks = [e for e in events if e["ph"] == "C"
              and e["name"] == "h2d_bytes"]
    assert len(tracks) == 1
    assert tracks[0]["args"]["h2d_bytes"] == 512  # not 1024


def test_report_cli_perfetto_rejects_multiple_inputs(tmp_path, capsys):
    from dask_ml_tpu.observability import report

    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for p in (a, b):
        open(p, "w").write("{}\n")
    rc = report.main([a, b, "--perfetto", str(tmp_path / "o.json")])
    assert rc == 2
    assert "exactly one input" in capsys.readouterr().err


def test_watchdog_noop_when_disabled():
    with config.set(watchdog_timeout_s=0.0):
        with obs.watchdog() as wd:
            assert wd is None
        assert not obs.watchdog_active()
    # a DIRECT Watchdog(0).start() must honor the same disable
    # semantics, not arm a poller whose deadline every span exceeds
    wd = obs.Watchdog(0.0).start()
    assert not obs.watchdog_active()
    wd.stop()


def test_report_of_watchdog_only_records_is_not_empty():
    """A killed hung run leaves ONLY watchdog records (its spans never
    closed): the report must render the stalls table without the
    contradictory 'no observability records found' epilogue."""
    recs = [{"watchdog": True, "span": "fit", "thread": "MainThread",
             "age_s": 12.5, "timeout_s": 5.0,
             "stacks": {"MainThread#1": ["frame"]}}]
    out = build_report(recs)
    assert "watchdog stalls" in out
    assert "no observability records found" not in out


def test_watchdog_dump_reaches_bound_logger(tmp_path):
    """A run recording through a thread-bound MetricsLogger only (no
    metrics_path/trace_dir): the watchdog thread cannot see the fitting
    thread's thread-local binding, so the dump falls back to the
    innermost GLOBAL binding — same best-available-guess as the jit
    callback threads."""
    p = str(tmp_path / "m.jsonl")
    with config.set(trace_dir="", metrics_path="",
                    watchdog_timeout_s=0.15):
        with obs.MetricsLogger(p) as lg, obs.active_logger(lg):
            with obs.watchdog(poll_s=0.03):
                with obs.span("bound.stall"):
                    time.sleep(0.5)
    wd = [r for r in _read_jsonl(p) if r.get("watchdog")]
    assert wd and wd[0]["span"] == "bound.stall"


def test_watchdog_callback_never_kills_the_fit(tmp_path):
    def bad_callback(rec):
        raise RuntimeError("observer crash")

    with config.set(trace_dir=str(tmp_path / "t"),
                    watchdog_timeout_s=0.1):
        with obs.watchdog(on_stall=bad_callback, poll_s=0.02):
            with obs.span("s"):
                time.sleep(0.3)


def test_open_spans_snapshot_tracks_nesting(tmp_path):
    with config.set(trace_dir=str(tmp_path / "t")):
        with obs.span("outer"):
            with obs.span("inner"):
                snap = obs.open_spans_snapshot()
                names = [s["span"] for s in snap]
                assert names == ["outer", "inner"]  # oldest first
                assert all(s["thread"] == threading.current_thread().name
                           for s in snap)
        assert obs.open_spans_snapshot() == []


def test_serving_worker_runs_under_watchdog(tmp_path):
    """A wedged batch execution dumps diagnostics from the serving
    worker thread — wire-through test via a slow host estimator."""
    from dask_ml_tpu.serving import ModelServer

    class SlowModel:
        n_features_in_ = 3

        def predict(self, X):
            time.sleep(0.5)
            return np.zeros(len(X))

    trace = str(tmp_path / "t")
    with config.set(trace_dir=trace, watchdog_timeout_s=0.15):
        with ModelServer(SlowModel(), methods=("predict",)) as srv:
            srv.predict(np.ones((4, 3), np.float32))
    recs = _read_jsonl(os.path.join(trace, "trace.jsonl"))
    wd = [r for r in recs if r.get("watchdog")]
    assert wd and wd[0]["span"] == "serving.batch"


# -- perfetto export ----------------------------------------------------------

def _schema_check_chrome_trace(trace):
    assert isinstance(trace, dict)
    events = trace["traceEvents"]
    assert isinstance(events, list) and events
    for ev in events:
        assert isinstance(ev["name"], str)
        assert ev["ph"] in ("X", "C", "M", "i")
        assert isinstance(ev["pid"], int)
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
            assert isinstance(ev["tid"], int)
        elif ev["ph"] == "C":
            assert len(ev["args"]) == 1
    return events


def test_export_span_tree_to_chrome_trace(tmp_path):
    trace = str(tmp_path / "t")
    with config.set(trace_dir=trace, obs_counters=True):
        with obs.span("outer", component="M", n_rows=10):
            obs.record_transfer(1024)
            with obs.span("inner"):
                time.sleep(0.01)
    records = load_records(os.path.join(trace, "trace.jsonl"))
    from dask_ml_tpu.observability.export import to_chrome_trace

    events = _schema_check_chrome_trace(to_chrome_trace(records))
    xs = {e["name"]: e for e in events if e["ph"] == "X"}
    assert "M.outer" in xs and "inner" in xs
    out, inn = xs["M.outer"], xs["inner"]
    # containment: the child nests inside the parent on the timeline
    assert out["ts"] <= inn["ts"]
    assert out["ts"] + out["dur"] >= inn["ts"] + inn["dur"]
    # counter deltas became a counter track
    assert any(e["ph"] == "C" and e["name"] == "h2d_bytes"
               for e in events)


def test_export_counter_and_step_records(tmp_path):
    p = str(tmp_path / "m.jsonl")
    recs = [
        {"time": 0.1, "component": "KMeans", "step": 0, "inertia": 9.0},
        {"time": 0.2, "component": "KMeans", "step": 1, "inertia": 4.0},
        {"time": 0.3, "counters": True, "recompiles": 3,
         "phase": "end"},  # stray string field must not crash
        {"time": 0.4, "span": "fit", "span_id": 1, "parent_id": None,
         "t_unix": 1000.4, "wall_s": 0.3, "sync_s": 0.0,
         "thread": "MainThread"},
    ]
    with open(p, "w") as fh:
        fh.write("\n".join(json.dumps(r) for r in recs) + "\n")
    from dask_ml_tpu.observability.export import write_chrome_trace

    out = str(tmp_path / "trace.json")
    trace = write_chrome_trace(load_records(p), out)
    _schema_check_chrome_trace(trace)
    reloaded = json.load(open(out))  # valid JSON on disk
    names = {e["name"] for e in reloaded["traceEvents"]}
    assert "KMeans.inertia" in names and "recompiles" in names


def test_report_cli_perfetto_flag(tmp_path, capsys):
    from dask_ml_tpu.observability import report

    trace = str(tmp_path / "t")
    with config.set(trace_dir=trace):
        with obs.span("fit", component="X", n_rows=5):
            pass
    out = str(tmp_path / "out.json")
    rc = report.main([os.path.join(trace, "trace.jsonl"),
                      "--perfetto", out])
    assert rc == 0
    captured = capsys.readouterr()
    # status line on stderr: --json's stdout must stay machine-readable
    # when the flags combine
    assert "perfetto" in captured.err and captured.out == ""
    trace_obj = json.load(open(out))
    _schema_check_chrome_trace(trace_obj)


# -- report: --json, hardening, double-count fix ------------------------------

def test_report_json_flag_round_trips(tmp_path, capsys):
    from dask_ml_tpu.observability import report

    trace = str(tmp_path / "t")
    with config.set(trace_dir=trace, obs_counters=True):
        with obs.span("fit", component="M", n_rows=100):
            obs.record_transfer(512)
    rc = report.main([os.path.join(trace, "trace.jsonl"), "--json"])
    assert rc == 0
    data = json.loads(capsys.readouterr().out)
    assert data["spans"][0]["span"] == "M.fit"
    assert data["counters"]["h2d_bytes"] == 512
    assert data["records"] >= 1 and data["path"].endswith("trace.jsonl")


def test_final_counters_drops_non_numeric_fields():
    recs = [{"counters": True, "recompiles": 2, "h2d_bytes": 100,
             "phase": "end", "run": "r1", "ok": True}]
    ctr = final_counters(recs)
    assert ctr == {"recompiles": 2, "h2d_bytes": 100}


def test_summarize_spans_no_double_count_nested_same_group():
    """A nested span of the SAME group (pass inside fit relabeled as
    fit, a retry inside a pass) sits inside its ancestor's wall and
    re-reports rows/flops the ancestor already carries — it must not
    skew the group's wall, samples/s, or program flops; different-group
    nesting keeps its own numbers."""
    recs = [
        {"span": "fit", "span_id": 1, "parent_id": None, "wall_s": 2.0,
         "sync_s": 0.0, "component": "M", "n_rows": 1000,
         "ctr_program_flops": 100.0},
        # same group, nested under 1: wall/rows/flops already contained
        # in the parent's
        {"span": "fit", "span_id": 2, "parent_id": 1, "wall_s": 1.0,
         "sync_s": 0.0, "component": "M", "n_rows": 1000,
         "ctr_program_flops": 60.0},
        # different group, nested: counts its own numbers
        {"span": "pass", "span_id": 3, "parent_id": 1, "wall_s": 0.5,
         "sync_s": 0.0, "component": "M", "n_rows": 400},
    ]
    rows = {key: (n, wall, sps, flops)
            for key, n, wall, sync, sps, flops in summarize_spans(recs)}
    n, wall, sps, flops = rows["M.fit"]
    assert n == 2 and wall == 2.0          # NOT 3.0
    assert sps == pytest.approx(1000 / 2.0)  # NOT 2000/3 or 1000/3
    assert flops == pytest.approx(100.0)   # NOT 160
    assert rows["M.pass"][2] == pytest.approx(400 / 0.5)


def test_report_programs_table_and_span_mfu(tmp_path):
    """Canned run with a programs snapshot + peak: the report renders
    the programs table and a per-span MFU consistent with the recorded
    flops/wall/peak."""
    p = str(tmp_path / "run.jsonl")
    recs = [
        {"span": "fit", "span_id": 1, "parent_id": None, "wall_s": 2.0,
         "sync_s": 0.0, "component": "M", "n_rows": 1000,
         "ctr_program_flops": 4e9},
        {"programs": [
            {"program": "glm.lbfgs", "compiles": 2, "compile_s": 1.5,
             "calls": 10, "exec_s": 2.0, "flops_per_call": 4e8,
             "bytes_per_call": 1e6, "flops_total": 4e9,
             "hbm_peak_bytes": 123 << 20}],
         "peak_flop_per_s_per_chip": 1e10, "peak_source": "measured",
         "device_kind": "cpu", "n_chips": 1},
    ]
    with open(p, "w") as fh:
        fh.write("\n".join(json.dumps(r) for r in recs) + "\n")
    records = load_records(p)
    data = report_data(records)
    # measured MFU: 4e9 flops / 2.0s / 1e10 peak = 0.2
    assert data["spans"][0]["mfu"] == pytest.approx(0.2)
    assert data["peak"]["flop_per_s_per_chip"] == 1e10
    out = build_report(records, path=p)
    assert "programs (XLA cost/memory per compiled entry point)" in out
    assert "glm.lbfgs" in out and "123.0MiB" in out
    assert "0.2000" in out  # both the span and program MFU columns


def test_span_mfu_within_2x_of_analytic(tmp_path):
    """Acceptance: on a recorded run the report's measured per-span MFU
    lands within 2x of the bench-style analytic MFU for the same
    workload (same peak denominator, XLA-counted vs hand-counted
    FLOPs)."""
    import jax

    from dask_ml_tpu.observability._peak import mfu_fields

    n, d, k = 512, 64, 128

    @obs.track_program("test.mfu_matmul")
    @jax.jit
    def mm(a, b):
        return a @ b

    a = np.random.RandomState(0).randn(n, d).astype(np.float32)
    b = np.random.RandomState(1).randn(d, k).astype(np.float32)
    trace = str(tmp_path / "t")
    reps = 50
    with config.set(trace_dir=trace, obs_programs=True,
                    obs_counters=True):
        jax.block_until_ready(mm(a, b))  # compile outside the span
        with obs.span("workload", n_rows=n) as sp:
            t0 = time.perf_counter()
            for _ in range(reps):
                out = mm(a, b)
            jax.block_until_ready(out)
            elapsed = time.perf_counter() - t0
            sp.sync(out)
        # any common denominator serves: the claim is that the report
        # and the analytic formula agree given the SAME peak
        peak = {"flops": 1e11, "source": "test", "device_kind": "test"}
        with obs.MetricsLogger(
                os.path.join(trace, "trace.jsonl")) as lg:
            lg.log(programs=obs.programs_snapshot(),
                   peak_flop_per_s_per_chip=peak["flops"],
                   peak_source=peak["source"],
                   device_kind=peak["device_kind"],
                   n_chips=len(jax.local_devices()))
    analytic = mfu_fields(2.0 * n * d * k * reps, elapsed,
                          len(jax.local_devices()), peak)["mfu"]
    data = report_data(load_records(os.path.join(trace, "trace.jsonl")))
    span_row = [r for r in data["spans"] if r["span"] == "workload"][0]
    assert span_row.get("mfu") is not None
    # measured within 2x of analytic (span wall includes host loop
    # overhead; XLA flops == analytic flops for a plain matmul)
    ratio = span_row["mfu"] / max(analytic, 1e-12)
    assert 0.5 <= ratio <= 2.0, (span_row["mfu"], analytic)


# -- mixed fit + serving recorded run (satellite) -----------------------------

def test_mixed_fit_serving_run_renders_all_tables(tmp_path, capsys):
    """One recorded run containing solver spans, serving.batch spans,
    stream-pass records, counter snapshots AND a programs snapshot
    renders every report table and round-trips through --json and
    --perfetto."""
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.models.sgd import SGDClassifier
    from dask_ml_tpu.observability import report
    from dask_ml_tpu.parallel import as_sharded
    from dask_ml_tpu.serving import ModelServer

    rng = np.random.RandomState(0)
    X = rng.randn(3000, 8).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    trace = str(tmp_path / "t")
    with config.set(trace_dir=trace, obs_programs=True,
                    obs_counters=True, stream_block_rows=400):
        # streamed solver fit -> stream-pass records + solver spans
        SGDClassifier(max_iter=2, random_state=0, shuffle=False).fit(X, y)
        clf = LogisticRegression(solver="lbfgs", max_iter=10).fit(
            as_sharded(X), as_sharded(y)
        )
        with ModelServer(clf, methods=("predict",)).warmup() as srv:
            srv.predict(X[:33])
        path = os.path.join(trace, "trace.jsonl")
        with obs.MetricsLogger(path) as lg:
            obs.log_counters(lg)
            obs.log_programs(lg)
    records = load_records(path)
    out = build_report(records, path=path)
    assert "spans (time by component)" in out
    assert "streaming overlap" in out
    assert "programs (XLA cost/memory per compiled entry point)" in out
    assert "counters" in out
    assert "serving.batch" in out
    assert "serving.LogisticRegression.predict" in out
    # --json round-trip
    rc = report.main([path, "--json"])
    assert rc == 0
    data = json.loads(capsys.readouterr().out)
    assert {r["span"] for r in data["spans"]} >= {"serving.batch"}
    assert data["streaming"]["n_passes"] >= 2
    assert any(p["program"].startswith("serving.")
               for p in data["programs"])
    assert data["counters"]["serving_requests"] >= 1
    # --perfetto round-trip
    pf = str(tmp_path / "trace.perfetto.json")
    rc = report.main([path, "--perfetto", pf])
    assert rc == 0
    _schema_check_chrome_trace(json.load(open(pf)))
