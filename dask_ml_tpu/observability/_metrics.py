"""JSONL metrics core: loggers, the ambient jit-step sink, and the
host-callback capability probe.

Reference: dask's diagnostics/dashboard (SURVEY.md §5 tracing row —
``dask/diagnostics``, bokeh task stream). TPU equivalent: per-step JSONL
metric lines (loss, inertia, samples/s/chip) a controller can tail, and
thin wrappers over ``jax.profiler`` for TensorBoard/Perfetto traces.
"""

from __future__ import annotations

import contextlib
import json
import sys
import threading
import time

import jax


class MetricsLogger:
    """Append one JSON object per step to a file (or stdout)."""

    def __init__(self, path=None, extra=None):
        self.path = path
        self.extra = extra or {}
        self._fh = None
        self.t0 = time.time()
        # log() is called from trial worker threads and jit callback
        # threads; one lock keeps the lazy open and each JSONL line atomic
        self._lock = threading.Lock()

    def _handle(self):
        if self.path is None:
            return sys.stdout
        if self._fh is None:
            self._fh = open(self.path, "a")
        return self._fh

    def log(self, step=None, **metrics):
        # t_unix anchors the record on the wall clock so `report --merge`
        # can place counters/programs-only files (no span records) on the
        # shared timeline; a record's own t_unix (spans) wins via update()
        now = time.time()
        rec = {"time": round(now - self.t0, 6),
               "t_unix": round(now, 6), **self.extra}
        if step is not None:
            rec["step"] = step
        rec.update(metrics)
        line = json.dumps(rec) + "\n"
        with self._lock:
            h = self._handle()
            h.write(line)
            h.flush()

    def close(self):
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# The jit-step sink registry is module-GLOBAL, not thread-local:
# jax.debug.callback runs on the runtime's callback threads, which never
# see the fitting thread's locals. Each fit registers its own logger and
# removes exactly ITS entry on exit (not a save/restore of a single slot,
# which a non-LIFO exit under concurrent fits would corrupt). Concurrent
# fits share the sink: records all land in the (one) configured metrics
# file, only the per-fit `extra` fields of overlapping fits may mix.
_active_loggers = []
_active_lock = threading.Lock()

# per-thread view of the same bindings: span sink resolution must only
# see the logger THIS thread bound — the global stack serves the jit
# callback threads, where "innermost" is the best available guess, but
# a concurrent fit on another thread must not have its span records
# routed through (and stamped with the extras of) this thread's logger
_thread_bindings = threading.local()


def thread_bound_logger():
    """The innermost logger bound via :func:`active_logger` ON THIS
    THREAD (None when this thread bound nothing)."""
    st = getattr(_thread_bindings, "stack", None)
    return st[-1] if st else None


@contextlib.contextmanager
def active_logger(logger):
    """Bind ``logger`` as an ambient jit-step sink: ``emit_jit_step``
    callbacks fired from inside compiled loops (lax.while_loop bodies)
    write to it. Device-side programs can't hold a Python handle, so the
    binding is ambient, scoped to the fit call. On exit, pending callback
    effects are flushed (``jax.effects_barrier``) before unbinding so tail
    iterations are never dropped."""
    if logger is None:
        yield None
        return
    st = getattr(_thread_bindings, "stack", None)
    if st is None:
        st = _thread_bindings.stack = []
    with _active_lock:
        _active_loggers.append(logger)
    st.append(logger)
    try:
        yield logger
    finally:
        try:
            jax.effects_barrier()  # drain in-flight debug callbacks first
        finally:
            # unbind even when the barrier raises (a failing callback):
            # a leaked entry would route every later fit's records — and
            # every later span on this thread — to a dead logger
            st.remove(logger)  # OUR entry (non-LIFO exits possible)
            with _active_lock:
                _active_loggers.remove(logger)


def _jit_step_cb(step, metrics_names, *values):
    with _active_lock:
        lg = _active_loggers[-1] if _active_loggers else None
    if lg is not None:
        lg.log(step=int(step),
               **{n: float(v) for n, v in zip(metrics_names, values)})
    # resident fits' in-jit step metrics (loss, grad_norm, ...) double
    # as live progress gauges; publish_progress is a no-op bool check
    # unless a telemetry server is running, and the values are already
    # host floats here (the callback runtime synced them) — no new sync
    try:
        from .live import publish_progress

        publish_progress(step=int(step),
                         **{n: float(v)
                            for n, v in zip(metrics_names, values)})
    except Exception:
        pass


def emit_jit_step(step, **metrics):
    """Call INSIDE a jitted loop body to emit one JSONL record per
    iteration via ``jax.debug.callback`` (callers gate on a static flag so
    the no-logging trace carries zero callback overhead)."""
    names = tuple(sorted(metrics))
    jax.debug.callback(
        _jit_step_cb, step, names, *(metrics[n] for n in names)
    )


@contextlib.contextmanager
def fit_logger(component, **extra):
    """Per-fit MetricsLogger bound to ``config.metrics_path``; yields None
    (a no-op for callers that guard on it) when the knob is unset. This is
    how estimators/solvers wire per-step JSONL without every call site
    touching config (BASELINE.md measurement protocol)."""
    from ..config import get_config
    from .live import ensure_telemetry

    # every fit passes through here: the one hook that arms the live
    # telemetry server for resident fits (config.obs_http_port; a single
    # config read when the knob is at its 0 default)
    ensure_telemetry()
    path = get_config().metrics_path
    if not path:
        yield None
        return
    logger = MetricsLogger(path, extra={"component": component, **extra})
    try:
        yield logger
    finally:
        logger.close()


def timed(fn, *args, **kwargs):
    """(result, seconds) with a block_until_ready barrier — the honest way
    to time an async-dispatch jax program."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    out = jax.block_until_ready(out)
    return out, time.perf_counter() - t0


@contextlib.contextmanager
def profile_trace(log_dir):
    """jax.profiler trace context (view in TensorBoard / Perfetto)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def start_profiler_server(port=9999):
    """Live-capture profiler endpoint (SURVEY.md §5:
    jax.profiler.start_server)."""
    return jax.profiler.start_server(port)
