"""Observability subsystem: JSONL metrics, hierarchical span tracing,
runtime counters, and the run-report CLI.

The dask-ml reference leaned on dask's diagnostics stack (task-stream
dashboard, progress bars, profilers — SURVEY.md §5); the TPU rebuild's
equivalent is this package (grown from the flat per-step logger in
``utils/observability.py``, which remains as a re-export shim):

- ``_metrics``  — ``MetricsLogger`` (JSONL sink), the ambient
  ``active_logger`` jit-step sink + ``emit_jit_step`` debug-callback
  bridge, the host-callback capability probe, profiler wrappers;
- ``_spans``    — ``span(name, **attrs)``: nested span records (fit →
  pass → solve) with wall time, device-sync time, parent ids, and
  counter deltas; kept in an in-process ring (``recent_spans()``) and
  mirrored as ``dmt.<name>`` annotations on the ``jax.profiler``
  timeline;
- ``_counters`` — flat counter/gauge registry: recompiles (via
  ``jax.monitoring``, with a jit-cache fallback), host↔device transfer
  bytes, donated-buffer reuse, per-device memory gauges;
- ``_programs`` — compiled-program registry: per-program compile time,
  XLA cost/memory analysis (FLOPs, bytes, HBM peak) and invocation
  counts for every tracked jit entry point (``config.obs_programs``);
- ``_watchdog`` — opt-in slow-span watchdog
  (``config.watchdog_timeout_s``): spans open past their deadline dump
  all-thread tracebacks + device memory gauges + the open-span stack to
  the trace sink without touching the fit;
- ``_peak``     — the peak-FLOPs table (published peaks by exact
  ``device_kind``; an unknown device is an error) the report's measured
  MFU divides by;
- ``export``    — span JSONL -> Chrome-trace/Perfetto JSON
  (``report ... --perfetto out.json``);
- ``report``    — ``python -m dask_ml_tpu.observability.report
  metrics.jsonl`` aggregates a recorded run into per-component tables
  (``--json`` for the machine-readable form; ``--merge`` folds several
  processes' trace files into ONE timeline/report);
- ``_hist``     — thread-safe fixed-boundary log-spaced histograms (the
  serving latency quantile core and /metrics histogram series);
- ``sketch``    — streaming data sketches (per-feature moments +
  fixed-boundary histograms, top-k categoricals): host-only, mergeable,
  JSON-safe — the training profiles streamed fits attach and the
  serving sketches the quality plane scores;
- ``drift``     — train-serve/window/version drift scoring (PSI/KS),
  hot-swap shadow canaries, the drift-alert counter, the background
  drift monitor (``config.obs_drift``);
- ``_requests`` — the per-REQUEST trace plane
  (``config.obs_trace_sample``): stage-stamped lifecycle traces through
  the serving queue/pack/execute/demux pipeline, tail sampling of
  interesting traces, per-stage exemplar histograms, the ``/traces``
  surface, and the admitted-traffic capture/replay substrate (ROADMAP
  4(c));
- ``live``      — the LIVE telemetry plane (``config.obs_http_port``):
  a process-wide gauge/histogram registry over the counter registry,
  fit-progress publication via span-close observers, and a background
  HTTP exporter serving Prometheus ``/metrics``, ``/healthz`` and a
  JSON ``/status`` (open-span stack, report tables, serving windows,
  watchdog stalls) while the run is still going;
- ``fleet``     — fleet-scope metrics federation
  (``config.obs_fleet_federate``): ``MetricsFederator`` rides the
  federation status poller, folds every process's scraped counters/
  gauges/histograms into one fleet registry (counters sum, gauges get
  a ``{process=}`` label, histograms merge bucket-for-bucket), and
  exposes it on the router's ``/metrics`` (``dask_ml_tpu_fleet_*``
  families) and ``/status/fleet`` with a fleet-wide SLO burn-rate and
  latched alerts;
- ``alerts``    — the alert rules engine (``config.obs_alert_rules``):
  declarative counter-rate/gauge-threshold rules plus built-ins
  (watchdog stalls, post-warmup recompiles, fleet SLO burn, drift,
  typed errors) evaluated by one ticker over the live registry, with
  firing/resolved state machines, ``alerts_firing{rule=}`` gauges, the
  ``/alerts`` endpoint, and the crossing ledger the drift/fleet latches
  route through;
- ``incidents`` — black-box incident capture (``config.incident_dir``):
  every firing transition freezes one rate-limited, bounded, atomic
  JSON bundle (open spans, counter/gauge/histogram snapshots, programs,
  device memory, fault plan, config fingerprint), plus on-demand deep
  profiling (``POST /profile?seconds=N``; jax.profiler windows on TPU,
  no-op-with-reason off it).

Everything is ambient and zero-overhead when disabled: no
``metrics_path``/``trace_dir`` configured means spans are no-ops and no
callback is ever traced into jitted code (asserted by
``tests/test_observability.py``).
"""

from ._counters import (
    count_recompiles,
    counter_add,
    counters_enabled,
    counters_reset,
    counters_snapshot,
    device_memory_gauges,
    install_recompile_tracking,
    log_counters,
    record_donation,
    record_fault_injected,
    record_gspmd_reduce,
    record_registry_publish,
    record_replica_failure,
    record_replica_restart,
    record_serving_batch,
    record_serving_drop,
    record_serving_request,
    record_serving_reroute,
    record_serving_slo_violation,
    record_serving_swap,
    record_shard_staging,
    record_sparse_spill,
    record_sparse_staging,
    record_stream_checkpoint,
    record_stream_quarantine,
    record_stream_retry,
    record_superblock,
    record_superblock_donation,
    record_transfer,
)
from ._metrics import (
    MetricsLogger,
    _active_lock,
    _active_loggers,
    active_logger,
    emit_jit_step,
    fit_logger,
    profile_trace,
    start_profiler_server,
    timed,
)
from ._programs import (
    log_programs,
    programs_enabled,
    programs_reset,
    programs_snapshot,
    track_program,
)
from ._hist import Histogram, merge_snapshots
from .fleet import SLO_BURN_BUDGET, MetricsFederator
from .sketch import CategoricalSketch, FeatureSketch, merge_profiles
from ._spans import (
    NOOP_SPAN,
    add_span_observer,
    current_span,
    current_span_id,
    open_spans_snapshot,
    recent_spans,
    remove_span_observer,
    reset_recent_spans,
    span,
)
from ._requests import (
    load_capture,
    replay,
    tracing_enabled,
    traces_data,
    traces_reset,
)
from ._watchdog import Watchdog, watchdog, watchdog_active
from .alerts import (
    AlertEngine,
    AlertRule,
    AlertRuleError,
    alerts_data,
    ensure_engine,
    note_event,
    parse_rules,
    stop_engine,
)
from .incidents import (
    capture_incident,
    deep_profile,
    incidents_data,
    load_bundles,
)
from .live import (
    TelemetryServer,
    ensure_telemetry,
    gauge_set,
    live_publishing,
    publish_progress,
    render_prometheus,
    status_data,
    stop_telemetry,
    telemetry_server,
)

# recompile telemetry is passive and cheap (a no-op listener call per
# compile when counters are disabled) — install at import so the counter
# covers warmup compiles too
install_recompile_tracking()

__all__ = [
    "AlertEngine",
    "AlertRule",
    "AlertRuleError",
    "CategoricalSketch",
    "FeatureSketch",
    "Histogram",
    "MetricsFederator",
    "MetricsLogger",
    "SLO_BURN_BUDGET",
    "merge_profiles",
    "merge_snapshots",
    "NOOP_SPAN",
    "TelemetryServer",
    "Watchdog",
    "active_logger",
    "add_span_observer",
    "alerts_data",
    "capture_incident",
    "deep_profile",
    "ensure_engine",
    "ensure_telemetry",
    "gauge_set",
    "live_publishing",
    "publish_progress",
    "remove_span_observer",
    "render_prometheus",
    "status_data",
    "stop_telemetry",
    "telemetry_server",
    "count_recompiles",
    "counter_add",
    "counters_enabled",
    "counters_reset",
    "counters_snapshot",
    "current_span",
    "current_span_id",
    "device_memory_gauges",
    "emit_jit_step",
    "fit_logger",
    "install_recompile_tracking",
    "incidents_data",
    "load_bundles",
    "load_capture",
    "log_counters",
    "log_programs",
    "note_event",
    "parse_rules",
    "replay",
    "reset_recent_spans",
    "traces_data",
    "traces_reset",
    "tracing_enabled",
    "open_spans_snapshot",
    "profile_trace",
    "programs_enabled",
    "programs_reset",
    "programs_snapshot",
    "recent_spans",
    "record_donation",
    "record_fault_injected",
    "record_gspmd_reduce",
    "record_registry_publish",
    "record_replica_failure",
    "record_replica_restart",
    "record_serving_batch",
    "record_serving_drop",
    "record_serving_request",
    "record_serving_reroute",
    "record_serving_slo_violation",
    "record_serving_swap",
    "record_shard_staging",
    "record_sparse_spill",
    "record_sparse_staging",
    "record_stream_checkpoint",
    "record_stream_quarantine",
    "record_stream_retry",
    "record_superblock",
    "record_superblock_donation",
    "record_transfer",
    "span",
    "start_profiler_server",
    "stop_engine",
    "timed",
    "track_program",
    "watchdog",
    "watchdog_active",
]
