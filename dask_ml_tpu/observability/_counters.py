"""Runtime counter/gauge registry.

The signals a perf PR must be able to cite (ROADMAP north star:
hardware-speed hot paths): how many XLA recompiles a run paid, how many
bytes crossed the host↔device boundary, how much buffer reuse the
streamer achieved, and where device memory stands. Counters are a flat
``name -> number`` registry guarded by one lock; spans snapshot it at
open and emit the deltas at close, so every JSONL span record carries
the counters *it* caused.

Gating: ``config.obs_counters`` (env ``DASK_ML_TPU_OBS_COUNTERS``)
switches recording off entirely; the hot-path call sites cost one
config lookup + dict add, and nothing is ever traced into jitted code.

Recompile counting rides ``jax.monitoring``'s
``/jax/core/compile/backend_compile_duration`` event where the
installed jax exposes it; runtimes without ``jax.monitoring`` fall back
to :func:`count_recompiles`, which wraps a jitted entry point (the
``ops/`` jit entries use it) and counts compile-cache growth.
"""

from __future__ import annotations

import functools
import threading

import jax

_lock = threading.Lock()
_counters: dict[str, float] = {}


def counters_enabled() -> bool:
    from ..config import get_config

    return bool(get_config().obs_counters)


def counter_add(name: str, value=1) -> None:
    """Unconditional add — call sites that already paid the enabled()
    check (or tests building fixtures) use this directly."""
    with _lock:
        _counters[name] = _counters.get(name, 0) + value


def counters_snapshot() -> dict:
    with _lock:
        return dict(_counters)


def counters_reset() -> None:
    with _lock:
        _counters.clear()


def record_transfer(nbytes: int, direction: str = "h2d") -> None:
    """One host↔device transfer of ``nbytes`` (the block streamer calls
    this per device_put batch)."""
    if counters_enabled():
        counter_add(f"{direction}_bytes", int(nbytes))
        counter_add(f"{direction}_transfers", 1)


def record_donation(nbytes: int) -> None:
    """A donated buffer was reused in place of a fresh allocation."""
    if counters_enabled():
        counter_add("donated_bytes_reused", int(nbytes))
        counter_add("donated_buffers_reused", 1)


def record_plan_build(cached: bool = False) -> None:
    """One ProgramPlan build: ``plan_builds`` for a fresh tracked jit,
    ``plan_cache_hits`` when the process-wide build cache returned an
    existing entry point (the second client's free warmup)."""
    if counters_enabled():
        counter_add("plan_cache_hits" if cached else "plan_builds", 1)


def record_plan_warmup(hit: bool = False) -> None:
    """One WarmupRegistry event: ``plan_warmups`` for an executed warm
    call, ``plan_cache_hits`` for a skip (the key — and therefore the
    compile it would have minted — was already warm)."""
    if counters_enabled():
        counter_add("plan_cache_hits" if hit else "plan_warmups", 1)


def record_superblock(n_blocks: int) -> None:
    """One super-block dispatch covering ``n_blocks`` real streamed
    blocks — superblock_blocks / superblock_dispatches is the measured
    dispatch amortization (≈K); a pass's dispatches_per_pass lives on
    its ``streaming.superblock`` span record."""
    if counters_enabled():
        counter_add("superblock_dispatches", 1)
        counter_add("superblock_blocks", int(n_blocks))


def record_shard_staging(n_shards: int) -> None:
    """One batch-sharded staging assembly: ``n_shards`` per-shard host
    slabs were placed onto their own devices (ISSUE 9 data-parallel
    streaming) — shard_slab_puts / shard_staging_batches is the
    measured data-axis width of the streamed hot loop."""
    if counters_enabled():
        counter_add("shard_staging_batches", 1)
        counter_add("shard_slab_puts", int(n_shards))


def record_sparse_staging(n_blocks: int, nnz: int) -> None:
    """One bucketed-nnz sparse staging assembly (ISSUE 13): ``n_blocks``
    streamed blocks staged as device-resident COO triples carrying
    ``nnz`` real nonzeros — sparse_nnz_staged / sparse_blocks_staged is
    the measured per-block nnz, and its ratio against h2d_bytes shows
    the densify traffic the sparse path did NOT pay."""
    if counters_enabled():
        counter_add("sparse_blocks_staged", int(n_blocks))
        counter_add("sparse_nnz_staged", int(nnz))


def record_sparse_spill() -> None:
    """One served sparse batch whose nnz exceeded the warmed nnz-bucket
    ladder's top rung and spilled to the densified dense entry point
    (still zero new compiles — the dense (rows) bucket is warm)."""
    if counters_enabled():
        counter_add("serving_sparse_spills", 1)


def record_gspmd_reduce(nbytes: int) -> None:
    """Estimated cross-device reduce payload one implicit-GSPMD
    dispatch moved (today: the sharded streamed-ADMM block-local
    Newton, whose per-iteration Hessian/gradient partial sums XLA
    all-reduces over the row shards — ROADMAP 1(c)'s previously
    unmeasured traffic). An ANALYTIC payload estimate, not a NIC
    counter: it sizes what must cross the mesh at least once; with
    obs_programs on, the matching ``...admm_local.gspmd`` program row
    carries XLA's own measured bytes beside it."""
    if counters_enabled():
        counter_add("gspmd_reduce_bytes", int(nbytes))
        counter_add("gspmd_reduce_dispatches", 1)


def record_superblock_donation(nbytes: int) -> None:
    """A super-block scan's donated carry was handed back to XLA for
    in-place reuse (the accumulator/weights buffer never reallocates
    across the pass's dispatches)."""
    if counters_enabled():
        counter_add("superblock_donated_bytes", int(nbytes))
        counter_add("superblock_donations", 1)


# -- serving -----------------------------------------------------------------
# the online-inference registry slice (dask_ml_tpu/serving): admitted
# work, batching efficiency, and backpressure outcomes. Kept here so the
# report CLI and span counter-deltas see serving exactly like the fit
# counters.

_SERVING_DROP_COUNTERS = {
    "shed": "serving_shed",          # admission control refused entry
    "timeout": "serving_timeouts",   # deadline passed while queued
    "error": "serving_errors",       # batch execution raised
    "slo_shed": "serving_slo_shed",  # SLO admission predicted a miss
}


def record_serving_request(n_rows: int) -> None:
    """One admitted serving request of ``n_rows`` rows."""
    if counters_enabled():
        counter_add("serving_requests", 1)
        counter_add("serving_rows", int(n_rows))


def record_serving_batch(rows: int, bucket: int) -> None:
    """One executed micro-batch: ``rows`` real rows padded to the
    ``bucket`` rung — padding waste accumulates as serving_padded_rows /
    (serving_rows + serving_padded_rows)."""
    if counters_enabled():
        counter_add("serving_batches", 1)
        counter_add("serving_padded_rows", int(bucket - rows))


def record_serving_drop(kind: str) -> None:
    """A request resolved without a result; ``kind`` in
    {'shed', 'timeout', 'error'}."""
    if counters_enabled():
        counter_add(_SERVING_DROP_COUNTERS[kind], 1)


def record_serving_swap(rebuilt: bool = False) -> None:
    """One model hot-swap applied to a serving entry-point set.
    ``rebuilt=True`` marks the slow path — the new version's shapes did
    not match, so the entry points were recompiled instead of swapped
    (the zero-recompile contract intentionally does not cover it)."""
    if counters_enabled():
        counter_add("serving_swaps", 1)
        if rebuilt:
            counter_add("serving_swap_rebuilds", 1)


def record_serving_reroute() -> None:
    """A fleet request was rerouted off a failed/closed replica onto a
    surviving one."""
    if counters_enabled():
        counter_add("serving_reroutes", 1)


def record_registry_publish(rollback: bool = False) -> None:
    """One model version published to (or rolled back in) a
    ModelRegistry."""
    if counters_enabled():
        counter_add("registry_publishes", 1)
        if rollback:
            counter_add("registry_rollbacks", 1)


def record_drift_alert() -> None:
    """A drift score (train-vs-serve / window PSI, or a canary delta)
    crossed ``config.obs_drift_threshold`` — latched once per
    below→above crossing by the drift engine. The quality-plane burn
    signal a scraper alerts on (``dask_ml_tpu_drift_alerts_total``)."""
    if counters_enabled():
        counter_add("drift_alerts", 1)


def record_telemetry_series_dropped() -> None:
    """The live metric registry refused a NEW labeled series past
    ``config.obs_max_series`` (cardinality guard) — visible as
    ``telemetry_series_dropped_total``."""
    if counters_enabled():
        counter_add("telemetry_series_dropped", 1)


# -- reliability / chaos plane (dask_ml_tpu/reliability/) --------------------

def record_fault_injected(site: str, kind: str) -> None:
    """One armed fault fired at a named site (config.fault_plan) —
    ``faults_injected`` totals plus a per-site breakdown so a chaos
    run's /metrics shows WHERE the plan struck."""
    if counters_enabled():
        counter_add("faults_injected", 1)
        counter_add(f"faults_injected_{site}", 1)


def record_stream_retry() -> None:
    """One staging/reader IO failure absorbed by the bounded-backoff
    retry (config.stream_io_retries) — ``stream_retries_total`` on
    /metrics is the transient-IO burn signal."""
    if counters_enabled():
        counter_add("stream_retries", 1)


def record_stream_quarantine() -> None:
    """One streamed block quarantined by the non-finite policy
    (config.stream_nonfinite="quarantine"): its data zeroed and its
    valid-row count folded to 0 by the existing prefix-count mask."""
    if counters_enabled():
        counter_add("stream_quarantined_blocks", 1)


def record_stream_checkpoint(resume: bool = False) -> None:
    """One pass-granular stream checkpoint saved — or, with
    ``resume=True``, a killed streamed fit restored from one
    (``stream_resumes``)."""
    if counters_enabled():
        counter_add("stream_resumes" if resume
                    else "stream_checkpoint_saves", 1)


def record_replica_restart() -> None:
    """The replica supervisor rebuilt a dead fleet replica (fresh
    server at the registry's current version, warmed off the serving
    path, rejoined routing)."""
    if counters_enabled():
        counter_add("serving_replica_restarts", 1)


def record_replica_failure() -> None:
    """A replica exceeded its restart budget and degraded to permanent
    failover — the page-an-operator signal."""
    if counters_enabled():
        counter_add("serving_replica_failures", 1)


def record_scale_up() -> None:
    """The autoscaler ADDED a replica (SLO headroom predicted a miss
    under the up-band for the configured patience) — live /metrics:
    ``dask_ml_tpu_serving_scale_ups_total`` beside the
    ``serving_replicas`` gauge."""
    if counters_enabled():
        counter_add("serving_scale_ups", 1)


def record_scale_down() -> None:
    """The autoscaler RETIRED a replica (sustained headroom under the
    down-band); the victim drained gracefully and its gauge series were
    dropped."""
    if counters_enabled():
        counter_add("serving_scale_downs", 1)


def record_process_reroute() -> None:
    """The federation router re-issued a request on a different fleet
    PROCESS after its first choice died/refused mid-flight — the
    cross-process twin of ``serving_reroutes``."""
    if counters_enabled():
        counter_add("serving_process_reroutes", 1)


def record_process_failover() -> None:
    """The federation router marked a whole fleet process DOWN
    (connection refused / status poll dead) and stopped routing to it
    until it answers again."""
    if counters_enabled():
        counter_add("serving_process_failovers", 1)


def record_federation_publish() -> None:
    """One registry publish fanned out across the federation boundary
    (origin registry -> every remote fleet process)."""
    if counters_enabled():
        counter_add("federation_publishes", 1)


def record_serving_slo_violation() -> None:
    """A served request's end-to-end latency exceeded the configured
    ``serving_slo_ms`` — the request still SUCCEEDED (unlike the drop
    counters above); this is the SLO burn signal a scraper alerts on
    (live /metrics: ``dask_ml_tpu_serving_slo_violations_total``)."""
    if counters_enabled():
        counter_add("serving_slo_violations", 1)


# -- recompile tracking ------------------------------------------------------

_recompile_listener_installed = False


def _on_compile_duration(name, secs, **kw):
    # one backend_compile per (function, shape) specialization — exactly
    # the "how many recompiles did this run pay" signal
    if name.endswith("backend_compile_duration") and counters_enabled():
        counter_add("recompiles", 1)
        counter_add("compile_secs", float(secs))


def install_recompile_tracking() -> bool:
    """Register the jax.monitoring compile listener (idempotent).
    Returns False on jax builds without the monitoring API — callers
    then keep :func:`count_recompiles` wrappers live instead."""
    global _recompile_listener_installed
    if _recompile_listener_installed:
        return True
    try:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(
            _on_compile_duration
        )
        _recompile_listener_installed = True
        return True
    except Exception:
        return False


def count_recompiles(fn):
    """Fallback recompile counter for jitted entry points when
    ``jax.monitoring`` is unavailable: wrap the jitted callable and count
    compile-cache growth per call. Identity when the listener installed —
    the wrapper would double-count."""
    if install_recompile_tracking():
        return fn
    if not hasattr(fn, "_cache_size"):  # not a jitted callable
        return fn

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        before = fn._cache_size()
        out = fn(*args, **kwargs)
        grew = fn._cache_size() - before
        if grew > 0 and counters_enabled():
            counter_add("recompiles", grew)
        return out

    wrapped.__wrapped_jit__ = fn
    return wrapped


# -- gauges ------------------------------------------------------------------

def device_memory_gauges() -> dict:
    """Per-device memory stats as a flat gauge dict (empty on backends
    that report none — CPU). Polled, not accumulated: emit via
    :func:`log_counters` or a span ``add`` when a footprint snapshot
    matters."""
    out = {}
    for dev in jax.local_devices():
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if key in stats:
                out[f"dev{dev.id}_{key}"] = int(stats[key])
    return out


def log_counters(logger, **extra) -> dict:
    """Emit one JSONL record holding the current counter snapshot plus
    device memory gauges; returns the snapshot. The report CLI reads the
    LAST such record as the run's totals."""
    snap = counters_snapshot()
    if logger is not None:
        logger.log(counters=True, **snap, **device_memory_gauges(),
                   **extra)
    return snap
