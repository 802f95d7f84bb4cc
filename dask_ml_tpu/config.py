"""Runtime configuration.

Reference: ``dask.config`` — layered YAML + ``DASK_*`` env vars + a
``set(...)`` context manager (SURVEY.md §5 config row). Estimator
hyperparameters stay sklearn-style (get_params/set_params — the MUST for
clone/search compat); this module covers *runtime* knobs only: a small
dataclass with env-var overrides (``DASK_ML_TPU_<FIELD>``) and a context
manager, no YAML cascade.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading


@dataclasses.dataclass
class Config:
    # fit compute dtype for device estimators ("auto" | "float32" |
    # "bfloat16"; "f32"/"fp32"/"bf16" are accepted aliases). "auto" —
    # the default — resolves to bfloat16 on TPU (where the MXU runs
    # bf16 at full rate and the bf16 fits are benched within the
    # documented parity tolerances, tests/test_bf16_policy.py +
    # tests/test_precision.py) and float32 everywhere else (CPU/GPU pay
    # a software bf16 penalty); the resolved choice and the fallback
    # reason are recorded in each fit's info (solver_info_ /
    # fit_dtype_). Estimators expose a per-instance ``fit_dtype``
    # override that wins over this knob.
    dtype: str = "auto"
    # rows per streamed block in out-of-core paths (0 = auto: n/8)
    stream_block_rows: int = 0
    # prefetch depth of the block streamer (1 = double buffering)
    stream_prefetch: int = 1
    # grow streamed blocks between epochs when transfer time dominates
    # compute (measured per pass; at most 2 doublings, ≥16 blocks).
    # Default OFF: resizing from wall-clock measurements makes the
    # minibatch partition — and hence a seeded fit's weights — depend on
    # machine load, breaking random_state reproducibility. Opt in for
    # throughput-bound production streaming.
    stream_autotune: bool = False
    # -- super-block scan execution (parallel/streaming.py) ---------------
    # blocks per super-block: streamed hot loops stack K fixed-shape
    # blocks into one [K, block_rows, d] device buffer and consume it in
    # ONE jitted lax.scan with a donated carry — one XLA dispatch per K
    # blocks instead of K. 0 = auto (8, capped by the pass length and a
    # device byte budget); 1 = per-block dispatch. Changing K never
    # changes the minibatch partition — only dispatch granularity — so
    # results are identical at any K.
    superblock_k: int = 0
    # -- data-parallel superblock streaming (ISSUE 9) ---------------------
    # data-axis shards for the STREAMED superblock hot loop: every
    # super-block stages as a batch-sharded jax.Array (per-shard host
    # slabs placed onto their own device by the staging worker, ragged
    # tails padded per shard with zero valid-row counts) and the scan
    # programs run under shard_map with REPLICATED carries — GLM
    # val/vg/vgh reducers and KMeans assign-stats pay one lax.psum over
    # "data" per super-block, streamed SGD one gradient psum per block
    # step. 0 = auto (all local devices — the sharded flavor engages
    # whenever more than one device is visible); 1 = single-device
    # streaming (the sharded machinery never enters the trace and the
    # streamed jaxprs are byte-identical to the pre-mesh programs);
    # N > 1 = shard over the first N local devices
    stream_mesh: int = 0
    # 2-D ("data", "model") mesh shape for the streamed/sharded plane
    # (parallel/mesh.py): "auto" = the 1-D data mesh over the resolved
    # device set (today's behavior — nothing changes); "DxM" = a 2-D
    # hybrid mesh with D data shards and M feature (model) shards, where
    # either factor may be -1 (inferred from the device count); a bare
    # "D" or "Dx1" collapses to the plain 1-D mesh so the 1-D programs
    # stay jaxpr-byte-identical. With M > 1 streamed X slabs stage as
    # (rows/D, d/M) per-device tiles and the GLM reducers / streamed
    # PCA run their feature-sharded flavors (psum over "model" exactly
    # where the math contracts over features) — per-chip HBM then stays
    # flat in d. Composes with stream_mesh: that knob first restricts
    # the device pool, this one shapes it
    mesh_shape: str = "auto"
    # simulated per-device staging byte budget for streamed fits: > 0
    # makes BlockStream refuse (typed StreamBudgetExceeded) any fit
    # whose per-device staged super-block bytes (K x block_rows/D x
    # ceil(d/M) x itemsize) exceed it, pointing at mesh_shape — the
    # CPU-verifiable stand-in for real per-chip HBM limits.
    # 0 = off (no budget enforced)
    stream_device_byte_budget: int = 0
    # fused Pallas streamed kernels (ops/pallas_fused.py): on real TPU
    # the super-block hot loops (SGD step, GLM val/vg/vgh reducers,
    # KMeans assign-stats) run fused objective+gradient kernels — one
    # VMEM pass over each block instead of separate forward/backward
    # reads. Off-TPU (or when shapes don't fit the VMEM tile budget /
    # the 128-row Mosaic grid) the XLA flavors run unchanged: with the
    # knob off the streamed jaxprs are byte-identical to the
    # pre-feature programs (asserted in tests)
    pallas_stream: bool = True
    # interpret-mode opt-in for the fused Pallas streamed kernels
    # off-TPU: with this on, the fused bodies (including the ones
    # running INSIDE the shard_map scan programs) execute through the
    # Pallas interpreter on CPU/GPU — the fused x sharded composition
    # is then testable/benchable without a chip, at interpreter speed.
    # Off (the default) keeps the off-TPU XLA flavors byte-identical;
    # real-TPU behavior is unaffected either way
    pallas_stream_interpret: bool = False
    # -- device-resident sparse streaming (parallel/sparse_stream.py) -----
    # stream sparse (CSR / SparseBlocks) sources as DEVICE-RESIDENT
    # bucketed-nnz blocks: values/column-indices/row-ids padded to a
    # geometric nnz-bucket ladder and consumed by sparse superblock
    # scan programs (take/segment_sum — nnz-proportional cost) instead
    # of densifying every block on host to n x d. ON by default
    # (ROADMAP 4a — flipped after the PR-13 parity suite held a round
    # and grew two more shapes): a sparse source whose density stays
    # under ``stream_sparse_max_density`` runs GLM val/vg/vgh, streamed
    # SGD (incl. multiclass, grad-accum and the search cohort scans)
    # and KMeans assign-stats through the ``superblock.sparse.*``
    # programs with the same one-dispatch-per-super-block /
    # zero-compiles-after-pass-1 / donation contracts as the dense
    # scan; over-density sources keep the per-block densify path with
    # the reason recorded (solver_info_["sparse_stream_reason"]). Off
    # restores the per-block densify path byte-identically. Dense
    # inputs are untouched either way
    stream_sparse: bool = True
    # streamed adaptive-search cohort rounds (model_selection): a
    # Hyperband/IncrementalSearchCV round over host-resident X advances
    # ALL surviving candidates through ONE BlockStream superblock pass
    # — each super-block is one dispatch whose donated carry holds the
    # stacked cohort weights (padded to the search's candidate count,
    # so shrinking brackets reuse one compiled scan), composing with
    # the stream mesh (shard_map + psum twins), the bucketed-nnz sparse
    # format and the fused Pallas bodies. Off keeps the SAME block
    # partition but executes rounds through the device-resident cohort
    # machinery
    search_stream: bool = True
    # automatic densify fallback threshold for the sparse streamed
    # path: a source whose overall nnz density exceeds this fraction
    # stages dense (the bucketed-nnz format stops paying for itself
    # around here — padded nnz triples approach the dense block's
    # bytes while paying gather/scatter instead of matmul)
    stream_sparse_max_density: float = 0.25
    # byte budget for one-shot dense materialization of a sparse corpus
    # (feature_extraction.text.to_sharded_dense): a corpus whose dense
    # form exceeds this refuses with the typed DenseBudgetExceeded
    # pointing at the streamed sparse path instead of silently
    # allocating tens of GB of host RAM
    to_dense_byte_budget: int = 1 << 30
    # expected nonzeros per row for the SPARSE serving entry points'
    # nnz-bucket ladder (serving/wrappers sparse_batch_fn): the
    # (rows, nnz) grid's nnz rungs run geometrically from
    # serving_min_batch * this to serving_max_batch * this with
    # serving_bucket_growth — a warmed grid then serves ragged hashed-
    # text traffic at zero steady-state compiles
    serving_sparse_nnz_per_row: int = 64
    # gradient-accumulation streamed SGD (models/sgd.py): 0 = off (the
    # sequential flavor; host-streamed SGD under a multi-process
    # runtime stays refused, because sequential per-block updates
    # cannot psum across process-local streams). A >= 1 accumulates
    # each process's raw gradient sums over A micro-blocks, merges ONCE
    # across processes (psum_host), and applies a single shared update
    # — the documented optimizer variant that lifts the cross-host
    # refusal. Exact parity with the sequential fit at A=1
    # single-process (bit-exact vs the single-device sequential
    # flavor; the sharded sequential scan differs at
    # float-reassociation level); at A>1 (or multi-process) the
    # effective batch per
    # update grows A x processes-fold, so expect fewer, larger steps
    # per pass (see README "Pod-scale streaming" for the convergence
    # caveat). Recorded in solver_info_["grad_accum"]
    stream_grad_accum: int = 0
    # -- reliability / chaos plane (dask_ml_tpu/reliability/) -------------
    # deterministic fault-injection plan ("" = off, the zero-overhead
    # default: every site costs one config read + branch and the
    # streamed jaxprs are byte-identical). Arms named host-side sites
    # by seeded invocation-index schedules — e.g.
    # "staging_read:io@2;replica_worker:crash@40" — so chaos runs
    # replay exactly; see reliability/faults.py for the grammar and
    # the site/kind tables
    fault_plan: str = ""
    # bounded exponential-backoff retries for transient staging/reader
    # IO failures (real disk hiccups and injected "io" faults alike):
    # a failing host block read is re-read positionally up to this many
    # times (stream_retries_total counts attempts) before raising the
    # typed StreamIORetriesExhausted. 0 = fail on first error
    stream_io_retries: int = 3
    # non-finite streamed-block policy: "off" (no check), "raise"
    # (typed NonFiniteBlock at the staging boundary), "quarantine"
    # (zero the block's data AND its valid-row count so the existing
    # masked prefix-count folds it out — no shape change, no recompile;
    # stream_quarantined_blocks counts). Inference streams treat
    # quarantine as raise (silently dropping prediction rows would
    # corrupt output alignment)
    stream_nonfinite: str = "off"
    # pass-granular checkpoint/auto-resume for streamed GLM/SGD/
    # Incremental fits ("" = off): the carry pytree + pass/lr-clock
    # state persist here (orbax, atomic rename) under a fingerprint
    # token — a killed fit rerun with the same data/knobs resumes at
    # the last saved pass, a wrong-fingerprint checkpoint is ignored,
    # completion clears it. Refused (fit runs uncheckpointed) under a
    # multi-process runtime: resume must be a collective decision
    stream_checkpoint_path: str = ""
    # passes between checkpoint saves when stream_checkpoint_path is
    # set (1 = every pass)
    stream_checkpoint_every: int = 1
    # deadline (seconds) on the multihost pass barrier
    # (distributed.sync_stream_pass): a lost peer turns the barrier
    # hang into a typed StreamSyncTimeout instead of wedging the fit
    # forever. 0 = no deadline
    stream_sync_timeout_s: float = 600.0
    # -- execution plans (dask_ml_tpu/plans/) -----------------------------
    # process-wide plan build cache: two ProgramPlan builds with an
    # identical spec (name, cache key, donation, static axes) return
    # the SAME tracked jitted entry point, so the second client's
    # warmup hits warm jit caches instead of re-tracing/re-compiling
    # (plan_cache_hits counts). Off = every build constructs a fresh
    # jit (the pre-ISSUE-15 behavior)
    plan_cache: bool = True
    # force the process-wide WarmupRegistry to re-execute every warm
    # request even for keys already registered warm (the executions are
    # semantic no-ops; debugging aid for compile-cache investigations).
    # Off (default) keeps warming idempotent per process
    plan_rewarm: bool = False
    # JSONL metrics path ("" = disabled)
    metrics_path: str = ""
    # span-trace directory: spans append to <trace_dir>/trace.jsonl even
    # outside a metrics_path fit ("" = spans fall back to metrics_path,
    # or no-op when both are unset)
    trace_dir: str = ""
    # runtime counter registry (recompiles, host<->device bytes, donated
    # buffer reuse) — cheap host-side adds; disable to make every
    # counter call site a single config lookup
    obs_counters: bool = True
    # compiled-program registry (observability/_programs.py): tracked jit
    # entry points record compile time + XLA cost/memory analysis per
    # program and feed the `program_flops` counter spans read for
    # measured MFU. Opt-in: the analysis pass re-lowers each program once
    # per fresh compile (an extra, in-memory-cached XLA compile that also
    # shows up in the recompiles counter), so steady-state zero-recompile
    # contracts keep it off by default. Also the flight recorder's switch
    # for spans: on, every span records into the in-memory ring
    # (observability.recent_spans()) and holds a `dmt.<name>`
    # jax.profiler.TraceAnnotation open, with no sink configured
    obs_programs: bool = False
    # live telemetry exporter (observability/live.py): port for the
    # background HTTP daemon serving Prometheus /metrics, /healthz and
    # the JSON /status (open-span stack, report tables, serving
    # windows) WHILE a run is going. 0 = off — the exporter thread is
    # never created, no span observer registers, and the hot paths keep
    # today's zero-overhead profile (env DASK_ML_TPU_OBS_HTTP_PORT)
    obs_http_port: int = 0
    # data/model-quality observability (observability/sketch.py +
    # drift.py): streamed fits fold per-feature training profiles on the
    # host staging path, serving folds request/prediction sketches, and
    # hot swaps score a shadow canary — all pure host numpy (never in a
    # jaxpr, never a device sync). Off = no sketch is ever allocated
    obs_drift: bool = True
    # background drift-score cadence (seconds) while a server runs:
    # every tick recomputes PSI/KS over the registered sketch pairs and
    # publishes drift_score gauges / drift_alerts. 0 = no monitor thread
    # (scores still compute on demand via drift.compute())
    obs_drift_interval_s: float = 5.0
    # PSI above this alerts (drift_alerts_total; 0.2 is the classic
    # "significant shift" line); canary disagreement/quantile-shift
    # share it
    obs_drift_threshold: float = 0.2
    # fraction of served rows stashed into the per-method shadow
    # reservoir a hot-swap canary scores against both versions
    # (0 = no shadow sampling, swaps record no canary)
    obs_shadow_fraction: float = 0.05
    # max LABELED series per metric family in the live registry:
    # per-feature drift gauges can mint unbounded label sets; past the
    # cap new series are dropped and counted
    # (telemetry_series_dropped_total)
    obs_max_series: int = 512
    # per-request trace plane (observability/_requests.py): the rolling
    # slowest fraction of ordinary completions tail-sampled with a full
    # stage breakdown (errors, timeouts, sheds, SLO violations,
    # reroutes, and fault-injected requests are ALWAYS kept; every
    # completion folds into the per-stage exemplar histograms either
    # way). 0 = the plane is off: no trace object is ever allocated on
    # the serving hot path and the serving jaxprs are byte-identical
    obs_trace_sample: float = 0.0
    # sampled traces retained in memory for /traces, /status and the
    # report CLI (a bounded deque; oldest sampled traces fall off)
    obs_trace_keep: int = 256
    # cross-process trace continuation (observability/_requests.py +
    # serving/federation.py): the router's trace id rides federated
    # submits as an X-Trace-Context header and the receiving process
    # CONTINUES the same pid-prefixed id through its own stages, so one
    # federated request joins into one Perfetto timeline. Only consulted
    # when the trace plane is on (obs_trace_sample > 0); off = every
    # process mints its own ids, pre-federation behavior
    obs_trace_propagate: bool = True
    # fleet metrics federation (observability/fleet.py): a
    # FederatedFleet router folds every process's scraped counters/
    # gauges/histograms into one fleet registry exposed on the router's
    # own /metrics (dask_ml_tpu_fleet_* families) and /status/fleet.
    # Off by default — no federator is built, no provider registers,
    # and the router's exposition is byte-identical to pre-fleet
    obs_fleet_federate: bool = False
    # minimum seconds between fleet-metrics ingests; 0 = fold on every
    # federation status poll (the federator RIDES the existing poller —
    # it never starts a thread or issues its own /status reads)
    obs_fleet_poll_s: float = 0.0
    # slow-span watchdog (observability/_watchdog.py): any span open past
    # this many seconds dumps all-thread tracebacks + device memory
    # gauges + the open-span stack to the trace sink, without touching
    # the fit. 0 = disabled (no thread, nothing armed)
    watchdog_timeout_s: float = 0.0
    # alert rules engine (observability/alerts.py): ","/";"-separated
    # declarative rules evaluated over the live host-side registry, e.g.
    # "serving_slo_violations:rate>5/60s, drift_score_max:gauge>0.2,
    # fit_eta_seconds:gauge>1800" — counter rate-over-window and gauge
    # threshold forms (ops > < >= <=). The special value "builtin" arms
    # only the built-in rules (watchdog stalls, post-warmup recompiles,
    # fleet SLO burn > 1.0 — always included once the engine is armed).
    # "" + incident_dir unset = no engine, no ticker thread (the
    # zero-overhead default)
    obs_alert_rules: str = ""
    # alert-engine evaluation cadence: seconds between ticker passes
    # over the counter/gauge snapshots (pure host dicts, zero device
    # syncs per tick)
    obs_alert_interval_s: float = 5.0
    # black-box incident capture (observability/incidents.py): any alert
    # transition to firing (plus watchdog stalls and reliability typed
    # errors) writes one rate-limited JSON bundle here — open-span
    # stack, recent span/trace rings, counter/gauge/histogram
    # snapshots, programs table, device memory gauges, armed fault
    # plan, config fingerprint — atomically (tmp + fsync + rename).
    # Setting it arms the alert engine's built-in rules even with
    # obs_alert_rules unset. "" = capture disabled (no bundle dir)
    incident_dir: str = ""
    # incident bundles retained under incident_dir: past the cap the
    # oldest bundles are evicted after each capture
    incident_keep: int = 16
    # capture a bounded jax.profiler trace window into the incident dir
    # on each incident (real device traces on TPU; documented
    # no-op-with-reason off-TPU — see incidents.deep_profile)
    obs_profile_on_incident: bool = False
    # checkpoint directory for adaptive searches ("" = disabled)
    checkpoint_dir: str = ""
    # -- serving (dask_ml_tpu/serving/) ----------------------------------
    # smallest / largest padded batch the micro-batcher emits; the shape
    # ladder is the geometric sequence between them, so steady-state
    # serving uses at most ceil(log_growth(max/min)) + 1 compiled
    # programs per method
    serving_min_batch: int = 8
    serving_max_batch: int = 1024
    # ladder growth factor (must be > 1); 2.0 bounds padding waste at
    # <50% of any emitted batch
    serving_bucket_growth: float = 2.0
    # admission control: max requests waiting in the server queue before
    # submit() sheds load with ServerOverloaded
    serving_max_queue: int = 1024
    # how long the batcher holds an admitted request hoping to coalesce
    # more (milliseconds); 0 = dispatch immediately
    serving_batch_window_ms: float = 2.0
    # per-request deadline (milliseconds) measured from admission; a
    # request still queued past it is shed with RequestTimeout
    # (0 = no deadline)
    serving_timeout_ms: float = 1000.0
    # latency SLO (milliseconds, end-to-end enqueue -> demux) — requests
    # over it increment the serving_slo_violations counter (visible in
    # /metrics and the report counters table). With an SLO set the
    # micro-batcher also switches from the fixed coalescing window to
    # DEADLINE-AWARE release: a partial batch dispatches as soon as the
    # oldest request's SLO budget minus the predicted execution time
    # (windowed per-(method, bucket) histogram quantile) says waiting
    # longer would miss, and may coalesce LONGER than the fixed window
    # when the budget is ample. 0 = no SLO accounting, fixed window
    serving_slo_ms: float = 0.0
    # -- serving fleet (dask_ml_tpu/serving/fleet.py) ---------------------
    # replica count for FleetServer; 0 = auto (one replica per local
    # device when several exist, else 1). More replicas than devices
    # share devices round-robin as thread replicas
    serving_replicas: int = 0
    # SLO-aware admission at the fleet door: when an SLO is configured
    # and every replica's predicted completion (queued rows / predicted
    # batch execution from the live latency histograms) would miss it,
    # shed IMMEDIATELY with SloShed instead of queueing a request that
    # is already doomed — backpressure before the latency collapse, not
    # after
    serving_slo_shed: bool = True
    # replica supervision (reliability/supervisor.py): FleetServer.start
    # arms a background supervisor that REBUILDS a dead replica off the
    # serving path — fresh ModelServer at the registry's current
    # version, warmed before it rejoins routing, its stranded queue
    # drained onto the replacement (serving_replica_restarts counts).
    # Off by default: restart-on-death is an operational policy;
    # failover-only fleets keep today's behavior
    serving_supervise: bool = False
    # max rebuilds per replica slot before it degrades to PERMANENT
    # failover (serving_replica_failures; stale gauges dropped) — a
    # crash-looping replica must not burn the fleet on rebuild loops
    serving_restart_budget: int = 3
    # supervisor sweep cadence (seconds)
    serving_supervise_interval_s: float = 0.5
    # versions a ModelRegistry keeps per model name for rollback (the
    # current version is never evicted)
    serving_registry_keep: int = 8
    # extra serving entry-point flavors to PRE-BUILD and warm alongside
    # the float32 ones (comma/space separated; only "int8" today).
    # ModelServer.warmup() then compiles BOTH flavors' (method, bucket)
    # grids, so a registry publish flagged quantize="int8" (and the
    # rollback to f32) hot-swaps with ZERO new XLA compiles — the
    # two-phase swap contract extended to precision flavors. Unlisted
    # flavors swap via rebuild_model (fresh compiles off the serving
    # path) instead
    serving_warm_flavors: str = ""
    # -- serving federation (dask_ml_tpu/serving/federation.py) -----------
    # how long a FederatedFleet router trusts a cached process /status
    # snapshot before re-polling it (seconds) — routing reads the cache;
    # only a stale cache pays the poll
    serving_federation_poll_s: float = 0.5
    # per-call deadline for one cross-process operation (a /status poll,
    # one routed submit, one publish fan-out push); a process that
    # cannot answer inside it is treated as down and failed over
    serving_federation_timeout_s: float = 10.0
    # how long a process marked down stays out of routing before the
    # router probes it again (seconds) — a rebooted process rejoins on
    # the first successful probe and is re-converged to the control
    # plane's current version
    serving_federation_retry_s: float = 2.0
    # -- serving autoscale (dask_ml_tpu/serving/autoscale.py) -------------
    # FleetServer.start arms a ReplicaAutoscaler: the SLO admission
    # signal (queued rows x windowed exec quantiles) ADDS replicas under
    # sustained predicted pressure and RETIRES them (graceful drain)
    # when it subsides, instead of only shedding. Off by default:
    # elasticity is an operational policy, fixed fleets keep today's
    # behavior
    serving_autoscale: bool = False
    # replica-count bounds the autoscaler never crosses (min also floors
    # scale-down; the fleet's construction-time count seeds the pool)
    serving_autoscale_min: int = 1
    serving_autoscale_max: int = 4
    # autoscaler sweep cadence (seconds)
    serving_autoscale_interval_s: float = 0.25
    # hysteresis bands on the predicted completion signal
    # (milliseconds): scale UP when the best replica's predicted
    # completion for a top-bucket request stays above the up band,
    # DOWN when it stays below the down band. 0 = derive from
    # serving_slo_ms (80% / 20% of the SLO)
    serving_autoscale_up_ms: float = 0.0
    serving_autoscale_down_ms: float = 0.0
    # consecutive over/under-band sweeps required before a scale action
    # fires (debounce: one bursty tick must not mint a replica)
    serving_autoscale_patience: int = 2
    # seconds after any scale action during which no further action
    # fires (the new pool must see traffic before being judged)
    serving_autoscale_cooldown_s: float = 2.0


_ENV_PREFIX = "DASK_ML_TPU_"
_state = threading.local()


def _from_env() -> Config:
    cfg = Config()
    for f in dataclasses.fields(Config):
        env = os.environ.get(_ENV_PREFIX + f.name.upper())
        if env is None:
            continue
        # f.type is the annotation STRING under `from __future__ import
        # annotations` — dispatch on the declared default's type instead
        kind = type(getattr(cfg, f.name))
        if kind is bool:
            value = env.strip().lower() in ("1", "true", "yes", "on")
        elif kind is int:
            value = int(env)
        elif kind is float:
            value = float(env)
        else:
            value = env
        setattr(cfg, f.name, value)
    return cfg


# accepted config.dtype spellings -> canonical names; the error message
# below enumerates them so a typo is a one-line fix, not a spelunk
_DTYPE_ALIASES = {
    "auto": "auto",
    "float32": "float32", "f32": "float32", "fp32": "float32",
    "bfloat16": "bfloat16", "bf16": "bfloat16",
}


def normalize_dtype(dt: str) -> str:
    """Canonical dtype name for a config/estimator dtype string.
    Unknown spellings raise — a typo silently training f32 would
    corrupt every precision and benchmark expectation downstream."""
    canon = _DTYPE_ALIASES.get(str(dt).strip().lower())
    if canon is None:
        raise ValueError(
            f"dtype={dt!r} is not supported; accepted spellings: "
            "'auto', 'float32' (aliases 'f32', 'fp32'), "
            "'bfloat16' (alias 'bf16')"
        )
    return canon


def resolve_dtype(override=None) -> tuple[str, str]:
    """(resolved canonical dtype, why) for a fit: the per-estimator
    ``override`` wins over ``config.dtype``; "auto" resolves to
    bfloat16 on real TPU (benched parity, MXU-rate bf16) and float32
    everywhere else — the automatic f32 fallback the fit info
    records."""
    src = "estimator" if override is not None else "config"
    dt = normalize_dtype(override if override is not None
                         else get_config().dtype)
    if dt != "auto":
        return dt, src
    import jax

    if jax.default_backend() == "tpu":
        return "bfloat16", "auto:tpu"
    return "float32", f"auto:{jax.default_backend()}-fallback"


def mxu_dtype(override=None):
    """The matmul compute dtype the current config (or the estimator's
    ``fit_dtype`` override) asks for, or None for plain f32 — the ONE
    mapping from ``config.dtype`` to the kernels' ``mxu_dtype``/cast
    arguments (KMeans distances, PCA Gram, SGD epoch grids, GLM design
    matrices)."""
    dt, _ = resolve_dtype(override)
    if dt == "bfloat16":
        import jax.numpy as jnp

        return jnp.bfloat16
    return None


def fit_dtype_info(override=None) -> dict:
    """The resolved fit compute dtype as fit-info fields: estimators
    merge this into ``solver_info_`` / expose it as ``fit_dtype_`` so
    an automatic f32 fallback (auto policy off-TPU) is on record, not
    silent."""
    dt, src = resolve_dtype(override)
    return {"fit_dtype": dt, "fit_dtype_source": src}


COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def ensure_compile_cache() -> str:
    """Place jax's persistent compilation cache; returns the directory
    in effect. Called ONCE, from the package's import (before anything
    can compile — jax latches the cache at its first compile).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache lives there
    and no directory is set in code (jax reads the variable itself);
    otherwise it lives at ``<checkout>/.jax_cache`` — a FIXED path,
    because the path is part of the cache key and a directory that
    moves never hits.

    The thresholds are zeroed either way so even sub-second
    streamed-block programs are cached: the dispatch-bound hot loops
    this repo cares about are exactly the ones whose many small
    compiles add up."""
    import jax

    d = os.environ.get(COMPILE_CACHE_ENV)
    if not d:
        d = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache",
        )
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d


def get_config() -> Config:
    stack = getattr(_state, "stack", None)
    if stack:
        return stack[-1]
    cached = getattr(_state, "base", None)
    if cached is None:
        cached = _from_env()
        _state.base = cached
    return cached


@contextlib.contextmanager
def set(**overrides):
    """``with config.set(stream_block_rows=1_000_000): ...`` — the
    dask.config.set analog."""
    base = get_config()
    new = dataclasses.replace(base, **overrides)
    stack = getattr(_state, "stack", None)
    if stack is None:
        stack = _state.stack = []
    stack.append(new)
    try:
        yield new
    finally:
        stack.pop()
