"""ModelServer: online inference over a fitted estimator.

The serving loop the dask-ml reference never had (its inference story
stops at offline blockwise ``ParallelPostFit``): many small, concurrently
arriving requests of ragged sizes are admitted into a bounded queue,
coalesced by a micro-batcher into padded batches drawn from a geometric
ladder of shape buckets (``_buckets``), executed through one compiled
static-shape entry point per method (``wrappers.compiled_batch_fn`` —
device-resident parameters, donated ping-pong input staging), and
demultiplexed back to the callers with padding rows masked out.

Around the hot loop:

- admission control / backpressure — ``submit`` never blocks: a full
  queue sheds immediately with :class:`ServerOverloaded` (the caller's
  cue to retry elsewhere), and requests whose deadline lapses while
  queued resolve with :class:`RequestTimeout`;
- ``warmup()`` — compiles every (method, bucket) program up front, so a
  warmed server answers steady-state ragged traffic with ZERO new XLA
  compiles (asserted by the serving tests via the observability
  recompile counter);
- graceful drain — ``stop()`` (or leaving the context manager) stops
  admissions, finishes every queued request, and joins the worker;
- telemetry — per-batch ``serving.batch`` spans plus queue-depth /
  occupancy / padding-waste / shed counters through
  ``dask_ml_tpu/observability`` (``serving/metrics.py``).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..wrappers import ParamSwapError, compiled_batch_fn
from . import metrics as smetrics
from ._batching import (
    BoundedQueue,
    PingPongStaging,
    Request,
    demux_outputs,
    fail_requests,
    pack_batch,
    release_deadline,
)
from ._buckets import BucketLadder
from .policy import ExecStats

__all__ = ["ModelServer", "ServingError", "ServerOverloaded",
           "RequestTimeout", "ServerClosed", "SloShed"]


class ServingError(RuntimeError):
    """Base class for typed serving failures."""


class ServerOverloaded(ServingError):
    """Admission control shed this request: the bounded queue is full.
    Retry with backoff, widen ``max_queue``, or add replicas."""


class SloShed(ServerOverloaded):
    """SLO-aware admission shed this request: every candidate replica's
    predicted completion (queued work x predicted execution time) would
    miss ``config.serving_slo_ms``. Queueing it anyway would only add a
    guaranteed violation — retry with backoff or add capacity."""


class RequestTimeout(ServingError, TimeoutError):
    """The request's deadline passed while it waited in the queue."""


class ServerClosed(ServingError):
    """submit() after stop()/drain began."""


class ModelServer:
    """Serve ``estimator``'s post-fit methods over micro-batched
    concurrent requests.

    Parameters
    ----------
    estimator : fitted estimator or pipeline ending in one
    methods : tuple of method names to serve (compiled entry points are
        built eagerly — a typo fails at construction, not first request)
    ladder : BucketLadder, default from config
        (``serving_min_batch`` / ``serving_max_batch`` /
        ``serving_bucket_growth``)
    max_queue : int, queued-request bound for admission control
    batch_window_ms : float, coalescing wait after the first request
    timeout_ms : float, per-request queue deadline (0 = none)

    Use as a context manager::

        with ModelServer(clf).warmup() as srv:
            fut = srv.submit(x)           # -> Future
            y = srv.predict(x)            # blocking convenience
    """

    def __init__(self, estimator, methods=("predict",), ladder=None,
                 max_queue=None, batch_window_ms=None, timeout_ms=None,
                 device=None, replica_id=None, name=None):
        from ..config import get_config

        cfg = get_config()
        # config is thread-local; the worker thread re-applies the
        # config active HERE so trace_dir/metrics/counter gating follow
        # the server's creator, not the daemon thread's defaults
        self._cfg = cfg
        self.estimator = estimator
        self.ladder = ladder if ladder is not None \
            else BucketLadder.from_config()
        self.max_queue = int(cfg.serving_max_queue
                             if max_queue is None else max_queue)
        self.batch_window_s = float(
            cfg.serving_batch_window_ms
            if batch_window_ms is None else batch_window_ms
        ) / 1e3
        self.timeout_s = float(
            cfg.serving_timeout_ms if timeout_ms is None else timeout_ms
        ) / 1e3
        # deadline-aware batch release (see _batching.release_deadline):
        # armed by an SLO in the creator's config
        self._slo_s = float(cfg.serving_slo_ms) / 1e3
        # per-replica placement: the fleet commits each replica's param
        # pytrees to its own device; None = default device
        self.device = device
        self.replica_id = replica_id
        self.model_version = 0          # stamped by swap/rebuild/fleet
        # quality observability (observability/drift.py): serving-side
        # sketches + the hot-swap shadow canary, keyed by this model
        # name (a fleet stamps its registry name onto every replica).
        # The gate is captured ONCE — the worker must not pay a config
        # read per batch
        self.model_name = str(name) if name else type(estimator).__name__
        self._drift_on = bool(cfg.obs_drift)
        # request trace plane (observability/_requests.py): the gate is
        # captured ONCE, like _drift_on — with obs_trace_sample=0 the
        # hot path never allocates a trace (one bool check per admit /
        # batch), and nothing the plane does ever enters a jaxpr
        self._trace_on = float(cfg.obs_trace_sample) > 0.0
        # versions whose publish ran the shadow canary — traces served
        # by such a version carry the canary_scored tag
        self._canary_versions = set()
        self._shadow_frac = float(cfg.obs_shadow_fraction)
        self._shadow = {}               # method -> drift.ShadowBuffer
        self._pend = {}                 # method -> pending fold sample
        self._pend_lock = threading.Lock()
        self._next_fold_t = 0.0         # backpressure gate (see _execute)
        self._fns = {m: compiled_batch_fn(estimator, m, device=device)
                     for m in methods}
        # sparse (CSR-in) entry points (ISSUE 13): linear predict /
        # decision_function bucketed by (rows, nnz) — built eagerly
        # (compiles only when called/warmed) so hashed-text traffic
        # stops paying the host fallback; methods/estimators without a
        # sparse story simply have no entry here and a sparse submit
        # refuses typed
        from ..wrappers import sparse_batch_fn

        self._sparse_fns = {}
        for m in methods:
            try:
                sfn = sparse_batch_fn(estimator, m, device=device)
            except Exception:
                sfn = None
            if sfn is not None:
                self._sparse_fns[m] = sfn
        # precision-flavor table: "" (float32) plus every flavor named
        # in config.serving_warm_flavors gets its OWN entry-point set,
        # built now and warmed by warmup() — so a registry publish
        # flagged quantize="int8" (and the rollback to f32) hot-swaps
        # between flavors with ZERO new XLA compiles. Methods without
        # an int8 path (predict_proba, non-linear families) build a
        # fresh higher-precision entry point inside the flavor, so a
        # quantized server still serves them.
        self._flavor_fns = {"": self._fns}
        for fl in str(cfg.serving_warm_flavors).replace(",", " ").split():
            if fl in self._flavor_fns:
                continue
            self._flavor_fns[fl] = {
                m: compiled_batch_fn(estimator, m, device=device,
                                     quantize=fl)
                for m in methods
            }
        self._active_flavor = ""
        self._queue = BoundedQueue(self.max_queue)
        self._staging = PingPongStaging()
        self._latency = smetrics.LatencyWindow()
        self._stats_cursor = None       # windowed-quantile cursor
        self._exec = ExecStats()        # per-(method,bucket) exec times
        self._lock = threading.Lock()
        self._thread = None
        self._stop = threading.Event()
        self._accepting = False
        self._paused = threading.Event()
        self._paused.set()              # set = running, cleared = paused
        self._parked = threading.Event()  # worker acknowledged a pause
        self._batches = 0
        self._warmed = False

    # -- lifecycle --------------------------------------------------------
    def start(self):
        from ..observability.live import ensure_telemetry, register_server

        # a serving process is exactly what the live exporter exists
        # for: arm it (no-op unless config.obs_http_port is set) and
        # list this server's stats() window on /status
        ensure_telemetry()
        register_server(self)
        if self._drift_on:
            # register the served version's training profile (when the
            # fit recorded one) and arm the background drift monitor —
            # both host-only, neither touches the request path
            from ..observability import drift

            drift.note_training_profile(
                self.model_name, self.model_version,
                getattr(self.estimator, "training_profile_", None),
            )
            drift.ensure_monitor(self._cfg)
        with self._lock:
            if self._thread is not None:
                return self
            if self._queue.closed:   # restart after stop(): fresh queue
                self._queue = BoundedQueue(self.max_queue)
            self._stop.clear()
            self._accepting = True
            self._thread = threading.Thread(
                target=self._run, name="dask-ml-tpu-serving", daemon=True
            )
            self._thread.start()
        return self

    def stop(self, drain=True, timeout=None):
        """Stop admissions; with ``drain`` (default) finish every queued
        request before joining the worker, else shed them with
        ServerClosed."""
        from ..observability.live import unregister_server

        unregister_server(self)
        with self._lock:
            self._accepting = False
            thread = self._thread
        # close the queue under ITS lock: every put that succeeded
        # happens-before this, so the worker's tail drain sees it —
        # submit() racing with stop() either gets ServerClosed or a
        # request the drain is guaranteed to serve
        self._queue.close()
        if thread is None:
            # never started: resolve anything queued directly
            self._shed_queue(drain)
            if self._drift_on:
                self._flush_quality()
            return
        if not drain:
            fail_requests(self._queue.drain_all(), ServerClosed(
                "server stopped without drain"
            ))
        self._paused.set()              # a paused server must still drain
        self._stop.set()
        self._queue.wake()
        thread.join(timeout)
        with self._lock:
            self._thread = None
        if self._drift_on:
            # the drained tail's pending sample folds before callers
            # read scores (tests stop the server, then compute)
            self._flush_quality()

    def _shed_queue(self, drain):
        reqs = self._queue.drain_all()
        if not reqs:
            return
        if drain:
            for r in reqs:
                self._execute([r])
        else:
            fail_requests(reqs, ServerClosed("server stopped"),
                          outcome="closed")

    def __enter__(self):
        return self.start()

    def __exit__(self, exc_type, exc, tb):
        self.stop(drain=exc_type is None)
        return False

    def pause(self):
        """Hold the worker between batches (requests keep queueing up to
        the admission bound) — maintenance windows and backpressure
        tests. Blocks briefly until the worker acknowledges the park, so
        requests submitted after pause() returns stay queued."""
        self._parked.clear()
        self._paused.clear()
        if self._thread is not None:
            self._parked.wait(5.0)
        return self

    def resume(self):
        self._paused.set()
        return self

    @property
    def healthy(self) -> bool:
        """Accepting requests with a live (or not-yet-started) worker —
        the fleet's routing predicate."""
        if not self._accepting:
            return False
        thread = self._thread
        return thread is None or thread.is_alive()

    # -- hot-swap ----------------------------------------------------------
    def swap_model(self, estimator, version=None, quantize=None):
        """Zero-recompile hot-swap: replace the served parameters with
        ``estimator``'s under the SAME compiled entry points
        (``CompiledBatchFn.swap_params`` — programs close over shapes,
        not values, so a same-shape swap mints no XLA compile; asserted
        via the recompile counters in tests and fleet_smoke). Raises
        :class:`~dask_ml_tpu.wrappers.ParamSwapError` when the new
        version is structurally incompatible — use :meth:`rebuild_model`
        then. In-flight batches finish on the old version; batches
        packed after return serve the new one. Safe under live traffic.

        ``quantize`` selects the serving precision FLAVOR for the new
        version ("int8" or None = float32). Flavors named in
        ``config.serving_warm_flavors`` were pre-built at construction
        and warmed with warmup(), so flipping a model between f32 and
        int8 is the same zero-compile swap as a same-flavor version
        push; an un-warmed flavor refuses with ParamSwapError (the
        rebuild_model cue), keeping the no-compiles-on-the-serving-path
        contract explicit.
        """
        flavor = quantize or ""
        fns = self._flavor_fns.get(flavor)
        if fns is None:
            raise ParamSwapError(
                f"serving flavor {flavor!r} was not pre-built on this "
                "server; add it to config.serving_warm_flavors (and "
                "re-warm) or install via rebuild_model"
            )
        # validate EVERY method against the new estimator before
        # mutating ANY entry point: a multi-method server must never be
        # left half-swapped (predict on v2, predict_proba on v1).
        # prepare_swap covers every entry-point flavor — compiled,
        # pipeline, host fallback — and touches no live state.
        tokens = {}
        for m, fn in fns.items():
            try:
                tokens[m] = fn.prepare_swap(estimator)
            except ParamSwapError as exc:
                raise ParamSwapError(f"method {m!r}: {exc}") from exc
        # the sparse entry points swap in the same two-phase pass — a
        # version flip must never leave dense serving v2 while sparse
        # still serves v1
        sparse_tokens = {}
        for m, fn in self._sparse_fns.items():
            try:
                sparse_tokens[m] = fn.prepare_swap(estimator)
            except ParamSwapError as exc:
                raise ParamSwapError(f"sparse method {m!r}: {exc}") \
                    from exc
        # canary phase 1 (obs_drift + a warmed server only): score the
        # shadow sample of recent traffic against the OUTGOING params
        # through the already-compiled entry points — the batch rides a
        # warmed ladder bucket, so both canary passes mint ZERO XLA
        # compiles (the zero-recompile swap contract holds with the
        # canary on)
        v_old = self.model_version
        if self._drift_on:
            # the outgoing version's pending sample must fold under ITS
            # version key before the flip
            self._flush_quality()
        old_outs = self._canary_pass() if self._drift_on else {}
        for m, fn in fns.items():
            fn.commit_swap(tokens[m])
        for m, fn in self._sparse_fns.items():
            fn.commit_swap(sparse_tokens[m])
        # flavor flip is one dict-reference assignment: the worker reads
        # self._fns[method] per batch, so it sees either the complete
        # old flavor or the complete new one
        self._fns = fns
        self._active_flavor = flavor
        self.estimator = estimator
        if version is not None:
            self.model_version = int(version)
        else:
            self.model_version += 1
        if old_outs:
            # traces served by this version carry canary_scored: the
            # publish was shadow-scored against recent traffic
            self._canary_versions.add(self.model_version)
            # canary phase 2: the SAME shadow rows through the
            # just-committed parameters; the per-method prediction
            # deltas (disagreement + max quantile shift) publish as
            # per-version series on /metrics and a JSONL drift record
            from ..observability import drift

            for m, (sample_n, old) in old_outs.items():
                try:
                    new = self._canary_run(m, sample_n[0], sample_n[1])
                    drift.record_canary(self.model_name, v_old,
                                        self.model_version, m, old, new)
                except Exception:
                    pass  # diagnostics never fail a swap
        if self._drift_on:
            from ..observability import drift

            drift.note_training_profile(
                self.model_name, self.model_version,
                getattr(estimator, "training_profile_", None),
            )
        smetrics.record_swap()
        if self.replica_id is not None:
            smetrics.set_replica_gauges(self.replica_id,
                                        version=self.model_version)
        return self

    def _canary_pass(self):
        """Run every shadow-sampled method's reservoir through the LIVE
        entry points (pre-commit = outgoing version). Returns
        {method: ((padded_batch, n_rows), outputs)} — phase 2 reruns the
        identical padded batch post-commit. Only a warmed server
        canaries (every ladder bucket is compiled, so the pass cannot
        mint a compile); failures return {} and never block the swap."""
        if not self._warmed:
            return {}
        outs = {}
        for m, buf in list(self._shadow.items()):
            fn = self._fns.get(m)
            if fn is None or not fn.jitted:
                continue
            try:
                sample = buf.sample()
                if sample is None:
                    continue
                sample = sample[: self.ladder.max_rows]
                bucket = self.ladder.bucket_for(len(sample))
                padded = np.zeros((bucket, sample.shape[1]), np.float32)
                padded[: len(sample)] = sample
                outs[m] = ((padded, len(sample)),
                           self._canary_run(m, padded, len(sample)))
            except Exception:
                continue
        return outs

    def _canary_run(self, method, padded, n_rows):
        return np.asarray(self._fns[method](padded))[:n_rows]

    _KEEP_FLAVOR = object()  # "caller didn't say": keep current flavor

    def rebuild_model(self, estimator, version=None, warm=None,
                      quantize=_KEEP_FLAVOR):
        """The slow path a shape-incompatible publish needs: build fresh
        compiled entry points for ``estimator`` (paying compiles), warm
        them off the serving path, then install atomically. ``warm``
        defaults to whether this server was warmed. Every pre-built
        flavor rebuilds (a shape change invalidates all of them);
        ``quantize`` picks which flavor serves afterward — with the
        SAME semantics as :meth:`swap_model` (None = float32; an
        int8-serving replica receiving a shape-changed f32 publish must
        come out serving f32, not its old flavor). Omitting the
        argument keeps the current flavor. Naming a flavor that wasn't
        in the table adds it (this is the paid path, so growing the
        flavor set here is fine)."""
        flavor = self._active_flavor \
            if quantize is ModelServer._KEEP_FLAVOR else (quantize or "")
        flavors = set(self._flavor_fns) | {flavor}
        table = {
            fl: {m: compiled_batch_fn(estimator, m, device=self.device,
                                      quantize=(fl or None))
                 for m in self._fns}
            for fl in flavors
        }
        if warm or (warm is None and self._warmed):
            for fns in table.values():
                self._warm_fns(fns)
        # sparse entry points rebuild alongside (fresh shapes) over the
        # SERVED methods, not the old sparse table — a server whose
        # previous estimator had no sparse story gains entry points
        # when the rebuilt one supports them; the (rows, nnz) grid
        # re-warms lazily or via warmup_sparse()
        from ..wrappers import sparse_batch_fn

        sparse_table = {}
        for m in self._fns:
            try:
                sfn = sparse_batch_fn(estimator, m, device=self.device)
            except Exception:
                sfn = None
            if sfn is not None:
                sparse_table[m] = sfn
        self._sparse_fns = sparse_table
        self._flavor_fns = table
        self._fns = table[flavor]
        self._active_flavor = flavor
        self.estimator = estimator
        if version is not None:
            self.model_version = int(version)
        else:
            self.model_version += 1
        if self._drift_on:
            # a rebuild changes shapes — the old shadow rows no longer
            # fit the new entry points, so no canary; the new version's
            # training profile still registers for train-vs-serve
            from ..observability import drift

            self._shadow.clear()
            drift.note_training_profile(
                self.model_name, self.model_version,
                getattr(estimator, "training_profile_", None),
            )
        smetrics.record_swap(rebuilt=True)
        if self.replica_id is not None:
            smetrics.set_replica_gauges(self.replica_id,
                                        version=self.model_version)
        return self

    # -- warmup -----------------------------------------------------------
    def warmup(self):
        """Compile every (method, bucket) program now, before traffic:
        one call per rung per method through the real entry point. After
        this, a workload whose batches stay on the ladder triggers zero
        new XLA compiles.

        Warming routes through the process-wide plans WarmupRegistry
        (ISSUE 15): each (entry point, rung) warms at most once per
        process — a second server over the same-shaped model (whose
        plan-cached build shares the first's compiled entry points)
        skips the redundant executions (``plan_cache_hits`` counts),
        and the plans table on ``/status`` / in the report CLI shows
        which ladder rung minted each specialization.

        These compiles also land in jax's persistent compilation cache
        (``config.ensure_compile_cache``): warmup still walks the full
        (method, bucket) grid, but a later process serving the same
        model shapes replays each program from disk instead of paying
        XLA again — cold-start warmup cost becomes mostly cache reads."""
        # every pre-built flavor warms (config.serving_warm_flavors):
        # a later f32 <-> int8 flavor swap then hits only warm caches
        for fns in self._flavor_fns.values():
            self._warm_fns(fns)
        self._warmed = True
        return self

    @staticmethod
    def _plan_token(fn):
        """The warm-dedup identity of a compiled entry point: the plan
        token of its (innermost, for pipelines) tracked jit. Plan-cached
        builds share tokens exactly when they share executables, so the
        registry skips precisely the warms whose compiles already
        exist; a host fallback (or a jit built outside the plan layer)
        gets a per-object token."""
        inner = fn
        while getattr(inner, "_inner", None) is not None:
            inner = inner._inner
        tgt = getattr(inner, "_fn", None)
        tok = getattr(tgt, "plan_token", None)
        return tok if tok is not None else ("obj", id(fn))

    @staticmethod
    def _plan_prog(fn):
        """The program name warmups attribute to — the innermost
        tracked jit's (a pipeline's own ``_fn`` is None; its compiled
        program is the final step's leaf)."""
        inner = fn
        while getattr(inner, "_inner", None) is not None:
            inner = inner._inner
        return getattr(getattr(inner, "_fn", None), "program_name",
                       None)

    def _warm_fns(self, fns):
        from ..plans import warmups

        for method, fn in fns.items():
            if not fn.jitted:
                continue   # host fallback: nothing to compile
            d = fn.n_features or self._probe_width()
            if d is None:
                raise ValueError(
                    "cannot infer n_features for warmup; estimator "
                    "exposes neither fitted params nor n_features_in_"
                )
            token = self._plan_token(fn)
            prog = self._plan_prog(fn)
            # the key carries the replica's device: XLA specializes per
            # param placement, so two replicas sharing one plan-cached
            # entry point still each warm their own device's programs
            for bucket in self.ladder:
                warmups.warm(
                    ("serving", token, self.device, int(bucket),
                     int(d)),
                    lambda b=bucket: fn(np.zeros((b, d), np.float32)),
                    program=prog, ladder="serving-rows",
                    rung=int(bucket),
                )

    def _probe_width(self):
        est = self.estimator
        if hasattr(est, "steps"):
            est = est.steps[0][1]
        return getattr(est, "n_features_in_", None)

    def warmup_sparse(self, max_nnz=None):
        """Compile the sparse entry points' (rows, nnz-bucket) grid —
        every row rung x every nnz rung (bounded above by
        ``max_nnz``'s rung when given, so a deployment that knows its
        traffic density doesn't compile the whole ladder). Routed
        through the plans WarmupRegistry like the dense grid. After
        this, sparse traffic whose batches stay on the grid mints zero
        new XLA compiles; over-top-nnz batches spill to the
        (dense-warmed) densify path."""
        from ..plans import warmups

        for fn in self._sparse_fns.values():
            top = fn.nnz_ladder.max_rows if max_nnz is None \
                else fn.nnz_bucket(min(max_nnz, fn.nnz_ladder.max_rows))
            token = self._plan_token(fn)
            prog = self._plan_prog(fn)
            for rb in self.ladder:
                for nb in fn.nnz_ladder:
                    if nb > top:
                        break
                    warmups.warm(
                        ("serving-sparse", token, self.device,
                         int(rb), int(nb)),
                        lambda rb=rb, nb=nb: fn.warm(rb, nb),
                        program=prog, ladder="serving-nnz",
                        rung=int(nb),
                    )
        return self

    # -- request plane ----------------------------------------------------
    def submit(self, X, method="predict"):
        """Admit one request; returns a ``concurrent.futures.Future``
        resolving to the method's output rows for ``X``. Sheds with
        ServerOverloaded when the queue is at bound, ServerClosed after
        stop. Requests taller than the top bucket are chunked internally
        and reassembled — one Future either way."""
        if method not in self._fns:
            raise ValueError(
                f"method {method!r} not served; constructed with "
                f"methods={tuple(self._fns)}"
            )
        if not self._accepting:
            raise ServerClosed("server is not accepting requests")
        import scipy.sparse as sp_

        if sp_.issparse(X):
            return self._submit_sparse(X, method)
        X = np.asarray(X, np.float32)
        if X.ndim == 1:
            X = X[None, :]
        if X.ndim != 2 or X.shape[0] == 0:
            raise ValueError(
                f"expected a non-empty (n, d) request, got {X.shape}"
            )
        want = self._fns[method].n_features
        if want is not None and X.shape[1] != want:
            raise ValueError(
                f"request has {X.shape[1]} features; the served model "
                f"expects {want}"
            )
        top = self.ladder.max_rows
        if X.shape[0] <= top:
            return self._admit([Request(X, method, self.timeout_s)])
        # oversize: chunk to top-bucket tiles, admit all-or-nothing
        # (atomic in the queue — a shed mid-request must not leave
        # orphaned chunks burning capacity), reassemble via callbacks
        parts = [X[i:i + top] for i in range(0, X.shape[0], top)]
        if len(parts) > self.max_queue:
            # structurally un-admittable even against an idle server:
            # ServerOverloaded ("retry with backoff") would lie — this
            # can never succeed, so fail fast and permanently
            raise ValueError(
                f"request of {X.shape[0]} rows needs {len(parts)} "
                f"chunks but max_queue={self.max_queue}; raise "
                "max_queue or split the request"
            )
        reqs = [Request(p, method, self.timeout_s) for p in parts]
        self._admit(reqs)
        return _gather_futures([r.future for r in reqs])

    def _submit_sparse(self, X, method):
        """Admit a scipy-sparse request onto the sparse serving lane
        (ISSUE 13): CSR blocks coalesce with other sparse requests of
        the same method (never with dense ones — the lane key keeps the
        batcher's packing homogeneous), bucket by (rows, nnz) and run
        the warmed sparse entry point; over-nnz batches spill to the
        densified dense rung. Refuses typed when the served estimator
        has no sparse entry point for ``method``."""
        if method not in self._sparse_fns:
            raise ValueError(
                f"method {method!r} has no sparse entry point on this "
                "server (sparse serving covers linear predict / "
                "decision_function); densify the request or serve a "
                "linear model"
            )
        import scipy.sparse as sp_

        X = X.tocsr() if not sp_.isspmatrix_csr(X) else X
        if X.ndim != 2 or X.shape[0] == 0:
            raise ValueError(
                f"expected a non-empty sparse (n, d) request, got "
                f"{X.shape}"
            )
        want = self._sparse_fns[method].n_features
        if want is not None and X.shape[1] != want:
            raise ValueError(
                f"request has {X.shape[1]} features; the served model "
                f"expects {want}"
            )
        lane = method + "#sparse"
        top = self.ladder.max_rows
        if X.shape[0] <= top:
            return self._admit([Request(X, lane, self.timeout_s)])
        parts = [X[i:i + top] for i in range(0, X.shape[0], top)]
        if len(parts) > self.max_queue:
            raise ValueError(
                f"request of {X.shape[0]} rows needs {len(parts)} "
                f"chunks but max_queue={self.max_queue}; raise "
                "max_queue or split the request"
            )
        reqs = [Request(p, lane, self.timeout_s) for p in parts]
        self._admit(reqs)
        return _gather_futures([r.future for r in reqs])

    def _admit(self, reqs):
        if self._trace_on:
            # traces exist BEFORE the queue decides: a shed/closed
            # request still produces a (tail-sampled) trace — the
            # contract that 100% of refused requests are attributable
            from ..observability import _requests as rtrace

            for r in reqs:
                r.trace = rtrace.new_trace(r.method, r.n_rows,
                                           t_admit=r.t_enqueue)
                if self.replica_id is not None:
                    r.trace.tag(replica=self.replica_id)
        verdict = self._queue.put_many(reqs)
        if verdict == "closed":
            for r in reqs:
                if r.trace is not None:
                    r.trace.finish("closed")
            raise ServerClosed("server is not accepting requests")
        if verdict != "ok":
            smetrics.record_drop("shed")
            for r in reqs:
                if r.trace is not None:
                    r.trace.finish("shed")
            raise ServerOverloaded(
                f"queue at bound ({self.max_queue} requests); request "
                "shed"
            )
        for r in reqs:
            smetrics.record_request(r.n_rows)
        return reqs[0].future

    # blocking conveniences ------------------------------------------------
    def _call(self, X, method):
        import concurrent.futures as cf

        fut = self.submit(X, method=method)
        extra = self.timeout_s if self.timeout_s > 0 else None
        # queue deadline + generous execution allowance; None = wait.
        # The wait-timeout surfaces as the package's typed error (which
        # still subclasses TimeoutError), not cf's — callers are told to
        # catch ServingError subclasses.
        try:
            return fut.result(None if extra is None else 30.0 + extra)
        except cf.TimeoutError:
            raise RequestTimeout(
                f"served {method} did not complete within the "
                f"{self.timeout_s * 1e3:.0f}ms deadline + 30s execution "
                "allowance"
            ) from None

    def predict(self, X):
        return self._call(X, "predict")

    def predict_proba(self, X):
        return self._call(X, "predict_proba")

    def decision_function(self, X):
        return self._call(X, "decision_function")

    def transform(self, X):
        return self._call(X, "transform")

    def score(self, X, y):
        """Served-path score: predictions via the batcher (so padding
        masking is exercised), metric via the package's own
        accuracy/r2 — same dispatch AND same edge-case conventions
        (e.g. constant-target r2 forced to 0.0) as ParallelPostFit."""
        from ..metrics import accuracy_score, r2_score

        pred = self.predict(X)
        y = np.asarray(y)
        if hasattr(self.estimator, "classes_") or hasattr(
                self.estimator, "predict_proba"):
            return float(accuracy_score(y, pred))
        return float(r2_score(y, pred))

    # -- stats -------------------------------------------------------------
    @property
    def queue_rows(self) -> int:
        """Rows currently queued — the fleet's least-loaded routing
        signal (requests vary 1..top-bucket rows, so row depth ranks
        load better than request depth)."""
        return self._queue.rows

    def predict_exec_s(self, method: str, n_rows: int):
        """Predicted execution seconds for an ``n_rows`` batch of
        ``method`` (windowed per-(method, bucket) quantile; None before
        any history) — the fleet admission's per-replica input."""
        try:
            bucket = self.ladder.bucket_for(min(n_rows,
                                                self.ladder.max_rows))
        except ValueError:
            bucket = self.ladder.max_rows
        return self._exec.predict_s(method, bucket)

    def stats(self):
        """Live snapshot: queue depth/rows/peak, batch count, request
        count, and latency quantiles — BOTH lifetime (``latency_s``:
        "how has this server behaved", the histogram keeps the whole
        run) and windowed (``latency_window_s``: quantiles over the
        requests since the PREVIOUS stats() call — the view routing
        and dashboards should ride, since a long fast history dilutes a
        fresh degradation). ``exec_s`` carries the per-(method, bucket)
        execution-time summary feeding deadline release and SLO
        admission."""
        q = self._queue
        cursor = self._stats_cursor
        cur = self._latency.snapshot()
        self._stats_cursor = cur
        out = {
            "queue_depth": q.depth,
            "queue_rows": q.rows,
            "queue_peak_depth": q.peak_depth,
            "batches": self._batches,
            "requests": self._latency.count,
            "warmed": self._warmed,
            "healthy": self.healthy,
            "version": self.model_version,
            "latency_s": self._latency.percentiles((50, 99)),
            "latency_window_s": self._latency.percentiles_between(
                cursor, (50, 99), cur=cur
            ),
            "exec_s": self._exec.snapshot(),
        }
        if self.replica_id is not None:
            out["replica"] = self.replica_id
        return out

    # -- worker ------------------------------------------------------------
    def _run(self):
        import dataclasses

        from .. import config
        from ..observability import watchdog

        # re-apply the creator's (thread-local) config in this thread so
        # spans/counters gate exactly as they did where the server was
        # built; the worker runs under the slow-span watchdog (a no-op
        # unless config.watchdog_timeout_s is set) so a wedged batch
        # execution dumps thread stacks + memory gauges instead of
        # silently freezing the queue
        with config.set(**dataclasses.asdict(self._cfg)):
            with watchdog():
                self._run_loop()

    def _run_loop(self):
        from ..reliability.faults import fault_point

        while True:
            # the replica-worker fault site, BEFORE any request is
            # popped (a crash here kills this worker thread with zero
            # requests in hand — the queued backlog stays recoverable
            # for the fleet supervisor's drain-and-requeue)
            fault_point("replica_worker")
            if not self._paused.is_set():
                if self._stop.is_set():
                    break
                self._parked.set()
                self._paused.wait(0.05)
                continue
            self._parked.clear()
            first = self._queue.pop_first(timeout=0.05)
            if first is None:
                if self._stop.is_set() and self._queue.depth == 0:
                    break
                continue
            self._serve_guarded(first)
        # drain tail: stop() requested with requests still queued
        while True:
            req = self._queue.pop_first(timeout=0.0)
            if req is None:
                break
            self._serve_guarded(req)

    def _serve_guarded(self, first):
        # the worker must be immortal: _execute already fails its own
        # batch on error, this outer guard covers the assembly path so
        # no exception can kill the thread and strand the queue
        try:
            self._serve_one(first)
        except Exception as exc:  # pragma: no cover - defensive
            smetrics.record_drop("error")
            fail_requests([first], ServingError(
                f"serving worker error: {type(exc).__name__}: {exc}"
            ), outcome="error")

    def _serve_one(self, first):
        if first.expired():
            smetrics.record_drop("timeout")
            fail_requests([first], RequestTimeout(
                f"request waited past its {self.timeout_s * 1e3:.0f}ms "
                "deadline"
            ), outcome="timeout")
            return
        batch = [first]
        rows = first.n_rows
        top = self.ladder.max_rows
        # coalescing deadline, measured from the FIRST dequeue (a
        # trickle of stragglers cannot hold a batch forever). With an
        # SLO configured and execution history to predict from, the
        # fixed window is REPLACED by the deadline-aware rule: release
        # when waiting longer would make the oldest request miss its
        # SLO (predicted exec for the CURRENT candidate bucket), and
        # keep coalescing past the fixed window while the budget is
        # ample (_batching.release_deadline)
        dequeue_t = time.perf_counter()
        if first.trace is not None:
            first.trace.stamp("queue_pop", dequeue_t)
        # exec predictions change once per ExecStats WINDOW (seconds),
        # not per coalescing wake (<=10ms) — cache per candidate bucket
        # for this assembly so the loop doesn't pay a locked histogram
        # snapshot + percentile scan on every iteration
        pred_cache = {}
        while rows < top and not self._stop.is_set():
            got = self._queue.drain_method(first.method, top - rows)
            for r in got:
                if r.trace is not None:
                    r.trace.stamp("queue_pop")
                if r.expired():
                    smetrics.record_drop("timeout")
                    fail_requests([r], RequestTimeout(
                        "request waited past its deadline"
                    ), outcome="timeout")
                else:
                    batch.append(r)
                    rows += r.n_rows
            now = time.perf_counter()
            if self._slo_s > 0:
                bucket = self.ladder.bucket_for(rows)
                if bucket not in pred_cache:
                    pred_cache[bucket] = self._exec.predict_s(
                        first.method, bucket
                    )
                predicted = pred_cache[bucket]
            else:
                predicted = None
            deadline = release_deadline(
                first.t_enqueue, dequeue_t, self.batch_window_s,
                self._slo_s, predicted,
            )
            if now >= deadline or rows >= top:
                break
            # sleep on THIS method's lane — depth > 0 from other
            # methods' requests must not turn the window into a spin
            self._queue.wait_method(first.method,
                                    min(deadline - now, 0.01))
        self._execute(batch)

    # pending-fold batching: the sketch fold's ~30 small numpy calls
    # cost ~0.2-1 ms of fixed overhead per invocation — paid per BATCH
    # on the worker thread, that taxes serving throughput by tens of
    # percent. The worker therefore only memcpy's a strided row sample
    # (a few µs) into a pending list and folds it in one amortized
    # chunk every _FOLD_PENDING_ROWS rows / _FOLD_PENDING_S seconds.
    _FOLD_PENDING_ROWS = 1024
    _FOLD_PENDING_S = 0.5
    _FOLD_ROWS_PER_BATCH = 128

    def _fold_quality(self, method, rows_view, out):
        """Serving-side sketch fold + shadow sampling (obs_drift only).
        Pure host numpy on buffers the batch already produced; any
        failure disables quality capture for this server rather than
        ever surfacing into the worker."""
        try:
            from ..observability import drift

            if rows_view.shape[1] > drift._MAX_SKETCH_FEATURES:
                self._drift_on = False   # ultra-wide model: skip capture
                return
            out_rows = None
            try:
                if hasattr(out, "__len__") and len(out) >= len(rows_view):
                    out_rows = np.asarray(out)[: len(rows_view)]
            except Exception:
                out_rows = None
            stride = max(
                -(-len(rows_view) // self._FOLD_ROWS_PER_BATCH), 1
            )
            sample_X = np.array(rows_view[::stride])
            sample_out = np.array(out_rows[::stride]) \
                if out_rows is not None else None
            now = time.monotonic()
            ready = []
            with self._pend_lock:
                pend = self._pend.get(method)
                if pend is not None \
                        and pend["version"] != self.model_version:
                    ready.append(self._pend.pop(method))  # old tail
                    pend = None
                if pend is None:
                    pend = self._pend[method] = {
                        "version": self.model_version, "X": [],
                        "out": [], "rows": 0, "t": now,
                    }
                pend["X"].append(sample_X)
                pend["out"].append(sample_out)
                pend["rows"] += sample_X.shape[0]
                if pend["rows"] >= self._FOLD_PENDING_ROWS \
                        or now - pend["t"] > self._FOLD_PENDING_S:
                    ready.append(self._pend.pop(method))
            for p in ready:
                self._fold_pending(method, p)
            if self._shadow_frac > 0:
                buf = self._shadow.get(method)
                if buf is None:
                    buf = self._shadow[method] = drift.ShadowBuffer()
                buf.offer(rows_view, self._shadow_frac)
        except Exception:  # pragma: no cover - defensive
            self._drift_on = False

    def _flush_quality(self):
        """Fold every method's pending row sample now — the swap path
        (sketches must be current per version before the version flips)
        and ``stop()`` (tests compute scores right after) call this
        from their own threads; the pop is under ``_pend_lock``."""
        with self._pend_lock:
            ready = dict(self._pend)
            self._pend.clear()
        for m, pend in ready.items():
            self._fold_pending(m, pend)

    def _fold_pending(self, method, pend):
        """One amortized sketch fold of a popped pending sample."""
        from ..observability import drift

        if not pend or not pend["rows"]:
            return
        X = np.concatenate(pend["X"], axis=0)
        outs = None
        if pend["out"] and all(o is not None for o in pend["out"]):
            try:
                outs = np.concatenate(
                    [np.atleast_1d(o) for o in pend["out"]], axis=0
                )
            except Exception:
                outs = None
        drift.fold_serving(self.model_name, pend["version"], method, X,
                           outs, max_rows=X.shape[0])

    @staticmethod
    def _tag_fault(batch, exc):
        """Mark every traced request in a failed batch whose failure
        was a chaos-plane injection (``fault_plan`` at the
        serving_execute site) — the tag makes injected faults
        distinguishable from organic batch failures on /traces."""
        from ..reliability.faults import FaultInjected

        if not isinstance(exc, FaultInjected):
            return
        for r in batch:
            if r.trace is not None:
                r.trace.tag(fault_injected=True)

    def _execute(self, batch):
        if batch[0].method.endswith("#sparse"):
            return self._execute_sparse(batch)
        # EVERYTHING from pack to demux sits inside the guard: an
        # exception anywhere (ragged widths slipping past validation,
        # a fallback output that isn't row-sliceable) must fail THIS
        # batch's futures, never kill the worker thread — a dead worker
        # would strand every later request behind a queue nobody drains
        try:
            # the serving-execute fault site sits INSIDE the guard: an
            # injected fault fails THIS batch's futures typed (the
            # worker survives) — the documented batch-failure contract,
            # now deterministically exercisable
            from ..reliability.faults import fault_point

            fault_point("serving_execute")
            method = batch[0].method
            fn = self._fns[method]
            buf, segments, bucket, rows = pack_batch(
                batch, self.ladder, self._staging
            )
            if self._trace_on:
                t_pack = time.perf_counter()
                canary = self.model_version in self._canary_versions
                for r in batch:
                    tr = r.trace
                    if tr is not None:
                        tr.stamp("pack", t_pack)
                        tr.tag(bucket=int(bucket),
                               flavor=self._active_flavor,
                               version=self.model_version)
                        if canary:
                            tr.tag(canary_scored=True)
            smetrics.set_queue_gauges(self._queue.depth, rows,
                                      replica=self.replica_id)
            t_exec = time.perf_counter()
            with smetrics.batch_span(
                method, bucket, rows, len(batch),
                self._queue.depth,
            ):
                out = fn(buf)
            self._batches += 1
            smetrics.record_batch(rows, bucket)
            done = time.perf_counter()
            # the deadline-release / SLO-admission predictor's feed:
            # execution wall of THIS (method, bucket), queue wait
            # excluded
            self._exec.observe(method, bucket, done - t_exec)
            for r in batch:
                lat = done - r.t_enqueue
                self._latency.observe(lat)
                # the /metrics histogram series: per (method, bucket)
                # so a capacity review sees which rung is slow, and the
                # SLO counter when config.serving_slo_ms is set
                smetrics.observe_request_latency(method, bucket, lat)
                tr = r.trace
                if tr is not None:
                    tr.stamp("dispatch", t_exec)
                    tr.stamp("execute_done", done)
                    if self._slo_s > 0 and lat > self._slo_s:
                        tr.tag(slo_violation=True)
            demux_outputs(out, segments)
            if self._drift_on:
                # quality sketches AFTER demux (callers already have
                # their results — the fold never adds request latency):
                # admitted rows + emitted predictions into the
                # per-(model, version, method) serving sketches, plus
                # the shadow reservoir the next hot-swap canary scores.
                # buf/out stay untouched until the next batch packs
                # (single worker thread), so the views are stable here.
                # RATE GATE: sample at most ~20 batches/s into the
                # sketches. A per-batch fold costs far more wall than
                # CPU under concurrent load — every extra preemption
                # point in the worker hands the GIL to a hammering
                # client for a whole switch interval — so the gate must
                # be ONE clock read + compare on the skipped path (a
                # queue-emptiness test flickers with coalescing and
                # makes the overhead nondeterministic)
                now2 = time.monotonic()
                if now2 >= self._next_fold_t:
                    self._next_fold_t = now2 + 0.05
                    self._fold_quality(method, buf[:rows], out)
        except Exception as exc:
            for _ in batch:   # per REQUEST, matching the timeout path
                smetrics.record_drop("error")
            self._tag_fault(batch, exc)
            try:
                # opt-in incident hook: a failed batch is a typed error
                # (one module-global check when the plane is disarmed)
                from ..observability import alerts as _obs_alerts

                _obs_alerts.note_error(exc, "serving_execute")
            except Exception:
                pass
            fail_requests(batch, ServingError(
                f"batch execution failed: {type(exc).__name__}: {exc}"
            ), outcome="error")
        finally:
            # inflight back to 0 on the failure path too — a failed
            # batch must not leave /metrics showing phantom inflight rows
            smetrics.set_queue_gauges(self._queue.depth, 0,
                                      replica=self.replica_id)

    def _execute_sparse(self, batch):
        """The sparse lane's pack → run → demux (ISSUE 13): vstack the
        coalesced CSR requests (O(nnz)), pick the (rows, nnz) grid
        cell, run the sparse entry point, slice per-request rows back
        out. A batch whose nnz overflows the warmed nnz ladder spills
        to the DENSE entry point over a densified batch — the dense
        row rung is already warm, so even the spill mints zero new XLA
        compiles (serving_sparse_spills counts). Same immortal-worker
        guard/metrics contract as the dense _execute."""
        import scipy.sparse as sp_

        try:
            from ..reliability.faults import fault_point

            fault_point("serving_execute")
            lane = batch[0].method
            method = lane[: -len("#sparse")]
            fn = self._sparse_fns[method]
            X = batch[0].X if len(batch) == 1 \
                else sp_.vstack([r.X for r in batch]).tocsr()
            rows = int(X.shape[0])
            bucket = self.ladder.bucket_for(rows)
            if self._trace_on:
                t_pack = time.perf_counter()
                canary = self.model_version in self._canary_versions
                for r in batch:
                    tr = r.trace
                    if tr is not None:
                        tr.stamp("pack", t_pack)
                        tr.tag(bucket=int(bucket),
                               flavor=self._active_flavor,
                               version=self.model_version)
                        if canary:
                            tr.tag(canary_scored=True)
            smetrics.set_queue_gauges(self._queue.depth, rows,
                                      replica=self.replica_id)
            t_exec = time.perf_counter()
            with smetrics.batch_span(lane, bucket, rows, len(batch),
                                     self._queue.depth):
                # the spill decision is an EXPLICIT nnz check, not an
                # exception catch — a real defect raised from the
                # sparse entry point must fail the batch typed, never
                # silently densify every batch forever
                if int(X.nnz) > fn.nnz_ladder.max_rows:
                    # nnz over the ladder top: densify THIS batch into
                    # the warmed dense rung instead of minting a novel
                    # sparse shape
                    from ..observability import record_sparse_spill

                    record_sparse_spill()
                    padded = np.zeros((bucket, X.shape[1]), np.float32)
                    padded[:rows] = X.toarray()
                    out = np.asarray(self._fns[method](padded))[:rows]
                else:
                    out = fn(X, n_rows=bucket)
            self._batches += 1
            smetrics.record_batch(rows, bucket)
            done = time.perf_counter()
            self._exec.observe(lane, bucket, done - t_exec)
            for r in batch:
                lat = done - r.t_enqueue
                self._latency.observe(lat)
                smetrics.observe_request_latency(lane, bucket, lat)
                tr = r.trace
                if tr is not None:
                    tr.stamp("dispatch", t_exec)
                    tr.stamp("execute_done", done)
                    if self._slo_s > 0 and lat > self._slo_s:
                        tr.tag(slo_violation=True)
            out = np.asarray(out)
            lo = 0
            for r in batch:
                f = r.future
                tr = r.trace
                if tr is not None:
                    tr.stamp("demux")
                if f.set_running_or_notify_cancel():
                    f.set_result(out[lo:lo + r.n_rows])
                    if tr is not None:
                        tr.stamp("complete")
                        tr.finish("ok")
                elif tr is not None:
                    tr.finish("cancelled")
                lo += r.n_rows
        except Exception as exc:
            for _ in batch:
                smetrics.record_drop("error")
            self._tag_fault(batch, exc)
            fail_requests(batch, ServingError(
                f"sparse batch execution failed: "
                f"{type(exc).__name__}: {exc}"
            ), outcome="error")
        finally:
            smetrics.set_queue_gauges(self._queue.depth, 0,
                                      replica=self.replica_id)


def _gather_futures(futures):
    """One Future resolving to the row-concatenation of ``futures``'
    results (oversize-request reassembly); the first failure propagates."""
    from concurrent.futures import Future

    out = Future()
    remaining = [len(futures)]
    lock = threading.Lock()

    def _fail(exc):
        try:
            if out.set_running_or_notify_cancel():
                out.set_exception(exc)
        except Exception:
            pass  # already resolved by a racing callback

    def _done(fut):
        # FIRST failure propagates immediately — a doomed oversize
        # request must not keep its caller waiting on the slow chunks
        exc = fut.exception() if not fut.cancelled() else None
        if exc is not None:
            _fail(exc)
            return
        with lock:
            remaining[0] -= 1
            if remaining[0] > 0 or out.done():
                return
        try:
            parts = [f.result() for f in futures]
        except BaseException as exc:  # noqa: BLE001 - forwarded, not hidden
            _fail(exc)
            return
        if out.set_running_or_notify_cancel():
            out.set_result(np.concatenate(parts, axis=0))

    for f in futures:
        f.add_done_callback(_done)
    return out
