"""Meta-estimator wrappers: ParallelPostFit and Incremental.

Reference: ``dask_ml/wrappers.py`` + ``dask_ml/_partial.py`` (SURVEY.md
§2a Wrappers row, §3.6):

- ``ParallelPostFit``: train on small in-memory data, parallelize
  predict/transform/score over blocks.
- ``Incremental``: out-of-core fit via a sequential ``partial_fit`` chain
  over blocks (optionally shuffled per call).

TPU mapping: "blocks" are the row ranges of a ShardedArray. A wrapped
dask_ml_tpu estimator predicts device-parallel as-is (no wrapper machinery
needed — GSPMD already parallelizes); the wrapper's job is interop with
*host* (sklearn-style) estimators: post-fit ops stream blocks through the
host estimator, and ``Incremental.fit`` is the streamed training loop the
reference builds as a linear task chain (the model no longer hops
worker-to-worker; blocks stream to it).
"""

from __future__ import annotations

import copy as _copy

import numpy as np

from .base import BaseEstimator, clone
from .metrics import accuracy_score, r2_score
from .parallel.sharded import ShardedArray, as_sharded

__all__ = ["ParallelPostFit", "Incremental", "CompiledBatchFn",
           "compiled_batch_fn", "ParamSwapError", "SparseBatchFn",
           "sparse_batch_fn"]


def _data_shards(mesh):
    from .parallel.mesh import data_shards

    return data_shards(mesh)


def _device_headroom(nbytes, sample, fraction=0.5):
    """What the headroom gate reads for an extra device allocation of
    ``nbytes`` sharded like ``sample``: ``{"needed": bytes a device,
    "free": the fullest device's bytes_limit - bytes_in_use, "fits":
    needed <= fraction x free}``. ``free`` is None (and the gate passes)
    where the runtime reports no memory_stats — the CPU — or the sample
    lives on the host."""
    out = {"needed": None, "free": None, "fits": True}
    try:
        data = getattr(sample, "data", None)
        if data is None:
            return out  # host sample: no device copy involved
        devs = list(data.devices())
        out["needed"] = int(nbytes // max(len(devs), 1))
        for dev in devs:
            stats = dev.memory_stats()
            if not stats:
                continue
            free = int(stats.get("bytes_limit", 0)
                       - stats.get("bytes_in_use", 0))
            if out["free"] is None or free < out["free"]:
                out["free"] = free
        if out["free"] is not None:
            out["fits"] = out["needed"] <= fraction * out["free"]
    except Exception:
        out["fits"] = True  # no reliable stats: assume fine (host CPU)
    return out


def _is_device_estimator(est):
    return est.__class__.__module__.startswith("dask_ml_tpu")


def _host_matrix(X):
    """Host representation supporting arbitrary row slicing: CSR for any
    sparse source (scipy matrix of any format, SparseBlocks), numpy
    otherwise — the ONE sparse/dense coercion point for the block loops."""
    import scipy.sparse as sp

    from .parallel.streaming import SparseBlocks

    if isinstance(X, SparseBlocks) or sp.issparse(X):
        return X.tocsr()
    return X.to_numpy() if isinstance(X, ShardedArray) else np.asarray(X)


def _host_blocks(X, block_size=100_000):
    """Yield host row blocks of a ShardedArray / array. Sparse X stays
    sparse — host (sklearn) estimators consume CSR blocks natively."""
    host = _host_matrix(X)
    for i in range(0, host.shape[0], block_size):
        yield host[i:i + block_size]


class ParallelPostFit(BaseEstimator):
    """Ref: dask_ml/wrappers.py::ParallelPostFit. The ``*_meta``
    parameters are accepted for API parity: the reference uses them to
    declare dask output metadata; here output types are concrete, so they
    only pin the output dtype when given."""

    def __init__(self, estimator=None, scoring=None, predict_meta=None,
                 predict_proba_meta=None, transform_meta=None):
        self.estimator = estimator
        self.scoring = scoring
        self.predict_meta = predict_meta
        self.predict_proba_meta = predict_proba_meta
        self.transform_meta = transform_meta

    # -- fit: plain in-memory fit of the wrapped estimator ---------------
    def fit(self, X, y=None, **kwargs):
        from .parallel.streaming import SparseBlocks

        est = clone(self.estimator)
        if isinstance(X, ShardedArray):
            Xh = X.to_numpy()
        elif isinstance(X, SparseBlocks):
            Xh = X.tocsr()  # host estimators consume CSR, not the view
        else:
            Xh = X
        yh = y.to_numpy() if isinstance(y, ShardedArray) else y
        if yh is None:
            est.fit(Xh, **kwargs)
        else:
            est.fit(Xh, yh, **kwargs)
        self.estimator_ = est
        return self

    @property
    def _est(self):
        # support wrapping an already-fitted estimator without fit()
        return getattr(self, "estimator_", self.estimator)

    @property
    def classes_(self):
        return self._est.classes_

    @property
    def training_profile_(self):
        """The wrapped estimator's per-feature training profile (see
        observability/sketch.py) — so a served `Incremental`/
        `ParallelPostFit` carries its drift baseline exactly like the
        bare estimator. AttributeError when the inner fit recorded
        none (sklearn hasattr semantics)."""
        prof = getattr(self._est, "training_profile_", None)
        if prof is None:
            raise AttributeError("training_profile_")
        return prof

    # -- parallel post-fit ops --------------------------------------------
    def _pin_meta(self, out, method):
        """Pin the output dtype when a *_meta hint was given (the
        reference uses metas to declare dask output metadata; here output
        types are concrete, so only the dtype survives)."""
        import scipy.sparse as sp

        meta = {"predict": self.predict_meta,
                "predict_proba": self.predict_proba_meta,
                "transform": self.transform_meta}.get(method)
        if meta is not None and hasattr(meta, "dtype") \
                and (isinstance(out, np.ndarray) or sp.issparse(out)):
            out = out.astype(meta.dtype, copy=False)
        return out

    def _apply(self, X, method):
        """One post-fit call: a root span named after ``method``
        (``predict``, ``transform``, ...) around it."""
        from .observability import span

        with span(method, component=type(self).__name__,
                  estimator=type(self._est).__name__):
            return self._apply_blocks(X, method)

    def _apply_blocks(self, X, method):
        est = self._est
        from .parallel.frames import PartitionedFrame

        if isinstance(X, PartitionedFrame):
            # the reference's dd path: map_partitions(est.<method>) —
            # partitions run concurrently through the frame's thread pool
            parts = X.map_partitions(getattr(est, method))
            if isinstance(parts, PartitionedFrame):  # frame-in, frame-out
                return parts
            return self._pin_meta(
                np.concatenate([np.asarray(p) for p in parts], axis=0),
                method,
            )
        if _is_device_estimator(est):
            return getattr(est, method)(X)
        mesh = X.mesh if isinstance(X, ShardedArray) else None
        # blocks are SLICES of one host buffer (views, not copies), so
        # listing them costs nothing beyond the to_numpy pull a host
        # estimator needs anyway
        blocks = list(_host_blocks(X))
        fn = getattr(est, method)
        if len(blocks) > 1:
            # the reference's map_blocks runs post-fit blocks on parallel
            # workers; here a thread pool over the host estimator's
            # (read-only, GIL-releasing sklearn C kernels) per-block calls
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(
                max_workers=min(8, len(blocks))
            ) as pool:
                parts = list(pool.map(fn, blocks))
        else:
            parts = [fn(b) for b in blocks]
        import scipy.sparse as sp

        if any(sp.issparse(p) for p in parts):
            # sparse estimator output (e.g. a transformer): stays sparse
            return self._pin_meta(sp.vstack(parts).tocsr(), method)
        out = self._pin_meta(np.concatenate(parts, axis=0), method)
        return as_sharded(out, mesh=mesh) if mesh is not None else out

    def predict(self, X):
        return self._apply(X, "predict")

    def predict_proba(self, X):
        return self._apply(X, "predict_proba")

    def predict_log_proba(self, X):
        return self._apply(X, "predict_log_proba")

    def decision_function(self, X):
        return self._apply(X, "decision_function")

    def transform(self, X):
        return self._apply(X, "transform")

    def score(self, X, y, compute=True):
        if self.scoring:
            from .metrics.scorer import get_scorer

            return get_scorer(self.scoring)(self, X, y)
        pred = self.predict(X)
        if hasattr(self._est, "classes_") or hasattr(self._est, "predict_proba"):
            return accuracy_score(y, pred)
        return r2_score(y, pred)


# --------------------------------------------------------------------------
# Compiled static-shape predict entry points (the serving subsystem's
# hot-loop contract; see dask_ml_tpu/serving/)
# --------------------------------------------------------------------------

class ParamSwapError(ValueError):
    """A hot-swap was structurally impossible: the new estimator's
    fitted parameters do not match the compiled entry point's shapes /
    family / method semantics. The caller must rebuild the entry point
    (paying fresh compiles) instead of swapping."""


class CompiledBatchFn:
    """A fitted estimator's ``method`` as ONE static-shape batch
    function: ``fn(X)`` takes a host float32 (B, d) block and returns a
    host ndarray with one output row per input row.

    For device estimators the core is a single jitted function of
    ``(params, X)`` — the fitted parameters are a pytree ARGUMENT, not a
    baked-in constant, so the compiled program closes over their SHAPES
    only. That is the hot-swap contract the serving fleet rides:
    :meth:`swap_params` replaces the param pytree under the same
    executable, and because XLA specializes per (param shapes, B), a
    swap to same-shape parameters hits the existing compile cache — ZERO
    new XLA compiles (asserted via the recompile counters in
    tests/test_fleet.py). Callers drawing B from a fixed bucket ladder
    pay a fixed, pre-warmable set of compiles and nothing after, across
    any number of swaps. On backends with real buffer donation
    (TPU/GPU) the input batch is donated (the params never are — they
    are reused every call). ``jitted=False`` marks the host fallback
    (sklearn-style estimators): still batchable and still swappable, no
    compile accounting to speak of.
    """

    __slots__ = ("method", "jitted", "n_features", "donates", "version",
                 "quantize", "_fn", "_state", "_extract", "_sig",
                 "_device", "_prefix", "_inner")

    def __init__(self, fn, method, jitted, n_features, donates=False,
                 params=None, post=None, extract=None, sig=None,
                 device=None, prefix=None, inner=None, quantize=None):
        self._fn = fn
        # pipeline flavor: _state holds the LIVE (prefix, inner) pair —
        # one attribute so a swap publishes both in one assignment.
        # leaf flavor: _state holds (params, post), same single-read
        # contract. _prefix/_inner stay as the flavor flag + debug view.
        self._state = (tuple(prefix), inner) if inner is not None \
            else (params, post)
        self._extract = extract
        self._sig = sig
        self._device = device
        self._prefix = prefix
        self._inner = inner
        self.method = method
        self.jitted = jitted
        self.n_features = n_features
        self.donates = donates
        self.version = 0
        # precision flavor this entry point was BUILT as ("int8" or
        # None = float32); swaps re-extract through the same flavor, so
        # an int8 entry point re-quantizes every published version at
        # publish time
        self.quantize = quantize

    def __call__(self, X):
        if self._inner is not None:
            # pipeline: host prefix transforms feed the final step's
            # compiled fn. ONE read of the live (prefix, inner) pair: a
            # concurrent swap publishes a fresh pair in a single
            # assignment, so a request never runs old transforms into
            # new weights (or vice versa)
            prefix, inner = self._state
            for t in prefix:
                X = _host_out(t.transform(X))
            return inner(np.asarray(X, np.float32))
        # ONE attribute read: a concurrent swap_params either lands
        # before (new params+post) or after (old pair) — never a torn
        # mix of new weights with old classes
        params, post = self._state
        out = self._fn(X) if params is None else self._fn(params, X)
        if self.donates:
            from .observability import record_donation

            record_donation(X.nbytes)
        out = _host_out(out)
        return post(out) if post is not None else out

    def swap_params(self, estimator):
        """Atomically replace the fitted parameters under the compiled
        entry point with ``estimator``'s — the zero-recompile hot-swap.

        The new estimator must map onto the SAME compiled structure:
        same family, same method semantics, same parameter shapes (all
        captured in the build-time signature). Anything else raises
        :class:`ParamSwapError` — the cue to rebuild entry points (and
        pay compiles) rather than swap. In-flight batches finish on the
        old parameters; batches packed after the swap see the new ones.

        ``swap_params`` is prepare+commit in one call; callers swapping
        SEVERAL entry points against one estimator (ModelServer.
        swap_model) run :meth:`prepare_swap` on all of them first so a
        late refusal cannot leave the set half-swapped.
        """
        return self.commit_swap(self.prepare_swap(estimator))

    def prepare_swap(self, estimator):
        """Validate ``estimator`` against this entry point WITHOUT
        touching any live state; returns an opaque token for
        :meth:`commit_swap`. Raises :class:`ParamSwapError` on any
        structural mismatch, leaving the entry point exactly as it was.
        """
        if self._inner is not None:
            if not (hasattr(estimator, "steps")
                    and hasattr(estimator, "named_steps")):
                raise ParamSwapError(
                    "entry point serves a pipeline; the swapped-in "
                    f"estimator {type(estimator).__name__} is not one"
                )
            prefix, inner = self._state
            if len(estimator.steps) != len(prefix) + 1:
                raise ParamSwapError(
                    f"pipeline step count changed: "
                    f"{len(prefix) + 1} -> {len(estimator.steps)}"
                )
            # the inner leaf's signature only sees the PREFIX's output
            # width — the pipeline's own input width must match too, or
            # a swap to a pipeline trained on different-width rows would
            # commit fine and then fail inside the prefix transform on
            # every request instead of refusing typed at publish time
            want = getattr(estimator, "n_features_in_", None)
            if want is None:
                want = getattr(estimator.steps[0][1],
                               "n_features_in_", None)
            if (self.n_features is not None and want is not None
                    and int(want) != self.n_features):
                raise ParamSwapError(
                    f"n_features changed: {self.n_features} -> {want}"
                )
            inner_tok = inner.prepare_swap(estimator.steps[-1][1])
            return ("pipe",
                    tuple(t for _, t in estimator.steps[:-1]),
                    inner_tok)
        if self._extract is None:
            # host fallback: rebind the bound method — no compiled
            # structure to protect, but keep the width contract
            target = getattr(estimator, self.method, None)
            if target is None:
                raise ParamSwapError(
                    f"{type(estimator).__name__} has no method "
                    f"{self.method!r}"
                )
            want = getattr(estimator, "n_features_in_", None)
            if (self.n_features is not None and want is not None
                    and want != self.n_features):
                raise ParamSwapError(
                    f"n_features changed: {self.n_features} -> {want}"
                )
            return ("host", target)
        try:
            built = self._extract(estimator)
        except AttributeError as exc:
            # build-time guards (e.g. predict_proba on a hinge loss)
            # surface as the swap's typed refusal, not a raw attribute
            # error mid-request
            raise ParamSwapError(str(exc)) from exc
        if built is None:
            raise ParamSwapError(
                f"{type(estimator).__name__} does not support "
                f"{self.method!r} on the compiled path"
            )
        params, post, sig = built
        if sig != self._sig:
            raise ParamSwapError(
                "compiled structure mismatch (shapes/family/method "
                f"semantics): built with {self._sig}, swap offers {sig}"
            )
        return ("leaf", params, post)

    def commit_swap(self, token):
        """Apply a :meth:`prepare_swap` token. The request-visible flip
        is ONE attribute assignment per entry point — concurrent calls
        see either the complete old state or the complete new one, never
        a torn mix (for a pipeline, old transforms never feed new
        weights: the (prefix, inner) pair is republished together, with
        the new params living in a CLONE of the inner leaf that shares
        the same jitted executable — same compile cache, no compile)."""
        kind = token[0]
        if kind == "pipe":
            _, prefix, inner_tok = token
            _, inner = self._state
            new_inner = _copy.copy(inner).commit_swap(inner_tok)
            self._state = (prefix, new_inner)
            self._prefix, self._inner = prefix, new_inner
        elif kind == "host":
            target = token[1]
            self._fn = lambda X: target(X)
        else:
            _, params, post = token
            # place the new pytree exactly like the old one (same
            # device / same committedness) so the jit cache key is
            # identical and the swap never mints a compile
            self._state = (_put_params(params, self._device), post)
        self.version += 1
        return self


def _host_out(out):
    import scipy.sparse as sp

    if isinstance(out, ShardedArray):
        return out.to_numpy()
    if sp.issparse(out):
        return out.toarray()
    return np.asarray(out)


def _donate_spec():
    """Donate the batch argument only where the runtime honors it; on
    CPU jax warns per call that donated buffers were unusable. Cores are
    ``(params, X)`` — argnum 1 is the batch; the params pytree is never
    donated (it is reused on every call until a swap replaces it)."""
    import jax

    return (1,) if jax.default_backend() in ("tpu", "gpu") else ()


def _tracked_jit(est, method, core, donate, flavor=None, sig=None):
    """Build a serving core's tracked jitted entry point through the
    plan layer (``plans.ProgramPlan`` — ISSUE 15): cache keying,
    ``track_program`` registration as
    ``serving.<Estimator>.<method>[.<flavor>]`` and donation wiring
    all happen there. ``sig`` (the swap
    contract's structural signature) is the plan cache key: two builds
    over same-shaped fitted params return the SAME entry point, so a
    second server's warmup hits warm jit caches instead of re-tracing
    — and the quantized flavor ranks separately in the report CLI's
    programs table."""
    from .plans import ProgramPlan

    name = f"serving.{type(est).__name__}.{method}"
    if flavor:
        name += f".{flavor}"
    return ProgramPlan(
        name=name, body=core, donate=tuple(donate),
        key=("serving", sig) if sig is not None else None,
        ladder="serving-rows", group="serving",
    ).build()


def _put_params(params, device):
    """Host param pytree -> device-resident arrays, committed to
    ``device`` when given (per-replica placement), else the default
    device. Build and every swap go through HERE so the jit cache key
    (shapes + placement) is identical across swaps."""
    import jax

    if device is None:
        return jax.device_put(params)
    return jax.device_put(params, device)


def _shapes(params):
    return tuple(sorted(
        (k, tuple(v.shape), str(v.dtype)) for k, v in params.items()
    ))


def _linear_wb(est):
    """(C, d) weight matrix + (C,) bias from a fitted linear model
    (C=1 encodes the binary/regression row)."""
    coef = np.asarray(est.coef_, np.float32)
    if coef.ndim == 1:
        coef = coef[None, :]
    b = np.ravel(np.asarray(getattr(est, "intercept_", 0.0),
                            np.float32))
    if b.shape[0] != coef.shape[0]:
        b = np.full(coef.shape[0], b[0] if b.size else 0.0, np.float32)
    return coef, b


def _linear_extract(est, method):
    """(host params, post, signature) for a linear-family estimator —
    the swap contract's one source of truth: everything the compiled
    program's STRUCTURE depends on (method semantics, multiclass-ness,
    link family, parameter shapes) lands in the signature; everything
    that may change per version (weights, bias, class labels) lands in
    params/post."""
    W, b = _linear_wb(est)
    multi = W.shape[0] > 1
    classes = getattr(est, "classes_", None)
    family = getattr(est, "family", None)
    if method == "decision_function":
        kind = "margin"
    elif method == "predict_proba":
        if classes is None:
            return None
        # mirror SGDClassifier's guard: sigmoid(margins) of a non-log
        # loss is NOT a probability — the direct method raises, so the
        # compiled path (and any swap onto it) must too
        loss = getattr(est, "_loss", None)
        if callable(loss) and loss() != "log_loss":
            raise AttributeError(
                "predict_proba requires loss='log_loss'"
            )
        kind = "proba"
    elif method == "predict":
        if classes is not None:
            kind = "classify"
        elif family == "poisson":
            kind = "poisson"
        else:
            kind = "regress"
    else:
        return None
    post = None
    if kind == "classify":
        cls = np.asarray(classes)
        post = lambda idx: cls[np.asarray(idx)]  # noqa: E731
    params = {"W": W, "b": b}
    sig = ("linear", kind, multi, _shapes(params))
    return params, post, sig


def _quantize_w(W):
    """Per-output-channel symmetric int8 quantization of a (C, d)
    weight matrix: ``scale[c] = max|W[c]| / 127`` (1.0 for an all-zero
    row), computed at publish/build time. Only W quantizes — biases
    stay f32 (C floats, added post-matmul for free)."""
    amax = np.max(np.abs(W), axis=1)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    Wq = np.clip(np.rint(W / scale[:, None]), -127, 127).astype(np.int8)
    return Wq, scale


def _linear_extract_int8(est, method):
    """The int8 twin of ``_linear_extract``: weights quantized
    per-output-channel at extract (= publish) time, scales/bias f32.
    Kinds whose output passes eta through a nonlinearity return None
    and stay on the higher-precision flavor: "proba" (a sigmoid's tail
    is exactly where int8's ~0.4% weight rounding shows) and "poisson"
    (exp(eta) amplifies the eta error multiplicatively — the >=99.5%
    agreement criterion only holds for sign/argmax/linear outputs).
    The signature leads with "linear-int8" so an f32 entry point can
    never silently accept quantized params (or vice versa)."""
    built = _linear_extract(est, method)
    if built is None:
        return None
    params, post, sig = built
    if sig[1] in ("proba", "poisson"):
        return None
    Wq, scale = _quantize_w(params["W"])
    qparams = {"Wq": Wq, "scale": scale, "b": params["b"]}
    return qparams, post, ("linear-int8", sig[1], sig[2],
                           _shapes(qparams))


def _linear_core(kind, multi, eta=None):
    """``(params, X)`` -> one method's rows at a serving batch: eta, then
    the kind's pointwise tail — ``models.glm.LINK_TAILS``, the functions
    the estimators' own ``glm.decision`` program applies over a whole
    resident X (which assembles its result lane-dense instead: the
    ``jnp.stack`` below is right at a few hundred rows only)."""
    import jax.numpy as jnp

    from .models.glm import LINK_TAILS as tails

    if eta is None:
        def eta(p, X):
            return X @ p["W"].T + p["b"][None, :]  # (B, C)

    if kind == "margin":
        return (lambda p, X: eta(p, X)) if multi \
            else (lambda p, X: eta(p, X)[:, 0])
    if kind == "proba":
        if multi:
            def core(p, X):
                pr = tails["proba"](eta(p, X))  # OvR sigmoids, normed
                return pr / jnp.maximum(
                    jnp.sum(pr, axis=1, keepdims=True), 1e-12
                )
        else:
            def core(p, X):
                p1 = tails["proba"](eta(p, X)[:, 0])
                return jnp.stack([1.0 - p1, p1], axis=1)
        return core
    if kind == "classify":
        if multi:
            return lambda p, X: jnp.argmax(eta(p, X), axis=1)
        return lambda p, X: tails["classify"](
            eta(p, X)[:, 0]).astype(jnp.int32)
    if kind == "poisson":
        return lambda p, X: tails["poisson"](eta(p, X)[:, 0])
    return lambda p, X: eta(p, X)[:, 0]            # regression


def _linear_core_int8(kind, multi):
    """Serving core over int8 weights: a dequantize-free mixed
    bf16×int8 matmul (XLA contracts the int8 operand directly; no f32
    copy of W ever materializes) with f32 accumulation, the per-channel
    scales applied to the (B, C) result — int8 keeps the weight
    pytree 4x smaller in HBM and the matmul on the low-precision
    units; prediction agreement vs f32 is >=99.5% on the parity suite
    (tests/test_precision.py)."""
    import jax
    import jax.numpy as jnp

    def eta(p, X):
        acc = jax.lax.dot_general(
            X.astype(jnp.bfloat16), p["Wq"],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # (B, C) f32
        return acc * p["scale"][None, :] + p["b"][None, :]

    return _linear_core(kind, multi, eta=eta)


def _jit_linear(est, method, device=None, quantize=None):
    """Jitted ``(params, X)`` programs for the linear-model family
    (GLM + SGD): the whole method is one matmul + pointwise tail over
    the swappable param pytree. ``quantize="int8"`` builds the
    weight-quantized flavor for the methods that support it
    (predict / decision_function); unsupported methods fall back to
    the f32 build so a quantized server still serves them."""
    if quantize == "int8":
        built = _linear_extract_int8(est, method)
        if built is not None:
            params, post, sig = built
            donate = _donate_spec()
            core = _linear_core_int8(sig[1], sig[2])
            return CompiledBatchFn(
                _tracked_jit(est, method, core, donate, flavor="int8",
                             sig=sig),
                method, True, params["Wq"].shape[1],
                donates=bool(donate),
                params=_put_params(params, device), post=post,
                extract=lambda e: _linear_extract_int8(e, method),
                sig=sig, device=device, quantize="int8",
            )
    elif quantize:
        raise ValueError(
            f"unknown quantize flavor {quantize!r}; supported: 'int8'"
        )
    built = _linear_extract(est, method)
    if built is None:
        return None
    params, post, sig = built
    donate = _donate_spec()
    core = _linear_core(sig[1], sig[2])
    return CompiledBatchFn(
        _tracked_jit(est, method, core, donate, sig=sig), method, True,
        params["W"].shape[1], donates=bool(donate),
        params=_put_params(params, device), post=post,
        extract=lambda e: _linear_extract(e, method), sig=sig,
        device=device,
    )


def _sparse_linear_extract(est, method):
    """The sparse twin of ``_linear_extract``: same params/post, a
    "linear-sparse"-prefixed signature so a dense entry point can never
    silently accept a sparse swap (or vice versa). predict /
    decision_function only — the sparse serving family is the hashed-
    text linear hot path."""
    if method not in ("predict", "decision_function"):
        return None
    built = _linear_extract(est, method)
    if built is None:
        return None
    params, post, sig = built
    return params, post, ("linear-sparse",) + tuple(sig[1:])


def _sparse_linear_core(kind, multi):
    """Serving core over a packed bucketed-nnz CSR batch: eta via one
    gather of the (C,)-wide weight columns per nonzero + a segment_sum
    over rows (ops/sparse_kernels math inlined on the padded triple) —
    nnz * C cost instead of B * d * C, which is the whole point at
    2**14+ hashed-text widths. ``n_rows`` (the row bucket) is static:
    the compiled set is the warmed (rows, nnz) grid."""
    import jax
    import jax.numpy as jnp

    def eta(p, data, cols, rows, n_rows):
        contrib = data[:, None] * jnp.take(p["W"].T, cols, axis=0)
        return jax.ops.segment_sum(contrib, rows,
                                   num_segments=n_rows) \
            + p["b"][None, :]

    if kind == "margin":
        if multi:
            return eta
        return lambda p, d_, c_, r_, n: eta(p, d_, c_, r_, n)[:, 0]
    if kind == "classify":
        if multi:
            return lambda p, d_, c_, r_, n: jnp.argmax(
                eta(p, d_, c_, r_, n), axis=1
            )
        return lambda p, d_, c_, r_, n: (
            eta(p, d_, c_, r_, n)[:, 0] > 0
        ).astype(jnp.int32)
    if kind == "poisson":
        return lambda p, d_, c_, r_, n: jnp.exp(
            eta(p, d_, c_, r_, n)[:, 0]
        )
    return lambda p, d_, c_, r_, n: eta(p, d_, c_, r_, n)[:, 0]


class SparseBatchFn(CompiledBatchFn):
    """A fitted linear estimator's ``method`` as a static-shape SPARSE
    batch function: ``fn(csr)`` takes a scipy CSR block, packs it to
    the (row-bucket, nnz-bucket) grid — rows padded up the serving
    ladder, the nnz triple padded up the geometric nnz ladder
    (``config.serving_sparse_nnz_per_row`` x the batch ladder's
    min/max, same growth) — and runs ONE compiled program per grid
    cell. Warm the grid (:meth:`warm`) and ragged hashed-text traffic
    pays zero steady-state XLA compiles; a batch whose nnz overflows
    the ladder's top rung raises ``ValueError`` for the caller to spill
    (ModelServer densifies into the already-warm dense rung). Hot-swap
    (prepare/commit) is inherited — the "linear-sparse" signature keys
    the same zero-recompile same-shape contract."""

    __slots__ = ("nnz_ladder",)

    def __init__(self, fn, method, n_features, params=None, post=None,
                 extract=None, sig=None, device=None, nnz_ladder=None):
        super().__init__(fn, method, True, n_features, params=params,
                         post=post, extract=extract, sig=sig,
                         device=device)
        self.nnz_ladder = nnz_ladder

    def nnz_bucket(self, nnz: int) -> int:
        return self.nnz_ladder.bucket_for(max(int(nnz), 1))

    def _pack(self, X):
        import scipy.sparse as sp

        X = X.tocsr() if not sp.isspmatrix_csr(X) else X
        n = int(X.shape[0])
        nnz = int(X.nnz)
        nb = self.nnz_bucket(nnz)
        data = np.zeros(nb, np.float32)
        cols = np.zeros(nb, np.int32)
        rows = np.zeros(nb, np.int32)
        data[:nnz] = X.data
        cols[:nnz] = X.indices
        rows[:nnz] = np.repeat(
            np.arange(n, dtype=np.int32), np.diff(X.indptr)
        )
        return data, cols, rows, n

    def __call__(self, X, n_rows=None):
        """Run the packed batch; ``n_rows`` pins the row bucket (the
        server picks it from the ladder), default = the batch's own
        rows. Returns the LOGICAL rows only (padding sliced off)."""
        params, post = self._state
        data, cols, rows, n = self._pack(X)
        out = self._fn(params, data, cols, rows,
                       int(n_rows if n_rows is not None else n))
        out = _host_out(out)[:n]
        return post(out) if post is not None else out

    def warm(self, row_bucket: int, nnz_bucket: int):
        """Compile one (rows, nnz) grid cell now (zero-filled operands
        — the program depends on shapes only)."""
        params, _ = self._state
        self._fn(params, np.zeros(nnz_bucket, np.float32),
                 np.zeros(nnz_bucket, np.int32),
                 np.zeros(nnz_bucket, np.int32), int(row_bucket))
        return self


def sparse_batch_fn(estimator, method="predict", device=None):
    """Build the sparse (CSR-in) serving entry point for a fitted
    LINEAR estimator's predict / decision_function — the hashed-text
    twin of :func:`compiled_batch_fn`, bucketed by (rows, nnz) instead
    of rows alone. Returns None for estimators/methods without a
    sparse story (pipelines, KMeans/PCA, predict_proba) — callers fall
    back to the dense path (which densifies per batch)."""
    est = estimator
    if not (_is_device_estimator(est) and hasattr(est, "coef_")):
        return None
    built = _sparse_linear_extract(est, method)
    if built is None:
        return None
    params, post, sig = built
    from .config import get_config
    from .serving._buckets import BucketLadder

    cfg = get_config()
    npr = max(int(cfg.serving_sparse_nnz_per_row), 1)
    nnz_ladder = BucketLadder(
        min_rows=max(cfg.serving_min_batch * npr, 1),
        max_rows=max(cfg.serving_max_batch * npr,
                     cfg.serving_min_batch * npr, 1),
        growth=cfg.serving_bucket_growth,
    )
    core = _sparse_linear_core(sig[1], sig[2])
    from .plans import ProgramPlan

    name = f"serving.{type(est).__name__}.{method}.sparse"
    fn = ProgramPlan(
        name=name, body=core, static_argnums=(4,),
        key=("serving-sparse", sig), ladder="serving-nnz",
        group="serving",
    ).build()
    return SparseBatchFn(
        fn, method, params["W"].shape[1],
        params=_put_params(params, device), post=post,
        extract=lambda e: _sparse_linear_extract(e, method), sig=sig,
        device=device, nnz_ladder=nnz_ladder,
    )


def _kmeans_extract(est, method):
    if method not in ("predict", "transform"):
        return None
    centers = np.asarray(est.cluster_centers_, np.float32)
    params = {"centers": centers}
    return params, None, ("kmeans", method, _shapes(params))


def _kmeans_core(method):
    import jax.numpy as jnp

    def dist2(p, X):
        # ||x-c||^2 via the expanded form: one (B,d)x(d,k) MXU matmul
        c = p["centers"]
        xx = jnp.sum(X * X, axis=1, keepdims=True)
        cc = jnp.sum(c * c, axis=1)[None, :]
        return jnp.maximum(xx + cc - 2.0 * (X @ c.T), 0.0)

    if method == "predict":
        return lambda p, X: jnp.argmin(dist2(p, X), axis=1).astype(
            jnp.int32
        )
    return lambda p, X: jnp.sqrt(dist2(p, X))


def _jit_kmeans(est, method, device=None):
    built = _kmeans_extract(est, method)
    if built is None:
        return None
    params, post, sig = built
    donate = _donate_spec()
    return CompiledBatchFn(
        _tracked_jit(est, method, _kmeans_core(method), donate,
                     sig=sig), method,
        True, int(params["centers"].shape[1]), donates=bool(donate),
        params=_put_params(params, device), post=post,
        extract=lambda e: _kmeans_extract(e, method), sig=sig,
        device=device,
    )


def _pca_extract(est, method):
    if method != "transform":
        return None
    params = {"components": np.asarray(est.components_, np.float32)}
    mean = getattr(est, "mean_", None)
    if mean is not None:
        params["mean"] = np.asarray(mean, np.float32)
    if getattr(est, "whiten", False):
        params["scale"] = np.sqrt(np.asarray(
            est.explained_variance_, np.float32
        ))
    # which optional terms exist is structural (the traced graph
    # branches on their presence), so it rides the signature via shapes
    return params, None, ("pca", _shapes(params))


def _pca_core(has_mean, has_scale):
    def core(p, X):
        xc = X - p["mean"][None, :] if has_mean else X
        sc = xc @ p["components"].T
        return sc / p["scale"][None, :] if has_scale else sc

    return core


def _jit_pca(est, method, device=None):
    built = _pca_extract(est, method)
    if built is None:
        return None
    params, post, sig = built
    donate = _donate_spec()
    core = _pca_core("mean" in params, "scale" in params)
    return CompiledBatchFn(
        _tracked_jit(est, method, core, donate, sig=sig), method, True,
        int(params["components"].shape[1]), donates=bool(donate),
        params=_put_params(params, device), post=post,
        extract=lambda e: _pca_extract(e, method), sig=sig,
        device=device,
    )


def _nb_extract(est, method):
    """(host params, post, signature) for a fitted GaussianNB — the
    ISSUE 15 onboarding: the joint-log-likelihood predict is one
    matmul-shaped program over a swappable {theta, var, log_prior}
    pytree, so naive_bayes serves through the same plan-built
    zero-recompile entry points (and hot-swap contract) as the linear
    family."""
    if method not in ("predict", "predict_proba"):
        return None
    theta = np.asarray(est.theta_, np.float32)
    var = np.asarray(est.var_, np.float32)
    prior = np.asarray(est.class_prior_, np.float64)
    params = {"theta": theta, "var": var,
              "log_prior": np.log(prior).astype(np.float32)}
    kind = "classify" if method == "predict" else "proba"
    post = None
    if kind == "classify":
        cls = np.asarray(est.classes_)
        post = lambda idx: cls[np.asarray(idx)]  # noqa: E731
    return params, post, ("nb", kind, _shapes(params))


def _nb_core(kind):
    import jax
    import jax.numpy as jnp

    from .naive_bayes import _jll_math

    def jll(p, X):
        # the ONE jll definition (naive_bayes._jll_math) over the
        # swappable param pytree — served and in-core predictions can
        # never numerically diverge
        return _jll_math(X, p["theta"], p["var"], p["log_prior"])

    if kind == "classify":
        return lambda p, X: jnp.argmax(jll(p, X), axis=1).astype(
            jnp.int32
        )
    return lambda p, X: jax.nn.softmax(jll(p, X), axis=1)


def _jit_nb(est, method, device=None):
    built = _nb_extract(est, method)
    if built is None:
        return None
    params, post, sig = built
    donate = _donate_spec()
    return CompiledBatchFn(
        _tracked_jit(est, method, _nb_core(sig[1]), donate, sig=sig),
        method, True, int(params["theta"].shape[1]),
        donates=bool(donate), params=_put_params(params, device),
        post=post, extract=lambda e: _nb_extract(e, method), sig=sig,
        device=device,
    )


def compiled_batch_fn(estimator, method="predict", device=None,
                      quantize=None):
    """Build the static-shape batch entry point for a fitted estimator
    (or sklearn-style pipeline ending in one) — the serving subsystem's
    per-method compile unit.

    Device estimators (GLM, SGD, KMeans, PCA/TruncatedSVD) lower to one
    jitted ``(params, X)`` program whose fitted parameters are a
    swappable pytree argument (see :meth:`CompiledBatchFn.swap_params`);
    ``device=`` commits the params to a specific device — the fleet's
    per-replica placement knob. A pipeline applies its prefix transforms
    per batch and feeds the final step's compiled fn (prefix outputs are
    shape-deterministic per batch height, so the compile set stays
    bounded by the bucket ladder). Anything else gets the host
    fallback — ``getattr(est, method)`` over the padded batch.

    ``quantize="int8"`` builds the weight-quantized serving flavor for
    linear-family predict / decision_function (per-output-channel
    scales computed here, mixed bf16×int8 matmul core); methods and
    estimator families without an int8 path — predict_proba, KMeans,
    PCA, pipelines, host fallbacks — build their standard
    higher-precision flavor instead (``.quantize`` on the result says
    which one you got).
    """
    est = estimator
    if hasattr(est, "steps") and hasattr(est, "named_steps"):
        inner = compiled_batch_fn(est.steps[-1][1], method,
                                  device=device)
        first = est.steps[0][1]
        return CompiledBatchFn(
            None, method, inner.jitted,
            getattr(first, "n_features_in_", None),
            prefix=tuple(t for _, t in est.steps[:-1]), inner=inner,
        )
    if _is_device_estimator(est):
        built = None
        if hasattr(est, "coef_"):
            built = _jit_linear(est, method, device=device,
                                quantize=quantize)
        elif hasattr(est, "cluster_centers_"):
            built = _jit_kmeans(est, method, device=device)
        elif hasattr(est, "components_"):
            built = _jit_pca(est, method, device=device)
        elif hasattr(est, "theta_"):
            built = _jit_nb(est, method, device=device)
        if built is not None:
            return built
    target = getattr(est, method, None)
    if target is None:
        raise AttributeError(
            f"{type(est).__name__} has no method {method!r}"
        )
    n_feat = getattr(est, "n_features_in_", None)
    return CompiledBatchFn(lambda X: target(X), method, False, n_feat)


class Incremental(ParallelPostFit):
    """Ref: dask_ml/wrappers.py::Incremental +
    dask_ml/_partial.py::fit.

    What a fitted wrapper keeps on the device: on the fused path (a device
    SGD estimator over a resident ``ShardedArray``) the X half of the epoch
    grid — the (B, S, d) block grid of X in the fit dtype, half of X's own
    bytes in bfloat16 — outlives the pass that built it, and the next
    ``fit`` / ``partial_fit`` handed the SAME device array (the object, not
    equal contents: a jax array is immutable) at the same mesh, blocking
    and fit dtype reads it instead of casting and re-laying X again
    (``pass_info_["grid_hit"]``). At most one grid a wrapper, and it goes
    when a pass arrives with another array or key (before the new grid is
    built), when the array it was built from is freed, or with the wrapper;
    it is never pickled, deep-copied or cloned. Until then those bytes are
    in use, and the headroom gate of ANOTHER wrapper's fused path sees
    them: where it would refuse, the grids that other wrappers keep are
    dropped first (they are caches; those wrappers' next pass rebuilds) and
    the gate is asked again, and only then is the block loop taken. The
    other three paths keep nothing."""

    def __init__(self, estimator=None, scoring=None, shuffle_blocks=True,
                 random_state=None, assume_equal_chunks=True,
                 predict_meta=None, predict_proba_meta=None,
                 transform_meta=None):
        self.estimator = estimator
        self.scoring = scoring
        self.shuffle_blocks = shuffle_blocks
        self.random_state = random_state
        self.assume_equal_chunks = assume_equal_chunks
        self.predict_meta = predict_meta
        self.predict_proba_meta = predict_proba_meta
        self.transform_meta = transform_meta

    def _pass(self, root, est, X, y, **fit_kwargs):
        """One pass of ``fit`` / ``partial_fit`` under the call's ``root``
        span, and the record of it: ``pass_info_`` —

        ``path``
            which of the four ran: ``"fused_epoch"`` (device estimator,
            device data, one scan program over a block grid),
            ``"block_loop"`` (the same blocks, one ``partial_fit`` each),
            ``"stream_pass"`` (device estimator, host data, super-block
            scans) or ``"host_loop"`` (one ``partial_fit`` a host block);
        ``blocks``, ``steps``
            blocks of the pass; updates it made (the clock's advance
            where the estimator keeps one);
        ``dispatches``
            tracked-program calls in the pass (the registry's delta;
            None unless ``config.obs_programs`` is on);
        ``grid_bytes``
            device bytes of the epoch grid the pass read (0 off the fused
            path);
        ``grid_hit``
            whether that grid's X half was the one kept from an earlier
            pass over the same device array (then no ``sgd.grid_x`` ran);
        ``headroom``
            what the fused path's gate read (``needed`` / ``free`` bytes a
            device, ``fits``; ``free`` None where the backend reports no
            memory stats; ``grids_dropped``, only where the gate refused,
            other wrappers' kept grids were dropped and this is its second
            reading; None where the gate was not asked: off the fused path,
            and on a hit, which allocates nothing);
        ``fit_dtype``, ``t_end``
            the resolved fit dtype and the clock after the pass (None for
            an estimator that has neither).

        One dict rebuilt per pass; its keys are also the root span's
        attributes (``dispatches`` there is the span ledger's own count:
        the same number, kept where the dispatch happens)."""
        from .observability import programs_enabled, programs_snapshot

        def program_calls():
            return sum(int(r["calls"]) for r in programs_snapshot())

        before = program_calls() if programs_enabled() else None
        t0 = getattr(est, "_t", None)
        rng = np.random.RandomState(self.random_state)
        info = {"path": None, "blocks": 0, "grid_bytes": 0,
                "grid_hit": False, "headroom": None}
        est = self._partial_fit_pass(est, X, y, self._block_size(X), rng,
                                     info, **fit_kwargs)
        t1 = getattr(est, "_t", None)
        info["steps"] = info["blocks"] if t0 is None or t1 is None \
            else int(t1) - int(t0)
        info["dispatches"] = None if before is None \
            else program_calls() - before
        info["fit_dtype"] = getattr(est, "fit_dtype_", None)
        info["t_end"] = None if t1 is None else int(t1)
        self.pass_info_ = info
        root.add(**{k: v for k, v in info.items() if k != "dispatches"})
        return est

    def _partial_fit_pass(self, est, X, y, block_size, rng, info,
                          **fit_kwargs):
        from .observability import span

        if _is_device_estimator(est) and isinstance(X, ShardedArray):
            # device estimator + device data: blocks are the fused-epoch
            # grid's contiguous S-row ranges (fused_blocks), so the
            # fused and per-block paths train identical minibatches.
            # Blocks materialize as sharded gathers (take_rows); the
            # dataset never round-trips through host (VERDICT r2 #4 —
            # the reference's partial_fit chain runs on worker-resident
            # chunks the same way, SURVEY §3.6)
            from .models.sgd import _KeptGrid, _epoch_grid_key, fused_blocks
            from .parallel.sharded import take_rows

            ys = y if isinstance(y, ShardedArray) or y is None \
                else np.asarray(y)
            B, S = fused_blocks(X)
            # the last grid block always holds ≥1 real row (padding < D
            # and S*(B-1) is a multiple of D), so B IS the block count
            order = list(range(B))
            if self.shuffle_blocks:
                rng.shuffle(order)
            info["blocks"] = B
            if (hasattr(est, "_fused_epoch") and ys is not None
                    and B > 1
                    and set(fit_kwargs) <= {"classes"}):
                # fused-epoch fast path: the whole pass compiles into ONE
                # scan program (same updates/order/lr clock as the block
                # loop) — per-block dispatch round trips vanish. The
                # grid is a second device copy of X, kept from pass to
                # pass while X is the same device array. A pass that has
                # to BUILD one first drops the kept one, then asks the
                # headroom gate (the loop gathers one block at a time,
                # keeps nothing, and stays the fallback near HBM
                # capacity); a hit allocates nothing and asks nothing.
                # Kept grids are caches: where the gate refuses, those of
                # OTHER wrappers go (their next pass rebuilds) and the
                # gate is asked once more, before the loop is taken.
                kept = getattr(self, "_epoch_grid", None)
                if kept is None:
                    kept = self._epoch_grid = _KeptGrid()
                hit = kept.get(
                    X.data, _epoch_grid_key(X, est.fit_dtype)) is not None
                if not hit:
                    kept.clear()
                    info["headroom"] = _device_headroom(X.data.nbytes, X)
                    if not info["headroom"]["fits"]:
                        dropped = _KeptGrid.drop_others(kept)
                        if dropped:
                            info["headroom"] = {
                                **_device_headroom(X.data.nbytes, X),
                                "grids_dropped": dropped}
                if hit or info["headroom"]["fits"]:
                    est._fused_epoch(
                        X, ys, order, n_blocks=B,
                        classes=fit_kwargs.get("classes"), kept=kept,
                    )
                    info.update({k: est.solver_info_[k] for k in
                                 ("path", "grid_bytes", "grid_hit")})
                    return est
            from .observability.live import publish_progress

            info["path"] = "block_loop"
            with span("pass.solve") as sp:
                for done, b in enumerate(order):
                    idx = np.arange(b * S, min((b + 1) * S, X.n_rows))
                    Xb = take_rows(X, idx)
                    if ys is None:
                        est.partial_fit(Xb, **fit_kwargs)
                    else:
                        yb = take_rows(ys, idx) \
                            if isinstance(ys, ShardedArray) else ys[idx]
                        est.partial_fit(Xb, yb, **fit_kwargs)
                    # live pass progress (host ints; no-op without the
                    # telemetry server)
                    publish_progress(block=done + 1, blocks_total=B)
                sp.add(steps=B)
            return est
        # sparse X blocks stay CSR host-side: a device estimator's
        # partial_fit densifies ONE block at placement (as_sharded), a
        # host estimator consumes the CSR block natively — either way
        # peak memory is O(block), never the dense corpus
        with span("pass.validate"):
            Xh = _host_matrix(X)
            yh = y.to_numpy() if isinstance(y, ShardedArray) \
                else np.asarray(y)
            starts = list(range(0, Xh.shape[0], block_size))
            order = np.arange(len(starts))
            if self.shuffle_blocks:
                rng.shuffle(order)
        info["blocks"] = len(starts)
        with span("pass.solve"):
            if (_is_device_estimator(est) and hasattr(est, "_stream_pass")
                    and set(fit_kwargs) <= {"classes"}):
                # super-block fast path for device estimators on host
                # data: the pass's per-block partial_fit dispatches
                # collapse into donated-carry scans over K-stacked blocks
                # — identical minibatches, order, and lr clock. Returns
                # False (sparse source, K == 1 opt-out, partition
                # mismatch) -> the per-block loop below.
                if est._stream_pass(Xh, yh, block_size, order=order,
                                    classes=fit_kwargs.get("classes")):
                    info["path"] = "stream_pass"
                    return est
            from .observability.live import publish_progress

            info["path"] = "host_loop"
            for done, oi in enumerate(order):
                s = starts[int(oi)]
                est.partial_fit(Xh[s:s + block_size], yh[s:s + block_size],
                                **fit_kwargs)
                publish_progress(block=done + 1, blocks_total=len(starts))
        return est

    # -- pass-granular checkpoint/auto-resume (ISSUE 11) -------------------
    # With config.stream_checkpoint_path set, every partial_fit pass of
    # a device SGD-family inner estimator persists (w, lr clock,
    # classes, completed pass count) under a fingerprint token; a FRESH
    # wrapper whose first partial_fit finds a matching checkpoint
    # resumes the inner model and exposes ``completed_passes_`` so a
    # killed pass-driver loop (serve_while_training, chaos harnesses)
    # skips the passes already done. Host estimators and non-numeric
    # class sets opt out; fit() (a fresh one-pass fit) clears any
    # matching slot rather than resuming into it.

    def _pass_checkpoint(self, est, X, y, fit_kwargs):
        from .config import get_config
        from .reliability.stream_ckpt import stream_checkpoint

        if not get_config().stream_checkpoint_path:
            return None   # knobs off: touch nothing, cost one read
        if not (hasattr(est, "_stream_pass") and hasattr(est, "_loss")):
            return None   # device SGD-family only (w/t carry contract)
        if isinstance(X, ShardedArray) or y is None:
            return None
        classes = fit_kwargs.get("classes",
                                 getattr(est, "classes_", None))
        if classes is not None:
            classes = np.asarray(classes)
            if classes.dtype.kind not in "fiub":
                return None   # string labels don't round-trip orbax
        Xh, yh = _host_matrix(X), np.asarray(y)
        parts = (
            "incremental", type(est).__name__,
            repr(sorted(est.get_params().items())),
            self.shuffle_blocks, self.random_state,
            None if classes is None else tuple(classes.tolist()),
            tuple(Xh.shape) if hasattr(Xh, "shape") else len(Xh),
        )
        ckpt = stream_checkpoint("incremental", parts, arrays=(Xh, yh))
        self._pass_ckpt_ = ckpt
        return ckpt

    def _clear_pass_checkpoint(self):
        """Completion hook (serve_while_training calls it): the pass
        sequence is done, the slot must not resume into a future fit."""
        ckpt = getattr(self, "_pass_ckpt_", None)
        if ckpt is not None:
            ckpt.clear()

    def resume_from_checkpoint(self, X, y=None, **fit_kwargs):
        """Restore a matching pass checkpoint into this FRESH wrapper
        WITHOUT training — pass-driver loops (serve_while_training)
        call it before their first pass so a driver killed after its
        final pass resumes to zero remaining work instead of training
        one pass past the target. Returns the completed pass count
        (0 when nothing restored / already fitted / knobs off)."""
        from .config import get_config

        if not get_config().stream_checkpoint_path:
            return 0
        if getattr(self, "estimator_", None) is not None:
            return int(getattr(self, "completed_passes_", 0))
        est = clone(self.estimator)
        ckpt = self._pass_checkpoint(est, X, y, fit_kwargs)
        if ckpt is None:
            return 0
        st = ckpt.restore()
        if st is None:
            return 0
        from .observability._counters import record_stream_checkpoint

        import jax.numpy as jnp

        classes = st.get("classes")
        if classes is not None:
            est._set_classes(np.asarray(classes))
        est._ensure_state(int(st["d"]))
        est._w = jnp.asarray(np.asarray(st["w"], np.float32))
        est._t = int(st["t"])
        est._publish(int(st["d"]))
        self.estimator_ = est
        self.completed_passes_ = int(st["passes"])
        record_stream_checkpoint(resume=True)
        return self.completed_passes_

    def fit(self, X, y=None, **fit_kwargs):
        from .observability import span

        with span("fit", component=type(self).__name__,
                  estimator=type(self.estimator).__name__) as root:
            self._fit(root, X, y, **fit_kwargs)
        return self

    def _fit(self, root, X, y, **fit_kwargs):
        est = clone(self.estimator)
        if not hasattr(est, "partial_fit"):
            raise ValueError(
                f"{type(est).__name__} has no partial_fit; Incremental "
                "requires a partial_fit-capable estimator"
            )
        # classifiers need `classes` on the first partial_fit; the
        # reference makes callers pass classes= explicitly (y is a lazy
        # dask array there, a global unique is a cluster job) — here y is
        # concrete, so infer it when omitted (explicit classes= still wins)
        from sklearn.base import is_classifier

        if (y is not None and "classes" not in fit_kwargs
                and is_classifier(est)):
            if isinstance(y, ShardedArray):
                # binary: a three-scalar device scan, no column gather
                from .utils.validation import device_classes

                fit_kwargs["classes"] = device_classes(y)
            else:
                fit_kwargs["classes"] = np.unique(np.asarray(y))
        # a fresh fit() must never resume a stale pass sequence
        try:
            ckpt = self._pass_checkpoint(est, X, y, fit_kwargs)
            if ckpt is not None:
                ckpt.clear()
        except Exception:
            pass
        self.estimator_ = self._pass(root, est, X, y, **fit_kwargs)

    def partial_fit(self, X, y=None, **fit_kwargs):
        from .observability import span

        with span("partial_fit", component=type(self).__name__,
                  estimator=type(self.estimator).__name__) as root:
            self._partial_fit(root, X, y, **fit_kwargs)
        return self

    def _partial_fit(self, root, X, y, **fit_kwargs):
        if getattr(self, "estimator_", None) is None:
            # fresh wrapper: a matching checkpoint restores the killed
            # driver's inner carry before this pass runs
            self.resume_from_checkpoint(X, y, **fit_kwargs)
        est = getattr(self, "estimator_", None)
        if est is None:
            est = clone(self.estimator)
        ckpt = self._pass_checkpoint(est, X, y, fit_kwargs)
        self.estimator_ = self._pass(root, est, X, y, **fit_kwargs)
        if ckpt is not None:
            self.completed_passes_ = \
                getattr(self, "completed_passes_", 0) + 1
            if ckpt.due(self.completed_passes_):
                inner = self.estimator_
                classes = getattr(inner, "classes_", None)
                ckpt.save(
                    w=np.asarray(inner._w), t=int(inner._t),
                    d=int(np.asarray(inner._w).shape[-1]) - 1,
                    passes=self.completed_passes_,
                    classes=None if classes is None
                    else np.asarray(classes),
                )

    @staticmethod
    def _block_size(X):
        if isinstance(X, ShardedArray):
            # the device branch of _partial_fit_pass derives its own
            # contiguous fused_blocks partition and ignores this value;
            # report that partition's row count for consistency
            from .models.sgd import fused_blocks

            return max(fused_blocks(X)[1], 1)
        # host inputs: the SAME grid partition the device path uses
        # (capped by the byte budget for sparse/memmap sources), so
        # host- and device-input fits train identical blocks
        from .parallel.streaming import fit_block_rows

        return fit_block_rows(X)
