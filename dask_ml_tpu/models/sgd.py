"""Device-native SGD estimators with ``partial_fit``.

The reference has no GLM partial_fit — its ``Incremental`` wrapper streams
blocks through *sklearn's* SGDClassifier (SURVEY.md §3.6), keeping the hot
loop on host CPU. These estimators keep the model AND the update on
device: each ``partial_fit`` is one jitted gradient(+prox) step on a
streamed block — the TPU-resident streaming-partial_fit path of
BASELINE.md configs[3]. Same sklearn contract, so they compose with
``Incremental``, ``IncrementalSearchCV`` and Hyperband.

Update rule: full-block gradient steps (minibatch GD), not per-sample SGD
— per-sample loops don't map to the MXU; a block IS the minibatch.
Penalties follow sklearn's SGD semantics: l2 inside the objective, l1 as
a proximal soft-threshold after the step, elasticnet as the l1_ratio mix.

Batched trials: N models with the same (class, loss, classes) but
different hyperparameters advance in ONE jitted step via ``jax.vmap``
over a stacked (N, d+1) weight matrix — the TPU replacement for the
reference's N concurrent model futures (``dask_ml/model_selection/
_incremental.py::_fit``, SURVEY.md §3.5): instead of N workers each
running one sklearn partial_fit, one XLA program advances the whole
cohort with the data block read from HBM once.
"""

from __future__ import annotations

import collections
import weakref
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..base import BaseEstimator, ClassifierMixin, RegressorMixin, to_host
from ..metrics import accuracy_score, r2_score
from ..observability import span, track_program
from ..plans import tracked as plan_tracked, warmups as plan_warmups
from ..plans.ladders import SlotRungLadder
from ..parallel.sharded import ShardedArray, as_sharded
from ..utils.validation import check_is_fitted
from .glm import link_fetch, link_finish, predict_resident

_LOSSES = ("log_loss", "hinge", "squared_error")
_PENALTIES = ("l2", "l1", "elasticnet", None, "none")


@jax.custom_vjp
def _design_matvec(Xd, coef):
    """``Xd @ coef`` with ``coef`` (f32) rounded to the design matrix's
    dtype for this product only, f32 accumulation — and an f32 GRADIENT:
    left to autodiff, the transpose of the cast rounds ``Xd^T r`` to the
    design's dtype too (a bf16 gradient under ``dtype="auto"`` on a TPU,
    2^-9 of every entry at every step), which nothing states and the f32
    update does not want. For an f32 ``Xd`` this is ``Xd @ coef``."""
    return jnp.matmul(Xd, coef.astype(Xd.dtype),
                      preferred_element_type=jnp.float32)


def _design_matvec_fwd(Xd, coef):
    return _design_matvec(Xd, coef), Xd


def _design_matvec_bwd(Xd, ct):
    # the same product autodiff makes, kept in f32; the design matrix is
    # data (no cotangent). Over a design NARROWER than f32 the product is
    # asked for at ``highest``: one weight vector's is a multiply-and-
    # reduce on the VPU, exact as it is, but vmapped over a cohort it is
    # an (N, S) x (S, d) matmul on the MXU, which at the default precision
    # rounds the f32 residuals to bf16 first — the rounding the cast's
    # transpose made. An f32 design keeps the default, as ``Xd @ coef``
    # does (what "f32" means on the MXU is one question for both products,
    # and the Mosaic kernels answer it the same way: PERF.md section 7)
    return None, jnp.matmul(
        ct, Xd, preferred_element_type=jnp.float32,
        precision=(None if Xd.dtype == jnp.float32
                   else jax.lax.Precision.HIGHEST))


_design_matvec.defvjp(_design_matvec_fwd, _design_matvec_bwd)


def _sgd_pointwise(eta, y, loss):
    """The per-row SGD loss at decision value ``eta`` — the ONE definition
    every autodiff reader of a block differentiates (the Pallas kernels'
    twin, with its derivative, is ``ops/pallas_fused.py::
    sgd_objective_terms``)."""
    if loss == "log_loss":
        return jax.nn.softplus(eta) - y * eta
    if loss == "hinge":
        margins = (2.0 * y - 1.0) * eta
        return jnp.maximum(0.0, 1.0 - margins)
    return 0.5 * (eta - y) ** 2  # squared_error


def _dense_eta(X, mxu=None):
    """``coef -> X @ coef`` for a dense block: the matvec runs at X's dtype
    (``mxu`` casts it first) with f32 accumulation and an f32 gradient
    (``_design_matvec``) — a bf16 block rides the MXU at bf16 rate; for
    an f32 X this is exactly ``X @ coef``."""
    return partial(_design_matvec, X if mxu is None else X.astype(mxu))


def _sparse_eta(data, cols, rows, S):
    """``coef -> X @ coef`` for a bucketed-nnz block of ``S`` rows, at nnz
    cost (take, then segment-sum; its autodiff backward scatter-adds)."""
    from ..ops.sparse_kernels import sparse_eta

    return lambda coef: sparse_eta(data, cols, rows, coef, int(S))


def _sgd_data_sum(w, y, eta_of, mask, iflag, loss, n_valid=None):
    """The data term of weight vector ``w`` over one block read through
    ``eta_of``: the masked sum of the per-row losses, or with ``n_valid``
    their mean over that many rows. ``iflag=0`` zeroes the intercept's
    contribution to eta, so grad[-1] is already 0 and the intercept stays
    frozen at its init (0)."""
    eta = eta_of(w[:-1]) + w[-1] * iflag
    s = jnp.sum(_sgd_pointwise(eta, y, loss) * mask)
    return s if n_valid is None else s / jnp.maximum(n_valid, 1.0)


def _sgd_data_loss(w, y, X, mask, n_valid, iflag, loss, mxu=None):
    """The minibatch data term of a dense block — shared by
    ``_sgd_update_one`` (which adds the l2 penalty inside its
    objective) and the grad-accum micro kernel (which normalizes by the
    accumulation GROUP's global valid-row count, so summing micro
    (value, grad) pairs over the group IS the group objective's
    value_and_grad; at A=1 single-process the traced expression is
    identical to the sequential step's)."""
    return _sgd_data_sum(w, y, _dense_eta(X, mxu), mask, iflag, loss,
                         n_valid)


def _sgd_prox_update(w, data_loss, lr, alpha, l2w, l1w):
    """One minibatch-GD(+prox) update of one weight vector whose
    NORMALIZED data term is ``data_loss(w)``: the l2 penalty inside the
    differentiated objective, the lr step, then the l1 soft-threshold
    (intercept unpenalized). Returns (w2, objective value)."""

    def objective(w):
        return data_loss(w) + 0.5 * alpha * l2w * jnp.sum(w[:-1] ** 2)

    val, grad = jax.value_and_grad(objective)(w)
    w = w - lr * grad
    thr = lr * alpha * l1w
    coef = jnp.sign(w[:-1]) * jnp.maximum(jnp.abs(w[:-1]) - thr, 0.0)
    return w.at[:-1].set(coef), val


def _sgd_update_one(w, y, X, mask, n_valid, lr, alpha, l2w, l1w, iflag,
                    loss, mxu=None):
    """One update of one weight vector on a dense block — the SINGLE
    definition shared by the model-batched and class-batched kernels (a
    divergence between them would silently split binary and multiclass
    semantics). ``mxu`` (static dtype, e.g. bf16 under
    config.dtype="auto" on TPU) casts ONLY the eta matvec's operands."""
    return _sgd_prox_update(
        w, lambda v: _sgd_data_loss(v, y, X, mask, n_valid, iflag, loss,
                                    mxu=mxu),
        lr, alpha, l2w, l1w)


def _sgd_many_update(W, loss_sums, grads, nv, lr, alpha, l2w, l1w,
                     iflag):
    """The vectorized `_sgd_update_one` epilogue on RAW block sums for
    an (N, d+1) weight stack, normalized by ``nv`` here — shared by the
    streamed scan (``_sgd_stream_program``) and the fused resident cohort
    scan. Per-row lr/alpha/penalty/iflag operands may be scalars (one
    model: one setting for all its rows) or (N,) vectors (cohort:
    per-model); broadcasting a scalar to a column changes no float op.
    The intercept's gradient is multiplied by its flag (the kernels' raw
    sums are flag-free). Returns (W2, per-row losses)."""
    def col(a):
        return jnp.reshape(
            jnp.broadcast_to(jnp.asarray(a, jnp.float32),
                             (W.shape[0],)), (-1, 1)
        )

    lrc, ac, l2c, l1c, ifc = (col(a) for a in
                              (lr, alpha, l2w, l1w, iflag))
    l2term = ac * l2c
    losses = loss_sums / nv \
        + 0.5 * l2term[:, 0] * jnp.sum(W[:, :-1] ** 2, axis=1)
    g = grads / nv
    g = g.at[:, :-1].add(l2term * W[:, :-1])
    g = g.at[:, -1].mul(ifc[:, 0])
    W2 = W - lrc * g
    thr = lrc * ac * l1c
    coef = jnp.sign(W2[:, :-1]) * jnp.maximum(
        jnp.abs(W2[:, :-1]) - thr, 0.0
    )
    return W2.at[:, :-1].set(coef), losses


@track_program("sgd.step_many")
@partial(jax.jit, static_argnames=("loss", "mxu"))
def _sgd_step_many(X, y, mask, n_valid, W, lrs, alphas, l2_ws, l1_ws,
                   int_flags, loss, mxu=None):
    """Advance N models one step in one program. W: (N, d+1) stacked
    weights (last column = intercept). X/y/mask are SHARED across models
    — the block is read once; lr/alpha/penalty weights/intercept flag
    are per-model dynamic scalars (no static recompile per setting)."""

    def one(w, lr, alpha, l2w, l1w, iflag):
        return _sgd_update_one(w, y, X, mask, n_valid, lr, alpha, l2w,
                               l1w, iflag, loss, mxu=mxu)

    return jax.vmap(one, in_axes=(0, 0, 0, 0, 0, 0))(
        W, lrs, alphas, l2_ws, l1_ws, int_flags
    )


@track_program("sgd.step_multi")
@partial(jax.jit, static_argnames=("loss", "mxu"))
def _sgd_step_multi(X, y_codes, mask, n_valid, W, lr, alpha, l2w, l1w,
                    iflag, loss, mxu=None):
    """Advance the C one-vs-rest problems of ONE multiclass model in one
    program. W: (C, d+1); ``y_codes`` holds class INDICES 0..C-1 (mapped
    at encode time — float32 equality on raw labels would collapse
    ID-like classes past 2**24), and each class's 0/1 target derives
    in-kernel — no (C, n) target matrix ever materializes."""

    def one(w, c):
        y = (y_codes == c).astype(jnp.float32)
        return _sgd_update_one(w, y, X, mask, n_valid, lr, alpha, l2w,
                               l1w, iflag, loss, mxu=mxu)

    return jax.vmap(one)(W, jnp.arange(W.shape[0], dtype=jnp.float32))


@track_program("sgd.grad_accum_micro")
@partial(jax.jit, static_argnames=("loss", "n_out", "mxu"))
def _sgd_accum_micro(W, Xb, yb, mask, nv_group, iflag, loss, n_out,
                     mxu=None):
    """value_and_grad of one micro-block's SHARE of an accumulation
    group's data objective (config.stream_grad_accum): the data term
    normalized by the group's GLOBAL valid-row count ``nv_group``
    INSIDE autodiff, so summing these (value, grad) pairs over the
    group's micro-blocks — and across processes — yields exactly the
    group objective's value_and_grad. At A=1 single-process the traced
    expression is the sequential step's own data term (the SINGLE
    ``_sgd_data_loss`` definition), which is what makes A=1 parity
    exact rather than merely close."""
    if n_out is not None:
        def one(w, c):
            y = (yb == c).astype(jnp.float32)
            return jax.value_and_grad(
                lambda ww: _sgd_data_loss(ww, y, Xb, mask, nv_group,
                                          iflag, loss, mxu=mxu)
            )(w)

        vals, grads = jax.vmap(one)(
            W, jnp.arange(n_out, dtype=jnp.float32)
        )
        return vals.sum(), grads
    return jax.value_and_grad(
        lambda w: _sgd_data_loss(w, yb, Xb, mask, nv_group, iflag,
                                 loss, mxu=mxu)
    )(W)


@track_program("sgd.grad_accum_apply")
@jax.jit
def _sgd_accum_apply(W, grad, lr, alpha, l2w, l1w):
    """The shared grad-accum epilogue: fold in the l2 penalty's
    gradient — via the SAME autodiff expression the sequential
    objective differentiates, so A=1 single-process updates stay
    bit-identical — then the lr step and the l1 proximal
    soft-threshold, exactly ``_sgd_update_one``'s tail."""
    reg_g = jax.grad(
        lambda w: 0.5 * alpha * l2w * jnp.sum(w[..., :-1] ** 2)
    )(W)
    g = grad + reg_g
    W2 = W - lr * g
    thr = lr * alpha * l1w
    coef = jnp.sign(W2[..., :-1]) * jnp.maximum(
        jnp.abs(W2[..., :-1]) - thr, 0.0
    )
    return W2.at[..., :-1].set(coef)


@plan_tracked("superblock.sparse.grad_accum_micro")
@partial(jax.jit, static_argnames=("loss", "n_out", "S"))
def _sgd_accum_micro_sparse(W, data, cols, rows, yb, mask, nv_group,
                            iflag, loss, n_out, S):
    """Sparse twin of :func:`_sgd_accum_micro` (the grad-accum flavor's
    per-micro-block value_and_grad, normalized by the GROUP's global
    valid-row count inside autodiff) over one bucketed-nnz block."""
    eta_of = _sparse_eta(data, cols, rows, S)

    def data_loss(w, y):
        return _sgd_data_sum(w, y, eta_of, mask, iflag, loss, nv_group)

    if n_out is not None:
        def one(w, c):
            y = (yb == c).astype(jnp.float32)
            return jax.value_and_grad(lambda ww: data_loss(ww, y))(w)

        vals, grads = jax.vmap(one)(
            W, jnp.arange(n_out, dtype=jnp.float32)
        )
        return vals.sum(), grads
    return jax.value_and_grad(lambda w: data_loss(w, yb))(W)


@plan_tracked("sgd.fused_epoch")
@partial(jax.jit, static_argnames=("loss", "schedule", "n_out"))
def _sgd_epoch(Xr, yr, order, W, t0, eta0, power_t, alpha, l2w, l1w,
               iflag, n_rows, loss, schedule, n_out):
    """One FULL epoch as one program: ``lax.scan`` over the block grid
    ``Xr (B, S, d)`` / ``yr (B, S)`` — block b is dataset rows
    [b*S, (b+1)*S), axis 1 row-sharded so every step uses the whole
    mesh. Replaces one dispatch per block with one per epoch (launch
    count: B -> 1). ``order`` holds the (possibly shuffled)
    block indices; the lr clock advances per block exactly as the
    per-block loop does."""
    S = Xr.shape[1]

    def lr_at(t):
        t = jnp.maximum(t, 1.0)
        if schedule == "constant":
            return jnp.float32(eta0)
        if schedule == "invscaling":
            return eta0 / t ** power_t
        return 1.0 / (alpha * (1e3 + t))  # "optimal"

    @jax.named_scope("sgd_step")
    def step(carry, b):
        W, t = carry
        Xb = jnp.take(Xr, b, axis=0)          # (S, d), axis 0 sharded
        yb = jnp.take(yr, b, axis=0)
        # grid row r of block b is dataset row b*S + r; pad rows (the
        # tail the grid rounds up to) fail the bound and mask out
        row_ids = b * S + jnp.arange(S)
        mask = (row_ids < n_rows).astype(jnp.float32)
        n_valid = jnp.sum(mask)
        t = t + 1.0
        lr = lr_at(t)
        if n_out is not None:
            def one(w, c):
                yy = (yb == c).astype(jnp.float32)
                return _sgd_update_one(w, yy, Xb, mask, n_valid, lr,
                                       alpha, l2w, l1w, iflag, loss)

            W2, _ = jax.vmap(one)(
                W, jnp.arange(n_out, dtype=jnp.float32)
            )
        else:
            W2, _ = _sgd_update_one(W, yb, Xb, mask, n_valid, lr, alpha,
                                    l2w, l1w, iflag, loss)
        return (W2, t), jnp.float32(0.0)

    (W, t), _ = jax.lax.scan(step, (W, jnp.float32(t0)), order)
    return W, t


@plan_tracked("sgd.cohort_scan", ladder="cohort-slots")
@partial(jax.jit, static_argnames=("loss", "mxu"))
def _sgd_cohort_scan(Xr, yr, NV, order, W, LRS, alphas, l2ws, l1ws,
                     iflags, loss, mxu=None):
    """Advance N cohort models through S block steps in ONE program:
    ``lax.scan`` over ``order`` (indices into the DEDUPLICATED block
    stack Xr (B, bs, d) — a rung asking for several epochs revisits
    blocks without duplicating them in HBM) with the models vmapped
    inside each step — the adaptive-search hot path's S separate
    ``_batched_partial_fit`` dispatches collapse to one. ``LRS`` (S, N)
    carries each model's host-precomputed lr schedule values; per-step
    validity is the scalar prefix count ``NV[b]`` (take_rows blocks
    have trailing padding)."""
    bs = Xr.shape[1]
    r = jnp.arange(bs)

    def step(W, inp):
        b, lrs = inp
        Xb = jnp.take(Xr, b, axis=0)
        yb = jnp.take(yr, b, axis=0)
        nv = jnp.take(NV, b)
        m = (r < nv).astype(jnp.float32)
        n_valid = nv.astype(jnp.float32)

        def one(w, lr, a, l2w, l1w, ifl):
            return _sgd_update_one(w, yb, Xb, m, n_valid, lr, a, l2w,
                                   l1w, ifl, loss, mxu=mxu)

        W2, losses = jax.vmap(one, in_axes=(0, 0, 0, 0, 0, 0))(
            W, lrs, alphas, l2ws, l1ws, iflags
        )
        return W2, losses

    W, losses = jax.lax.scan(step, W, (order, LRS))
    return W, losses[-1]


@plan_tracked("pallas.sgd_cohort", ladder="cohort-slots")
@partial(jax.jit, static_argnames=("loss", "mxu", "interpret"))
def _sgd_cohort_scan_pallas(Xr, yr, NV, order, W, LRS, alphas, l2ws,
                            l1ws, iflags, loss, mxu=None,
                            interpret=False):
    """Pallas flavor of :func:`_sgd_cohort_scan` (ISSUE 12): each block
    step is ONE fused VMEM pass serving the WHOLE cohort — the
    ``fused_sgd_many_block_grad`` kernel's (tile, N) MXU matmul against
    the stacked coef rows replaces N vmapped forward+backward X reads —
    followed by the identical per-model lr/l2/prox epilogue on the raw
    sums. Same prefix-count masking and lr clocks as the XLA scan;
    selected by ``_batched_fused_calls`` when the stacked block height
    satisfies ``sgd_many_stream_tile``."""
    from ..ops.pallas_fused import fused_sgd_many_block_grad

    def step(W, inp):
        b, lrs = inp
        Xb = jnp.take(Xr, b, axis=0)
        yb = jnp.take(yr, b, axis=0)
        nv = jnp.take(NV, b)
        nvf = jnp.maximum(nv.astype(jnp.float32), 1.0)
        loss_sums, grads = fused_sgd_many_block_grad(
            Xb, nv, yb, W, iflags, loss, codes=False, mxu=mxu,
            interpret=interpret,
        )
        return _sgd_many_update(W, loss_sums, grads, nvf, lrs, alphas,
                                l2ws, l1ws, iflags)

    W, losses = jax.lax.scan(step, W, (order, LRS))
    return W, losses[-1]


# -- the streamed super-block scans ----------------------------------------
# Every streamed SGD dispatch is ONE program from ``_sgd_stream_program``:
# one model or a search cohort's slot rung; dense, Pallas or bucketed-nnz
# blocks; one device or a "data" mesh. The cohort is a CLIENT of the same
# plane as a single model — one BlockStream pass advances EVERY surviving
# candidate, each super-block ONE dispatch whose donated carry holds the
# stacked (n_slots, d+1) cohort weights, so a round reads its data once
# whatever the candidate count. Three mechanisms ride the cohort's scan:
#   - ``act (K, width)``: per-model STEP activity — heterogeneous rounds
#     ({model_id: n_calls} with differing counts) run in the SAME scan, a
#     model advancing only on its own window of block steps;
#   - ``idx (width,)``: the slot-rung gather — each dispatch pulls the
#     union of its ACTIVE slots out of the full (n_slots, d+1) donated
#     carry into the smallest rung width of the plans subsystem's
#     SlotRungLadder (powers of two below the candidate count, then the
#     full count). Every rung compiles during round 1 (warm dispatches
#     recorded in the process-wide plans WarmupRegistry), so compute
#     scales with the LIVE bracket while a shrinking bracket picks its
#     rung at zero new compiles — and a second search over the same
#     shapes skips the warm executions entirely;
#   - padding block slots (``counts == 0``, the ragged final super-block)
#     pass every row through, and padding SLOT columns (``act`` all-zero)
#     pass their rows back unchanged through the ``.at[idx].set`` scatter.

_COHORT_LADDER = SlotRungLadder()


def _cohort_rungs(n_slots):
    return _COHORT_LADDER.rungs_for(n_slots)


def _cohort_rung_of(n_active, n_slots):
    return _COHORT_LADDER.rung_for(n_active, n_slots)


# the program name of each (block reader, cohort carry); the
# data-parallel program adds ".psum"
_STREAM_PROGRAMS = {
    ("xla", False): "superblock.sgd_scan",
    ("pallas", False): "pallas.sgd_step",
    ("sparse", False): "superblock.sparse.sgd_scan",
    ("xla", True): "superblock.sgd_cohort",
    ("pallas", True): "pallas.sgd_cohort",
    ("sparse", True): "superblock.sparse.sgd_cohort",
}


def _stream_flavor(sb, rows, fit_dtype):
    """``(source, mxu, interpret, reason)`` of one super-block — THE gate
    of the streamed scans. ``source`` names the block reader: ``"sparse"``
    for a bucketed-nnz slab; ``"pallas"`` when the fused kernels are opted
    in (a real TPU, or interpret mode via
    ``config.pallas_stream_interpret``) and the PER-SHARD slab height
    (S/D rows, what each kernel instance sees) fits the kernel's tile;
    else ``"xla"``. ``rows`` is None for one flat weight vector (the
    one-row kernel) or the height of the stack a dispatch carries — a
    cohort gates at its full padded slot count, so a tile that fits the
    top rung fits every narrower one. ``mxu`` is the resolved compute
    dtype (config.dtype="auto" → bf16 on TPU only), ``reason`` the gate
    that refused the kernels (None when they engaged)."""
    from ..config import mxu_dtype
    from ..ops.pallas_fused import (sgd_many_stream_tile, sgd_stream_tile,
                                    stream_kernel_mode, stream_mode_reason,
                                    stream_tile_reason)
    from ..parallel.sparse_stream import SparseSlab

    if isinstance(sb.arrays[0], SparseSlab):
        return "sparse", None, False, "sparse-stream"
    mxu = mxu_dtype(fit_dtype)
    reason = stream_mode_reason()
    if reason is not None:
        return "xla", mxu, False, reason
    S, d = (int(v) for v in sb.arrays[0].shape[1:])
    D = 1 if sb.shard_counts is None else int(sb.shard_counts.shape[0])
    S_local = S // max(D, 1)
    tile = (sgd_stream_tile(S_local, d) if rows is None
            else sgd_many_stream_tile(S_local, d, int(rows)))
    reason = stream_tile_reason(S_local, tile)
    if reason is not None:
        return "xla", mxu, False, reason
    return "pallas", mxu, stream_kernel_mode()[1], None


def _stream_operands(sb):
    """``(mesh, block, S)`` of a super-block for ``_sgd_stream_program``:
    the mesh its blocks are batch-sharded over (None on one device), the
    block leaves — the dense ``(K, S, d)`` stack, or a sparse slab's
    ``(data, cols, rows)`` — and a sparse slab's static per-shard row
    count (None for a dense stack)."""
    from ..parallel.sparse_stream import SparseSlab

    mesh = None if sb.shard_counts is None else sb.shard_counts.sharding.mesh
    slab = sb.arrays[0]
    if isinstance(slab, SparseSlab):
        return mesh, (slab.data, slab.cols, slab.rows), slab.n_rows
    return mesh, (slab,), None


def _pallas_block_sums(Xb, c, yb, W, iflag, loss, mxu, interpret, codes,
                       one_row):
    """Raw (loss sums (R,), gradient sums (R, d+1)) of the rows of ``W``
    over a dense block in ONE fused VMEM pass: ``fused_sgd_block_grad``
    for one flat model, ``fused_sgd_many_block_grad`` (one (tile, R) MXU
    matmul) for a stack. The intercept sums are iflag-free;
    ``_sgd_many_update`` folds the flag."""
    from ..ops.pallas_fused import (fused_sgd_block_grad,
                                    fused_sgd_many_block_grad)

    if one_row:
        v, g = fused_sgd_block_grad(Xb, c, yb, W[0], iflag, loss, mxu=mxu,
                                    interpret=interpret)
        return v[None], g[None]
    return fused_sgd_many_block_grad(Xb, c, yb, W, iflag, loss,
                                     codes=codes, mxu=mxu,
                                     interpret=interpret)


@lru_cache(maxsize=64)
def _sgd_stream_program(mesh, source, loss, cohort, n_out=None, mxu=None,
                        interpret=False, S=None):
    """THE streamed SGD scan: the K block steps of one super-block in ONE
    dispatch with the weight carry donated, for a ``(mesh, block reader,
    loss, carry)`` — cached, so every pass of a fit reuses one jitted
    callable, tracked under its ``_STREAM_PROGRAMS`` name.

    ``run(W, block, ys, counts, lrs, alpha, l2w, l1w, iflag,
    shard_counts=None, idx=None, act=None)``. The carry is one flat model
    ``W (d+1,)``, a one-vs-rest model ``W (n_out, d+1)`` (row c trains on
    ``ys == c``: ``ys`` holds class codes), or with ``cohort`` a search's
    full slot stack ``W (n_slots, d+1)`` whose ``idx (width,)`` rung is
    gathered, stepped and scattered back. A single model takes scalar
    hyperparameters and ``lrs (K,)`` (the host-precomputed lr clock,
    identical to the per-block loop's ``_step_args`` sequence); a cohort
    takes ``(width,)`` vectors and ``lrs`` / ``act (K, width)``.
    ``block`` is what ``source`` reads: ``(Xs (K, S, d),)`` for ``"xla"``
    (autodiff through ``_design_matvec``) and ``"pallas"``
    (``_pallas_block_sums``), a slab's ``(data, cols, rows)`` of ``S`` rows
    for ``"sparse"`` (autodiff through ``sparse_eta``). ``counts (K,)``
    are the blocks' valid-row prefix counts.

    Each step computes every row's raw (loss sum, gradient sum) over the
    block's valid rows, psums them over "data" under ``mesh`` — blocks
    staged batch-sharded, ``shard_counts (D, K)`` the per-shard counts,
    the carry replicated: SGD's update is sequential in the blocks, so
    each step pays one psum — and applies ``_sgd_many_update``. Where no
    psum stands between the sums and the update (one device, an autodiff
    reader) the step normalizes inside autodiff instead,
    ``_sgd_update_one``'s form: the single-device scan trains the per-block
    loop's exact updates. A row advances where its step is active and the
    block holds rows; padding block slots and inactive slots pass through
    untouched (a masked-empty update would still apply the l2/prox
    terms). Returns ``(W advanced, per-step losses)``: summed over a single
    model's rows, per slot for a cohort."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, data_shard_spec

    hp_axis = 0 if cohort else None       # per-model or shared settings
    t_axis = None if n_out is None else 0  # shared targets or class codes
    fold = mesh is None and source != "pallas"

    def target(yb, c):
        return yb if c is None else (yb == c).astype(jnp.float32)

    def step(Wc, r, classes, hp, inp):
        blk, yb, c_loc, c, lr, act = inp
        alpha, l2w, l1w, iflag = hp
        mask = (r < c_loc).astype(jnp.float32)
        nv = c.astype(jnp.float32)
        if source != "pallas":
            eta_of = (_sparse_eta(*blk, S) if source == "sparse"
                      else _dense_eta(blk[0], mxu))
        if fold:
            def one(w, cc, lr, a, l2, l1, ifl):
                y = target(yb, cc)
                return _sgd_prox_update(
                    w, lambda v: _sgd_data_sum(v, y, eta_of, mask, ifl,
                                               loss, nv),
                    lr, a, l2, l1)

            W2, losses = jax.vmap(
                one, in_axes=(0, t_axis) + (hp_axis,) * 5
            )(Wc, classes, lr, alpha, l2w, l1w, iflag)
        else:
            if source == "pallas":
                sums = _pallas_block_sums(
                    blk[0], c_loc, yb, Wc, iflag, loss, mxu, interpret,
                    n_out is not None, not cohort and n_out is None)
            else:
                sums = jax.vmap(
                    lambda w, cc, ifl: jax.value_and_grad(
                        lambda v: _sgd_data_sum(v, target(yb, cc), eta_of,
                                                mask, ifl, loss))(w),
                    in_axes=(0, t_axis, hp_axis),
                )(Wc, classes, iflag)
            if mesh is not None:
                sums = jax.lax.psum(sums, DATA_AXIS)
            W2, losses = _sgd_many_update(Wc, *sums, jnp.maximum(nv, 1.0),
                                          lr, alpha, l2w, l1w, iflag)
        keep = c > 0 if act is None else (act > 0) & (c > 0)
        return jnp.where(keep[..., None], W2, Wc), losses

    def scan(Wc, blk, ys, shard_counts, counts, lrs, act, hp):
        r = jnp.arange(S if source == "sparse" else blk[0].shape[1])
        classes = (None if n_out is None
                   else jnp.arange(n_out, dtype=jnp.float32))
        c_loc = counts if shard_counts is None else shard_counts[0]
        return jax.lax.scan(
            lambda Wc, inp: step(Wc, r, classes, hp, inp), Wc,
            (blk, ys, c_loc, counts, lrs, act))

    def run(W, blk, ys, counts, lrs, alpha, l2w, l1w, iflag,
            shard_counts=None, idx=None, act=None):
        # the rung gather/scatter runs OUTSIDE any shard_map, on the
        # replicated full carry
        Wc = jnp.take(W, idx, axis=0) if cohort else \
            W.reshape(-1, W.shape[-1])
        args = (Wc, blk, ys, shard_counts, counts, lrs, act,
                (alpha, l2w, l1w, iflag))
        if mesh is None:
            Wc, losses = scan(*args)
        else:
            data = jax.tree.map(lambda a: data_shard_spec(a, 1), (blk, ys))
            Wc, losses = jax.shard_map(
                scan, mesh=mesh,
                in_specs=(P(), *data, P(DATA_AXIS, None), P(), P(),
                          None if act is None else P(), P()),
                out_specs=(P(), P()), check_vma=False,
            )(*args)
        if cohort:
            return W.at[idx].set(Wc), losses
        return Wc.reshape(W.shape), losses.sum(axis=1)

    name = _STREAM_PROGRAMS[source, cohort] + \
        ("" if mesh is None else ".psum")
    return plan_tracked(name, jax.jit(run, donate_argnums=(0,)),
                        ladder="cohort-slots" if cohort else None)


@partial(jax.jit, static_argnames=("n_rows",))
def _batched_eta_sparse(data, cols, rows, W, n_rows):
    """(n_rows, N) decision values of N stacked models over ONE packed
    sparse slab — the streamed-validation scoring dispatch for sparse
    holdouts (one ``sparse_eta_multi`` pass serves the whole cohort)."""
    from ..ops.sparse_kernels import sparse_eta_multi

    eta = sparse_eta_multi(data, cols, rows, W[:, :-1], n_rows)
    return eta + W[:, -1][None, :]


def _stack_cohort_weights(models, n_slots):
    """The cohort's (n_slots, d+1) host weight stack: live models in
    their slot rows, padding slots zero. Built on HOST so the stack's
    device shape never depends on the surviving candidate count — the
    one device_put per dispatch/score is what keeps shrinking brackets
    at zero recompiles."""
    d1 = int(np.asarray(models[0]._w).shape[-1])
    Wh = np.zeros((max(int(n_slots), len(models)), d1), np.float32)
    for i, m in enumerate(models):
        Wh[i] = np.asarray(m._w, np.float32)
    return Wh


def fused_blocks(X) -> tuple[int, int]:
    """(n_blocks B, rows-per-block S) of the fused-epoch grid for a
    ShardedArray: CONTIGUOUS blocks of S = padded/D rows rounded up to a
    multiple of D (so the grid's row axis shards evenly), B = however
    many cover the padded rows. The Incremental wrapper's per-block
    fallback loop uses the same partition so both paths train identical
    minibatches.

    Layout note: a STRIDED partition ({r ≡ b mod B}, grid (S, B, d)
    axis-0-sharded) would make the grid build collective-free, but each
    scan step would then read d-length runs strided B·d apart; the
    contiguous grid pays one all-to-all at build (on more than one
    device) and every step reads one contiguous (S, d) slab."""
    from ..parallel.mesh import data_shards
    from ..parallel.streaming import grid_partition

    return grid_partition(X.padded_shape[0], max(data_shards(X.mesh), 1))


@lru_cache(maxsize=32)
def _grid_builders(mesh, B, S, dtype=None):
    """Cached block-grid programs per (mesh, grid shape), tracked as
    ``sgd.grid_x`` / ``sgd.grid_y``: pad the (n_pad, d) row-sharded array
    to B*S rows and reshape to (B, S, d) with axis 1 sharded (every scan
    step uses the whole mesh) — one contiguous pad + reshape + reshard,
    with the cast to the fit dtype fused in. Cached because a fresh jit
    per fit would retrace every epoch."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS

    sh3 = NamedSharding(mesh, P(None, DATA_AXIS, None))
    sh2 = NamedSharding(mesh, P(None, DATA_AXIS))

    def grid_x(a):
        return jnp.pad(
            a, ((0, B * S - a.shape[0]), (0, 0))
        ).reshape(B, S, a.shape[1]).astype(dtype or a.dtype)

    def grid_y(a):
        return jnp.pad(a, (0, B * S - a.shape[0])).reshape(B, S)

    return (plan_tracked("sgd.grid_x", jax.jit(grid_x, out_shardings=sh3)),
            plan_tracked("sgd.grid_y", jax.jit(grid_y, out_shardings=sh2)))


def _epoch_grid_key(X, fit_dtype):
    """What, beside the source array itself, decides an epoch grid of the
    ShardedArray ``X``: ``(mesh, B, S, grid dtype)``."""
    from ..config import mxu_dtype

    return (X.mesh, *fused_blocks(X), mxu_dtype(fit_dtype))


class _KeptGrid:
    """ONE kept epoch grid: the X half ``Xr`` of ``_grid_builders``' grid, a
    WEAK reference to the device array it was built from and its
    ``_epoch_grid_key``. A jax array is immutable, so the same source
    OBJECT is the same rows; the reference is held (never a bare ``id()``,
    which is reused once the source is freed) and its callback drops the
    grid with the source. ``Incremental`` owns one across its passes. It
    pickles, deep-copies and clones as an empty holder. A kept grid is a
    CACHE: ``drop_others`` empties every other holder of the process, so
    that a wrapper whose headroom gate refuses takes back what older fitted
    wrappers still hold before it falls to the block loop."""

    __slots__ = ("Xr", "key", "_src", "__weakref__")
    _live = weakref.WeakSet()    # the holders that keep a grid now

    def __init__(self):
        self.clear()

    def clear(self):
        self.Xr = self.key = self._src = None
        self._live.discard(self)

    @classmethod
    def drop_others(cls, mine):
        """Empty every holder but ``mine``; how many kept a grid."""
        others = [h for h in list(cls._live) if h is not mine]
        for holder in others:
            holder.clear()
        return len(others)

    def get(self, src, key):
        """The kept grid of exactly this source and key, else None."""
        if self._src is not None and self._src() is src and self.key == key:
            return self.Xr
        return None

    def keep(self, src, key, Xr):
        me = weakref.ref(self)   # the callback must not keep the holder

        def source_died(ref):
            holder = me()
            if holder is not None and holder._src is ref:
                holder.clear()

        self.Xr, self.key = Xr, key
        self._src = weakref.ref(src, source_died)
        self._live.add(self)

    def __reduce__(self):
        return (type(self), ())


# what a cohort scan reads: the DISTINCT blocks ``Xr (B, S, d)`` in the
# design dtype, their encoded targets ``yr (B, S)`` (f32) and each block's
# count of valid leading rows ``NV (B,)``
CohortGrid = collections.namedtuple("CohortGrid", "Xr yr NV")


@lru_cache(maxsize=32)
def _split_builders(mesh, B, S, T, dtype=None):
    """Cached programs of an adaptive search over a resident table, per
    (mesh, shapes), tracked as ``search.split_x`` / ``search.split_y``:
    the train/held-out split and the blocking as ONE gather — rows
    ``tr (B*S,)`` of the (n_pad, d) row-sharded array as the ``(B, S, d)``
    block grid (axis 1 sharded, as ``_grid_builders`` lays it out) and
    rows ``te (T,)`` as the held-out block, the cast to the fit dtype
    fused in, so no float32 copy of either split is ever made."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS

    def sh(*spec):
        return NamedSharding(mesh, P(*spec))

    def split_x(a, tr, te):
        dt = dtype or a.dtype
        return (jnp.take(a, tr, axis=0).astype(dt).reshape(B, S, a.shape[1]),
                jnp.take(a, te, axis=0).astype(dt))

    def split_y(a, tr, te):
        return jnp.take(a, tr).reshape(B, S), jnp.take(a, te)

    return (plan_tracked("search.split_x", jax.jit(
                split_x, out_shardings=(sh(None, DATA_AXIS, None),
                                        sh(DATA_AXIS, None)))),
            plan_tracked("search.split_y", jax.jit(
                split_y, out_shardings=(sh(None, DATA_AXIS),
                                        sh(DATA_AXIS)))))


@plan_tracked("sgd.cohort_score")
@partial(jax.jit, static_argnames=("kind",))
def _sgd_cohort_score(Xt, yt, n_valid, W, kind):
    """The default score (``kind``: ``"accuracy"`` / ``"r2"``) of EVERY row
    of the stacked weights ``W (n_slots, d+1)`` on the held-out block
    ``Xt (T, d)`` (design dtype; the first ``n_valid`` rows count): one
    program of one shape a search, whoever survives. The eta product keeps
    ``_design_matvec``'s contract — coef rounded to the block's dtype for
    this product only, f32 accumulation."""
    mask = (jnp.arange(Xt.shape[0]) < n_valid).astype(jnp.float32)
    n = jnp.maximum(n_valid.astype(jnp.float32), 1.0)
    eta = jnp.matmul(
        Xt, W[:, :-1].T.astype(Xt.dtype),
        preferred_element_type=jnp.float32,
        # a float32 block's product is float32 on the MXU too; a narrower
        # block's products are exact as they are
        precision=(jax.lax.Precision.HIGHEST if Xt.dtype == jnp.float32
                   else None),
    ) + W[:, -1][None, :]
    if kind == "accuracy":
        hit = ((eta > 0).astype(jnp.float32) == yt[:, None])
        return jnp.sum(hit * mask[:, None], axis=0) / n
    y_mean = jnp.sum(yt * mask) / n
    ss_tot = jnp.sum(((yt - y_mean) * mask) ** 2)
    ss_res = jnp.sum(((eta - yt[:, None]) * mask[:, None]) ** 2, axis=0)
    return 1.0 - ss_res / jnp.maximum(ss_tot, 1e-12)


@jax.jit
def _batched_eta(X, W):
    """(n, N) decision values for N stacked models on one shared X."""
    return X @ W[:, :-1].T + W[:, -1][None, :]


@jax.jit
def _batched_accuracy(X, y01, mask, n_valid, W):
    eta = _batched_eta(X, W)
    correct = (eta > 0).astype(jnp.float32) == y01[:, None]
    return jnp.sum(correct * mask[:, None], axis=0) / jnp.maximum(n_valid, 1.0)


@jax.jit
def _batched_r2(X, y, mask, n_valid, W):
    eta = _batched_eta(X, W)
    n = jnp.maximum(n_valid, 1.0)
    y_mean = jnp.sum(y * mask) / n
    ss_tot = jnp.sum(((y - y_mean) * mask) ** 2)
    ss_res = jnp.sum((((eta - y[:, None]) * mask[:, None]) ** 2), axis=0)
    return 1.0 - ss_res / jnp.maximum(ss_tot, 1e-12)


class _SGDBase(BaseEstimator):
    loss_default = "squared_error"

    def __init__(self, loss=None, penalty="l2", alpha=1e-4, l1_ratio=0.15,
                 eta0=0.01, learning_rate="invscaling", power_t=0.25,
                 max_iter=5, tol=1e-3, shuffle=True, random_state=None,
                 warm_start=False, fit_intercept=True, fit_dtype=None):
        self.loss = loss
        # per-estimator precision override: None follows config.dtype
        # ("auto" = bf16 on TPU, f32 elsewhere); "float32" opts this
        # estimator out of the bf16 default, "bfloat16" forces it on.
        # The resolved choice lands on `fit_dtype_` after fit.
        self.fit_dtype = fit_dtype
        self.penalty = penalty
        self.alpha = alpha
        self.l1_ratio = l1_ratio
        self.eta0 = eta0
        self.learning_rate = learning_rate
        self.power_t = power_t
        self.max_iter = max_iter
        self.tol = tol
        self.shuffle = shuffle
        self.random_state = random_state
        self.warm_start = warm_start
        self.fit_intercept = fit_intercept

    def _loss(self):
        loss = self.loss or self.loss_default
        if loss not in _LOSSES:
            raise ValueError(f"loss must be one of {_LOSSES}, got {loss!r}")
        return loss

    def _penalty_weights(self):
        """(l2_weight, l1_weight) implementing sklearn SGD semantics."""
        p = self.penalty
        if p == "l2":
            return 1.0, 0.0
        if p == "l1":
            return 0.0, 1.0
        if p == "elasticnet":
            return 1.0 - self.l1_ratio, self.l1_ratio
        if p is None or p == "none":
            return 0.0, 0.0
        raise ValueError(f"penalty must be one of {_PENALTIES}, got {p!r}")

    def _lr(self):
        t = max(self._t, 1)
        if self.learning_rate == "constant":
            return self.eta0
        if self.learning_rate == "invscaling":
            return self.eta0 / (t ** self.power_t)
        if self.learning_rate == "optimal":
            return 1.0 / (self.alpha * (1e3 + t))
        raise ValueError(f"Unknown learning_rate {self.learning_rate!r}")

    def _n_out(self):
        """Number of one-vs-rest rows for a multiclass classifier, else
        None (binary / regression use a flat weight vector)."""
        classes = getattr(self, "classes_", None)
        return len(classes) if classes is not None and len(classes) > 2 \
            else None

    def _ensure_state(self, d):
        if not hasattr(self, "_w") or self._w is None:
            C = self._n_out()
            shape = (C, d + 1) if C is not None else (d + 1,)
            self._w = jnp.zeros(shape, jnp.float32)
            self._t = 0
        self._penalty_weights()  # validate penalty eagerly
        # resolved fit compute dtype, on record (an auto policy that
        # fell back to f32 off-TPU must be visible, not silent)
        from ..config import fit_dtype_info

        info = fit_dtype_info(self.fit_dtype)
        self.fit_dtype_ = info["fit_dtype"]
        self.fit_dtype_source_ = info["fit_dtype_source"]

    def _step_args(self):
        """Per-model dynamic scalars for the (batched) step. The model's
        step clock advances here."""
        self._t += 1
        l2w, l1w = self._penalty_weights()
        return (
            np.float32(self._lr()), np.float32(self.alpha),
            np.float32(l2w), np.float32(l1w),
            np.float32(1.0 if self.fit_intercept else 0.0),
        )

    def _block(self, X, y):
        X = as_sharded(X, dtype=np.float32)
        y = as_sharded(self._encode_y(y), mesh=X.mesh, dtype=np.float32)
        return X, y

    def partial_fit(self, X, y, classes=None, **kwargs):
        # a root span when called on its own; under Incremental's block
        # loop it nests in the wrapper's pass.solve
        with span("partial_fit", component=type(self).__name__) as sp:
            if classes is not None:
                self._set_classes(np.asarray(classes))
            X, y = self._block(X, y)
            self._ensure_state(X.shape[1])
            self._one_step(X.data, y.data, X.row_mask(jnp.float32),
                           X.n_rows)
            self._publish(X.shape[1])
            sp.add(n_rows=X.n_rows, t_end=int(self._t))
        return self

    def _fused_epoch(self, X, y, order, n_blocks=None, classes=None,
                     kept=None):
        """One full streaming epoch in ONE program (the Incremental
        wrapper's fast path for device data): the dataset is padded and
        reshaped once into its (B, S, d) contiguous block grid (axis 1
        row-sharded; one all-to-all — see ``fused_blocks`` for why this
        beats a collective-free strided layout) and ``_sgd_epoch`` scans
        the blocks in ``order``. Semantically identical to ``order``
        partial_fit calls over the same contiguous blocks (same update,
        same lr clock, same masking), minus one dispatch round trip per
        block. NOTE the grid is a second device copy of the dataset. Called
        alone it lives for the epoch's duration. Given ``kept`` (a
        ``_KeptGrid``; the wrapper hands its own) the X half outlives the
        pass: a pass over the SAME device array under the same
        ``_epoch_grid_key`` reads the kept grid and dispatches no
        ``sgd.grid_x``; any other pass drops the kept grid BEFORE it builds
        and keeps its own, so there are never two. The wrapper asks the HBM
        headroom gate before a pass that has to build, and falls back to
        the block loop when headroom is insufficient.

        Three spans, children of the caller's root (``Incremental.fit`` /
        ``partial_fit``): ``pass.validate`` (classes, ``as_sharded``, the
        label encoding with its one-scalar fetch), ``pass.grid`` (the
        DISPATCH of ``sgd.grid_x`` — on a miss — and ``sgd.grid_y``: it
        does not wait for them, so the grid's device time lands in
        ``pass.solve``, which waits for everything; ``grid_hit`` and
        ``grid_bytes``, the bytes of the grid the pass READS, are on it)
        and ``pass.solve`` (``sgd.fused_epoch``
        through the weights on the host). ``solver_info_`` records what
        ran."""
        with span("pass.validate"):
            if classes is not None:
                self._set_classes(np.asarray(classes))
            if isinstance(self, ClassifierMixin) and \
                    getattr(self, "classes_", None) is None:
                raise ValueError(
                    "classes must be passed on the first call to "
                    "partial_fit."
                )
            X = as_sharded(X, dtype=np.float32)
            y_enc = as_sharded(self._encode_y(y), mesh=X.mesh,
                               dtype=np.float32)
            d = X.data.shape[1]
            key = _epoch_grid_key(X, self.fit_dtype)
            _, B, S, _ = key
            if n_blocks is not None and n_blocks != B:
                # ``order`` indexes the caller's block partition; a
                # mismatched one would silently train wrong minibatches
                raise ValueError(
                    f"_fused_epoch grid has {B} blocks of {S} rows; "
                    f"caller partitioned into {n_blocks}"
                )
            order = np.asarray(order, np.int32)
            if order.size and (order.min() < 0 or order.max() >= B):
                raise ValueError(
                    f"order indexes blocks 0..{B - 1}; got "
                    f"[{order.min()}, {order.max()}]"
                )
            self._ensure_state(d)
            self._lr()  # validate the schedule name eagerly, like the loop
        with span("pass.grid") as sp:
            # bf16 epoch grid: halves the grid's HBM (it's a second copy
            # of X) and the scan's matvecs ride the MXU at bf16 rate with
            # f32 accumulation; weights/targets/updates stay f32. Weight
            # parity vs f32 ~1e-2 relative (input rounding on the design
            # matrix)
            fX, fy = _grid_builders(*key)
            if kept is None:
                kept = _KeptGrid()   # called alone: gone with this call
            Xr = kept.get(X.data, key)
            grid_hit = Xr is not None
            if not grid_hit:
                kept.clear()         # never two grids alive
                Xr = fX(X.data)
                kept.keep(X.data, key, Xr)
            # y may be new labels over the same X, and is a new encoded
            # array every pass anyway
            yr = fy(y_enc.data)
            grid_bytes = int(Xr.nbytes) + int(yr.nbytes)
            sp.add(grid_bytes=grid_bytes, grid_hit=grid_hit)
        with span("pass.solve") as sp:
            l2w, l1w = self._penalty_weights()
            W, _t = _sgd_epoch(
                Xr, yr, jnp.asarray(order), self._w,
                np.float32(self._t), np.float32(self.eta0),
                np.float32(self.power_t), np.float32(self.alpha),
                np.float32(l2w), np.float32(l1w),
                np.float32(1.0 if self.fit_intercept else 0.0),
                np.int32(X.n_rows), loss=self._loss(),
                schedule=self.learning_rate, n_out=self._n_out(),
            )
            self._w = sp.sync(W)
            self._t += int(len(order))
            self._publish(d)
            sp.add(steps=int(len(order)), t_end=int(self._t))
        self.solver_info_ = {
            "path": "fused_epoch", "program": "sgd.fused_epoch",
            "blocks": int(B), "block_rows": int(S),
            "steps": int(len(order)), "grid_bytes": grid_bytes,
            "grid_hit": grid_hit,
        }
        return self

    # -- batched-trial protocol (consumed by model_selection._incremental) --
    def _batch_prepare(self, fit_params):
        """Apply first-call side effects (classes) before grouping."""
        classes = (fit_params or {}).get("classes")
        if classes is not None:
            self._set_classes(np.asarray(classes))

    def _batch_key(self):
        """Models sharing a key can advance in one vmapped step. None
        disables batching. Hyperparameters (lr schedule, alpha, penalty)
        are DYNAMIC per-model scalars, so only structure is in the key."""
        try:
            loss = self._loss()
            self._penalty_weights()
            from ..config import fit_dtype_info

            # the batched step is ONE program for the cohort, so only
            # models resolving to the SAME compute dtype may share it
            dtype = fit_dtype_info(self.fit_dtype)["fit_dtype"]
        except ValueError:
            return None  # invalid params: surface the error on the solo path
        classes = getattr(self, "classes_", None)
        return (type(self).__name__, loss, dtype,
                tuple(np.asarray(classes).tolist()) if classes is not None
                else None)

    @classmethod
    def _batched_partial_fit(cls, models, X, y):
        """One shared data block, one jitted step, N models advanced.

        X/y may be host arrays or ShardedArray; they are canonicalized
        once for the whole cohort (the reference pays this once per model
        per worker)."""
        Xs = as_sharded(X, dtype=np.float32)
        ys = as_sharded(models[0]._encode_y(y), mesh=Xs.mesh,
                        dtype=np.float32)
        d = Xs.shape[1]
        for m in models:
            m._ensure_state(d)
        mask = Xs.row_mask(jnp.float32)
        args = np.asarray([m._step_args() for m in models], np.float32)
        W = jnp.stack([m._w for m in models])
        from ..config import mxu_dtype

        W, losses = _sgd_step_many(
            Xs.data, ys.data, mask, jnp.float32(Xs.n_rows), W,
            jnp.asarray(args[:, 0]), jnp.asarray(args[:, 1]),
            jnp.asarray(args[:, 2]), jnp.asarray(args[:, 3]),
            jnp.asarray(args[:, 4]), models[0]._loss(),
            mxu=mxu_dtype(models[0].fit_dtype),  # cohort shares (keyed)
        )
        for i, m in enumerate(models):
            m._w = W[i]
            m._last_loss = losses[i]
        return models

    @classmethod
    def _batch_publish(cls, models, d):
        """Materialize coef_/intercept_ once per round (one D2H sync for
        the cohort, not one per model per step)."""
        for m in models:
            m._publish(d)

    def _lr_schedule(self, n_calls):
        """The next ``n_calls`` lr values this model's clock would
        produce — EXACTLY ``_step_args``'s increment-then-``_lr``
        sequence, precomputed on host so a fused multi-call program can
        carry them as one (S,) operand."""
        out = []
        t0 = self._t
        for i in range(n_calls):
            self._t = t0 + i + 1
            out.append(self._lr())
        self._t = t0
        return np.asarray(out, np.float32)

    @classmethod
    def _cohort_grid_of_blocks(cls, enc, blocks):
        """A :class:`CohortGrid` stacked from a LIST of (X, y) blocks
        (host arrays or ShardedArrays; targets encoded by ``enc``), padded
        to the tallest with per-block valid-row counts — what a search
        over host input hands ``_batched_fused_calls`` a round. A search
        over a resident table builds its grid once a fit instead
        (``search.split_x``)."""
        Xs_list, ys_list, nvs = [], [], []
        for Xb, yb in blocks:
            Xs = as_sharded(Xb, dtype=np.float32)
            ys = as_sharded(enc._encode_y(yb), mesh=Xs.mesh,
                            dtype=np.float32)
            Xs_list.append(Xs)
            ys_list.append(ys)
            nvs.append(Xs.n_rows)
        bs_max = max(x.data.shape[0] for x in Xs_list)

        def padded(a):
            pad = bs_max - a.shape[0]
            if pad:
                a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            return a

        return CohortGrid(jnp.stack([padded(x.data) for x in Xs_list]),
                          jnp.stack([padded(y.data) for y in ys_list]),
                          jnp.asarray(nvs, jnp.int32))

    @classmethod
    def _batched_fused_calls(cls, models, blocks, order=None, W=None):
        """Advance the cohort through a sequence of block steps in ONE
        scan program (``_sgd_cohort_scan``) — equivalent to that many
        ``_batched_partial_fit`` calls (same updates, same per-model lr
        clocks) minus the per-call dispatch round trips. ``blocks`` is a
        :class:`CohortGrid` of the DISTINCT blocks (or a list of (X, y)
        blocks, stacked here) and ``order`` (default: each once, in
        sequence) indexes the steps into them — a multi-epoch rung
        revisits blocks without duplicating them on device.

        ``W (N, d+1)``: the cohort's stacked weights where the caller
        keeps them stacked on the device (a search over a resident table);
        ``(the advanced stack, the program's name)`` is returned and no
        model's ``_w`` is touched. Without it the stack is built from and
        written back to the models, which are returned."""
        grid = blocks if isinstance(blocks, CohortGrid) \
            else cls._cohort_grid_of_blocks(models[0], blocks)
        if order is None:
            order = list(range(grid.Xr.shape[0]))
        stacked = W is not None
        if not stacked:
            for m in models:
                m._ensure_state(int(grid.Xr.shape[2]))
            W = jnp.stack([m._w for m in models])
        W, losses, name = cls._cohort_scan(
            models, grid, order, W, cls._cohort_operands(models, len(order)))
        if stacked:
            return W, name
        for i, m in enumerate(models):
            m._w = W[i]
            m._last_loss = losses[i]
        return models

    @classmethod
    def _cohort_operands(cls, models, n_steps):
        """The per-model operands of a cohort scan of ``n_steps`` steps, on
        the device: ``(LRS (S, N), alphas, l2 weights, l1 weights,
        intercept flags)`` — each model's next lr clock values and its
        penalties. Host work; nothing of the scan is dispatched."""
        LRS = np.stack([m._lr_schedule(n_steps) for m in models], axis=1)
        args = np.asarray(
            [(m.alpha,) + m._penalty_weights()
             + (1.0 if m.fit_intercept else 0.0,) for m in models],
            np.float32,
        )
        return (jnp.asarray(LRS),) + tuple(
            jnp.asarray(args[:, j]) for j in range(4))

    @classmethod
    def _cohort_scan(cls, models, grid, order, W, operands):
        """Dispatch ONE cohort scan of ``len(order)`` steps over ``grid``
        for the stacked ``W`` and advance the models' clocks:
        ``(W, last losses, the program's name)``, nothing waited for."""
        from ..config import mxu_dtype
        from ..ops.pallas_fused import (sgd_many_stream_tile,
                                        stream_kernel_mode)

        # fused cohort flavor (ISSUE 12): one VMEM pass per block step
        # serves every model in the cohort when the stacked block height
        # fits the kernel grid — cohort weights are flat by construction
        # (_batch_key refuses multiclass), so the kernel's (N, d+1) stack
        # always applies. Not over a design narrower than float32: the
        # kernel multiplies the residuals at the design's dtype (a bf16
        # gradient product), and ``_design_matvec`` states an f32 one
        enc = models[0]
        bs, d = (int(v) for v in grid.Xr.shape[1:])
        mxu = mxu_dtype(enc.fit_dtype)
        use_k, interp = stream_kernel_mode()
        fused = bool(use_k and grid.Xr.dtype == jnp.float32
                     and mxu is None and sgd_many_stream_tile(
                         bs, d, len(models)) is not None)
        runner = (partial(_sgd_cohort_scan_pallas, interpret=interp)
                  if fused else _sgd_cohort_scan)
        W, losses = runner(
            grid.Xr, grid.yr, grid.NV,
            jnp.asarray(np.asarray(order, np.int32)), W, *operands,
            enc._loss(), mxu=mxu,
        )
        for m in models:
            m._t += len(order)
        return W, losses, "pallas.sgd_cohort" if fused else "sgd.cohort_scan"

    # -- resident-cohort protocol (consumed by model_selection.
    # _incremental's _ResidentCohortPlane: a search over a resident table) --
    _cohort_score_kind = "r2"

    @staticmethod
    def _cohort_split_programs(mesh, B, S, T, dtype):
        return _split_builders(mesh, B, S, T, dtype)

    @staticmethod
    def _cohort_grid_dtype(estimator, searched=False):
        """The grid's dtype: the fit dtype the estimator resolves to (a
        bfloat16 design under ``dtype="auto"`` on a TPU), None for the
        table's own. A search OVER ``fit_dtype`` keeps the table's and
        every cohort casts for itself."""
        from ..config import mxu_dtype

        return None if searched else mxu_dtype(estimator.fit_dtype)

    @classmethod
    def _cohort_score(cls, W, Xt, yt, n_valid):
        return _sgd_cohort_score(Xt, yt, n_valid, W,
                                 kind=cls._cohort_score_kind)

    # -- streamed-cohort protocol (ISSUE 14 tentpole; consumed by
    # model_selection._incremental's _StreamCohortPlane) ----------------
    @classmethod
    def _streamed_cohort_round(cls, models, stream, order, act,
                               n_slots, warm=False):
        """Advance a (possibly heterogeneous) adaptive-search cohort
        through ONE streamed super-block pass — the ISSUE 14 tentpole.

        ``order`` is the round's block-step timeline (``order[s]`` is
        the block every active model trains on at step ``s``) and
        ``act`` the ``(len(order), len(models))`` step-activity matrix:
        model ``i`` advances exactly on its own window of steps, with
        the SAME updates and lr clock a per-model ``partial_fit`` loop
        over those blocks would produce. Each super-block is one
        dispatch with the stacked carry donated; the data is read once
        per round regardless of candidate count.

        Slot rungs: the full carry holds ``n_slots`` rows (the
        search's candidate count), but each dispatch GATHERS the union
        of its active slots into the smallest rung of the
        ``_cohort_rungs`` ladder — compute scales with the live
        bracket, not the padded stack — and scatters the rows back.
        ``warm=True`` (the search's first streamed round) dispatches
        every OTHER rung once against the first super-block with an
        all-zero activity mask (a semantic no-op), so bracket halving
        later in the search picks any rung at zero new XLA compiles.

        Each dispatch is the cohort carry of ``_sgd_stream_program``
        through the reader ``_stream_flavor`` picks at the first
        super-block, gated at the full slot stack
        (``superblock[.sparse].sgd_cohort`` / ``pallas.sgd_cohort``,
        ``.psum`` over a batch-sharded stream). Returns an
        engagement/dispatch info dict for the search's telemetry."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..observability import record_superblock_donation

        enc = models[0]
        N = len(models)
        n_slots = max(int(n_slots), N)
        d = int(stream.arrays[0].shape[1])
        for m in models:
            m._ensure_state(d)
        order = np.asarray(order, np.int64)
        act = np.asarray(act, np.float32)
        S_total = len(order)
        LRS = np.ones((S_total, n_slots), np.float32)
        ACT = np.zeros((S_total, n_slots), np.float32)
        ACT[:, :N] = act
        for i, m in enumerate(models):
            steps = np.flatnonzero(act[:, i] > 0)
            LRS[steps, i] = m._lr_schedule(len(steps))
        args = np.zeros((n_slots, 4), np.float32)
        for i, m in enumerate(models):
            l2w, l1w = m._penalty_weights()
            args[i] = (m.alpha, l2w, l1w,
                       1.0 if m.fit_intercept else 0.0)
        # the carry commits REPLICATED on the stream's mesh once per
        # round (single-device meshes included — the scan operands live
        # there), so every dispatch hits one executable and donation
        # aliases in place
        rep = NamedSharding(stream.mesh, P())
        W = jax.device_put(_stack_cohort_weights(models, n_slots), rep)
        loss_name = enc._loss()
        info = {"streamed": True, "n_steps": int(S_total),
                "shards": int(stream.sb_data_shards()),
                "sparse": bool(stream.sb_sparse()),
                "fused": False, "fused_reason": None,
                "dispatches": 0, "warm_dispatches": 0}
        w_bytes = int(n_slots * (d + 1)) * 4
        flavor = None

        def program(sb):
            mesh, blk, S = _stream_operands(sb)
            source, mxu, interp, _ = flavor
            return _sgd_stream_program(mesh, source, loss_name, True,
                                       None, mxu, interp, S), blk

        def dispatch(W, sb, idx, lr_k, act_k):
            run, blk = program(sb)
            return run(W, blk, sb.arrays[1], sb.counts, jnp.asarray(lr_k),
                       *(jnp.asarray(args[idx, j]) for j in range(4)),
                       shard_counts=sb.shard_counts,
                       idx=jnp.asarray(idx), act=jnp.asarray(act_k))

        all_slots = np.arange(n_slots)
        pos = 0
        losses = np.zeros((S_total, N), np.float32)
        losses_parts = []
        for sb in stream.superblocks(order=order):
            K = int(sb.counts.shape[0])
            take = sb.n_blocks
            cols = np.flatnonzero(act[pos:pos + take, :].any(axis=0))
            width = _cohort_rung_of(max(len(cols), 1), n_slots)
            spare = np.setdiff1d(all_slots, cols)[: width - len(cols)]
            idx = np.concatenate([cols, spare]).astype(np.int32)
            if flavor is None:
                flavor = _stream_flavor(sb, n_slots, enc.fit_dtype)
                info["fused"] = flavor[0] == "pallas"
                info["fused_reason"] = flavor[3]
            if warm and info["dispatches"] == 0:
                # round-1 rung warmup: every OTHER ladder width runs
                # once over this super-block with an all-zero activity
                # mask (weights pass through bit-identically), so the
                # whole ladder is compiled before bracket shrinks ask
                # for a narrower rung. Once per PROCESS per shape via
                # the plans WarmupRegistry: a later search over the same
                # shapes finds the programs already compiled and skips
                # the executions — and the plans table names the
                # program and rungs that minted them
                wkey = (cls.__name__, loss_name, stream.mesh,
                        sb.shard_counts is not None, n_slots, d, K,
                        int(stream.block_rows),
                        getattr(sb.arrays[0], "cap", None),
                        flavor[0], str(flavor[1]), flavor[2])
                cohort_prog = program(sb)[0].program_name
                for rw in _cohort_rungs(n_slots):
                    if rw == width \
                            or plan_warmups.warmed(("cohort", wkey, rw)):
                        continue
                    W, _ = dispatch(
                        W, sb, np.arange(rw, dtype=np.int32),
                        np.ones((K, rw), np.float32),
                        np.zeros((K, rw), np.float32),
                    )
                    plan_warmups.note(("cohort", wkey, rw),
                                      program=cohort_prog,
                                      ladder="cohort-slots", rung=rw,
                                      ran=True)
                    info["warm_dispatches"] += 1
                # the REAL dispatch below compiles this round's own
                # width — register it too, or a later same-shape
                # search starting at a different width would re-run
                # its warm no-op for a program that already exists
                plan_warmups.note(("cohort", wkey, width),
                                  program=cohort_prog,
                                  ladder="cohort-slots", rung=width)
            lr_k = np.ones((K, width), np.float32)
            act_k = np.zeros((K, width), np.float32)
            lr_k[:take] = LRS[pos:pos + take][:, idx]
            act_k[:take] = ACT[pos:pos + take][:, idx]
            W, lv = dispatch(W, sb, idx, lr_k, act_k)
            record_superblock_donation(w_bytes)
            info["dispatches"] += 1
            # loss pulls DEFER to pass end: a per-dispatch np.asarray
            # would synchronize the host on every scan, stalling the
            # staging/compute overlap
            losses_parts.append((pos, take, idx, lv))
            pos += take
        # ONE stable-shape D2H pull per round: weights land back as
        # host rows (a per-model device slice would mint a fresh tiny
        # program per surviving N — exactly the recompile leak the
        # padded stack exists to avoid)
        rows = np.asarray(W, np.float32)
        for p, take, idx, lv in losses_parts:
            lvh = np.asarray(lv, np.float32)[:take]
            live = idx < N
            if live.any():
                losses[p:p + take, idx[live]] = lvh[:, live]
        for i, m in enumerate(models):
            steps = np.flatnonzero(act[:, i] > 0)
            m._w = rows[i].copy()
            m._t += len(steps)
            if len(steps):
                m._last_loss = float(losses[steps[-1], i])
        cls._batch_publish(models, d)
        return info

    @classmethod
    def _cohort_holdout(cls, X_test, y_test, model):
        """Stage the search's validation split ONCE — every round then
        scores the whole surviving cohort against it in one batched
        dispatch. Dense splits stage as device arrays; sparse splits as
        one packed COO triple (nnz cost, no densify)."""
        from ..parallel.streaming import (_is_sparse_source,
                                          as_row_sliceable)

        y_enc = np.asarray(model._encode_y(np.asarray(y_test)),
                           np.float32)
        if _is_sparse_source(X_test):
            from ..parallel.sparse_stream import coo_rows

            src = as_row_sliceable(X_test)
            n = int(src.shape[0])
            data, cols, rows = coo_rows(src, 0, n)
            return {"kind": "sparse", "data": jnp.asarray(data),
                    "cols": jnp.asarray(cols),
                    "rows": jnp.asarray(rows), "n": n, "y": y_enc}
        Xs = as_sharded(np.asarray(X_test), dtype=np.float32)
        ys = as_sharded(y_enc, mesh=Xs.mesh, dtype=np.float32)
        return {"kind": "dense", "X": Xs, "y": ys}

    def _one_step(self, Xb, yb, mask, n_valid):
        from ..config import mxu_dtype

        mxu = mxu_dtype(self.fit_dtype)
        lr, alpha, l2w, l1w, iflag = self._step_args()
        if self._n_out() is not None:
            # multiclass: C one-vs-rest rows advance in one program; yb
            # holds class codes, per-class targets derive in-kernel
            W, losses = _sgd_step_multi(
                Xb, yb, mask, jnp.float32(n_valid), self._w,
                jnp.float32(lr), jnp.float32(alpha), jnp.float32(l2w),
                jnp.float32(l1w), jnp.float32(iflag), self._loss(),
                mxu=mxu,
            )
            self._w = W
            self._last_loss = losses.sum()
            return
        W, losses = _sgd_step_many(
            Xb, yb, mask, jnp.float32(n_valid), self._w[None],
            jnp.asarray([lr]), jnp.asarray([alpha]), jnp.asarray([l2w]),
            jnp.asarray([l1w]), jnp.asarray([iflag]), self._loss(),
            mxu=mxu,
        )
        self._w = W[0]
        self._last_loss = losses[0]

    def _sb_step(self, sb):
        """Advance through one SuperBlock — K minibatch steps, ONE
        ``_sgd_stream_program`` dispatch, donated weight carry, through
        the block reader ``_stream_flavor`` picks. The lr clock advances
        exactly as K ``_step_args`` calls would (``_lr_schedule``
        precomputes the same host values); padding slots get a
        placeholder lr their pass-through step never reads. Over a
        batch-sharded super-block the carry is committed replicated ONCE,
        so every dispatch of the fit hits the same executable (and
        donation aliases in place)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..observability import record_superblock_donation

        k = int(sb.counts.shape[0])
        lrs = np.ones(k, np.float32)
        lrs[:sb.n_blocks] = self._lr_schedule(sb.n_blocks)
        l2w, l1w = self._penalty_weights()
        n_out = self._n_out()
        source, mxu, interp, reason = _stream_flavor(sb, n_out,
                                                     self.fit_dtype)
        # on record for solver_info_ (the engagement audit trail
        # tpu_smoke asserts on)
        self._fused_stream = source == "pallas"
        self._fused_stream_reason = reason
        if source == "sparse":
            self._sparse_stream = True
        mesh, blk, S = _stream_operands(sb)
        if mesh is not None:
            rep = NamedSharding(mesh, P())
            if getattr(self._w, "sharding", None) != rep:
                self._w = jax.device_put(self._w, rep)
        w_bytes = int(np.prod(self._w.shape)) * 4
        run = _sgd_stream_program(mesh, source, self._loss(), False, n_out,
                                  mxu, interp, S)
        W, losses = run(
            self._w, blk, sb.arrays[1], sb.counts, jnp.asarray(lrs),
            jnp.float32(self.alpha), jnp.float32(l2w), jnp.float32(l1w),
            jnp.float32(1.0 if self.fit_intercept else 0.0),
            shard_counts=sb.shard_counts,
        )
        record_superblock_donation(w_bytes)
        self._w = W
        self._t += sb.n_blocks
        self._last_loss = losses[sb.n_blocks - 1]

    def _stream_pass(self, Xh, yh, block_rows, order=None, classes=None,
                     shuffle=False, seed=None):
        """One partial_fit pass over host data as super-block scans (the
        Incremental wrapper's fused driver for host-resident X): block
        ``order[j]`` is the j-th minibatch, identical updates and lr
        clock to a per-block ``partial_fit`` loop over the same
        partition. Returns False when the super-block path is
        unavailable (opt-out, K == 1, sparse source) — the caller runs
        its per-block loop instead."""
        from ..parallel.streaming import BlockStream, _is_sparse_source

        sparse_src = _is_sparse_source(Xh)
        if classes is not None:
            self._set_classes(np.asarray(classes))
        if isinstance(self, ClassifierMixin) and \
                getattr(self, "classes_", None) is None:
            raise ValueError(
                "classes must be passed on the first call to partial_fit."
            )
        if not sparse_src:
            Xh = np.asarray(Xh)
        y_enc = np.asarray(self._encode_y(np.asarray(yh)))
        stream = BlockStream((Xh, y_enc), block_rows=block_rows,
                             shuffle=shuffle, seed=seed)
        if sparse_src and stream.sparse_plan is None:
            # sparse source without a device-resident staging plan
            # (config.stream_sparse off, over-density fallback): the
            # caller's per-block densify loop stays the path
            return False
        if stream.block_rows != int(block_rows):
            # the stream rounds block_rows to a shard multiple; a caller
            # partition it cannot reproduce must keep its own loop —
            # training different minibatches would be a silent change
            return False
        if not stream.use_superblocks():
            return False
        self._ensure_state(Xh.shape[1])
        for sb in stream.superblocks(order=order):
            self._sb_step(sb)
        self._last_stream_stats = getattr(stream, "stats", None)
        prof = stream.profile_snapshot()
        if prof is not None:
            # accumulate across partial_fit calls: one training profile
            # covers every pass this model ever trained on
            from ..observability.sketch import merge_profiles

            self.training_profile_ = merge_profiles(
                getattr(self, "training_profile_", None), prof
            )
        self._publish(Xh.shape[1])
        return True

    def _stream_fit_checkpoint(self, Xh, y_enc, stream):
        """A fingerprint-keyed pass-granular checkpoint slot for this
        host-streamed fit (reliability/stream_ckpt.py), or None when
        checkpointing is off, refused (multi-process), or the fit is a
        ``warm_start`` continuation (its starting weights are not
        derivable from the hyperparameters, so the identity token
        cannot cover them)."""
        if self.warm_start:
            return None
        from ..reliability.stream_ckpt import stream_checkpoint

        classes = getattr(self, "classes_", None)
        parts = (
            type(self).__name__, self._loss(), self.penalty,
            self.alpha, self.l1_ratio, self.eta0, self.learning_rate,
            self.power_t, self.max_iter, self.tol, self.shuffle,
            self.random_state, self.fit_intercept, self.fit_dtype,
            None if classes is None
            else tuple(np.asarray(classes).tolist()),
            tuple(Xh.shape), int(stream.block_rows),
        )
        return stream_checkpoint("sgd", parts, arrays=(Xh, y_enc))

    def _fit_stream_checkpointed(self, stream, ckpt):
        """The checkpointed flavor of the streamed epoch loop:
        identical minibatches and lr clock to the plain loops (the
        shuffle stream is fast-forwarded by one permutation draw per
        completed pass — np.random's shuffle consumption depends only
        on the array LENGTH, so the resumed pass sequence is
        bit-identical to the uninterrupted fit's), with the weight
        carry + lr clock saved after each pass and the slot cleared on
        completion. Autotune never applies here: a mid-fit partition
        resize would invalidate the checkpoint's identity token."""
        from ..observability._counters import record_stream_checkpoint

        start = 0
        st = ckpt.restore()
        if st is not None:
            self._w = jnp.asarray(np.asarray(st["w"], np.float32))
            self._t = int(st["t"])
            start = int(st["epoch"])
            record_stream_checkpoint(resume=True)
        if self.shuffle:
            burn = np.arange(stream.n_blocks)
            for _ in range(min(start, int(self.max_iter))):
                stream.rng.shuffle(burn)
        use_sb = stream.use_superblocks()
        for e in range(start, int(self.max_iter)):
            if use_sb:
                for sb in stream.superblocks():
                    self._sb_step(sb)
            else:
                for block in stream:
                    if block.n_rows == 0:
                        self._t += 1
                        continue
                    Xb, yb = block.arrays
                    self._one_step(Xb, yb, block.mask, block.n_rows)
            if ckpt.due(e + 1):
                ckpt.save(w=np.asarray(self._w), t=self._t, epoch=e + 1)
        ckpt.clear()

    def _fit_stream_grad_accum(self, stream, A):
        """The gradient-accumulation streamed fit
        (``config.stream_grad_accum`` = A >= 1): each update consumes A
        LOCAL micro-blocks' gradient sums — merged ONCE across
        processes (``psum_host``, f64, fixed gather order) — then one
        shared epilogue applies the update, so every process holds
        identical weights after every step. This is the documented
        optimizer variant that lifts the cross-host streamed-SGD
        refusal: sequential per-block updates cannot psum across
        process-local streams, but accumulated GROUP gradients can.

        Contracts: exact parity with the sequential single-process fit
        at A=1 (the micro kernel normalizes by the group's GLOBAL
        valid-row count inside autodiff — at A=1 single-process that IS
        the sequential step's traced objective; bit-exact vs the
        single-DEVICE sequential flavor, while the sharded sequential
        scan normalizes its raw sums after the psum and so differs at
        float-reassociation level on non-power-of-two block counts);
        at A>1 or P>1 the
        effective batch per update is A x P x block_rows — fewer,
        larger steps per pass (README documents the convergence
        caveat), with the lr clock ticking once per UPDATE. Local
        micro sums accumulate on host in f64 in block order — the same
        additions the cross-process merge performs, so a P-process fit
        at A and a single-process fit at P*A over the round-robin
        block interleave are bit-identical whenever the per-block
        kernels run at matching device partitioning (e.g.
        stream_mesh=1; different mesh widths reassociate the matmul
        partial sums at the usual ~1e-7 relative level). Pass-granular
        checkpointing does not arm here (a multi-process resume must
        be a collective decision)."""
        from ..config import get_config, mxu_dtype
        from ..parallel import distributed as dist

        if get_config().stream_nonfinite == "quarantine":
            # the per-group GLOBAL valid-row counts are exchanged (a
            # collective) BEFORE the blocks are read, so a count folded
            # to zero at read time would leave the group normalizer —
            # and the skip-empty-update contract every other flavor
            # honors — silently wrong. Refuse loudly instead
            raise ValueError(
                "stream_grad_accum does not compose with "
                "stream_nonfinite='quarantine' (group counts are "
                "exchanged before blocks are read); use "
                "stream_nonfinite='raise' or the sequential flavor"
            )
        if get_config().stream_checkpoint_path:
            import warnings

            warnings.warn(
                "stream_checkpoint_path is set but the grad-accum "
                "streamed SGD flavor does not checkpoint (its update "
                "schedule is a collective); the fit runs uncheckpointed",
                RuntimeWarning,
            )
        A = int(A)
        multi = dist.process_count() > 1
        n_blocks = stream.n_blocks
        block_rows = stream.block_rows
        starts = np.arange(n_blocks, dtype=np.int64) * block_rows
        counts = np.minimum(starts + block_rows, stream.n_rows) - starts
        n_groups_local = max(-(-n_blocks // A), 1)
        # every process must join the same NUMBER of group merges per
        # pass (the merge is a collective): pad to the widest local
        # pass; a process past its own blocks contributes zero sums
        n_groups = int(max(dist.allgather_object(n_groups_local))) \
            if multi else n_groups_local
        mxu = mxu_dtype(self.fit_dtype)
        n_out = self._n_out()
        loss_name = self._loss()
        iflag = np.float32(1.0 if self.fit_intercept else 0.0)
        w_shape = tuple(np.shape(self._w))
        # commit the weight carry REPLICATED on the stream's mesh once:
        # the micro kernels then always see compatible devices (a
        # virtual rank's blocks stage on ITS local submesh, not the
        # process default device), and every update's output inherits
        # the placement
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(stream.mesh, P())
        if getattr(self._w, "sharding", None) != rep:
            self._w = jax.device_put(self._w, rep)
        # the sparse grad-accum micro flavor (ISSUE 13): bucketed-nnz
        # per-block staging + nnz-cost value_and_grad. Single-device
        # streams only — the sparse per-block slabs place on the
        # stream's (replicated) mesh, and grad-accum's merge is the
        # host psum anyway; sharded streams keep the densify micro path
        use_sparse = (getattr(stream, "sparse_plan", None) is not None
                      and stream.sb_data_shards() == 1)
        self._sparse_stream = bool(use_sparse)
        for _ in range(int(self.max_iter)):
            order = np.arange(n_blocks)
            if self.shuffle:
                stream.rng.shuffle(order)
            # the per-group GLOBAL valid-row counts, exchanged once per
            # pass: the micro kernels normalize by them inside autodiff
            local_nv = np.zeros(n_groups, np.float64)
            for g in range(n_groups_local):
                local_nv[g] = float(
                    counts[order[g * A:(g + 1) * A]].sum()
                )
            group_nv = np.asarray(dist.psum_host(local_nv)) if multi \
                else local_nv
            for g in range(n_groups):
                gsum, lsum = None, 0.0
                nv = jnp.float32(group_nv[g])
                for b in order[g * A:(g + 1) * A]:
                    if use_sparse:
                        slab, dense, mask_d, _m = \
                            stream.sparse_block_put(int(b))
                        v, gr = _sgd_accum_micro_sparse(
                            self._w, slab.data, slab.cols, slab.rows,
                            dense[0], mask_d, nv, jnp.float32(iflag),
                            loss_name, n_out, slab.n_rows,
                        )
                    else:
                        blk = stream._put(stream._block_host(int(b)))
                        Xb, yb = blk.arrays
                        v, gr = _sgd_accum_micro(
                            self._w, Xb, yb, blk.mask, nv,
                            jnp.float32(iflag), loss_name, n_out,
                            mxu=mxu,
                        )
                    lsum += float(v)
                    g64 = np.asarray(gr, np.float64)
                    gsum = g64 if gsum is None else gsum + g64
                if gsum is None:
                    gsum = np.zeros(w_shape, np.float64)
                if multi:
                    lsum, gsum = dist.psum_host(
                        np.asarray(lsum, np.float64), gsum
                    )
                lr, alpha, l2w, l1w, _ = self._step_args()
                w_old = np.asarray(self._w, np.float64)
                self._w = _sgd_accum_apply(
                    self._w, jnp.asarray(np.asarray(gsum, np.float32)),
                    jnp.float32(lr), jnp.float32(alpha),
                    jnp.float32(l2w), jnp.float32(l1w),
                )
                self._last_loss = float(np.asarray(lsum)) \
                    + 0.5 * alpha * l2w \
                    * float(np.sum(w_old[..., :-1] ** 2))
            # the profile folds the first pass only, like the streams
            stream._passes = getattr(stream, "_passes", 0) + 1

    def _fit_device(self, X: ShardedArray, y, kwargs):
        """Epoch loop over DEVICE-resident blocks: each block is a sharded
        gather (take_rows) of the input — the (n, d) data never
        round-trips through host (VERDICT r2 #4; the reference's
        Incremental chains partial_fit over worker-resident chunks the
        same way, SURVEY.md §3.6)."""
        from ..parallel.sharded import take_rows

        ys = y if isinstance(y, ShardedArray) \
            else ShardedArray.from_array(np.asarray(y), mesh=X.mesh)
        if isinstance(self, ClassifierMixin):
            classes = kwargs.get("classes")
            if classes is not None:
                self._set_classes(np.asarray(classes))
            elif getattr(self, "classes_", None) is None:
                from ..utils.validation import device_classes

                self._set_classes(device_classes(ys))
        y_enc = self._encode_y(ys)
        n = X.n_rows
        # the grid_partition blocks — the SAME minibatches a host-input
        # fit or the Incremental wrapper trains (reproducibility across
        # input residency)
        _, S = fused_blocks(X)
        ranges = [r for r in
                  (np.arange(s, min(s + S, n)) for s in range(0, n, S))
                  if len(r)]
        self._ensure_state(X.shape[1])
        rng = np.random.RandomState(self.random_state)
        order = np.arange(len(ranges))
        for _ in range(self.max_iter):
            if self.shuffle:
                rng.shuffle(order)
            # blocks gather lazily per step (one extra block resident at
            # a time) — materializing all of them would hold a second
            # full copy of X in HBM for the whole fit
            for b in order:
                Xb = take_rows(X, ranges[b])
                yb = take_rows(y_enc, ranges[b])
                self._one_step(Xb.data, yb.data,
                               Xb.row_mask(jnp.float32), Xb.n_rows)
        self._publish(X.shape[1])
        self.n_iter_ = self.max_iter
        return self

    def fit(self, X, y, **kwargs):
        with span("fit", component=type(self).__name__) as sp:
            self._fit(X, y, **kwargs)
            sp.add(n_iter=int(self.n_iter_), t_end=int(self._t))
        return self

    def _fit(self, X, y, **kwargs):
        if not self.warm_start:
            self._w = None
            if getattr(self, "classes_", None) is not None:
                self.classes_ = None  # fresh fit re-derives classes
        if isinstance(X, ShardedArray):
            return self._fit_device(X, y, kwargs)
        from ..parallel import distributed as dist
        from ..parallel.streaming import (BlockStream, _is_sparse_source,
                                          fit_block_rows)

        from ..config import get_config

        grad_accum = int(get_config().stream_grad_accum)
        if dist.process_count() > 1 and grad_accum <= 0:
            # sequential per-block updates are ORDER-dependent — unlike
            # the additive GLM/KMeans/PCA accumulators they cannot psum
            # into a global fit; silently fitting each shard separately
            # would hand every process a different model. The
            # gradient-accumulation flavor (config.stream_grad_accum=A)
            # IS the documented cross-host variant: accumulated GROUP
            # gradients psum exactly
            raise NotImplementedError(
                "host-streamed SGD fit is single-process by default "
                "(sequential updates cannot psum across process-local "
                "streams); set config.stream_grad_accum=A (>= 1) for "
                "the gradient-accumulation flavor — one cross-host "
                "psum per A micro-blocks — or use the streamed GLM "
                "fits / device-resident data on the global mesh"
            )
        # sparse X streams as-is: BlockStream densifies one block at a
        # time (the text-pipeline bridge — a whole-corpus np.asarray
        # would materialize the dense matrix this path exists to avoid)
        Xh = X if _is_sparse_source(X) else np.asarray(X)
        yh = y.to_numpy() if isinstance(y, ShardedArray) else np.asarray(y)
        if isinstance(self, ClassifierMixin):
            classes = kwargs.get("classes")
            if classes is not None:
                self._set_classes(np.asarray(classes))
            elif getattr(self, "classes_", None) is None:
                self._set_classes(np.unique(yh))
        y_enc = np.asarray(self._encode_y(yh))
        stream = BlockStream(
            (Xh, y_enc),
            block_rows=fit_block_rows(Xh),
            shuffle=self.shuffle, seed=self.random_state,
        )
        self._ensure_state(Xh.shape[1])
        # fused/sparse-engagement audit defaults; _sb_step overwrites
        # when the super-block path runs
        self._fused_stream = False
        self._fused_stream_reason = "per-block-path"
        self._sparse_stream = False
        if grad_accum >= 1:
            # gradient-accumulation flavor (cross-host capable): A
            # micro-blocks' sums -> one psum -> one shared update
            self._fused_stream_reason = "grad-accum-xla"
            self._fit_stream_grad_accum(stream, grad_accum)
        elif (ckpt := self._stream_fit_checkpoint(Xh, y_enc,
                                                  stream)) is not None:
            # pass-granular checkpoint/auto-resume (ISSUE 11): same
            # minibatches and lr clock as the plain loops below, plus a
            # carry save after each pass and a clear on completion
            self._fit_stream_checkpointed(stream, ckpt)
        elif stream.use_superblocks():
            # super-block hot loop: one scan dispatch per K blocks with
            # the weight carry donated (same minibatches, same shuffled
            # order, same lr clock as the per-block loop below)
            for sb in stream.superblock_epochs(self.max_iter):
                self._sb_step(sb)
        else:
            for block in stream.epochs(self.max_iter):
                if block.n_rows == 0:
                    # quarantined block (stream_nonfinite): no update,
                    # but the lr clock advances exactly like the
                    # superblock scan's zero-count pass-through slot
                    self._t += 1
                    continue
                Xb, yb = block.arrays
                self._one_step(Xb, yb, block.mask, block.n_rows)
        # last pass's overlap accounting (host/put/wait vs compute) for
        # bench and diagnosis of transfer-bound fits
        self._last_stream_stats = getattr(stream, "stats", None)
        # per-feature training profile (drift.py scores serving traffic
        # against it); a fresh fit replaces any previous profile
        self.training_profile_ = stream.profile_snapshot()
        # the streamed-fit audit record (GLM fits carry the same keys):
        # which flavor ran, why fused was gated off if it was, and the
        # grad-accum width — so smoke suites assert engagement instead
        # of trusting the gate
        sparse_on = bool(getattr(self, "_sparse_stream", False))
        if sparse_on:
            sparse_reason = None
        elif getattr(stream, "sparse_plan", None) is not None:
            sparse_reason = "per-block-path"
        elif getattr(stream, "sparse_reason", None) is not None:
            sparse_reason = stream.sparse_reason
        else:
            sparse_reason = "dense-source"
        self.solver_info_ = {
            "streamed": True,
            "n_blocks": int(stream.n_blocks),
            "stream_shards": int(stream.sb_data_shards())
            if stream.use_superblocks() and grad_accum < 1 else 1,
            "grad_accum": grad_accum if grad_accum >= 1 else 0,
            "fused_stream": bool(getattr(self, "_fused_stream", False)),
            "fused_stream_reason": getattr(
                self, "_fused_stream_reason", None
            ),
            # the device-resident sparse audit trail (ISSUE 13),
            # mirroring fused_stream_reason: None iff the bucketed-nnz
            # programs carried the fit
            "sparse_stream": sparse_on,
            "sparse_stream_reason": sparse_reason,
        }
        self._publish(Xh.shape[1])
        self.n_iter_ = self.max_iter
        return self

    def _decision(self, X, link="identity"):
        """The device half for a resident X, as the GLMs': ``(X as placed,
        the fetched host array of glm.decision under link)`` — the same
        f32 matvec over ``_w`` (the intercept its last entry) and the link
        in one program, one fetch; ``link_finish`` is the host half. The
        weights ride as host numpy, as in ``_eta_stream``: X may be placed
        on another mesh than the one the fit committed ``_w`` to."""
        X = as_sharded(X, dtype=np.float32)
        return X, link_fetch(X, np.asarray(self._w, np.float32), link,
                             getattr(self, "classes_", None))

    def _eta_stream(self, X, block_rows):
        """Decision values for out-of-core / sparse X: blocks stream
        through the fitted weights, (n,) or (n, C) host result — same
        bridge as the GLM predict paths. The weights ride as HOST
        numpy: a cohort-trained ``_w`` may be committed to the full
        ambient mesh while the predict stream stages on its own
        (possibly single-device) stream mesh — an uncommitted operand
        follows the block's placement instead of raising a
        mixed-devices error."""
        from ..parallel.streaming import streamed_map

        W = np.asarray(self._w, np.float32)
        if self._n_out() is not None:
            return streamed_map(
                X, block_rows, lambda blk: _batched_eta(blk.arrays[0], W)
            )
        return streamed_map(
            X, block_rows, lambda blk: blk.arrays[0] @ W[:-1] + W[-1]
        )

    def _encode_y(self, y):
        if isinstance(y, ShardedArray):
            return y
        return np.asarray(y)

    def _publish(self, d):
        pass


class SGDClassifier(ClassifierMixin, _SGDBase):
    """Binary classifier; device analog of sklearn's SGDClassifier for the
    Incremental / adaptive-search streaming paths."""

    loss_default = "log_loss"
    _cohort_score_kind = "accuracy"

    def _batch_key(self):
        if getattr(self, "classes_", None) is None:
            # solo path enforces the first-call classes contract (raises);
            # batching without classes would train on un-encoded labels
            return None
        if self._n_out() is not None:
            return None  # multiclass weights are (C, d+1): solo path
        return super()._batch_key()

    def _set_classes(self, classes):
        if len(classes) < 2:
            raise ValueError("SGDClassifier needs at least 2 classes")
        have = getattr(self, "classes_", None)
        if have is not None and not np.array_equal(classes, have):
            # sklearn contract: classes must be identical across calls —
            # silently re-encoding labels mid-training corrupts the model
            raise ValueError(
                f"classes={classes} is not the same as on last call "
                f"to partial_fit, was: {have}"
            )
        self.classes_ = classes

    def partial_fit(self, X, y, classes=None, **kwargs):
        # sklearn contract: classes required on the first partial_fit call
        # (adaptive searches pass it through fit_params, as with dask-ml)
        if classes is None and getattr(self, "classes_", None) is None:
            raise ValueError(
                "classes must be passed on the first call to partial_fit."
            )
        return super().partial_fit(X, y, classes=classes, **kwargs)

    def _encode_y(self, y):
        if getattr(self, "classes_", None) is None:
            return y if isinstance(y, ShardedArray) else np.asarray(y)
        if self._n_out() is not None:
            # multiclass: labels map to class CODES 0..C-1 (searchsorted
            # over the sorted classes_, in the labels' NATIVE dtype —
            # handles string labels and >2**24 integer ids exactly);
            # the codes ride to the kernel as float32 (C-1 is tiny).
            # sklearn partial_fit contract: a label absent from classes_
            # (e.g. first appearing in a later block) must raise, not
            # silently train as a neighboring code — one host sync per
            # block buys that check.
            if isinstance(y, ShardedArray):
                classes_d = jnp.asarray(
                    np.asarray(self.classes_, np.dtype(str(y.dtype)))
                )
                idx = jnp.searchsorted(classes_d, y.data)
                idx_c = jnp.clip(idx, 0, len(self.classes_) - 1)
                ok = jnp.take(classes_d, idx_c) == y.data
                bad = jnp.any(y.row_mask(jnp.bool_) & ~ok)
                if bool(to_host(bad)):
                    raise ValueError(
                        "y contains classes not passed via `classes` on "
                        "the first partial_fit call"
                    )
                return ShardedArray(
                    idx_c.astype(jnp.float32), y.n_rows, y.mesh,
                )
            yh = np.asarray(y)
            idx = np.clip(np.searchsorted(self.classes_, yh),
                          0, len(self.classes_) - 1)
            if not np.array_equal(np.take(self.classes_, idx), yh):
                raise ValueError(
                    "y contains classes not passed via `classes` on the "
                    "first partial_fit call"
                )
            return idx.astype(np.float32)
        neg, pos = self.classes_[0], self.classes_[1]
        if isinstance(y, ShardedArray):
            is_pos = y.data == jnp.asarray(pos)
            known = is_pos | (y.data == jnp.asarray(neg))
            if bool(to_host(jnp.any(y.row_mask(jnp.bool_) & ~known))):
                raise ValueError(
                    "y contains classes not passed via `classes` on the "
                    "first partial_fit call"
                )
            return ShardedArray(
                is_pos.astype(jnp.float32), y.n_rows, y.mesh,
            )
        yh = np.asarray(y)
        if not np.isin(yh, self.classes_).all():
            raise ValueError(
                "y contains classes not passed via `classes` on the "
                "first partial_fit call"
            )
        return (yh == pos).astype(np.float32)

    def _publish(self, d):
        w = to_host(self._w).astype(np.float64)
        if self._n_out() is not None:
            self.coef_ = w[:, :-1]
            self.intercept_ = w[:, -1]
        else:
            self.coef_ = w[:-1].reshape(1, -1)
            self.intercept_ = np.atleast_1d(w[-1])

    @classmethod
    def _batched_score_default(cls, models, X, y):
        """Accuracy of N models on a shared (device) test split — one
        matmul on the MXU instead of N predict calls."""
        Xs = as_sharded(X, dtype=np.float32)
        ys = as_sharded(models[0]._encode_y(y), mesh=Xs.mesh,
                        dtype=np.float32)
        W = jnp.stack([m._w for m in models])
        acc = _batched_accuracy(
            Xs.data, ys.data, Xs.row_mask(jnp.float32),
            jnp.float32(Xs.n_rows), W,
        )
        return np.asarray(acc, np.float64)

    @classmethod
    def _cohort_holdout_scores(cls, models, holdout, n_slots):
        """Round scoring as ONE batched dispatch over the staged
        validation slab (ISSUE 14): the PADDED slot stack keeps the
        scoring program's shape constant across shrinking brackets —
        same accuracy math as ``_batched_score_default``."""
        W = jnp.asarray(_stack_cohort_weights(models, n_slots))
        N = len(models)
        if holdout["kind"] == "sparse":
            eta = np.asarray(_batched_eta_sparse(
                holdout["data"], holdout["cols"], holdout["rows"], W,
                n_rows=holdout["n"],
            ))[:, :N]
            y01 = holdout["y"]
            acc = ((eta > 0).astype(np.float32)
                   == y01[:, None]).mean(axis=0)
            return np.asarray(acc, np.float64)
        Xs, ys = holdout["X"], holdout["y"]
        acc = _batched_accuracy(
            Xs.data, ys.data, Xs.row_mask(jnp.float32),
            jnp.float32(Xs.n_rows), W,
        )
        return np.asarray(acc, np.float64)[:N]

    def _device_link_applies(self, X):
        """Where the link runs (the GLMs' rule): on the device for a
        resident X and a binary fit; a streamed X and the ``(n, C)``
        one-vs-rest scores keep the host tail and say ``link="host"``."""
        from ..parallel.streaming import stream_plan

        return self._n_out() is None and stream_plan(X) is None

    def decision_function(self, X):
        check_is_fitted(self, "coef_")
        from ..parallel.streaming import stream_plan

        block_rows = stream_plan(X)
        if block_rows is not None:
            return self._eta_stream(X, block_rows)
        if self._n_out() is not None:
            Xs = as_sharded(X, dtype=np.float32)
            eta = _batched_eta(Xs.data, self._w)   # (n, C)
            return to_host(eta)[: Xs.n_rows]
        X, host = self._decision(X)
        return link_finish(host, X.n_rows, "identity")

    def predict(self, X):
        """A root span when called on its own; under ParallelPostFit /
        Incremental it nests in the wrapper's ``predict``. Binary and
        resident: the matvec, ``eta > 0`` and the choice between the two
        class values run in ONE device program (``glm.decision``,
        ``predict.decision`` with its fetch); ``predict.host`` is what the
        labels' dtype leaves — one ``astype`` to ``classes_.dtype``, or
        the lookup of a one-byte index for classes the device cannot
        carry. Streamed or multiclass: the scores come to the host and the
        threshold or argmax and the class lookup over every row are
        ``predict.host``."""
        check_is_fitted(self, "coef_")
        if self._device_link_applies(X):
            return predict_resident(self, X, "label")
        with span("predict", component=type(self).__name__) as root:
            with span("predict.decision", link="host"):
                scores = self.decision_function(X)
            root.add(n_rows=len(scores))
            with span("predict.host"):
                if self._n_out() is not None:
                    return self.classes_[np.argmax(scores, axis=1)]
                return self.classes_[(scores > 0).astype(int)]

    def predict_proba(self, X):
        if self._loss() != "log_loss":
            raise AttributeError("predict_proba requires loss='log_loss'")
        check_is_fitted(self, "coef_")
        if self._device_link_applies(X):
            return predict_resident(self, X, "proba2")
        from scipy.special import expit

        if self._n_out() is not None:
            p = expit(self.decision_function(X))   # OvR sigmoids
            return p / np.maximum(p.sum(axis=1, keepdims=True), 1e-12)
        p1 = expit(self.decision_function(X))
        return np.stack([1 - p1, p1], axis=1)

    def score(self, X, y):
        return accuracy_score(
            y.to_numpy() if isinstance(y, ShardedArray) else np.asarray(y),
            self.predict(X),
        )


class SGDRegressor(RegressorMixin, _SGDBase):
    loss_default = "squared_error"

    def _set_classes(self, classes):  # pragma: no cover - defensive
        raise AttributeError("SGDRegressor has no classes")

    def _batch_prepare(self, fit_params):
        pass

    def _publish(self, d):
        w = to_host(self._w).astype(np.float64)
        self.coef_ = w[:-1]
        self.intercept_ = float(w[-1])

    @classmethod
    def _batched_score_default(cls, models, X, y):
        Xs = as_sharded(X, dtype=np.float32)
        ys = as_sharded(y, mesh=Xs.mesh, dtype=np.float32)
        W = jnp.stack([m._w for m in models])
        r2 = _batched_r2(
            Xs.data, ys.data, Xs.row_mask(jnp.float32),
            jnp.float32(Xs.n_rows), W,
        )
        return np.asarray(r2, np.float64)

    @classmethod
    def _cohort_holdout_scores(cls, models, holdout, n_slots):
        """R^2 twin of the classifier's one-dispatch round scoring —
        padded slot stack, stable program shape across bracket
        shrinks."""
        W = jnp.asarray(_stack_cohort_weights(models, n_slots))
        N = len(models)
        if holdout["kind"] == "sparse":
            eta = np.asarray(_batched_eta_sparse(
                holdout["data"], holdout["cols"], holdout["rows"], W,
                n_rows=holdout["n"],
            ))[:, :N]
            y = np.asarray(holdout["y"], np.float64)
            ss_tot = float(np.sum((y - y.mean()) ** 2))
            ss_res = np.sum((eta - y[:, None]) ** 2, axis=0)
            return 1.0 - ss_res / max(ss_tot, 1e-12)
        Xs, ys = holdout["X"], holdout["y"]
        r2 = _batched_r2(
            Xs.data, ys.data, Xs.row_mask(jnp.float32),
            jnp.float32(Xs.n_rows), W,
        )
        return np.asarray(r2, np.float64)[:N]

    def predict(self, X):
        check_is_fitted(self, "coef_")
        from ..parallel.streaming import stream_plan

        block_rows = stream_plan(X)
        if block_rows is not None:
            return self._eta_stream(X, block_rows)
        X, host = self._decision(X)
        return link_finish(host, X.n_rows, "identity")

    def score(self, X, y):
        return r2_score(
            y.to_numpy() if isinstance(y, ShardedArray) else np.asarray(y),
            self.predict(X),
        )
