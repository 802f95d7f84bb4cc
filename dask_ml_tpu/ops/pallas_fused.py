"""Pallas TPU kernels for the hot ops.

SURVEY.md §2b row 7: the reference's inner-loop math is sklearn's Cython
``pairwise_distances_argmin_min`` called per block; §7 B1 plans a "Pallas
fused distance-argmin". This kernel goes further than fusing distance +
argmin: one pass over X computes the assignment AND accumulates the
centroid sums/counts — the entire data touch of a Lloyd iteration — so X
streams through VMEM exactly once per iteration. The XLA fallback path
reads X twice (distance matmul + segment_sum) and materializes the (n, k)
distance matrix; here only (tile, k) lives on-chip.

Layout notes (pallas_guide.md + Mosaic lowering constraints verified on a
real v5e chip):

- distances via the MXU matmul ``x @ c.T`` with f32 accumulation;
- every intermediate stays RANK-2 — Mosaic's vector layouts cannot
  relayout rank-1 values produced by cross-lane reductions ("Offset
  change" errors), so argmin is an iota-min with ``keepdims=True``,
  center norms arrive precomputed as a (1, k) operand, and the scalar
  inertia sum happens in XLA on the kernel's masked min-distance output;
- accumulator outputs revisit the same block every grid step (constant
  index_map) with @pl.when(first) init — TPU grids are sequential, so
  accumulation is race-free;
- rows are padded to a 128-multiple tile (Mosaic minor-tiling), with the
  mask zeroing padded rows out of every statistic;
- per-row quantities come in two layouts. COLUMN form, ``(tile, 1)``: what
  a lane reduction with ``keepdims=True`` leaves. Cheap to write, but a
  ``(tile, 1)`` f32 value holds one useful number per 128-lane line, and an
  ``(n, 1)`` f32 array in HBM is tiled T(8,128), so it takes 512 B a row —
  128x its data, built and re-read on every call that takes ``y[:, None]``.
  ROW form, ``(r, tile)``: rows of the data ride along LANES, ``y`` arrives
  as a ``(1, n)`` view of the vector (no padding in HBM), eta is the MXU
  contraction ``b(r, d) . x(tile, d)^T`` and the gradient the ordinary
  product ``resid(r, tile) @ x(tile, d)`` (the q.k^T / p.v pair of an
  attention kernel). ``fused_glm_value_grad`` — the resident single-target
  kernel every lbfgs / gradient_descent / proximal_grad fit runs — is in
  row form (``_eta_rows`` / ``_lane_mask`` / ``_glm_row_terms``). Its
  siblings (``fused_glm_value_grad_hess``, ``fused_glm_multi_value_grad``'s
  class codes, the ``*_stream`` and SGD kernels) still carry the column
  contract (``_row_dot`` / ``_tile_mask`` / ``_glm_eta_terms``): no
  benchmark cell runs them, so a conversion could not be measured; the row
  helpers are written for them to move onto (ROADMAP S4).
  ``fused_glm_newton_stats`` (PR 36, ADMM's local step, the cell
  ``logreg_admm_l1``) is column form WITHOUT a label operand: what it sums
  needs eta alone (Σ mean(eta) x, the Gram of ``x sqrt(w)``, the intercept's
  border), the labels' part of the gradient is the caller's, once a solve —
  so no ``(n, 1)`` array exists for it either;
- the intercept is a SCALAR OPERAND, never a column of X: a ``(1, 1)`` f32
  block read as a scalar and added to eta, its gradient a ``(1, 1)``
  accumulator of its own. A 257th column costs a 256-wide bf16 design its
  layout (the TPU compiler stores ``bf16[n, 257]`` column-major, so the
  column is a transpose and a pad where it is appended and a transpose back
  in front of the kernel) and half again its bytes (257 columns pad to 384
  lanes in the kernel's blocks). The streamed and SGD kernels always took
  it so (``b0_ref``); since PR 28 ``fused_glm_value_grad`` does too
  (``intercept=``). ``fused_glm_value_grad_hess`` and
  ``fused_glm_multi_value_grad`` still read it from a column their callers
  append (Newton's bordered Hessian and the one-vs-rest stack index it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pick_tile(n):
    """Row tile for the grid. Mosaic requires output blocks to be
    multiples of the minor tiling (128), so tiles are always
    128-multiples and callers pad n up to a tile multiple."""
    if n <= 1024:
        return -(-n // 128) * 128  # single grid step, ≤127 padded rows
    return 1024 if n % 1024 == 0 else 512


_GLM_TILE_BUDGET = 4 * 1024 * 1024  # x-block bytes kept well under VMEM


def _budget_tile(n, cost):
    """Shrink the row tile until ``cost(tile)`` fits the VMEM budget
    (128-row Mosaic floor); None when nothing fits — the ONE copy of
    the halve-until-budget rule for every GLM kernel gate."""
    tile = _pick_tile(n)
    while tile > 128 and cost(tile) > _GLM_TILE_BUDGET:
        tile //= 2
    tile = max(tile, 128)
    return tile if cost(tile) <= _GLM_TILE_BUDGET else None


def glm_tile(n, d, itemsize):
    """Row tile for the GLM kernel bounded by BOTH n and the x-block's
    VMEM footprint; None when even a 128-row tile of a very wide design
    would blow the budget — callers then keep the XLA loss (its matmuls
    tile the feature dim freely)."""
    return _budget_tile(n, lambda t: t * d * itemsize)


def _assign_update_kernel(x_ref, m_ref, c_ref, c2_ref, labels_ref, mind_ref,
                          sums_ref, counts_ref):
    i = pl.program_id(0)
    x = x_ref[:]                       # (tile, d)
    m = m_ref[:]                       # (tile, 1)
    c = c_ref[:]                       # (k, d)
    c2 = c2_ref[:]                     # (1, k) precomputed ||c||^2
    k = c.shape[0]
    # ||x||^2 - 2 x.c + ||c||^2 ; the matmul rides the MXU, epilogue fuses
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                   # (tile, k)
    d2 = jnp.sum(x * x, axis=1, keepdims=True) - 2.0 * xc + c2
    d2 = jnp.maximum(d2, 0.0)
    mind = jnp.min(d2, axis=1, keepdims=True)          # (tile, 1)
    iota = jax.lax.broadcasted_iota(
        jnp.int32, (x.shape[0], k), 1
    ).astype(jnp.float32)
    # first-occurrence argmin, all rank-2: min over lanes of iota where
    # the distance achieves the row minimum
    labf = jnp.min(jnp.where(d2 <= mind, iota, float(k)), axis=1,
                   keepdims=True)                       # (tile, 1)
    labels_ref[:] = labf.astype(jnp.int32)
    mind_ref[:] = mind * m

    onehot = (iota == labf).astype(jnp.float32) * m     # (tile, k)

    @pl.when(i == 0)
    def _init():
        sums_ref[:] = jnp.zeros_like(sums_ref)
        counts_ref[:] = jnp.zeros_like(counts_ref)

    sums_ref[:] += jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                   # (k, d) MXU accumulation
    counts_ref[:] += jnp.sum(onehot, axis=0, keepdims=True)


def _lloyd_stats_kernel(x_ref, nv_ref, c_ref, c2_ref, sums_ref, counts_ref,
                        inertia_ref, *, tile):
    i = pl.program_id(0)
    x = x_ref[:]                       # (tile, d)
    c = c_ref[:]                       # (k, d)
    c2 = c2_ref[:]                     # (1, k)
    k = c.shape[0]
    # row validity from the GLOBAL row index (valid rows are a prefix of
    # the padded array by construction) — no (n, 1) mask operand, whose
    # T(8,128) layout would pad 128× in HBM
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0) \
        + i * tile
    m = (row_ids < nv_ref[0, 0]).astype(jnp.float32)    # (tile, 1) VMEM
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    d2 = jnp.sum(x * x, axis=1, keepdims=True) - 2.0 * xc + c2
    d2 = jnp.maximum(d2, 0.0)
    mind = jnp.min(d2, axis=1, keepdims=True)
    iota = jax.lax.broadcasted_iota(
        jnp.int32, (x.shape[0], k), 1
    ).astype(jnp.float32)
    labf = jnp.min(jnp.where(d2 <= mind, iota, float(k)), axis=1,
                   keepdims=True)
    onehot = (iota == labf).astype(jnp.float32) * m

    @pl.when(i == 0)
    def _init():
        sums_ref[:] = jnp.zeros_like(sums_ref)
        counts_ref[:] = jnp.zeros_like(counts_ref)
        inertia_ref[:] = jnp.zeros_like(inertia_ref)

    sums_ref[:] += jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    counts_ref[:] += jnp.sum(onehot, axis=0, keepdims=True)
    inertia_ref[:] += jnp.sum(mind * m, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_lloyd_stats(x, n_valid, centers, interpret=False):
    """Lloyd-iteration statistics WITHOUT per-row outputs: returns only
    (sums (k, d), counts (k,), inertia scalar). The full kernel's
    per-row labels/min-d2 outputs are (n, 1) arrays whose TPU tiled
    layout T(8,128) pads them 128× in HBM (~512 B/row) — at 10⁷+ rows
    that alone OOMs the chip, and the Lloyd loop never reads them. Row
    validity rides in as one scalar (valid rows are a prefix of the
    padded block)."""
    n, d = x.shape
    k = centers.shape[0]
    x = x.astype(jnp.float32)
    centers = centers.astype(jnp.float32)
    tile = _pick_tile(n)
    n_pad = -(-n // tile) * tile
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    grid = (n_pad // tile,)
    c2 = jnp.sum(centers * centers, axis=1)[None, :]
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1, 1)
    sums, counts, inertia = pl.pallas_call(
        functools.partial(_lloyd_stats_kernel, tile=tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, nv, centers, c2)
    return sums, counts[0], inertia[0, 0]


def _tile_mask(x, nv_ref, i, tile):
    """Per-tile prefix-validity mask from the global row index vs the
    scalar valid-row count — shared by every COLUMN-form GLM kernel
    (``_lane_mask`` is its row-form twin)."""
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0) \
        + i * tile
    return (row_ids < nv_ref[0, 0]).astype(jnp.float32)  # (tile, 1)


def _row_dot(x, b):
    """(tile, d) x (1, d) -> (tile, 1) matvec, f32 accumulation, with
    ``b`` rounded to x's dtype first (solvers._smooth_loss's contract:
    a bf16 design sees a bf16 beta, so the Pallas and XLA etas agree).

    Written as a VPU multiply + lane reduction, not ``dot_general``:
    jax 0.9's Mosaic lowering special-cases exactly this shape (one rhs
    row) into the same multiply-reduce, and for a non-f32 operand emits
    an ill-typed ``vector.broadcast`` (bf16 source, f32 result) that
    fails verification. v5e has no bf16 VPU, so the f32 upcast is what
    the hardware does either way.

    The result is a COLUMN: one useful value per 128-lane line, and the
    reason its callers take ``y`` as ``(tile, 1)`` blocks of a 128x-padded
    ``(n, 1)`` array. ``fused_glm_value_grad`` no longer calls this (see
    ``_eta_rows``); the Newton, streamed and SGD kernels still do. ``b``
    is the coefficients alone wherever the intercept is a scalar operand
    (streamed, SGD); the resident Newton and multi-target kernels pass a
    ``b`` whose last entry meets a ones column of ``x``, and that
    intercept IS rounded to x's dtype with the rest."""
    bx = b.astype(x.dtype).astype(jnp.float32)
    return jnp.sum(x.astype(jnp.float32) * bx, axis=1, keepdims=True)


def _glm_eta_terms(x, yv, b, family):
    """eta plus the family's pointwise NLL / residual, COLUMN form
    (``_glm_row_terms`` is the row form). Family formulas come from
    models/solvers/families.py — pure jnp ops that lower inside the
    kernel, so the Pallas and XLA losses cannot diverge."""
    eta = _row_dot(x, b)                # (tile, 1)
    from ..models.solvers.families import get_family

    fam = get_family(family)
    per = fam.pointwise(eta, yv)
    resid = fam.mean(eta) - yv
    return fam, eta, per, resid


# rows of the beta / residual operand of the row-form contractions: one
# f32 sublane tile, the rows copies of each other, row 0 read out. Not
# ONE row: that is the matmul shape jax 0.9's Mosaic special-cases and
# mistypes for bf16 (see ``_row_dot``); 1, 8 and 16 rows timed the same
# on the v5e, the contractions hide behind the X tile's DMA.
_ROW_SUBLANES = 8


def _lane_mask(shape, nv_ref, i, tile):
    """Row-form prefix-validity mask: the global row index runs along
    LANES (dim 1 of ``shape``), against the scalar valid-row count."""
    row_ids = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + i * tile
    return (row_ids < nv_ref[0, 0]).astype(jnp.float32)


def _eta_rows(x, b):
    """(r, d) . (tile, d)^T -> (r, tile) eta on the MXU, f32
    accumulation, ``b`` rounded to x's dtype first (``_row_dot``'s
    contract). Products of two bf16 values are exact in f32, so a bf16
    design differs from ``_row_dot`` in summation order only; an f32
    design asks for ``HIGHEST``, or Mosaic's default would multiply in
    bf16 (measured on the v5e: loss off by 3e-5 relative, as a bf16
    design's)."""
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else None)
    return jax.lax.dot_general(
        b.astype(x.dtype), x, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    )


def _glm_row_terms(x, yv, b, nv_ref, i, tile, family, b0=None):
    """Row-form ``_glm_eta_terms``: (family, eta, MASKED pointwise NLL,
    MASKED residual), each ``(r, tile)`` with rows along lanes; ``yv``
    is the ``(1, tile)`` label row, broadcast over the r equal rows.
    ``b0`` is the intercept as an f32 SCALAR added to eta after the
    contraction (never rounded to x's dtype); padding rows then see
    ``eta = b0``, and the mask zeroes their terms as before."""
    from ..models.solvers.families import get_family

    fam = get_family(family)
    eta = _eta_rows(x, b)
    if b0 is not None:
        eta = eta + b0
    m = _lane_mask(eta.shape, nv_ref, i, tile)
    return fam, eta, fam.pointwise(eta, yv) * m, (fam.mean(eta) - yv) * m


def _glm_value_grad_kernel(x_ref, y_ref, nv_ref, b_ref, *refs, tile,
                           family, intercept):
    """One X pass computing Σ pointwise-NLL AND Σ ∂NLL/∂β.

    The XLA path reads X twice per value_and_grad (forward matvec +
    gradient matmul) — at GLM arithmetic intensity the fit is HBM-bound,
    so this halves the data traffic of every solver iteration. ROW form
    (module header): every per-row quantity lives along lanes, validity
    from the global row index vs one scalar, accumulators revisited with
    a constant index_map (sequential TPU grid: race-free).

    With ``intercept`` the refs carry one more operand and one more
    output, as the streamed kernels do (``_glm_stream_kernel``): the
    intercept as a ``(1, 1)`` block read as a scalar, and its gradient
    Σ resid as a ``(1, 1)`` accumulator — X carries no ones column."""
    if intercept:
        b0_ref, *outs = refs
        b0 = b0_ref[0, 0]
    else:
        outs, b0 = refs, None
    loss_ref, grad_ref = outs[:2]
    i = pl.program_id(0)
    x = x_ref[:]                       # (tile, d) — f32 or bf16
    yv = y_ref[:]                      # (1, tile) f32
    b = b_ref[:]                       # (r, d) f32, r equal rows
    _, _, per, resid = _glm_row_terms(x, yv, b, nv_ref, i, tile, family,
                                      b0)

    @pl.when(i == 0)
    def _init():
        for o in outs:
            o[:] = jnp.zeros_like(o)

    loss_ref[:] += jnp.sum(per[0:1, :], axis=1, keepdims=True)
    grad_ref[:] += jax.lax.dot_general(
        resid.astype(x.dtype), x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[0:1, :]                           # (1, d) f32 accumulation
    if intercept:
        outs[2][:] += jnp.sum(resid[0:1, :], axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("family", "interpret"))
def fused_glm_value_grad(x, n_valid, y, beta, family, interpret=False,
                         intercept=None):
    """(Σ pointwise-NLL, Σ ∂/∂β (d,)) of one (per-device) block in ONE
    data pass. ``beta`` is f32 (d,); ``y`` f32 (n,); row validity is the
    scalar prefix count ``n_valid`` (GLM padding is trailing per shard).
    Callers psum the outputs across shards and add the penalty/mean
    scaling in XLA.

    ``intercept``: an f32 scalar added to eta (``eta = x @ beta +
    intercept``); the result is then (Σ NLL, Σ ∂/∂β (d,), Σ ∂/∂intercept
    scalar). ``None`` is a model without one — or a caller whose ``x``
    carries it as a ones column.

    LANE-DENSE: ``y`` reaches the kernel as a ``(1, n)`` view of the
    vector in ``(1, tile)`` blocks, never as ``y[:, None]`` — this
    wrapper is traced inside the solvers' ``while_loop``s, where an
    ``(n, 1)`` operand was a 128x-padded buffer written and re-read on
    every objective evaluation. The Newton / multi-target / streamed
    kernels below still take the ``y`` column (module header)."""
    n, d = x.shape
    y = y.astype(jnp.float32)
    beta = beta.astype(jnp.float32)
    tile = glm_tile(n, d, x.dtype.itemsize)
    if tile is None:
        raise ValueError(
            f"design too wide for the fused GLM kernel VMEM budget "
            f"(d={d}); use the XLA loss (use_pallas=False)"
        )
    n_pad = -(-n // tile) * tile
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        y = jnp.pad(y, (0, n_pad - n))
    grid = (n_pad // tile,)
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1, 1)
    r = _ROW_SUBLANES
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))
    operands = [x, y[None, :], nv, jnp.broadcast_to(beta[None, :], (r, d))]
    in_specs = [
        pl.BlockSpec((tile, d), lambda i: (i, 0)),
        pl.BlockSpec((1, tile), lambda i: (0, i)),
        scalar,
        pl.BlockSpec((r, d), lambda i: (0, 0)),
    ]
    out_specs = [scalar, pl.BlockSpec((1, d), lambda i: (0, 0))]
    out_shape = [jax.ShapeDtypeStruct((1, 1), jnp.float32),
                 jax.ShapeDtypeStruct((1, d), jnp.float32)]
    if intercept is not None:
        operands.append(jnp.asarray(intercept, jnp.float32).reshape(1, 1))
        in_specs.append(scalar)
        out_specs.append(scalar)
        out_shape.append(jax.ShapeDtypeStruct((1, 1), jnp.float32))
    loss, grad, *gb = pl.pallas_call(
        functools.partial(_glm_value_grad_kernel, tile=tile,
                          family=family, intercept=intercept is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    if gb:
        return loss[0, 0], grad[0], gb[0][0, 0]
    return loss[0, 0], grad[0]


def _glm_vgh_kernel(x_ref, y_ref, nv_ref, b_ref, loss_ref, grad_ref,
                    hess_ref, *, tile, family):
    """Newton's whole data touch in one X pass: Σ NLL, Σ ∂/∂β, AND the
    Σ XᵀWX Gauss-Newton Hessian — the XLA path reads X ~3x per
    iteration (forward, gradient, weighted Hessian matmul)."""
    i = pl.program_id(0)
    x = x_ref[:]                       # (tile, d)
    yv = y_ref[:]                      # (tile, 1)
    b = b_ref[:]                       # (1, d)
    m = _tile_mask(x, nv_ref, i, tile)
    fam, eta, per, resid = _glm_eta_terms(x, yv, b, family)
    w = fam.hess_weight(eta, yv) * m                    # (tile, 1)

    @pl.when(i == 0)
    def _init():
        loss_ref[:] = jnp.zeros_like(loss_ref)
        grad_ref[:] = jnp.zeros_like(grad_ref)
        hess_ref[:] = jnp.zeros_like(hess_ref)

    loss_ref[:] += jnp.sum(per * m, axis=0, keepdims=True)
    grad_ref[:] += jax.lax.dot_general(
        (resid * m).astype(x.dtype), x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    xw = x * w.astype(x.dtype)
    hess_ref[:] += jax.lax.dot_general(
        xw, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                   # (d, d)


def glm_newton_tile(n, d, itemsize):
    """Row tile for the Newton kernel: budget covers the x block, the
    weighted copy, and the (d, d) Hessian accumulator."""
    return _budget_tile(n, lambda t: 2 * t * d * itemsize + d * d * 4)


@functools.partial(jax.jit, static_argnames=("family", "interpret"))
def fused_glm_value_grad_hess(x, n_valid, y, beta, family,
                              interpret=False):
    """(Σ NLL, Σ ∂/∂β (d,), Σ XᵀWX (d, d)) of one block in ONE pass —
    the per-shard Newton statistics; callers psum all three."""
    n, d = x.shape
    y = y.astype(jnp.float32)
    beta = beta.astype(jnp.float32)
    tile = glm_newton_tile(n, d, x.dtype.itemsize)
    if tile is None:
        raise ValueError(
            f"design too wide for the fused Newton kernel (d={d}); use "
            "the XLA path (use_pallas=False)"
        )
    n_pad = -(-n // tile) * tile
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        y = jnp.pad(y, (0, n_pad - n))
    grid = (n_pad // tile,)
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1, 1)
    loss, grad, hess = pl.pallas_call(
        functools.partial(_glm_vgh_kernel, tile=tile, family=family),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((d, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((d, d), jnp.float32),
        ],
        interpret=interpret,
    )(x, y[:, None], nv, beta[None, :])
    return loss[0, 0], grad[0], hess


def _glm_newton_stats_kernel(x_ref, nv_ref, b_ref, b0_ref, sp_ref, s1_ref,
                             hess_ref, hb_ref, hbb_ref, *, tile, family):
    """A local Newton step's statistics in ONE X pass, with NO label
    operand: Σ mean(eta) x and Σ mean(eta) (the residual's sums less the
    label's, ``X^T y`` and Σ y, which do not change from step to step and
    are taken once a solve by the caller), the Gram Σ (x sqrt w)^T (x sqrt
    w), and the intercept's border Σ w x, Σ w. COLUMN form: eta is the
    VPU's exact f32 multiply-and-reduce (``_row_dot``), the per-row weights
    scale x's rows as ``(tile, 1)`` columns, and the two vector sums are
    VPU sublane reductions — f32 throughout. The Gram alone goes to the
    MXU, in bf16 (one pass): a curvature estimate, and the Gram of ONE
    matrix, so whatever the rounding it stays symmetric positive
    semi-definite."""
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)   # (tile, d)
    from ..models.solvers.families import get_family

    fam = get_family(family)
    m = _tile_mask(x, nv_ref, i, tile)                  # (tile, 1)
    eta = _row_dot(x, b_ref[:]) + b0_ref[0, 0]
    mu = fam.mean(eta) * m
    w = fam.hess_weight(eta, 0.0) * m

    @pl.when(i == 0)
    def _init():
        for o in (sp_ref, s1_ref, hess_ref, hb_ref, hbb_ref):
            o[:] = jnp.zeros_like(o)

    s1_ref[:] += jnp.sum(x * mu, axis=0, keepdims=True)
    sp_ref[:] += jnp.sum(mu, axis=0, keepdims=True)
    hb_ref[:] += jnp.sum(x * w, axis=0, keepdims=True)
    hbb_ref[:] += jnp.sum(w, axis=0, keepdims=True)
    xw = (x * jnp.sqrt(w)).astype(jnp.bfloat16)
    hess_ref[:] += jax.lax.dot_general(
        xw, xw, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                   # (d, d)


@functools.partial(jax.jit, static_argnames=("family", "interpret"))
def fused_glm_newton_stats(x, n_valid, beta, intercept, family,
                           interpret=False):
    """(Σ mean(eta) x (d,), Σ mean(eta), Σ XᵀWX (d, d), Σ w x (d,), Σ w) of
    one (per-device) block of rows in ONE pass over X, ``eta = x @ beta +
    intercept`` — the statistics of a Newton step with the intercept a
    scalar beside an ``(n, d)`` X (ADMM's local step; ``intercept`` is 0.0
    for a model without one, whose caller drops the border). The gradient's
    data term is ``Σ mean(eta) x - Xᵀy``: the label sums are the caller's,
    once a solve. Row validity is the prefix count ``n_valid``. ``n`` must
    be whole row tiles (``glm_newton_tile``): a padded copy of X is exactly
    what this kernel exists to avoid."""
    n, d = x.shape
    tile = glm_newton_tile(n, d, x.dtype.itemsize)
    if tile is None or n % tile:
        raise ValueError(
            f"the fused Newton-statistics kernel needs whole row tiles that "
            f"fit its VMEM budget (n={n}, d={d}, tile={tile}); use the "
            "blocked XLA statistics"
        )
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))
    row = pl.BlockSpec((1, d), lambda i: (0, 0))
    one = jax.ShapeDtypeStruct((1, 1), jnp.float32)
    vec = jax.ShapeDtypeStruct((1, d), jnp.float32)
    sp, s1, hess, hb, hbb = pl.pallas_call(
        functools.partial(_glm_newton_stats_kernel, tile=tile,
                          family=family),
        grid=(n // tile,),
        in_specs=[pl.BlockSpec((tile, d), lambda i: (i, 0)), scalar, row,
                  scalar],
        out_specs=[scalar, row, pl.BlockSpec((d, d), lambda i: (0, 0)), row,
                   scalar],
        out_shape=[one, vec, jax.ShapeDtypeStruct((d, d), jnp.float32), vec,
                   one],
        interpret=interpret,
    )(x, jnp.asarray(n_valid, jnp.int32).reshape(1, 1),
      beta.astype(jnp.float32)[None, :],
      jnp.asarray(intercept, jnp.float32).reshape(1, 1))
    return s1[0], sp[0, 0], hess, hb[0], hbb[0, 0]


def _glm_multi_value_grad_kernel(x_ref, yc_ref, nv_ref, b_ref, loss_ref,
                                 grad_ref, *, tile, family):
    """Multi-target twin of ``_glm_value_grad_kernel``: ONE X pass
    serves all C one-vs-rest problems. ``yc_ref`` holds class codes;
    per-class 0/1 targets derive in-kernel from an iota compare, eta is
    one (tile, C) MXU matmul against the stacked B, and the (C, d)
    gradient accumulates with a second MXU contraction."""
    i = pl.program_id(0)
    x = x_ref[:]                       # (tile, d)
    yc = yc_ref[:]                     # (tile, 1) f32 codes
    B = b_ref[:]                       # (C, d) f32
    C = B.shape[0]
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0) \
        + i * tile
    m = (row_ids < nv_ref[0, 0]).astype(jnp.float32)    # (tile, 1)
    eta = jax.lax.dot_general(
        x, B.astype(x.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                   # (tile, C)
    iota = jax.lax.broadcasted_iota(
        jnp.int32, (x.shape[0], C), 1
    ).astype(jnp.float32)
    yv = (iota == yc).astype(jnp.float32)               # (tile, C)
    from ..models.solvers.families import get_family

    fam = get_family(family)
    per = fam.pointwise(eta, yv) * m
    resid = (fam.mean(eta) - yv) * m

    @pl.when(i == 0)
    def _init():
        loss_ref[:] = jnp.zeros_like(loss_ref)
        grad_ref[:] = jnp.zeros_like(grad_ref)

    loss_ref[:] += jnp.sum(per, axis=0, keepdims=True).sum(
        axis=1, keepdims=True
    )
    grad_ref[:] += jax.lax.dot_general(
        resid.astype(x.dtype), x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                   # (C, d)


def glm_multi_tile(n, d, n_classes, itemsize):
    """Row tile for the multi-target kernel bounded by the combined
    VMEM footprint of the x block, the (tile, C) intermediates, and the
    two (C, d) operands; None when no 128-row tile fits."""
    return _budget_tile(n, lambda t: (
        t * d * itemsize + t * n_classes * 4 * 3 + 2 * n_classes * d * 4
    ))


@functools.partial(jax.jit, static_argnames=("family", "interpret"))
def fused_glm_multi_value_grad(x, n_valid, y_codes, B, family,
                               interpret=False):
    """(Σ pointwise-NLL over classes+rows, Σ ∂/∂B (C, d)) of one block
    in ONE data pass — the reference analog would be C separate
    dask-glm objective evaluations. ``y_codes`` holds class indices
    0..C-1 (f32); callers psum both outputs across shards."""
    n, d = x.shape
    C = B.shape[0]
    y_codes = y_codes.astype(jnp.float32)
    B = B.astype(jnp.float32)
    tile = glm_multi_tile(n, d, C, x.dtype.itemsize)
    if tile is None:
        raise ValueError(
            f"design too wide for the fused multi-target GLM kernel "
            f"(d={d}, C={C}); use the stacked XLA path"
        )
    n_pad = -(-n // tile) * tile
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        y_codes = jnp.pad(y_codes, (0, n_pad - n), constant_values=-1.0)
    grid = (n_pad // tile,)
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1, 1)
    loss, grad = pl.pallas_call(
        functools.partial(_glm_multi_value_grad_kernel, tile=tile,
                          family=family),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((C, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((C, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((C, d), jnp.float32),
        ],
        interpret=interpret,
    )(x, y_codes[:, None], nv, B)
    return loss[0, 0], grad


# ---------------------------------------------------------------------------
# streamed super-block kernels (ISSUE 8 tentpole): the per-block bodies
# the donated-carry super-block scans call INSTEAD of their XLA flavors
# when `config.pallas_stream` is on, the backend is a real TPU, and the
# block shape fits the grid/VMEM rules below. Each kernel is ONE VMEM
# pass over its block — objective AND gradient (AND Hessian) from a
# single X read, where the XLA flavors read X two to three times
# (forward matvec + autodiff backward + weighted Hessian matmul). Row
# validity is the streamed block's prefix count (SuperBlock.counts),
# exactly the scalar the resident kernels already take. ``mxu`` casts
# the matmul operands to bf16 in VMEM (f32 accumulation — the
# config.dtype="auto" TPU path); everything else stays f32.
# ---------------------------------------------------------------------------


def stream_tile(S, cost):
    """Largest 128-multiple tile that DIVIDES the streamed block height
    and fits the VMEM budget; None when the height isn't a 128-multiple
    or nothing fits. Streamed kernels cannot pad: a pad inside the
    consumer's scan would copy the block in HBM on every step, which is
    exactly the traffic the fusion removes — callers fall back to the
    XLA flavor instead (``use_stream_kernels`` gates on this)."""
    if S <= 0 or S % 128:
        return None
    for t in (1024, 512, 256, 128):
        if S % t == 0 and cost(t) <= _GLM_TILE_BUDGET:
            return t
    return None


def sgd_stream_tile(S, d, itemsize=4):
    return stream_tile(S, lambda t: t * d * itemsize)


def glm_stream_tile(S, d, kind, itemsize=4):
    """Tile for the streamed GLM ``kind`` reducer; the vgh budget also
    covers the weighted copy and the (d, d) Hessian accumulator."""
    if kind == "vgh":
        return stream_tile(
            S, lambda t: 2 * t * d * itemsize + d * d * 4
        )
    return stream_tile(S, lambda t: t * d * itemsize)


def kmeans_stream_tile(S, d, k, itemsize=4):
    return stream_tile(
        S, lambda t: t * d * itemsize + t * k * 4 + 2 * k * d * 4
    )


def glm_multi_stream_tile(S, d, n_classes, itemsize=4):
    """Tile for the streamed multi-target GLM reducers: the x block,
    the three (tile, C) intermediates (eta / targets / residual), and
    the two (C, d) weight/gradient operands."""
    return stream_tile(S, lambda t: (
        t * d * itemsize + t * n_classes * 4 * 3 + 2 * n_classes * d * 4
    ))


def sgd_many_stream_tile(S, d, n_models, itemsize=4):
    """Tile for the multi-weight streamed SGD kernel (multiclass OvR
    rows, a batched-trial cohort, or a search cohort's slot stack —
    the streamed cohort scans gate at the FULL padded slot count, so a
    tile that fits the top rung fits every narrower one): same
    footprint shape as the multi-target GLM reducer."""
    return glm_multi_stream_tile(S, d, n_models, itemsize)


def stream_kernel_mode(backend=None):
    """(use, interpret) for the fused streamed kernel family: opted in
    (config.pallas_stream, default on) AND a real TPU backend —
    compiled Mosaic kernels, interpret False. Off-TPU the fused bodies
    only run when ``config.pallas_stream_interpret`` additionally opts
    into the Pallas interpreter (CI parity / dry-run benches);
    otherwise the XLA flavors run unchanged — with the knobs off their
    jaxprs are byte-identical to the pre-feature programs."""
    from ..config import get_config

    cfg = get_config()
    if not cfg.pallas_stream:
        return False, False
    if backend is None:
        backend = jax.default_backend()
    if backend == "tpu":
        return True, False
    return (True, True) if cfg.pallas_stream_interpret else (False, False)


def use_stream_kernels(backend=None):
    """The auto-gate for the fused streamed kernel family — see
    :func:`stream_kernel_mode` (this keeps the historical bool shape
    for callers that don't care about interpret mode)."""
    return stream_kernel_mode(backend)[0]


# the fused-flavor audit vocabulary lives HERE and only here — the GLM
# and SGD flavor selectors both record these strings in
# solver_info_["fused_stream_reason"], and tpu_smoke/README compare
# them literally, so a renamed reason must change in exactly one place

def stream_mode_reason():
    """Why the fused streamed kernels are off for this process (knob or
    backend), or None when :func:`stream_kernel_mode` says go."""
    from ..config import get_config

    if not get_config().pallas_stream:
        return "pallas-stream-off"
    return None if stream_kernel_mode()[0] else "off-TPU"


def stream_tile_reason(S_local, tile):
    """Why a tile gate refused the per-shard slab of ``S_local`` rows
    (None when ``tile`` was accepted)."""
    if tile is not None:
        return None
    return "non-128-mult shard rows" if S_local % 128 else "vmem-budget"


def _mxu_cast(a, mxu):
    return a if mxu is None else a.astype(mxu)


def sgd_objective_terms(eta, yv, loss):
    """(pointwise loss, dloss/deta) for the SGD losses — the ONE
    definition shared by the fused step kernel and any epilogue, so the
    Pallas and autodiff (models/sgd.py::_sgd_pointwise) objectives
    cannot diverge. ``eta``/``yv`` rank-2."""
    if loss == "log_loss":
        per = jax.nn.softplus(eta) - yv * eta
        resid = jax.nn.sigmoid(eta) - yv
    elif loss == "hinge":
        sign = 2.0 * yv - 1.0
        margins = sign * eta
        per = jnp.maximum(0.0, 1.0 - margins)
        resid = -sign * (margins < 1.0).astype(jnp.float32)
    elif loss == "squared_error":
        diff = eta - yv
        per = 0.5 * diff * diff
        resid = diff
    else:  # pragma: no cover - validated upstream
        raise ValueError(f"unknown SGD loss {loss!r}")
    return per, resid


def _sgd_grad_kernel(x_ref, y_ref, nv_ref, w_ref, b0_ref, loss_ref,
                     gw_ref, gb_ref, *, tile, loss, mxu):
    """Σ pointwise-loss, Σ ∂/∂coef, Σ ∂/∂intercept of one streamed
    block in ONE X pass (the XLA step reads X twice: forward matvec +
    autodiff backward). Same layout rules as every kernel here: rank-2
    throughout, prefix-count validity, constant-index accumulators on
    the sequential TPU grid."""
    i = pl.program_id(0)
    x = x_ref[:]                        # (tile, d) f32
    yv = y_ref[:]                       # (tile, 1) f32
    w = w_ref[:]                        # (1, d) f32 coef row
    m = _tile_mask(x, nv_ref, i, tile)
    xd = _mxu_cast(x, mxu)
    # the (1, 1) intercept*iflag operand is read as a SCALAR: a (1, 1)
    # vector added to a (tile, 1) one is a lane broadcast Mosaic does
    # not implement
    eta = _row_dot(xd, w) + b0_ref[0, 0]    # (tile, 1)
    per, resid = sgd_objective_terms(eta, yv, loss)
    rm = resid * m

    @pl.when(i == 0)
    def _init():
        loss_ref[:] = jnp.zeros_like(loss_ref)
        gw_ref[:] = jnp.zeros_like(gw_ref)
        gb_ref[:] = jnp.zeros_like(gb_ref)

    loss_ref[:] += jnp.sum(per * m, axis=0, keepdims=True)
    gw_ref[:] += jax.lax.dot_general(
        rm.astype(xd.dtype), xd, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                   # (1, d)
    gb_ref[:] += jnp.sum(rm, axis=0, keepdims=True)


def fused_sgd_block_grad(x, n_valid, y, w_ext, iflag, loss,
                         mxu=None, interpret=False):
    """(Σ pointwise-loss, Σ ∂/∂w (d+1,)) of one streamed block in ONE
    X pass. ``w_ext`` is the (d+1,) weight vector (last entry the
    intercept); ``iflag`` zeroes the intercept's contribution exactly
    like the XLA step. Raw sums — the caller divides by n_valid and
    adds the l2/prox terms (models/sgd.py's epilogue). Traced inside
    the consumer's scan: shapes must already satisfy
    ``sgd_stream_tile`` (no padding here, by design)."""
    S, d = x.shape
    tile = sgd_stream_tile(S, d, x.dtype.itemsize)
    grid = (S // tile,)
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1, 1)
    b0 = (w_ext[-1] * iflag).astype(jnp.float32).reshape(1, 1)
    loss_sum, gw, gb = pl.pallas_call(
        functools.partial(_sgd_grad_kernel, tile=tile, loss=loss,
                          mxu=mxu),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, y[:, None], nv, w_ext[None, :-1], b0)
    grad = jnp.concatenate([gw[0], gb[0]])
    return loss_sum[0, 0], grad


def _glm_stream_kernel(x_ref, y_ref, nv_ref, b_ref, b0_ref, *outs,
                       tile, family, kind, mxu):
    """Streamed-GLM reducer body: ``kind`` picks which sums accumulate
    (val: loss; vg: + gradient; vgh: + Gauss-Newton Hessian pieces).
    The intercept rides as the (1, 1) ``b0`` operand and its gradient/
    Hessian border accumulate as separate outputs — the caller
    assembles the bordered (d+1, d+1) form in XLA, identical to
    ``_block_val_grad_hess``'s ``jnp.block``."""
    i = pl.program_id(0)
    x = x_ref[:]                        # (tile, d)
    yv = y_ref[:]                       # (tile, 1)
    b = b_ref[:]                        # (1, d)
    m = _tile_mask(x, nv_ref, i, tile)
    xd = _mxu_cast(x, mxu)
    eta = _row_dot(xd, b) + b0_ref[0, 0]    # scalar read: see _sgd_grad_kernel
    from ..models.solvers.families import get_family

    fam = get_family(family)
    per = fam.pointwise(eta, yv)

    @pl.when(i == 0)
    def _init():
        for o in outs:
            o[:] = jnp.zeros_like(o)

    loss_ref = outs[0]
    loss_ref[:] += jnp.sum(per * m, axis=0, keepdims=True)
    if kind == "val":
        return
    resid = (fam.mean(eta) - yv) * m
    grad_ref, gb_ref = outs[1], outs[2]
    grad_ref[:] += jax.lax.dot_general(
        resid.astype(xd.dtype), xd, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                   # (1, d)
    gb_ref[:] += jnp.sum(resid, axis=0, keepdims=True)
    if kind == "vg":
        return
    hess_ref, col_ref, wsum_ref = outs[3], outs[4], outs[5]
    w = fam.hess_weight(eta, yv) * m
    xw = xd * w.astype(xd.dtype)
    hess_ref[:] += jax.lax.dot_general(
        xw, xd, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                   # (d, d)
    col_ref[:] += jnp.sum(xw.astype(jnp.float32), axis=0, keepdims=True)
    wsum_ref[:] += jnp.sum(w, axis=0, keepdims=True)


def fused_glm_stream(kind, x, n_valid, y, beta, family, intercept,
                     mxu=None, interpret=False):
    """One streamed block's ``kind`` sums in ONE X pass, matching the
    XLA block kernels in models/solvers/streamed.py:

    - "val":  Σ pointwise-NLL (scalar)
    - "vg":   (Σ NLL, Σ ∂/∂beta) — beta is (d+1,) when ``intercept``
    - "vgh":  (Σ NLL, Σ ∂/∂beta, Σ bordered Gauss-Newton Hessian)

    Raw sums over valid rows (prefix count ``n_valid``); the streamed
    objective's epilogue adds mean scaling and penalties exactly as for
    the XLA flavors."""
    S, d_ext = x.shape[0], x.shape[1]
    beta = beta.astype(jnp.float32)
    tile = glm_stream_tile(S, d_ext, kind, x.dtype.itemsize)
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1, 1)
    if intercept:
        b, b0 = beta[None, :-1], beta[-1].reshape(1, 1)
    else:
        b, b0 = beta[None, :], jnp.zeros((1, 1), jnp.float32)
    d = b.shape[1]
    out_specs = [pl.BlockSpec((1, 1), lambda i: (0, 0))]
    out_shape = [jax.ShapeDtypeStruct((1, 1), jnp.float32)]
    if kind != "val":
        out_specs += [pl.BlockSpec((1, d), lambda i: (0, 0)),
                      pl.BlockSpec((1, 1), lambda i: (0, 0))]
        out_shape += [jax.ShapeDtypeStruct((1, d), jnp.float32),
                      jax.ShapeDtypeStruct((1, 1), jnp.float32)]
    if kind == "vgh":
        out_specs += [pl.BlockSpec((d, d), lambda i: (0, 0)),
                      pl.BlockSpec((1, d), lambda i: (0, 0)),
                      pl.BlockSpec((1, 1), lambda i: (0, 0))]
        out_shape += [jax.ShapeDtypeStruct((d, d), jnp.float32),
                      jax.ShapeDtypeStruct((1, d), jnp.float32),
                      jax.ShapeDtypeStruct((1, 1), jnp.float32)]
    outs = pl.pallas_call(
        functools.partial(_glm_stream_kernel, tile=tile, family=family,
                          kind=kind, mxu=mxu),
        grid=(S // tile,),
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x, y[:, None], nv, b, b0)
    loss = outs[0][0, 0]
    if kind == "val":
        return (loss,)
    grad = outs[1][0]
    if intercept:
        grad = jnp.concatenate([grad, outs[2][0]])
    if kind == "vg":
        return loss, grad
    hess, col, wsum = outs[3], outs[4][0], outs[5]
    if intercept:
        hess = jnp.block([
            [hess, col[:, None]],
            [col[None, :], wsum],
        ])
    return loss, grad, hess


def _glm_multi_stream_kernel(x_ref, yc_ref, nv_ref, b_ref, b0_ref, *outs,
                             tile, family, kind, mxu):
    """Streamed multi-target GLM reducer body: ONE X pass serves all C
    one-vs-rest problems of a streamed block. Class codes ride in as a
    (tile, 1) operand and per-class 0/1 targets derive in-kernel from an
    iota compare (the streamed twin of ``_glm_multi_value_grad_kernel``,
    plus the streamed contracts: prefix-count validity, intercept as the
    (1, C) ``b0`` operand with its gradient a separate output, no
    padding)."""
    i = pl.program_id(0)
    x = x_ref[:]                        # (tile, d)
    yc = yc_ref[:]                      # (tile, 1) f32 class codes
    B = b_ref[:]                        # (C, d) f32
    b0 = b0_ref[:]                      # (1, C)
    C = B.shape[0]
    m = _tile_mask(x, nv_ref, i, tile)
    xd = _mxu_cast(x, mxu)
    eta = jax.lax.dot_general(
        xd, B.astype(xd.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + b0                              # (tile, C)
    iota = jax.lax.broadcasted_iota(
        jnp.int32, (x.shape[0], C), 1
    ).astype(jnp.float32)
    yv = (iota == yc).astype(jnp.float32)
    from ..models.solvers.families import get_family

    fam = get_family(family)
    per = fam.pointwise(eta, yv) * m

    @pl.when(i == 0)
    def _init():
        for o in outs:
            o[:] = jnp.zeros_like(o)

    outs[0][:] += jnp.sum(per, axis=0, keepdims=True).sum(
        axis=1, keepdims=True
    )
    if kind == "val":
        return
    resid = (fam.mean(eta) - yv) * m
    grad_ref, gb_ref = outs[1], outs[2]
    grad_ref[:] += jax.lax.dot_general(
        resid.astype(xd.dtype), xd, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                   # (C, d)
    gb_ref[:] += jnp.sum(resid, axis=0, keepdims=True)   # (1, C)


def fused_glm_multi_stream(kind, x, n_valid, y_codes, B, family,
                           intercept, mxu=None, interpret=False):
    """One streamed block's multi-target ``kind`` sums in ONE X pass —
    the fused flavor of ``_block_val_multi`` / ``_block_val_grad_multi``
    (kinds "val" and "vg"; the per-class Hessian stack stays XLA). ``B``
    is (C, d+1) when ``intercept`` (last column the intercepts); raw
    sums over valid rows, shapes must satisfy
    ``glm_multi_stream_tile``."""
    S = x.shape[0]
    d_full = x.shape[1]
    B = B.astype(jnp.float32)
    C = B.shape[0]
    tile = glm_multi_stream_tile(S, d_full, C, x.dtype.itemsize)
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1, 1)
    if intercept:
        Bm, b0 = B[:, :-1], B[:, -1][None, :]
    else:
        Bm, b0 = B, jnp.zeros((1, C), jnp.float32)
    d = Bm.shape[1]
    out_specs = [pl.BlockSpec((1, 1), lambda i: (0, 0))]
    out_shape = [jax.ShapeDtypeStruct((1, 1), jnp.float32)]
    if kind != "val":
        out_specs += [pl.BlockSpec((C, d), lambda i: (0, 0)),
                      pl.BlockSpec((1, C), lambda i: (0, 0))]
        out_shape += [jax.ShapeDtypeStruct((C, d), jnp.float32),
                      jax.ShapeDtypeStruct((1, C), jnp.float32)]
    outs = pl.pallas_call(
        functools.partial(_glm_multi_stream_kernel, tile=tile,
                          family=family, kind=kind, mxu=mxu),
        grid=(S // tile,),
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((C, d), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x, y_codes[:, None], nv, Bm, b0)
    loss = outs[0][0, 0]
    if kind == "val":
        return (loss,)
    grad = outs[1]
    if intercept:
        grad = jnp.concatenate([grad, outs[2].T], axis=1)
    return loss, grad


def _sgd_many_grad_kernel(x_ref, y_ref, nv_ref, w_ref, b0_ref, loss_ref,
                          gw_ref, gb_ref, *, tile, loss, mxu, codes):
    """Multi-weight twin of ``_sgd_grad_kernel``: ONE X pass serves N
    weight rows — the C one-vs-rest rows of a multiclass model
    (``codes=True``: y holds class indices, per-class 0/1 targets derive
    in-kernel) or the N models of a batched-trial cohort (``codes=False``:
    the (tile, 1) target broadcasts across the weight columns). eta is
    one (tile, N) MXU matmul against the stacked coef rows; the (N, d)
    gradient accumulates with a second MXU contraction."""
    i = pl.program_id(0)
    x = x_ref[:]                        # (tile, d)
    yv = y_ref[:]                       # (tile, 1) targets or codes
    W = w_ref[:]                        # (N, d) coef rows
    N = W.shape[0]
    m = _tile_mask(x, nv_ref, i, tile)
    xd = _mxu_cast(x, mxu)
    if N == 1:
        # a cohort rung of ONE slot is the single-row matvec shape:
        # same two Mosaic corners as _sgd_grad_kernel (see _row_dot)
        eta = _row_dot(xd, W) + b0_ref[0, 0]
    else:
        eta = jax.lax.dot_general(
            xd, W.astype(xd.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + b0_ref[:]                   # (tile, N) + (1, N) intercepts
    if codes:
        iota = jax.lax.broadcasted_iota(
            jnp.int32, (x.shape[0], N), 1
        ).astype(jnp.float32)
        yv = (iota == yv).astype(jnp.float32)
    per, resid = sgd_objective_terms(eta, yv, loss)
    rm = resid * m

    @pl.when(i == 0)
    def _init():
        loss_ref[:] = jnp.zeros_like(loss_ref)
        gw_ref[:] = jnp.zeros_like(gw_ref)
        gb_ref[:] = jnp.zeros_like(gb_ref)

    loss_ref[:] += jnp.sum(per * m, axis=0, keepdims=True)   # (1, N)
    gw_ref[:] += jax.lax.dot_general(
        rm.astype(xd.dtype), xd, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                   # (N, d)
    gb_ref[:] += jnp.sum(rm, axis=0, keepdims=True)          # (1, N)


def fused_sgd_many_block_grad(x, n_valid, y, W_ext, iflags, loss,
                              codes, mxu=None, interpret=False):
    """(Σ pointwise-loss per row (N,), Σ ∂/∂W (N, d+1)) of one streamed
    block in ONE X pass for N stacked weight vectors — the fused flavor
    of the multiclass streamed SGD step (``codes=True``; ``iflags`` a
    scalar) and of the cohort scan's vmapped step (``codes=False``;
    ``iflags`` (N,) per-model). Raw sums — the caller divides by
    n_valid and applies each row's lr/l2/prox epilogue."""
    S, d = x.shape
    N = W_ext.shape[0]
    tile = sgd_many_stream_tile(S, d, N, x.dtype.itemsize)
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1, 1)
    b0 = (W_ext[:, -1] * iflags).astype(jnp.float32)[None, :]
    loss_sums, gw, gb = pl.pallas_call(
        functools.partial(_sgd_many_grad_kernel, tile=tile, loss=loss,
                          mxu=mxu, codes=codes),
        grid=(S // tile,),
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((N, d), lambda i: (0, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, N), lambda i: (0, 0)),
            pl.BlockSpec((N, d), lambda i: (0, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, N), jnp.float32),
            jax.ShapeDtypeStruct((N, d), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        interpret=interpret,
    )(x, y[:, None], nv, W_ext[:, :-1], b0)
    grads = jnp.concatenate([gw, gb.T], axis=1)   # (N, d+1)
    return loss_sums[0], grads


def _kmeans_stream_kernel(x_ref, nv_ref, c_ref, c2_ref, sums_ref,
                          counts_ref, inertia_ref, *, tile, mxu):
    """``_lloyd_stats_kernel`` with the streamed blocks' bf16 policy:
    only the cross-term matmul runs at ``mxu`` (f32 accumulation), the
    norms/statistics stay f32 — mirroring
    ``euclidean_distances_sq(mxu_dtype=...)`` on the XLA flavor."""
    i = pl.program_id(0)
    x = x_ref[:]                        # (tile, d)
    c = c_ref[:]                        # (k, d)
    c2 = c2_ref[:]                      # (1, k)
    k = c.shape[0]
    m = _tile_mask(x, nv_ref, i, tile)
    xd, cd = _mxu_cast(x, mxu), _mxu_cast(c, mxu)
    xc = jax.lax.dot_general(
        xd, cd, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    d2 = jnp.sum(x * x, axis=1, keepdims=True) - 2.0 * xc + c2
    d2 = jnp.maximum(d2, 0.0)
    mind = jnp.min(d2, axis=1, keepdims=True)
    iota = jax.lax.broadcasted_iota(
        jnp.int32, (x.shape[0], k), 1
    ).astype(jnp.float32)
    labf = jnp.min(jnp.where(d2 <= mind, iota, float(k)), axis=1,
                   keepdims=True)
    onehot = (iota == labf).astype(jnp.float32) * m

    @pl.when(i == 0)
    def _init():
        sums_ref[:] = jnp.zeros_like(sums_ref)
        counts_ref[:] = jnp.zeros_like(counts_ref)
        inertia_ref[:] = jnp.zeros_like(inertia_ref)

    sums_ref[:] += jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    counts_ref[:] += jnp.sum(onehot, axis=0, keepdims=True)
    inertia_ref[:] += jnp.sum(mind * m, axis=0, keepdims=True)


def fused_kmeans_block_stats(x, n_valid, centers, mxu=None,
                             interpret=False):
    """(Σ x per label (k, d), count per label (k,), Σ min-d² scalar) of
    one streamed block in ONE X pass — the fused flavor of
    ``models/kmeans.py::_block_assign_stats`` (whose XLA form reads X
    twice: distance matmul + segment_sum) with the same prefix-count
    validity as the resident ``fused_lloyd_stats``. No padding: shapes
    must satisfy ``kmeans_stream_tile``."""
    S, d = x.shape
    k = centers.shape[0]
    centers = centers.astype(jnp.float32)
    tile = kmeans_stream_tile(S, d, k, x.dtype.itemsize)
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1, 1)
    c2 = jnp.sum(centers * centers, axis=1)[None, :]
    sums, counts, inertia = pl.pallas_call(
        functools.partial(_kmeans_stream_kernel, tile=tile, mxu=mxu),
        grid=(S // tile,),
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, nv, centers, c2)
    return sums, counts[0], inertia[0, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_assign_update(x, mask, centers, interpret=False):
    """One Lloyd-iteration data pass over a (per-device) block.

    x: (n, d), mask: (n,) row validity, centers: (k, d).
    Returns (labels (n,) int32, min_d2 (n,), sums (k, d), counts (k,),
    inertia scalar) — caller psums the last three across shards.
    """
    n, d = x.shape
    k = centers.shape[0]
    x = x.astype(jnp.float32)
    mask = mask.astype(jnp.float32)
    centers = centers.astype(jnp.float32)
    tile = _pick_tile(n)
    n_pad = -(-n // tile) * tile
    if n_pad != n:
        # masked rows contribute nothing; labels/mind sliced back below
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        mask = jnp.pad(mask, (0, n_pad - n))
    grid = (n_pad // tile,)
    c2 = jnp.sum(centers * centers, axis=1)[None, :]    # (1, k) in XLA
    labels, mind, sums, counts = pl.pallas_call(
        _assign_update_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
        ],
        interpret=interpret,
    )(x, mask[:, None], centers, c2)
    mind = mind[:n, 0]
    inertia = jnp.sum(mind)  # XLA fuses this with the kernel output
    return labels[:n, 0], mind, sums, counts[0], inertia
