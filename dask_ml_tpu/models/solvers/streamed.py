"""Out-of-core GLM solvers: gradient/loss/Hessian accumulation over
streamed host blocks.

Reference equivalent: dask's chunk scheduling under ``dask_glm`` — the
optimizer lives on the client and every objective evaluation is a lazy
graph over host-backed chunks (``dask_glm/algorithms.py``, SURVEY.md §3.2
"host-resident optimizer, cluster-resident data"). TPU design (SURVEY.md
§7 B0 / design stance #1): the dataset stays in host RAM or an
``np.memmap``; fixed-shape blocks stream through ``BlockStream``
(prefetched ``device_put``) into per-block jitted kernels that return
partial (loss, gradient[, Hessian]) sums; a small host-side optimizer
(d-vector state) consumes the accumulated totals. One objective
evaluation = one full pass over the data — line searches pay extra
passes, exactly as the reference pays extra cluster round-trips, so the
pass budget per solver is explicit below.

Passes per outer iteration:

- ``lbfgs`` (two-loop recursion): 1 + line-search trials (Armijo)
- ``gradient_descent``: 1 + trials
- ``proximal_grad``: 1 + trials
- ``newton``: 1 (grad+Hessian fused in one pass) + step-halving trials
- ``admm``: exactly 1 (block-local prox solves; the one-pass-friendly
  choice SURVEY.md §7 recommends at >HBM scale)
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ...observability import track_program
from ...plans import tracked as plan_tracked
from . import regularizers
from .families import get_family


# ---------------------------------------------------------------------------
# per-block jitted kernels. A consumed block's HBM is released when the
# stream iterator drops its reference, so peak device footprint stays
# ≈ (prefetch + 1) blocks.
# ---------------------------------------------------------------------------

@track_program("glm.stream.block_vg")
@partial(jax.jit, static_argnames=("family", "intercept"))
def _block_val_grad(beta, X, y, mask, family, intercept):
    """(Σ pointwise-NLL, Σ ∂NLL/∂β) over one block's valid rows."""

    def f(b):
        bd = b.astype(X.dtype)
        eta = (X @ bd[:-1] + bd[-1]) if intercept else X @ bd
        return jnp.sum(get_family(family).pointwise(eta, y) * mask)

    return jax.value_and_grad(f)(beta)


@track_program("glm.stream.block_val")
@partial(jax.jit, static_argnames=("family", "intercept"))
def _block_val(beta, X, y, mask, family, intercept):
    """Forward-only Σ pointwise-NLL — line-search/step-halving trials that
    only need the value skip the backward pass entirely."""
    bd = beta.astype(X.dtype)
    eta = (X @ bd[:-1] + bd[-1]) if intercept else X @ bd
    return jnp.sum(get_family(family).pointwise(eta, y) * mask)


@track_program("glm.stream.block_vgh")
@partial(jax.jit, static_argnames=("family", "intercept"))
def _block_val_grad_hess(beta, X, y, mask, family, intercept):
    """One fused pass: (Σ NLL, Σ grad, Σ Xᵀ W X) for Newton."""
    fam = get_family(family)
    bd = beta.astype(X.dtype)
    eta = (X @ bd[:-1] + bd[-1]) if intercept else X @ bd

    def f(b):
        bb = b.astype(X.dtype)
        e = (X @ bb[:-1] + bb[-1]) if intercept else X @ bb
        return jnp.sum(fam.pointwise(e, y) * mask)

    val, grad = jax.value_and_grad(f)(beta)
    w = fam.hess_weight(eta, y) * mask
    Xw = X * w[:, None]
    hess = jnp.einsum("ni,nj->ij", Xw, X, preferred_element_type=jnp.float32)
    if intercept:
        col = jnp.sum(Xw, axis=0)
        hess = jnp.block([
            [hess, col[:, None]],
            [col[None, :], jnp.sum(w)[None, None]],
        ])
    return val, grad, hess


@partial(jax.jit, static_argnames=("reg",))
def _finish_vg(val_sum, grad_sum, beta, n_rows, lam, pmask, l1_ratio, reg):
    """mean NLL + smooth penalty, and its gradient, from block sums."""
    pen, pen_g = jax.value_and_grad(
        lambda b: regularizers.value(reg, b, lam, pmask, l1_ratio)
    )(beta)
    return val_sum / n_rows + pen, grad_sum / n_rows + pen_g


# -- multiclass (one-vs-rest) block kernels ---------------------------------
# One data pass is SHARED across all C classes: the block's one-hot
# targets are built on device from class codes and the per-class math is
# vmapped, so X streams through HBM once per epoch regardless of C
# (VERDICT r3 missing #2 — the reference has one fit path for all label
# sets; dask_ml/linear_model/glm.py::LogisticRegression).

def onehot_targets(y, mask, classes_d):
    """(C, n) one-vs-rest targets; padding rows zeroed. The ONE place
    the target-encoding invariant lives — the in-core fit (glm.py's
    jitted wrapper) and every multiclass block kernel build targets
    here."""
    return (y[None, :] == classes_d[:, None]).astype(jnp.float32) \
        * mask[None, :]


def _codes_onehot(y, mask, n_classes):
    return onehot_targets(y, mask, jnp.arange(n_classes, dtype=y.dtype))


@track_program("glm.stream.block_vg_multi")
@partial(jax.jit, static_argnames=("family", "intercept", "n_classes"))
def _block_val_grad_multi(Beta, X, y, mask, family, intercept, n_classes):
    """(Σ_total NLL over classes+rows, ∂/∂Beta (C, d)) for one block.
    ``y`` holds class CODES 0..C-1."""
    Y = _codes_onehot(y, mask, n_classes)

    def f(B):
        Bd = B.astype(X.dtype)
        eta = (X @ Bd[:, :-1].T + Bd[:, -1]) if intercept else X @ Bd.T
        per_class = jax.vmap(
            lambda e, yc: jnp.sum(get_family(family).pointwise(e, yc) * mask),
            in_axes=(1, 0),
        )(eta, Y)
        return jnp.sum(per_class)

    return jax.value_and_grad(f)(Beta)


@track_program("glm.stream.block_val_multi")
@partial(jax.jit, static_argnames=("family", "intercept", "n_classes"))
def _block_val_multi(Beta, X, y, mask, family, intercept, n_classes):
    Y = _codes_onehot(y, mask, n_classes)
    Bd = Beta.astype(X.dtype)
    eta = (X @ Bd[:, :-1].T + Bd[:, -1]) if intercept else X @ Bd.T
    per_class = jax.vmap(
        lambda e, yc: jnp.sum(get_family(family).pointwise(e, yc) * mask),
        in_axes=(1, 0),
    )(eta, Y)
    return jnp.sum(per_class)


@track_program("glm.stream.block_vgh_multi")
@partial(jax.jit, static_argnames=("family", "intercept", "n_classes"))
def _block_val_grad_hess_multi(Beta, X, y, mask, family, intercept,
                               n_classes):
    """One fused pass: (Σ NLL, grad (C, d), per-class Hessians (C, d, d))."""
    Y = _codes_onehot(y, mask, n_classes)
    val, grad = _block_val_grad_multi.__wrapped__(
        Beta, X, y, mask, family, intercept, n_classes
    )
    fam = get_family(family)

    def one_class(beta_c, y_c):
        bd = beta_c.astype(X.dtype)
        eta = (X @ bd[:-1] + bd[-1]) if intercept else X @ bd
        w = fam.hess_weight(eta, y_c) * mask
        Xw = X * w[:, None]
        h = jnp.einsum("ni,nj->ij", Xw, X,
                       preferred_element_type=jnp.float32)
        if intercept:
            col = jnp.sum(Xw, axis=0)
            h = jnp.block([
                [h, col[:, None]],
                [col[None, :], jnp.sum(w)[None, None]],
            ])
        return h
    hess = jax.vmap(one_class)(Beta, Y)
    return val, grad, hess


def _admm_local_body(X, y, mask, b, u, z, rho, n_rows, local_iter, family,
                     intercept):
    """ADMM block-local Newton steps toward prox target v = z - u.

    Identical math to the in-memory shard-local solve
    (``solvers.py::_admm_run::local_newton``) with the mesh shard replaced
    by the streamed block."""
    fam = get_family(family)
    v = z - u

    def local_newton(_, b):
        bd = b.astype(X.dtype)
        eta = (X @ bd[:-1] + bd[-1]) if intercept else X @ bd
        resid = jax.grad(lambda e: jnp.sum(fam.pointwise(e, y) * mask))(eta)
        if intercept:
            g = jnp.concatenate([X.T @ resid, jnp.sum(resid)[None]]) / n_rows \
                + rho * (b - v)
        else:
            g = X.T @ resid / n_rows + rho * (b - v)
        w = fam.hess_weight(eta, y) * mask
        Xw = X * w[:, None]
        h = jnp.einsum("ni,nj->ij", Xw, X,
                       preferred_element_type=jnp.float32) / n_rows
        if intercept:
            col = jnp.sum(Xw, axis=0) / n_rows
            h = jnp.block([
                [h, col[:, None]],
                [col[None, :], (jnp.sum(w) / n_rows)[None, None]],
            ])
        h = h + rho * jnp.eye(b.shape[0], dtype=b.dtype)
        return b - jnp.linalg.solve(h, g)

    return jax.lax.fori_loop(0, local_iter, local_newton, b)


_block_admm_local = track_program("glm.stream.admm_local")(
    partial(jax.jit, static_argnames=(
        "local_iter", "family", "intercept",
    ))(_admm_local_body)
)


@track_program("glm.stream.admm_local_multi")
@partial(jax.jit, static_argnames=("family", "intercept", "local_iter",
                                   "n_classes"))
def _block_admm_local_multi(X, y, mask, B, U, Z, rho, n_rows, local_iter,
                            family, intercept, n_classes):
    """Per-class block-local ADMM Newton, vmapped: one block read serves
    all C consensus problems. B/U/Z are (C, d); y holds class codes."""
    Y = _codes_onehot(y, mask, n_classes)
    return jax.vmap(
        lambda yc, b, u, z: _admm_local_body(
            X, yc, mask, b, u, z, rho, n_rows, local_iter, family, intercept
        )
    )(Y, B, U, Z)


# ---------------------------------------------------------------------------
# super-block scan kernels (ISSUE 3 tentpole): K stacked blocks consumed
# by ONE jitted lax.scan whose accumulator carry is DONATED — one XLA
# dispatch per K blocks, the accumulator buffers reused in place across
# every dispatch of the pass, and no host round-trip inside the scan.
# Per-step masks derive from the super-block's valid-row counts, so an
# all-padding slot (the ragged final super-block) contributes exactly
# zero to every sum — block-order accumulation is identical to the
# per-block loop's.
# ---------------------------------------------------------------------------

import functools as _ft


def _reducer_blocks(kind, n_classes):
    """(per-block kernel, extra static args) for one objective flavor —
    shared by the single-device scan and the sharded shard_map scan so
    the two flavors can never diverge on the per-block math."""
    if n_classes:
        fn = {"val": _block_val_multi, "vg": _block_val_grad_multi,
              "vgh": _block_val_grad_hess_multi}[kind].__wrapped__
        return fn, (n_classes,)
    fn = {"val": _block_val, "vg": _block_val_grad,
          "vgh": _block_val_grad_hess}[kind].__wrapped__
    return fn, ()


def _sb_reducer_sharded(kind, family, intercept, n_classes, mesh,
                        mxu=None, fused=False, interpret=False):
    """Data-parallel super-block reducer (ISSUE 9): the same K-step
    accumulation as :func:`_sb_reducer`, run under ``shard_map`` over
    the stream mesh's "data" axis. Each device scans ONLY its own row
    slab of every block (masks derive from the per-shard valid-row
    counts — ragged tails pad per shard with zero counts), the carry is
    REPLICATED (in/out spec P()), and the dispatch pays exactly ONE
    ``lax.psum`` over "data": the local K-block delta merges once, then
    adds to the running replicated carry. Donation at the jit level
    keeps the carry advancing in place exactly like the single-device
    flavor.

    ``fused=True`` (ISSUE 12 tentpole) swaps the per-block body for the
    fused Pallas kernel running INSIDE the shard_map: each device's
    kernel sees its OWN (S/D, d) slab (tile selection reasons about the
    per-shard slab height, not the global block), produces local raw
    sums from ONE VMEM pass, and the existing single psum per
    super-block merges them — the per-chip kernel speed of the fused
    flavor composed with the data mesh.

    Both flavors run with ``check_vma=False``: the XLA body autodiffs
    the REPLICATED ``beta`` and then psums the local sums itself; under
    ``check_vma=True`` jax would already have psummed that gradient
    (the transpose of the implicit ``pvary``) and the explicit psum
    would make it D times too large."""
    from jax.sharding import PartitionSpec as P

    from ...parallel.mesh import DATA_AXIS, data_shard_spec as spec_of

    if fused:
        from ...ops.pallas_fused import (fused_glm_multi_stream,
                                         fused_glm_stream)

        if n_classes:
            def block_sums(beta, Xb, yb, c):
                return fused_glm_multi_stream(
                    kind, Xb, c, yb, beta, family, intercept,
                    mxu=mxu, interpret=interpret,
                )
        else:
            def block_sums(beta, Xb, yb, c):
                return fused_glm_stream(
                    kind, Xb, c, yb, beta, family, intercept,
                    mxu=mxu, interpret=interpret,
                )
    else:
        fn, extra = _reducer_blocks(kind, n_classes)

    def body(acc, beta, Xs, ys, counts):
        # LOCAL view: Xs (K, S/D, d), counts (1, K) — this shard's own
        # valid-row counts
        r = jnp.arange(Xs.shape[1])
        cts = counts[0]
        local = jax.tree.map(jnp.zeros_like, acc)

        def step(lacc, Xb, yb, c):
            if fused:
                out = block_sums(beta, Xb, yb, c)
            else:
                mask = (r < c).astype(Xb.dtype)
                out = fn(beta, Xb, yb, mask, family, intercept, *extra)
                out = out if isinstance(out, tuple) else (out,)
            return tuple(l + o for l, o in zip(lacc, out))

        def scan_step(lacc, inp):
            return step(lacc, *inp), jnp.float32(0.0)

        local, _ = jax.lax.scan(scan_step, local, (Xs, ys, cts))
        # the super-block's ONE collective: local sums -> replicated
        # global sums, folded into the replicated running carry
        local = jax.lax.psum(local, DATA_AXIS)
        return tuple(a + l for a, l in zip(acc, local))

    @partial(jax.jit, donate_argnums=(0,))
    def run(acc, beta, Xs, ys, counts):
        xs_spec = spec_of(Xs, 1)
        ys_spec = spec_of(ys, 1)
        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), xs_spec, ys_spec, P(DATA_AXIS, None)),
            out_specs=P(),
            check_vma=False,
        )
        return f(acc, beta, Xs, ys, counts)

    from ...parallel.mesh import mesh_str

    suffix = "_multi" if n_classes else ""
    name = (f"pallas.glm_{kind}{suffix}.psum" if fused
            else f"superblock.glm.{kind}{suffix}.psum")
    return plan_tracked(name, run, mesh=mesh_str(mesh))


def _sb_reducer_feature_sharded(kind, family, intercept, n_classes,
                                mesh, model_shards):
    """Feature-sharded super-block reducer (ISSUE 18 tentpole): the 2-D
    ("data", "model") flavor of :func:`_sb_reducer_sharded`. Each device
    scans its OWN (K, S/D, d/M) tile of every block — per-chip HBM for
    the streamed X slabs is flat in d — and the replicated (d,)-sized
    carries/operands (beta in, loss/grad[/Hessian] sums out) are the
    only full-width device arrays, so the interface to ``_sb_pass`` /
    ``_merge`` / the host solvers is unchanged (L-BFGS S/Y memory lives
    in host RAM as before — per-chip HBM never sees it).

    Collective structure: the dispatch keeps exactly ONE ``lax.psum``
    over "data" per super-block (the K-step local sums merge once, as
    in the 1-D flavor) and adds "model" collectives exactly where the
    math contracts over features — a per-block psum for
    ``eta = Σ_m X_m @ w_m`` (the feature-dot), and one per-super-block
    ``all_gather`` reassembling the per-feature gradient (and Hessian
    row-tile) slices. The trivial M == 1 case never reaches here:
    ``_sb_pass`` only selects this flavor when the stream actually
    tiled (``sb_model_shards() > 1``), so the 1-D programs stay
    jaxpr-byte-identical."""
    from jax.sharding import PartitionSpec as P

    from ...parallel.mesh import DATA_AXIS, MODEL_AXIS

    fam = get_family(family)

    def _x_spec(a, lead):
        # X tiles: rows over "data", features (last axis) over "model"
        return P(*((None,) * lead + (DATA_AXIS,)
                   + (None,) * (a.ndim - lead - 2) + (MODEL_AXIS,)))

    def _y_spec(a, lead):
        return P(*((None,) * lead + (DATA_AXIS,)
                   + (None,) * (a.ndim - lead - 1)))

    def _w_local(bd, dm):
        # this shard's (dm,)/(C, dm) feature slice of the replicated
        # weights (intercept column already stripped by the caller)
        mi = jax.lax.axis_index(MODEL_AXIS)
        if bd.ndim == 1:
            return jax.lax.dynamic_slice(bd, (mi * dm,), (dm,))
        return jax.lax.dynamic_slice(
            bd, (0, mi * dm), (bd.shape[0], dm)
        )

    def _gather_feat(t, axis):
        # per-feature slices -> the full-width array, replicated over
        # "model": scatter this shard's tile into a zero full-width
        # buffer and psum (adding zeros — exact)
        mi = jax.lax.axis_index(MODEL_AXIS)
        dm = t.shape[axis]
        full = t.shape[:axis] + (dm * model_shards,) + t.shape[axis + 1:]
        start = (0,) * axis + (mi * dm,) + (0,) * (t.ndim - axis - 1)
        z = jax.lax.dynamic_update_slice(
            jnp.zeros(full, t.dtype), t, start
        )
        return jax.lax.psum(z, MODEL_AXIS)

    def block_sums(beta, Xb, yb, mask):
        """Local (val, grad-slice[, hess-tile]) sums for ONE block's
        (S/D, d/M) tile. ``eta`` pays the per-block feature psum; val
        and the intercept pieces come out model-REPLICATED, the
        per-feature pieces model-VARYING (gathered once per
        super-block, after the data psum)."""
        dm = Xb.shape[-1]
        bd = beta.astype(Xb.dtype)
        if n_classes:
            B = bd[:, :-1] if intercept else bd
            B_loc = _w_local(B, dm)
            eta = jax.lax.psum(Xb @ B_loc.T, MODEL_AXIS)  # (S/D, C)
            if intercept:
                eta = eta + bd[:, -1]
            Y = _codes_onehot(yb, mask, n_classes)

            def per_eta(e):
                per_class = jax.vmap(
                    lambda ec, yc: jnp.sum(fam.pointwise(ec, yc) * mask),
                    in_axes=(1, 0),
                )(e, Y)
                return jnp.sum(per_class)

            val, r = jax.value_and_grad(per_eta)(eta)
            g_loc = r.T @ Xb  # (C, d/M) — this shard's grad slice
            if kind == "val":
                return (val,)
            if kind == "vg":
                out = (val, g_loc)
                if intercept:
                    out += (jnp.sum(r, axis=0),)  # (C,), replicated
                return out
            # multiclass vgh: the (C, p, p) Hessian stack needs the
            # full-width rows — gather the block's tile (transient,
            # one block at a time) and reuse the 1-D per-class math;
            # every model shard computes the identical stack, so it
            # rides the data psum replicated
            Xf = _gather_feat(Xb, axis=1)  # (S/D, d)
            W = jax.vmap(lambda e, yc: fam.hess_weight(e, yc) * mask,
                         in_axes=(1, 0))(eta, Y)  # (C, S/D)
            XW = Xf[None, :, :] * W[:, :, None]
            H = jnp.einsum("cni,nj->cij", XW, Xf,
                           preferred_element_type=jnp.float32)
            if intercept:
                col = jnp.sum(XW, axis=1)  # (C, d)
                wsum = jnp.sum(W, axis=1)  # (C,)
                H = jnp.concatenate([
                    jnp.concatenate([H, col[:, :, None]], axis=2),
                    jnp.concatenate(
                        [col[:, None, :], wsum[:, None, None]], axis=2
                    ),
                ], axis=1)
            g_full = _gather_feat(g_loc, axis=1)
            if intercept:
                g_full = jnp.concatenate(
                    [g_full, jnp.sum(r, axis=0)[:, None]], axis=1
                )
            return (val, g_full, H)
        w = bd[:-1] if intercept else bd
        w_loc = _w_local(w, dm)
        eta = jax.lax.psum(Xb @ w_loc, MODEL_AXIS)  # the feature-dot
        if intercept:
            eta = eta + bd[-1]
        val, r = jax.value_and_grad(
            lambda e: jnp.sum(fam.pointwise(e, yb) * mask)
        )(eta)
        if kind == "val":
            return (val,)
        g_loc = Xb.T @ r  # (d/M,) — this shard's grad slice
        if kind == "vg":
            out = (val, g_loc)
            if intercept:
                out += (jnp.sum(r),)
            return out
        # vgh: Hessian row-tile H_m = (X_m W)^T X — (d/M, d); the full
        # rows come from a transient per-block gather (the Hessian is
        # inherently (d, d); the streamed wide-d path is lbfgs/vg)
        wgt = fam.hess_weight(eta, yb) * mask
        Xw = Xb * wgt[:, None]
        Xf = _gather_feat(Xb, axis=1)  # (S/D, d)
        H_loc = jnp.einsum("ni,nj->ij", Xw, Xf,
                           preferred_element_type=jnp.float32)
        out = (val, g_loc, H_loc)
        if intercept:
            out += (jnp.sum(r), jnp.sum(Xw, axis=0), jnp.sum(wgt))
        return out

    def _assemble(parts):
        """Replicated full-width sums from the data-psummed local
        tuple: gather the per-feature slices over "model" (their ONE
        per-super-block collective), rebuild the 1-D reducer's
        (val[, grad[, hess]]) carry layout."""
        if kind == "val" or (n_classes and kind == "vgh"):
            return parts  # already full-width / assembled per block
        if n_classes:  # multiclass vg
            if intercept:
                val, g_loc, g_b = parts
                g = jnp.concatenate(
                    [_gather_feat(g_loc, axis=1), g_b[:, None]], axis=1
                )
            else:
                val, g_loc = parts
                g = _gather_feat(g_loc, axis=1)
            return (val, g)
        if kind == "vg":
            if intercept:
                val, g_loc, g_b = parts
                g = jnp.concatenate([_gather_feat(g_loc, axis=0),
                                     g_b[None]])
            else:
                val, g_loc = parts
                g = _gather_feat(g_loc, axis=0)
            return (val, g)
        # binary vgh: grad slices + Hessian row-tiles -> full (p,) /
        # (p, p), intercept row/col appended exactly like the 1-D
        # kernel's jnp.block assembly
        val, g_loc, H_loc = parts[:3]
        g = _gather_feat(g_loc, axis=0)
        H = _gather_feat(H_loc, axis=0)  # (d, d)
        if intercept:
            g_b, col_loc, wsum = parts[3:]
            g = jnp.concatenate([g, g_b[None]])
            col = _gather_feat(col_loc, axis=0)
            H = jnp.block([
                [H, col[:, None]],
                [col[None, :], wsum[None, None]],
            ])
        return (val, g, H)

    def body(acc, beta, Xs, ys, counts):
        r = jnp.arange(Xs.shape[1])
        cts = counts[0]
        p = acc[1].shape[-1] if len(acc) > 1 else 0

        def zeros_local():
            # local accumulators mirror block_sums' output layout
            # (per-feature slices stay sliced until after the data
            # psum), not the replicated carry's
            dm = Xs.shape[-1]

            def z(*s):
                return jnp.zeros(s, jnp.float32)

            if kind == "val":
                return (z(),)
            if n_classes:
                if kind == "vg":
                    out = (z(), z(n_classes, dm))
                    return out + ((z(n_classes),) if intercept else ())
                return (z(), z(n_classes, p), z(n_classes, p, p))
            if kind == "vg":
                out = (z(), z(dm))
                return out + ((z(),) if intercept else ())
            d_full = dm * model_shards
            out = (z(), z(dm), z(dm, d_full))
            return out + ((z(), z(dm), z()) if intercept else ())

        def step(lacc, Xb, yb, c):
            mask = (r < c).astype(Xb.dtype)
            out = block_sums(beta, Xb, yb, mask)
            return tuple(l + o for l, o in zip(lacc, out))

        local = zeros_local()

        def scan_step(lacc, inp):
            return step(lacc, *inp), jnp.float32(0.0)

        local, _ = jax.lax.scan(scan_step, local, (Xs, ys, cts))
        # the super-block's ONE data collective, as in the 1-D flavor
        local = jax.lax.psum(local, DATA_AXIS)
        # ... then the per-super-block feature reassembly
        full = _assemble(local)
        return tuple(a + f for a, f in zip(acc, full))

    @partial(jax.jit, donate_argnums=(0,))
    def run(acc, beta, Xs, ys, counts):
        xs_spec = _x_spec(Xs, 1)
        ys_spec = _y_spec(ys, 1)
        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), xs_spec, ys_spec, P(DATA_AXIS, None)),
            out_specs=P(),
            check_vma=False,
        )
        return f(acc, beta, Xs, ys, counts)

    from ...parallel.mesh import mesh_str

    suffix = "_multi" if n_classes else ""
    return plan_tracked(f"superblock.glm.{kind}{suffix}.model_psum",
                        run, mesh=mesh_str(mesh))


@_ft.lru_cache(maxsize=64)
def _sb_reducer(kind, family, intercept, n_classes, mxu=None,
                fused=False, interpret=False, mesh=None,
                model_shards=1):
    """The donated-carry super-block program for one objective flavor:
    ``kind`` in {"val", "vg", "vgh"} lifts the matching per-block kernel
    into a scan over the (K, S, ...) stacks, accumulating its sum tuple.
    Cached per flavor so every pass reuses ONE jitted callable (a fresh
    jax.jit per pass would retrace).

    ``mesh`` (a >1-shard stream mesh, ISSUE 9) selects the shard_map
    data-parallel flavor — replicated carry, per-shard blocks, one
    psum per super-block; its counts operand is the (D, K) per-shard
    matrix, not the global (K,) vector. With ``mesh=None`` (and the
    other knobs at default) this function is byte-for-byte the
    pre-mesh program.

    ``fused=True`` (see ``StreamedObjective._sb_flavor``'s gate) swaps
    the per-block body for the Pallas ``fused_glm_stream`` /
    ``fused_glm_multi_stream`` kernel: ONE VMEM pass per block for
    loss+grad(+Hessian) where the XLA body reads X two to three times,
    with ``mxu`` running the matmuls at bf16/f32-acc
    (config.dtype="auto" on TPU). ``fused`` composes with ``mesh``
    (ISSUE 12): the fused body then runs inside the shard_map program
    on each device's own slab. With ``fused=False`` and ``mxu`` unset
    this function is byte-for-byte the pre-feature program.

    ``model_shards`` > 1 (ISSUE 18: the stream's X tiles actually
    sharded over a 2-D mesh's "model" axis) selects the
    feature-sharded flavor — per-device (K, S/D, d/M) tiles, the
    feature-contracting psums over "model", program names
    ``superblock.glm.*.model_psum``. Callers leave it at the default
    whenever the stream didn't tile, so the M == 1 cache keys (and the
    1-D jaxprs) are untouched."""
    if mesh is not None and model_shards > 1:
        return _sb_reducer_feature_sharded(
            kind, family, intercept, n_classes, mesh, model_shards
        )
    if mesh is not None:
        return _sb_reducer_sharded(kind, family, intercept, n_classes,
                                   mesh, mxu=mxu, fused=fused,
                                   interpret=interpret)
    if fused:
        from ...ops.pallas_fused import (fused_glm_multi_stream,
                                         fused_glm_stream)

        if n_classes:
            def block_sums(beta, Xb, yb, c):
                return fused_glm_multi_stream(
                    kind, Xb, c, yb, beta, family, intercept,
                    mxu=mxu, interpret=interpret,
                )
        else:
            def block_sums(beta, Xb, yb, c):
                return fused_glm_stream(
                    kind, Xb, c, yb, beta, family, intercept,
                    mxu=mxu, interpret=interpret,
                )

        @partial(jax.jit, donate_argnums=(0,))
        def run_fused(acc, beta, Xs, ys, counts):
            def step(acc, Xb, yb, c):
                out = block_sums(beta, Xb, yb, c)
                return tuple(a + o for a, o in zip(acc, out))

            def scan_step(acc, inp):
                return step(acc, *inp), jnp.float32(0.0)

            acc, _ = jax.lax.scan(scan_step, acc, (Xs, ys, counts))
            return acc

        suffix = "_multi" if n_classes else ""
        return plan_tracked(f"pallas.glm_{kind}{suffix}", run_fused)
    fn, extra = _reducer_blocks(kind, n_classes)

    @partial(jax.jit, donate_argnums=(0,))
    def run(acc, beta, Xs, ys, counts):
        # the shape every super-block reducer repeats (the flavors in
        # this file, models/kmeans.py, models/streamed_svd.py): prefix
        # masks from one arange over the block rows, a scan_step that
        # drops lax.scan's per-step output, ONE scan over the
        # (K, S, ...) stacks and their (K,) valid-row counts
        r = jnp.arange(Xs.shape[1])

        def step(acc, Xb, yb, c):
            mask = (r < c).astype(Xb.dtype)
            out = fn(beta, Xb, yb, mask, family, intercept, *extra)
            out = out if isinstance(out, tuple) else (out,)
            return tuple(a + o for a, o in zip(acc, out))

        def scan_step(acc, inp):
            return step(acc, *inp), jnp.float32(0.0)

        acc, _ = jax.lax.scan(scan_step, acc, (Xs, ys, counts))
        return acc

    suffix = "_multi" if n_classes else ""
    return plan_tracked(f"superblock.glm.{kind}{suffix}", run)


# -- device-resident sparse reducers (ISSUE 13 tentpole) --------------------
# The bucketed-nnz flavor of the super-block scan: blocks arrive as
# fixed-shape COO triples (data/cols/rows, padding entries zero-valued)
# and the objective's matvec/gradient run at nnz-proportional cost via
# take + segment_sum (ops/sparse_kernels.py) — XLA's own cost analysis
# then attributes nnz FLOPs to the `superblock.sparse.*` programs, not
# n*d. The Newton Hessian (intrinsically O(d^2) math) scatters its
# block dense ON DEVICE and reuses the exact dense per-block kernel, so
# sparse-vs-dense Newton parity is float-roundoff only. Masks stay
# row-based (the same prefix-count contract as the dense scan).

def _sparse_reducer_sums(kind, family, intercept, n_classes, n_rows,
                         n_features):
    """Per-block sum tuple ``f(beta, data, cols, rows, yb, c)`` for one
    sparse objective flavor — shared by the single-device scan and the
    shard_map flavor (``n_rows`` is the LOCAL slab height there)."""
    from ...ops.sparse_kernels import (sparse_densify, sparse_eta,
                                       sparse_eta_multi)

    S = int(n_rows)

    if kind == "vgh":
        fn, extra = _reducer_blocks("vgh", n_classes)

        def sums(beta, data, cols, rows, yb, c):
            mask = (jnp.arange(S) < c).astype(jnp.float32)
            Xd = sparse_densify(data, cols, rows, S, int(n_features))
            return fn(beta, Xd, yb, mask, family, intercept, *extra)

        return sums

    if n_classes:
        def data_val(B, data, cols, rows, yb, mask):
            W = B[:, :-1] if intercept else B
            eta = sparse_eta_multi(data, cols, rows, W, S)   # (S, C)
            if intercept:
                eta = eta + B[:, -1][None, :]
            Y = _codes_onehot(yb, mask, n_classes)           # (C, S)
            per_class = jax.vmap(
                lambda e, yc: jnp.sum(
                    get_family(family).pointwise(e, yc) * mask
                ),
                in_axes=(1, 0),
            )(eta, Y)
            return jnp.sum(per_class)
    else:
        def data_val(beta, data, cols, rows, yb, mask):
            w = beta[:-1] if intercept else beta
            eta = sparse_eta(data, cols, rows, w, S)
            if intercept:
                eta = eta + beta[-1]
            return jnp.sum(get_family(family).pointwise(eta, yb) * mask)

    if kind == "val":
        def sums(beta, data, cols, rows, yb, c):
            mask = (jnp.arange(S) < c).astype(jnp.float32)
            return (data_val(beta, data, cols, rows, yb, mask),)

        return sums

    def sums(beta, data, cols, rows, yb, c):     # "vg"
        mask = (jnp.arange(S) < c).astype(jnp.float32)
        return jax.value_and_grad(
            lambda b: data_val(b, data, cols, rows, yb, mask)
        )(beta)

    return sums


@_ft.lru_cache(maxsize=64)
def _sb_reducer_sparse(kind, family, intercept, n_classes, n_rows,
                       n_features, mesh=None):
    """The donated-carry super-block program for one SPARSE objective
    flavor: the scan steps through the (K, cap) COO stacks accumulating
    the same sum tuple as :func:`_sb_reducer` — one dispatch per
    super-block, zero recompiles after pass 1 (the plan pads every
    super-block of a fit to ONE capacity). ``mesh`` selects the
    shard_map data-parallel flavor: each device scans its own (K, cap)
    nnz segment with shard-local row ids against its (K, S/D) slab of
    the dense side arrays, and the dispatch pays exactly ONE psum —
    identical collective shape to the dense flavor."""
    suffix = "_multi" if n_classes else ""
    if mesh is None:
        sums = _sparse_reducer_sums(kind, family, intercept, n_classes,
                                    n_rows, n_features)

        @partial(jax.jit, donate_argnums=(0,))
        def run(acc, beta, data, cols, rows, ys, counts):
            def scan_step(acc, inp):
                db, cb, rb, yb, c = inp
                out = sums(beta, db, cb, rb, yb, c)
                out = out if isinstance(out, tuple) else (out,)
                return tuple(a + o for a, o in zip(acc, out)), \
                    jnp.float32(0.0)

            acc, _ = jax.lax.scan(scan_step, acc,
                                  (data, cols, rows, ys, counts))
            return acc

        return plan_tracked(f"superblock.sparse.glm.{kind}{suffix}",
                            run)

    from jax.sharding import PartitionSpec as P

    from ...parallel.mesh import DATA_AXIS

    sums = _sparse_reducer_sums(kind, family, intercept, n_classes,
                                n_rows, n_features)

    def body(acc, beta, data, cols, rows, ys, counts):
        # LOCAL view: data/cols/rows (K, cap) — this shard's nnz
        # segments with shard-local row ids; ys (K, S/D); counts (1, K)
        cts = counts[0]
        local = jax.tree.map(jnp.zeros_like, acc)

        def scan_step(lacc, inp):
            db, cb, rb, yb, c = inp
            out = sums(beta, db, cb, rb, yb, c)
            out = out if isinstance(out, tuple) else (out,)
            return tuple(l + o for l, o in zip(lacc, out)), \
                jnp.float32(0.0)

        local, _ = jax.lax.scan(scan_step, local,
                                (data, cols, rows, ys, cts))
        local = jax.lax.psum(local, DATA_AXIS)
        return tuple(a + l for a, l in zip(acc, local))

    @partial(jax.jit, donate_argnums=(0,))
    def run(acc, beta, data, cols, rows, ys, counts):
        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), P(None, DATA_AXIS), P(None, DATA_AXIS),
                      P(None, DATA_AXIS), P(None, DATA_AXIS),
                      P(DATA_AXIS, None)),
            out_specs=P(),
            check_vma=False,
        )
        return f(acc, beta, data, cols, rows, ys, counts)

    return plan_tracked(
        f"superblock.sparse.glm.{kind}{suffix}.psum", run
    )


@_ft.lru_cache(maxsize=32)
def _sb_admm_local(local_iter, family, intercept, n_classes,
                   gspmd=False):
    """Super-block ADMM block-local Newton: the K consensus members of
    one super-block solve their independent local problems in ONE
    vmapped dispatch (their (b, u) state slices ride in stacked; the
    stacked B carry is donated). All-padding slots pass their b through
    unchanged.

    ``gspmd=True`` (ROADMAP 1(c) measurement): the super-block arrays
    arrived BATCH-SHARDED over the stream mesh and this plain jit rides
    implicit GSPMD — XLA partitions each block's XᵀWX / Xᵀresid over
    the row shards and inserts cross-device all-reduces of the (d, d)
    Hessian and gradient per local-Newton iteration. Numerically
    identical; tracked under its own ``...admm_local.gspmd`` program
    name (so the report CLI ranks it separately, and with obs_programs
    on XLA's own bytes-accessed lands beside it) while the caller
    records the per-dispatch reduce-volume estimate on the
    ``gspmd_reduce_bytes`` counter."""

    @partial(jax.jit, donate_argnums=(0,))
    def run(Bk, Uk, Xs, ys, counts, z, rho, n_rows):
        r = jnp.arange(Xs.shape[1])

        def one(b, u, X, y, c):
            mask = (r < c).astype(X.dtype)
            if n_classes:
                Y = _codes_onehot(y, mask, n_classes)
                nb = jax.vmap(
                    lambda yc, bb, uu, zz: _admm_local_body(
                        X, yc, mask, bb, uu, zz, rho, n_rows,
                        local_iter, family, intercept,
                    )
                )(Y, b, u, z.reshape(n_classes, -1))
            else:
                nb = _admm_local_body(X, y, mask, b, u, z, rho, n_rows,
                                      local_iter, family, intercept)
            return jnp.where(c > 0, nb, b)

        return jax.vmap(one)(Bk, Uk, Xs, ys, counts)

    suffix = "_multi" if n_classes else ""
    tail = ".gspmd" if gspmd else ""
    return plan_tracked(f"superblock.glm.admm_local{suffix}{tail}",
                        run)


# ---------------------------------------------------------------------------
# streamed objective: one call = one pass over the stream
# ---------------------------------------------------------------------------

class StreamedObjective:
    """value_and_grad over a BlockStream; counts data passes.

    ``reduce``: optional cross-PROCESS sum of the per-pass accumulators
    (``parallel.distributed.psum_host``) — under a live multi-host
    runtime each process streams only its local shard, the raw
    loss/gradient/Hessian sums merge once per pass, and every process
    sees the identical GLOBAL objective (``n_rows`` is then the global
    row count). The host solvers run replicated on identical inputs, so
    their iterates never diverge across processes."""

    n_classes = None  # multiclass subclass overrides

    def __init__(self, stream, n_rows, lam, pmask, l1_ratio, family, reg,
                 intercept, logger=None, reduce=None, fit_dtype=None):
        self.stream = stream
        self.n_rows = float(n_rows)
        self.lam = lam
        self.pmask = pmask
        self.l1_ratio = l1_ratio
        self.family = family
        self.reg = reg
        self.intercept = intercept
        self.passes = 0
        self.logger = logger
        self.reduce = reduce
        self.fit_dtype = fit_dtype

    def _smooth_clone(self):
        """Same objective with the penalty stripped (proximal solvers
        evaluate the smooth part only and handle the penalty in the
        prox). Overridden by the multiclass subclass so the clone keeps
        its class structure."""
        return type(self)(
            self.stream, self.n_rows, self.lam * 0.0, self.pmask,
            self.l1_ratio, self.family, "none", self.intercept,
            logger=self.logger, reduce=self.reduce,
            fit_dtype=self.fit_dtype,
        )

    def _sb_flavor(self, kind):
        """(mxu, fused, interpret, reason) for this stream's ``kind``
        reducer: the Pallas fused flavor (ISSUE 8, composed with the
        data mesh by ISSUE 12) when opted in and the PER-SHARD slab
        shape (S/D rows — the rows each kernel instance actually sees
        inside shard_map; the whole block on a 1-shard mesh) fits the
        128-row grid/VMEM budget — with the resolved bf16 matmul policy
        riding along — else the XLA flavor, untouched and f32 (the
        streamed XLA reducers accumulate in f32 carries by
        construction; bf16 streamed GLM compute is a fused-kernel-only
        feature, so off-TPU fits fall back to f32 whatever config.dtype
        says). ``reason`` names why fused was gated off (None when it
        engaged) — recorded as solver_info_["fused_stream_reason"] so
        smoke suites can assert the kernels actually ran instead of
        silently falling back."""
        from ...config import mxu_dtype
        from ...ops.pallas_fused import (glm_multi_stream_tile,
                                         glm_stream_tile,
                                         stream_kernel_mode,
                                         stream_mode_reason,
                                         stream_tile_reason)

        if self.n_classes and kind == "vgh":
            # the per-class (C, d, d) Hessian stack stays XLA: a Pallas
            # body would hold C Hessian accumulators in VMEM at once,
            # and multiclass newton is not a streamed hot path
            return None, False, False, "multiclass-hessian-xla"
        M = int(getattr(self.stream, "sb_model_shards", lambda: 1)())
        if M > 1:
            # feature-sharded tiles (2-D mesh, ISSUE 18) stay XLA: the
            # fused Pallas bodies have no per-feature-slice story (the
            # model-axis psum sits mid-objective)
            return None, False, False, f"feature-sharded(M={M})"
        reason = stream_mode_reason()
        if reason is not None:
            return None, False, False, reason
        _, interp = stream_kernel_mode()
        s = self.stream
        try:
            S = int(s.block_rows)
            d = int(np.prod(s.arrays[0].shape[1:], dtype=np.int64))
        except Exception:
            return None, False, False, "no-stream-shape"
        # the fused body runs on each device's OWN slab: the tile gate
        # must reason about S/D rows, not the global block height
        D = max(int(getattr(s, "sb_data_shards", lambda: 1)()), 1)
        S_local = S // D
        tile = (glm_multi_stream_tile(S_local, d, self.n_classes)
                if self.n_classes
                else glm_stream_tile(S_local, d, kind))
        reason = stream_tile_reason(S_local, tile)
        if reason is not None:
            return None, False, False, reason
        if kind in ("vgh", "val"):
            # Hessian passes stay f32 even when fused — the SAME policy
            # the resident path enforces (glm.py restricts bf16 to the
            # smooth first-order solvers: bf16 Hessians risk
            # conditioning, and the matmul they'd speed up is the one
            # whose error a Newton step amplifies). "val" rides along:
            # its ONLY streamed consumer is newton's step-halving line
            # search, and comparing a bf16 objective against the f32
            # vgh value would spuriously reject steps near convergence
            # (the rounding gap exceeds the true decrease there)
            return None, True, interp, None
        return mxu_dtype(self.fit_dtype), True, interp, None

    def _merge(self, *accs):
        """Local pass sums → global sums (merged f64 on host, identical
        on every process; back to f32 for the device epilogue so x64
        stays untouched). Identity without a reduce."""
        if self.reduce is None:
            return accs if len(accs) > 1 else accs[0]
        out = self.reduce(*(np.asarray(a, np.float64) for a in accs))
        out = out if isinstance(out, tuple) else (out,)
        out = tuple(np.asarray(o, np.float32) for o in out)
        return out if len(out) > 1 else out[0]

    def _sb_pass(self, kind, B, init):
        """One super-block pass of the ``kind`` objective: the tuple of
        accumulated sums, or None when the stream doesn't super-block
        (no support, opt-out, sparse source, or K == 1) — the caller
        then runs its per-block loop. The accumulator tuple is the
        scan's DONATED carry: one dispatch per K blocks, its buffers
        reused in place across the whole pass."""
        s = self.stream
        if not (hasattr(s, "use_superblocks") and s.use_superblocks()):
            return None
        from ...observability import record_superblock_donation

        if bool(getattr(s, "sb_sparse", lambda: False)()):
            return self._sb_pass_sparse(kind, B, init)
        sharded = bool(getattr(s, "sb_sharded", lambda: False)())
        mxu, fused, interp, _ = self._sb_flavor(kind)
        if sharded:
            # data-parallel superblock flavor (ISSUE 9): shard_map over
            # the stream mesh, one psum per super-block — with the
            # fused Pallas body inside it when the flavor gate passes
            # (ISSUE 12). The carry enters COMMITTED-replicated so
            # every dispatch (including the first) hits the same
            # compiled executable and the donated buffers alias in
            # place
            from jax.sharding import NamedSharding, PartitionSpec as P

            # the feature-sharded flavor engages ONLY when the stream's
            # X actually tiled over "model" (sb_model_shards > 1); the
            # kwarg is omitted otherwise so the M == 1 reducer cache
            # keys — and with them the 1-D jaxprs — stay byte-identical
            m_shards = int(getattr(s, "sb_model_shards",
                                   lambda: 1)())
            kw = {"model_shards": m_shards} if m_shards > 1 else {}
            run = _sb_reducer(kind, self.family, self.intercept,
                              self.n_classes or 0, mxu=mxu, fused=fused,
                              interpret=interp, mesh=s.mesh, **kw)
            init = jax.device_put(init, NamedSharding(s.mesh, P()))
        else:
            run = _sb_reducer(kind, self.family, self.intercept,
                              self.n_classes or 0, mxu=mxu, fused=fused,
                              interpret=interp)
        acc = init
        acc_bytes = sum(4 * int(np.prod(a.shape) or 1) for a in acc)
        for sb in s.superblocks():
            counts = sb.shard_counts if sharded else sb.counts
            acc = run(acc, B, sb.arrays[0], sb.arrays[1], counts)
            record_superblock_donation(acc_bytes)
        return acc

    def _sb_pass_sparse(self, kind, B, init):
        """The bucketed-nnz flavor of :meth:`_sb_pass` (ISSUE 13): the
        stream stages sparse slabs, the reducers run take/segment_sum
        math at nnz cost, and the dispatch/donation/psum contracts are
        the dense scan's exactly."""
        from ...observability import record_superblock_donation

        s = self.stream
        plan = s.sparse_plan
        sharded = bool(getattr(s, "sb_sharded", lambda: False)())
        D = s.sb_data_shards() if sharded else 1
        S_local = s.block_rows // D
        if sharded:
            from jax.sharding import NamedSharding, PartitionSpec as P

            run = _sb_reducer_sparse(kind, self.family, self.intercept,
                                     self.n_classes or 0, S_local,
                                     plan.n_features, mesh=s.mesh)
            init = jax.device_put(init, NamedSharding(s.mesh, P()))
        else:
            run = _sb_reducer_sparse(kind, self.family, self.intercept,
                                     self.n_classes or 0, S_local,
                                     plan.n_features)
        acc = init
        acc_bytes = sum(4 * int(np.prod(a.shape) or 1) for a in acc)
        for sb in s.superblocks():
            slab = sb.arrays[0]
            counts = sb.shard_counts if sharded else sb.counts
            acc = run(acc, B, slab.data, slab.cols, slab.rows,
                      sb.arrays[1], counts)
            record_superblock_donation(acc_bytes)
        return acc

    def value_and_grad(self, beta):
        self.passes += 1
        beta = jnp.asarray(beta, jnp.float32)
        out = self._sb_pass("vg", beta, (
            jnp.zeros((), jnp.float32), jnp.zeros_like(beta),
        ))
        if out is not None:
            vs, gs = out
        else:
            vs, gs = None, None
            for blk in self.stream:
                Xb, yb = blk.arrays
                v, g = _block_val_grad(beta, Xb, yb, blk.mask, self.family,
                                       self.intercept)
                vs = v if vs is None else vs + v
                gs = g if gs is None else gs + g
        vs, gs = self._merge(vs, gs)
        val, grad = _finish_vg(vs, gs, beta, self.n_rows, self.lam,
                               self.pmask, self.l1_ratio, self.reg)
        return float(val), np.asarray(grad, np.float64)

    def value(self, beta):
        self.passes += 1
        beta = jnp.asarray(beta, jnp.float32)
        out = self._sb_pass("val", beta, (jnp.zeros((), jnp.float32),))
        if out is not None:
            vs, = out
        else:
            vs = None
            for blk in self.stream:
                Xb, yb = blk.arrays
                v = _block_val(beta, Xb, yb, blk.mask, self.family,
                               self.intercept)
                vs = v if vs is None else vs + v
        vs = self._merge(vs)
        pen = regularizers.value(self.reg, beta, self.lam, self.pmask,
                                 self.l1_ratio)
        return float(vs / self.n_rows + pen)

    def value_and_grad_and_hess(self, beta):
        self.passes += 1
        beta = jnp.asarray(beta, jnp.float32)
        p = beta.shape[0]
        out = self._sb_pass("vgh", beta, (
            jnp.zeros((), jnp.float32), jnp.zeros_like(beta),
            jnp.zeros((p, p), jnp.float32),
        ))
        if out is not None:
            vs, gs, hs = out
        else:
            vs, gs, hs = None, None, None
            for blk in self.stream:
                Xb, yb = blk.arrays
                v, g, h = _block_val_grad_hess(beta, Xb, yb, blk.mask,
                                               self.family, self.intercept)
                vs = v if vs is None else vs + v
                gs = g if gs is None else gs + g
                hs = h if hs is None else hs + h
        vs, gs, hs = self._merge(vs, gs, hs)
        val, grad = _finish_vg(vs, gs, beta, self.n_rows, self.lam,
                               self.pmask, self.l1_ratio, self.reg)
        return (float(val), np.asarray(grad, np.float64),
                np.asarray(hs, np.float64) / self.n_rows)

    def log(self, it, val, gnorm):
        from ...observability.live import publish_progress

        # the streamed solvers hold loss/grad_norm on HOST already (the
        # per-pass reduction fetched them) — publishing live gauges
        # costs dict writes, never a device sync; no-op without a
        # telemetry server
        publish_progress(loss=float(val), grad_norm=float(gnorm),
                         iteration=int(it), pass_count=self.passes)
        if self.logger is not None:
            self.logger.log(step=it, loss=float(val), grad_norm=float(gnorm),
                            passes=self.passes)


class MulticlassStreamedObjective(StreamedObjective):
    """Sum of C one-vs-rest objectives over ONE shared stream pass.

    The host solvers see a FLAT (C*d,) parameter vector — the joint
    objective is separable across classes, so minimizing the sum jointly
    (lbfgs/gd/prox on the concatenated vector) reaches each class's own
    optimum; ``pmask`` arrives pre-tiled to (C*d,). Newton and ADMM read
    ``n_classes`` to keep their per-class (d, d) structure."""

    def __init__(self, *args, n_classes=None, **kwargs):
        super().__init__(*args, **kwargs)
        self.n_classes = n_classes

    def _smooth_clone(self):
        return type(self)(
            self.stream, self.n_rows, self.lam * 0.0, self.pmask,
            self.l1_ratio, self.family, "none", self.intercept,
            logger=self.logger, n_classes=self.n_classes,
            reduce=self.reduce, fit_dtype=self.fit_dtype,
        )

    def _B(self, beta_flat):
        return jnp.asarray(beta_flat, jnp.float32).reshape(
            self.n_classes, -1
        )

    def value_and_grad(self, beta):
        self.passes += 1
        B = self._B(beta)
        out = self._sb_pass("vg", B, (
            jnp.zeros((), jnp.float32), jnp.zeros_like(B),
        ))
        if out is not None:
            vs, gs = out
        else:
            vs, gs = None, None
            for blk in self.stream:
                Xb, yb = blk.arrays
                v, g = _block_val_grad_multi(B, Xb, yb, blk.mask,
                                             self.family, self.intercept,
                                             self.n_classes)
                vs = v if vs is None else vs + v
                gs = g if gs is None else gs + g
        vs, gs = self._merge(vs, gs)
        val, grad = _finish_vg(vs, jnp.asarray(gs).ravel(),
                               jnp.asarray(beta, jnp.float32),
                               self.n_rows, self.lam, self.pmask,
                               self.l1_ratio, self.reg)
        return float(val), np.asarray(grad, np.float64)

    def value(self, beta):
        self.passes += 1
        B = self._B(beta)
        out = self._sb_pass("val", B, (jnp.zeros((), jnp.float32),))
        if out is not None:
            vs, = out
        else:
            vs = None
            for blk in self.stream:
                Xb, yb = blk.arrays
                v = _block_val_multi(B, Xb, yb, blk.mask, self.family,
                                     self.intercept, self.n_classes)
                vs = v if vs is None else vs + v
        vs = self._merge(vs)
        pen = regularizers.value(self.reg, jnp.asarray(beta, jnp.float32),
                                 self.lam, self.pmask, self.l1_ratio)
        return float(vs / self.n_rows + pen)

    def value_and_grad_and_hess(self, beta):
        self.passes += 1
        B = self._B(beta)
        p = B.shape[1]
        out = self._sb_pass("vgh", B, (
            jnp.zeros((), jnp.float32), jnp.zeros_like(B),
            jnp.zeros((self.n_classes, p, p), jnp.float32),
        ))
        if out is not None:
            vs, gs, hs = out
        else:
            vs, gs, hs = None, None, None
            for blk in self.stream:
                Xb, yb = blk.arrays
                v, g, h = _block_val_grad_hess_multi(
                    B, Xb, yb, blk.mask, self.family, self.intercept,
                    self.n_classes,
                )
                vs = v if vs is None else vs + v
                gs = g if gs is None else gs + g
                hs = h if hs is None else hs + h
        vs, gs, hs = self._merge(vs, gs, hs)
        val, grad = _finish_vg(vs, jnp.asarray(gs).ravel(),
                               jnp.asarray(beta, jnp.float32),
                               self.n_rows, self.lam, self.pmask,
                               self.l1_ratio, self.reg)
        return (float(val), np.asarray(grad, np.float64),
                np.asarray(hs, np.float64) / self.n_rows)


def _armijo(obj, beta, val, grad, direction, t0=1.0, c=1e-4, backtrack=0.5,
            max_trials=30):
    """Backtracking line search; each trial is one data pass. Returns
    (t, new_val, new_grad) at the accepted point."""
    dg = float(grad @ direction)
    if dg >= 0:  # numerical non-descent: fall back to steepest descent
        direction = -grad
        dg = -float(grad @ grad)
    t = t0
    for _ in range(max_trials):
        nv, ng = obj.value_and_grad(beta + t * direction)
        if nv <= val + c * t * dg or t <= 1e-20:
            return t, direction, nv, ng
        t *= backtrack
    return t, direction, nv, ng


# ---------------------------------------------------------------------------
# solvers (host optimizer state — a handful of d-vectors — over streamed
# device evaluation)
# ---------------------------------------------------------------------------
#
# Every solver takes an optional ``ckpt`` (reliability/stream_ckpt.py):
# the host optimizer state — the iterate plus whatever the solver needs
# to continue bit-exactly — saves after each outer iteration (each
# iteration = one-plus data passes) and clears on completion, so a
# killed multi-hour streamed GLM fit resumes at iteration granularity
# instead of restarting from scratch. A wrong-fingerprint checkpoint
# restores as None and the fit simply starts fresh.

def _ckpt_restore(ckpt):
    if ckpt is None:
        return None
    st = ckpt.restore()
    if st is not None:
        from ...observability._counters import record_stream_checkpoint

        record_stream_checkpoint(resume=True)
    return st


def lbfgs(obj: StreamedObjective, beta0, max_iter=100, tol=1e-6, memory=10,
          ckpt=None, **_):
    if obj.reg not in regularizers.SMOOTH:
        raise ValueError(
            "streamed lbfgs handles smooth penalties only (l2/none); use "
            "solver='proximal_grad' or 'admm' for l1/elastic_net"
        )
    beta = np.asarray(beta0, np.float64)
    S, Y = [], []
    it0 = n_iter = 0
    st = _ckpt_restore(ckpt)
    if st is not None:
        beta = np.asarray(st["beta"], np.float64)
        val = float(st["val"])
        grad = np.asarray(st["grad"], np.float64)
        if "S" in st:
            S = [np.asarray(r, np.float64) for r in np.asarray(st["S"])]
            Y = [np.asarray(r, np.float64) for r in np.asarray(st["Y"])]
        it0 = n_iter = int(st["it"])
        obj.passes = int(st["passes"])
    else:
        val, grad = obj.value_and_grad(beta)
    for it in range(it0, int(max_iter)):
        gnorm = float(np.linalg.norm(grad))
        obj.log(it, val, gnorm)
        if gnorm <= tol:
            break
        # two-loop recursion on host (d-vector ops; data never touched)
        q = grad.copy()
        alphas = []
        for s, y_ in zip(reversed(S), reversed(Y)):
            rho = 1.0 / float(y_ @ s)
            a = rho * float(s @ q)
            q -= a * y_
            alphas.append((rho, a))
        if Y:
            q *= float(S[-1] @ Y[-1]) / float(Y[-1] @ Y[-1])
        for (rho, a), s, y_ in zip(reversed(alphas), S, Y):
            q += (a - rho * float(y_ @ q)) * s
        t, direction, nv, ng = _armijo(obj, beta, val, grad, -q)
        s = t * direction
        y_ = ng - grad
        if float(s @ y_) > 1e-10 * np.linalg.norm(s) * np.linalg.norm(y_):
            S.append(s)
            Y.append(y_)
            if len(S) > memory:
                S.pop(0)
                Y.pop(0)
        beta = beta + s
        val, grad = nv, ng
        n_iter = it + 1
        if ckpt is not None and ckpt.due(n_iter):
            state = dict(beta=beta, val=np.float64(val), grad=grad,
                         it=n_iter, passes=obj.passes)
            if S:
                state["S"], state["Y"] = np.stack(S), np.stack(Y)
            ckpt.save(**state)
    if ckpt is not None:
        ckpt.clear()
    return beta, {"n_iter": n_iter, "grad_norm": float(np.linalg.norm(grad)),
                  "data_passes": obj.passes}


def gradient_descent(obj: StreamedObjective, beta0, max_iter=100, tol=1e-6,
                     init_step=1.0, ckpt=None, **_):
    if obj.reg not in regularizers.SMOOTH:
        raise ValueError(
            "streamed gradient_descent handles smooth penalties only"
        )
    beta = np.asarray(beta0, np.float64)
    it0 = n_iter = 0
    st = _ckpt_restore(ckpt)
    if st is not None:
        beta = np.asarray(st["beta"], np.float64)
        val = float(st["val"])
        grad = np.asarray(st["grad"], np.float64)
        step = float(st["step"])
        it0 = n_iter = int(st["it"])
        obj.passes = int(st["passes"])
    else:
        val, grad = obj.value_and_grad(beta)
        step = init_step
    for it in range(it0, int(max_iter)):
        gnorm = float(np.linalg.norm(grad))
        obj.log(it, val, gnorm)
        if gnorm <= tol:
            break
        t, direction, nv, ng = _armijo(obj, beta, val, grad, -grad, t0=step)
        beta = beta + t * direction
        val, grad = nv, ng
        step = t * 2.0
        n_iter = it + 1
        if ckpt is not None and ckpt.due(n_iter):
            ckpt.save(beta=beta, val=np.float64(val), grad=grad,
                      step=np.float64(step), it=n_iter, passes=obj.passes)
    if ckpt is not None:
        ckpt.clear()
    return beta, {"n_iter": n_iter, "grad_norm": float(np.linalg.norm(grad)),
                  "data_passes": obj.passes}


def newton(obj: StreamedObjective, beta0, max_iter=50, tol=1e-6, ckpt=None,
           **_):
    if obj.reg not in regularizers.SMOOTH:
        raise ValueError("streamed newton handles smooth penalties only")
    beta = np.asarray(beta0, np.float64)
    d = beta.shape[0]
    pmask = np.asarray(obj.pmask, np.float64)
    ridge = (float(obj.lam) * pmask if obj.reg == "l2"
             else np.zeros(d)) + 1e-8
    it0 = n_iter = 0
    st = _ckpt_restore(ckpt)
    if st is not None:
        # newton recomputes val/grad/hess at the loop top, so the
        # iterate + clocks are the whole state (resume pays one extra
        # pass re-evaluating the saved iterate; the math is identical)
        beta = np.asarray(st["beta"], np.float64)
        it0 = n_iter = int(st["it"])
        obj.passes = int(st["passes"])
    gnorm = np.inf
    for it in range(it0, int(max_iter)):
        val, grad, hess = obj.value_and_grad_and_hess(beta)
        gnorm = float(np.linalg.norm(grad))
        obj.log(it, val, gnorm)
        if gnorm <= tol:
            break
        if obj.n_classes:
            # per-class (d, d) solves against the block-diagonal Hessian
            C = obj.n_classes
            G = grad.reshape(C, -1)
            R = ridge.reshape(C, -1)
            delta = np.concatenate([
                np.linalg.lstsq(hess[c] + np.diag(R[c]), G[c], rcond=None)[0]
                for c in range(C)
            ])
        else:
            delta = np.linalg.lstsq(hess + np.diag(ridge), grad,
                                    rcond=None)[0]
        t = 1.0
        while t > 1e-6:
            if obj.value(beta - t * delta) <= val:
                break
            t *= 0.5
        beta = beta - t * delta
        n_iter = it + 1
        if ckpt is not None and ckpt.due(n_iter):
            ckpt.save(beta=beta, it=n_iter, passes=obj.passes)
    if ckpt is not None:
        ckpt.clear()
    return beta, {"n_iter": n_iter, "grad_norm": gnorm,
                  "data_passes": obj.passes}


def proximal_grad(obj: StreamedObjective, beta0, max_iter=100, tol=1e-7,
                  init_step=1.0, ckpt=None, **_):
    # penalty handled by the prox; the streamed objective evaluates the
    # smooth part only
    smooth = obj._smooth_clone()
    lam = float(np.asarray(obj.lam))
    pmask_j = jnp.asarray(obj.pmask)
    beta = np.asarray(beta0, np.float64)
    it0 = n_iter = 0
    st = _ckpt_restore(ckpt)
    if st is not None:
        beta = np.asarray(st["beta"], np.float64)
        val = float(st["val"])
        grad = np.asarray(st["grad"], np.float64)
        step = float(st["step"])
        it0 = n_iter = int(st["it"])
        smooth.passes = int(st["passes"])
    else:
        val, grad = smooth.value_and_grad(beta)
        step = init_step
    delta = np.inf

    def candidate(t):
        return np.asarray(regularizers.prox(
            obj.reg, jnp.asarray(beta - t * grad), lam, t, pmask_j,
            obj.l1_ratio,
        ), np.float64)

    for it in range(it0, int(max_iter)):
        t = step
        while True:
            z = candidate(t)
            dz = z - beta
            quad = val + float(grad @ dz) + float(dz @ dz) / (2.0 * t)
            # evaluate value AND gradient in the trial pass: the accepted
            # candidate's gradient is reused below, so acceptance costs no
            # extra epoch over the stream
            zv, zg = smooth.value_and_grad(z)
            if zv <= quad or t <= 1e-20:
                break
            t *= 0.5
        delta = float(np.linalg.norm(z - beta)) / max(t, 1e-20)
        beta = z
        val, grad = zv, zg
        smooth.log(it, val, delta)
        step = t * 1.2
        n_iter = it + 1
        if ckpt is not None and ckpt.due(n_iter):
            ckpt.save(beta=beta, val=np.float64(val), grad=grad,
                      step=np.float64(step), it=n_iter,
                      passes=smooth.passes)
        if delta <= tol:
            break
    if ckpt is not None:
        ckpt.clear()
    obj.passes = smooth.passes
    return beta, {"n_iter": n_iter, "opt_residual": float(delta),
                  "data_passes": obj.passes}


def admm(obj: StreamedObjective, beta0, max_iter=250, tol=1e-4, rho=1.0,
         local_iter=8, ckpt=None, **_):
    """Block-consensus ADMM: each streamed block is a consensus member
    (the in-memory version's mesh shard, ``solvers.py::_admm_run``).
    Per-block (b, u) state is (n_blocks, d) on host — tiny next to X."""
    reg = obj.reg
    lam = float(np.asarray(obj.lam))
    if reg == "none":
        reg, lam = "l2", 0.0
    n_blocks = obj.stream.n_blocks
    # consensus spans every process's blocks: the z-update and residuals
    # use GLOBAL block sums/counts so all processes step identically
    reduce = obj.reduce or (lambda *a: a[0] if len(a) == 1 else a)
    glob_blocks = int(reduce(np.asarray(float(n_blocks))))
    d = len(np.asarray(beta0))
    B = np.tile(np.asarray(beta0, np.float32)[None], (n_blocks, 1))
    U = np.zeros((n_blocks, d), np.float32)
    z = jnp.asarray(beta0, jnp.float32)
    pmask_j = jnp.asarray(obj.pmask)
    rho_f = float(rho)
    it0 = n_iter = 0
    st = _ckpt_restore(ckpt)
    if st is not None and np.asarray(st["B"]).shape == B.shape:
        B = np.asarray(st["B"], np.float32)
        U = np.asarray(st["U"], np.float32)
        z = jnp.asarray(np.asarray(st["z"], np.float32))
        rho_f = float(st["rho"])
        it0 = n_iter = int(st["it"])
        obj.passes = int(st["passes"])
    primal = dual = np.inf
    C = obj.n_classes
    s = obj.stream
    # ADMM's block-local Newton is O(d^2) per member whatever the input
    # format — sparse-staged streams keep the per-block densify loop
    # (reason recorded via _fused_stream_info as "admm-local-newton")
    use_sb = (hasattr(s, "use_superblocks") and s.use_superblocks()
              and not bool(getattr(s, "sb_sparse", lambda: False)()))
    for it in range(it0, int(max_iter)):
        obj.passes += 1
        bi = 0
        if use_sb:
            # one dispatch advances the K consensus members of each
            # super-block (GLM local-Newton, vmapped over the stack;
            # stacked-B carry donated)
            from ...observability import (record_gspmd_reduce,
                                          record_superblock_donation)

            sb_sharded = bool(getattr(s, "sb_sharded", lambda: False)())
            runner = _sb_admm_local(int(local_iter), obj.family,
                                    obj.intercept, C or 0,
                                    gspmd=sb_sharded)
            for sb in s.superblocks():
                k = int(sb.counts.shape[0])
                kr = sb.n_blocks
                Bk = np.zeros((k, d), np.float32)
                Uk = np.zeros((k, d), np.float32)
                Bk[:kr] = B[bi:bi + kr]
                Uk[:kr] = U[bi:bi + kr]
                if C:
                    out = runner(
                        jnp.asarray(Bk).reshape(k, C, -1),
                        jnp.asarray(Uk).reshape(k, C, -1),
                        sb.arrays[0], sb.arrays[1], sb.counts, z.ravel(),
                        jnp.float32(rho_f), jnp.float32(obj.n_rows),
                    )
                    B[bi:bi + kr] = np.asarray(out).reshape(k, -1)[:kr]
                else:
                    out = runner(
                        jnp.asarray(Bk), jnp.asarray(Uk), sb.arrays[0],
                        sb.arrays[1], sb.counts, z,
                        jnp.float32(rho_f), jnp.float32(obj.n_rows),
                    )
                    B[bi:bi + kr] = np.asarray(out)[:kr]
                record_superblock_donation(Bk.nbytes)
                if sb_sharded:
                    # implicit-GSPMD reduce volume of this dispatch
                    # (ROADMAP 1(c)): per block slot, class, and
                    # local-Newton iteration, the partitioned XᵀWX +
                    # Xᵀresid pay one cross-device all-reduce of the
                    # (p, p) Hessian and the (p,) gradient; logical
                    # payload = iters * K * C * (p² + p) * 4 bytes,
                    # counted once per crossing (ring traffic
                    # multiplies by ~2(D-1)/D on real links — the
                    # counter records the payload, the topology factor
                    # belongs to the interconnect)
                    p = d // (C or 1)
                    record_gspmd_reduce(
                        int(local_iter) * k * (C or 1) * (p * p + p) * 4
                    )
                bi += kr
        else:
            for blk in obj.stream:
                Xb, yb = blk.arrays
                if C:
                    # one block read serves all C consensus problems
                    B[bi] = np.asarray(_block_admm_local_multi(
                        Xb, yb, blk.mask, jnp.asarray(B[bi]).reshape(C, -1),
                        jnp.asarray(U[bi]).reshape(C, -1), z.reshape(C, -1),
                        jnp.float32(rho_f), jnp.float32(obj.n_rows),
                        local_iter, obj.family, obj.intercept, C,
                    )).ravel()
                else:
                    B[bi] = np.asarray(_block_admm_local(
                        Xb, yb, blk.mask, jnp.asarray(B[bi]),
                        jnp.asarray(U[bi]), z, jnp.float32(rho_f),
                        jnp.float32(obj.n_rows), local_iter, obj.family,
                        obj.intercept,
                    ))
                bi += 1
        bu_sum, = (reduce(np.asarray((B + U).sum(axis=0), np.float64)),)
        bu_mean = jnp.asarray(np.asarray(bu_sum, np.float32) / glob_blocks)
        z_new = regularizers.prox(reg, bu_mean, lam,
                                  1.0 / (rho_f * glob_blocks), pmask_j,
                                  obj.l1_ratio)
        z_h = np.asarray(z_new, np.float32)
        U = U + B - z_h[None, :]
        primal2 = float(reduce(
            np.asarray(((B - z_h[None, :]) ** 2).sum(), np.float64)
        ))
        primal = float(np.sqrt(primal2))
        dual = float(rho_f * np.sqrt(glob_blocks)
                     * np.linalg.norm(z_h - np.asarray(z)))
        z = z_new
        obj.log(it, primal, dual)
        n_iter = it + 1
        if primal <= tol and dual <= tol:
            break
        if primal > 10.0 * dual:
            rho_f *= 2.0
            U /= 2.0
        elif dual > 10.0 * primal:
            rho_f *= 0.5
            U *= 2.0
        if ckpt is not None and ckpt.due(n_iter):
            # saved AFTER the rho adaptation so a resumed iteration
            # continues with exactly the state an uninterrupted run
            # would carry into it
            ckpt.save(B=B, U=U, z=np.asarray(z, np.float32),
                      rho=np.float64(rho_f), it=n_iter,
                      passes=obj.passes)
    if ckpt is not None:
        ckpt.clear()
    return (np.asarray(z, np.float64),
            {"n_iter": n_iter, "primal_residual": primal,
             "dual_residual": dual, "data_passes": obj.passes})


STREAMED_SOLVERS = {
    "admm": admm,
    "lbfgs": lbfgs,
    "newton": newton,
    "gradient_descent": gradient_descent,
    "proximal_grad": proximal_grad,
}


def solve_streamed(solver, stream, n_rows, beta0, family, reg, lam, pmask,
                   l1_ratio=0.5, intercept=True, max_iter=100, tol=1e-6,
                   logger=None, reduce=None, fit_dtype=None, ckpt=None,
                   **kwargs):
    """``reduce`` (``distributed.psum_host``): merge per-pass block sums
    across processes — each process streams its LOCAL shard, ``n_rows``
    is the GLOBAL count, and the fit equals the single-process fit over
    the concatenated data. ``ckpt`` (a reliability.StreamCheckpoint)
    arms iteration-granular save/auto-resume in the solver."""
    if solver not in STREAMED_SOLVERS:
        raise ValueError(
            f"Unknown solver {solver!r}; options: {sorted(STREAMED_SOLVERS)}"
        )
    obj = StreamedObjective(
        stream, n_rows, jnp.asarray(lam, jnp.float32), jnp.asarray(pmask),
        l1_ratio, family, reg, intercept, logger=logger, reduce=reduce,
        fit_dtype=fit_dtype,
    )
    beta, info = STREAMED_SOLVERS[solver](
        obj, beta0, max_iter=max_iter, tol=tol, ckpt=ckpt, **kwargs
    )
    info["streamed"] = True
    info["n_blocks"] = stream.n_blocks
    info.update(_fused_stream_info(obj, stream, solver, fit_dtype))
    from .solvers import check_finite_result

    return check_finite_result(beta, info, solver)


def _fused_stream_info(obj, stream, solver, fit_dtype):
    """The fit-info fields describing the streamed pass flavor: the
    data-parallel width, whether the fused Pallas reducers carried the
    pass, WHY they did not (``fused_stream_reason`` — None when fused
    engaged, else e.g. "off-TPU" / "non-128-mult shard rows" /
    "per-block-path", so tpu_smoke can assert fused actually ran
    instead of silently falling back), and the resolved precision
    policy (streamed XLA flavors are f32-only — an auto policy that
    fell back must be on record). The flavor gate is checked for the
    reducer KIND this solver's passes actually run: newton's vgh tile
    budget (it also holds the (d, d) Hessian accumulator) can refuse a
    width the vg kernel accepts, and admm never uses the reducers at
    all."""
    out = {}
    use_sb = hasattr(stream, "use_superblocks") and stream.use_superblocks()
    out["stream_shards"] = int(
        getattr(stream, "sb_data_shards", lambda: 1)()
    ) if use_sb else 1
    # 2-D mesh audit trail (ISSUE 18): the model-axis width the X tiles
    # actually sharded over (1 on 1-D meshes and wherever tiling was
    # refused), and WHY a 2-D mesh didn't tile (None when it did or
    # when there was no model axis to tile over)
    out["stream_model_shards"] = int(
        getattr(stream, "sb_model_shards", lambda: 1)()
    ) if use_sb else 1
    out["model_tile_reason"] = getattr(stream, "model_tile_reason",
                                       None)
    # the device-resident sparse flavor's audit trail (ISSUE 13),
    # mirroring fused_stream_reason: None iff the bucketed-nnz scan
    # carried the pass, else why it fell back — "stream-sparse-off",
    # the plan's density/spill reason, "per-block-path" (K == 1),
    # "admm-local-newton", or "dense-source" for dense inputs
    sparse_sb = bool(getattr(stream, "sb_sparse", lambda: False)())
    plan = getattr(stream, "sparse_plan", None)
    src_reason = getattr(stream, "sparse_reason", None)
    if sparse_sb and solver != "admm":
        out["sparse_stream"] = True
        out["sparse_stream_reason"] = None
    else:
        out["sparse_stream"] = False
        if sparse_sb and solver == "admm":
            out["sparse_stream_reason"] = "admm-local-newton"
        elif plan is not None:
            out["sparse_stream_reason"] = "per-block-path"
        elif src_reason is not None:
            out["sparse_stream_reason"] = src_reason
        else:
            out["sparse_stream_reason"] = "dense-source"
    info_kind = {"newton": "vgh", "admm": None}.get(solver, "vg")
    if info_kind is None:
        mxu, fused, reason = None, False, "admm-local-newton"
    elif out["sparse_stream"]:
        # the fused Pallas kernels are a dense-slab feature; the sparse
        # scan runs its own XLA programs
        mxu, fused, reason = None, False, "sparse-stream"
    elif not use_sb:
        mxu, fused, reason = None, False, "per-block-path"
    else:
        mxu, fused, _, reason = obj._sb_flavor(info_kind)
    out["fused_stream"] = bool(fused)
    out["fused_stream_reason"] = reason
    from ...config import fit_dtype_info

    if fused and mxu is not None:
        out.update(fit_dtype_info(fit_dtype))
    elif fused:
        # fused but f32 (the vgh/Hessian reducer rejects bf16)
        out.update({"fit_dtype": "float32",
                    "fit_dtype_source": "hessian-f32"})
    else:
        out.update({"fit_dtype": "float32",
                    "fit_dtype_source": "streamed-xla"})
    return out


def solve_streamed_multi(solver, stream, n_rows, B0, family, reg, lam,
                         pmask, l1_ratio=0.5, intercept=True, max_iter=100,
                         tol=1e-6, logger=None, reduce=None,
                         fit_dtype=None, ckpt=None, **kwargs):
    """One-vs-rest streamed fit: ``B0``/result are (C, d); ``pmask`` is
    the per-class (d,) mask, tiled here. Every epoch reads the data
    ONCE for all classes (class-stacked block kernels); the host solvers
    run unchanged on the flattened (C*d,) vector."""
    if solver not in STREAMED_SOLVERS:
        raise ValueError(
            f"Unknown solver {solver!r}; options: {sorted(STREAMED_SOLVERS)}"
        )
    B0 = np.asarray(B0, np.float32)
    C, d = B0.shape
    pmask_t = np.tile(np.asarray(pmask, np.float32), C)
    obj = MulticlassStreamedObjective(
        stream, n_rows, jnp.asarray(lam, jnp.float32),
        jnp.asarray(pmask_t), l1_ratio, family, reg, intercept,
        logger=logger, n_classes=C, reduce=reduce, fit_dtype=fit_dtype,
    )
    beta, info = STREAMED_SOLVERS[solver](
        obj, B0.ravel(), max_iter=max_iter, tol=tol, ckpt=ckpt, **kwargs
    )
    info["streamed"] = True
    info["n_blocks"] = stream.n_blocks
    info["n_classes"] = C
    info.update(_fused_stream_info(obj, stream, solver, fit_dtype))
    from .solvers import check_finite_result

    beta, info = check_finite_result(np.asarray(beta), info, solver)
    return np.asarray(beta).reshape(C, d), info
