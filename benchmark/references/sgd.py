"""The plain reference for the ``sgd`` family: minibatch gradient steps of
a linear model over the blocks of a resident table, one step a block, in
straightforward ``jax.numpy`` — float32, every product under
``jax.default_matmul_precision("highest")`` (a TPU multiplies f32 operands in
bf16 otherwise), the gradient written out by hand, no scan, no grid, no code
of ``dask_ml_tpu/models/``. It works block by block, so beside X it holds one
block's temporaries, and it sums over a block's rows in chunks of 1,024
whose partial sums the host adds in float64: the reference's own sums are
then exact to float32's last bits whatever the block's size.

One step on block ``b`` (rows ``[b S, min((b + 1) S, n))``, ``m`` of them)
at clock ``t`` (``t = 1`` at the first step of the first pass, running on
across passes):

    eta  = X_b w[:-1] + w[-1]
    L    = sum_i l(eta_i, y_i) / m + 0.5 alpha l2 ||w[:-1]||^2
    w   <- w - lr_t grad L,      lr_t = eta0 / t^power_t  ("invscaling")
    w[:-1] <- soft-threshold(w[:-1], lr_t alpha l1)        (the l1 part)

with ``l`` the log loss ``softplus(eta) - y eta``, the hinge
``max(0, 1 - (2 y - 1) eta)`` or the squared error ``0.5 (eta - y)^2``.
Weights start at zero.

``design_dtype`` states a precision as data. ``None``: the float32
description above. ``"bfloat16"``: each block of X, and the copy of
``w[:-1]`` that enters ``eta``, are ROUNDED to bfloat16 first (the values
only: they are then float32 numbers again); every product, sum, update,
the clock and the penalty stay float32 — what ``dtype="auto"`` states on a
TPU. ``lower`` names ONE further rounding, for showing that a limit fails
a precision below the stated one: ``"accumulate"`` (the two products'
results rounded to bfloat16) or ``"update"`` (the weights rounded to
bfloat16 after every step).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LOSSES = ("log_loss", "hinge", "squared_error")


def _round(a, dtype):
    """``a`` (float32) rounded to ``dtype``'s values, still float32. By
    ``lax.reduce_precision``: a cast there and back is an "excess
    precision" the TPU compiler is free to drop (it did, on the first chip
    run of PR 30: the two precisions read alike to the last bit)."""
    if dtype is None:
        return a
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(a, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _residual(eta, y, loss):
    """d l / d eta, per row."""
    if loss == "log_loss":
        return jax.nn.sigmoid(eta) - y
    if loss == "hinge":
        s = 2.0 * y - 1.0
        return jnp.where(s * eta < 1.0, -s, 0.0)
    if loss == "squared_error":
        return eta - y
    raise ValueError(f"loss must be one of {LOSSES}, got {loss!r}")


def _per_row(eta, y, loss):
    if loss == "log_loss":
        return jax.nn.softplus(eta) - y * eta
    if loss == "hinge":
        return jnp.maximum(0.0, 1.0 - (2.0 * y - 1.0) * eta)
    if loss == "squared_error":
        return 0.5 * (eta - y) ** 2
    raise ValueError(f"loss must be one of {LOSSES}, got {loss!r}")


CHUNK = 1024


@functools.partial(jax.jit, static_argnames=("loss", "design_dtype",
                                             "fit_intercept", "lower"))
def _chunk_sums(w, Xb, yb, *, loss, design_dtype=None, fit_intercept=True,
                lower=None):
    """The two sums of a step over one block, ``sum_i r_i x_i`` and
    ``sum_i r_i``, left as partial sums of ``CHUNK`` rows, ``(C, d)`` and
    ``(C,)``: a float32 sum runs over 1,024 terms at most, and the caller
    adds the partial sums in float64 (one float32 sum over a block's
    524,288 rows read 9e-8 from this on the chip — tolerances_sgd.py —
    so what the comparison finds is not the reference's sums)."""
    Xb = _round(Xb.astype(jnp.float32), design_dtype)
    b0 = w[-1] if fit_intercept else jnp.float32(0.0)
    eta = jnp.dot(Xb, _round(w[:-1], design_dtype))
    if lower == "accumulate":
        eta = _round(eta, jnp.bfloat16)
    r = _residual(eta + b0, yb.astype(jnp.float32), loss)
    pad = -Xb.shape[0] % CHUNK
    if pad:                      # rows of zeros add nothing to either sum
        Xb, r = jnp.pad(Xb, ((0, pad), (0, 0))), jnp.pad(r, (0, pad))
    r = r.reshape(-1, CHUNK)
    Xc = Xb.reshape(r.shape[0], CHUNK, Xb.shape[1])
    return jnp.sum(r[:, :, None] * Xc, axis=1), jnp.sum(r, axis=1)


def _bf16(a):
    """A float32 host array rounded to bfloat16's values."""
    return np.asarray(a, np.float32).astype(jnp.bfloat16).astype(np.float32)


def step(w, Xb, yb, lr, alpha, l2, l1, *, loss, design_dtype=None,
         fit_intercept=True, lower=None):
    """One update of the host float32 ``w`` (``(d + 1,)``, intercept last)
    on one device block: the block's sums from the device in chunks, added
    in float64, the update itself in float32."""
    w = np.asarray(w, np.float32)
    lr, alpha, l2, l1 = (np.float32(v) for v in (lr, alpha, l2, l1))
    sums, rsum = _chunk_sums(jnp.asarray(w), Xb, yb, loss=loss,
                             design_dtype=design_dtype,
                             fit_intercept=fit_intercept, lower=lower)
    m = float(Xb.shape[0])
    g_data = (np.asarray(sums, np.float64).sum(axis=0) / m).astype(np.float32)
    if lower == "accumulate":
        g_data = _bf16(g_data)
    g_coef = g_data + alpha * l2 * w[:-1]
    g_b = np.float32(np.asarray(rsum, np.float64).sum() / m) \
        if fit_intercept else np.float32(0.0)
    coef = w[:-1] - lr * g_coef
    thr = lr * alpha * l1
    coef = np.sign(coef) * np.maximum(np.abs(coef) - thr, np.float32(0.0))
    w = np.r_[coef, w[-1] - lr * g_b].astype(np.float32)
    return _bf16(w) if lower == "update" else w


def learning_rate(t, schedule, eta0, power_t, alpha):
    t = max(int(t), 1)
    if schedule == "constant":
        return eta0
    if schedule == "invscaling":
        return eta0 / t ** power_t
    if schedule == "optimal":
        return 1.0 / (alpha * (1e3 + t))
    raise ValueError(f"unknown learning_rate {schedule!r}")


def fit(X, y, orders, block_rows, n_rows=None, *, loss="log_loss",
        alpha=1e-4, l2=1.0, l1=0.0, eta0=0.01, power_t=0.25,
        schedule="invscaling", fit_intercept=True, design_dtype=None,
        lower=None):
    """``(w, t)`` after the passes ``orders`` (one sequence of block
    indices a pass) over the first ``n_rows`` rows of the device arrays
    ``X (>= n, d)`` / ``y (>= n,)`` (0/1 targets, or real ones for the
    squared error), in blocks of ``block_rows``."""
    n = int(X.shape[0] if n_rows is None else n_rows)
    S = int(block_rows)
    w = np.zeros(X.shape[1] + 1, np.float32)
    t = 0
    with jax.default_matmul_precision("highest"):
        for order in orders:
            for b in order:
                lo, hi = int(b) * S, min((int(b) + 1) * S, n)
                if hi <= lo:
                    raise ValueError(
                        f"block {b} of {S} rows lies past row {n}")
                t += 1
                lr = learning_rate(t, schedule, eta0, power_t, alpha)
                w = step(w, X[lo:hi], y[lo:hi], lr, alpha, l2, l1,
                         loss=loss, design_dtype=design_dtype,
                         fit_intercept=fit_intercept, lower=lower)
    return w, t


@functools.partial(jax.jit, static_argnames=("loss",))
def _loss_sum(w, Xb, yb, loss):
    eta = jnp.dot(Xb.astype(jnp.float32), w[:-1]) + w[-1]
    return jnp.sum(_per_row(eta, yb.astype(jnp.float32), loss))


def objective(w, X, y, block_rows, n_rows=None, *, loss="log_loss",
              alpha=1e-4, l2=1.0):
    """The float32 loss over ALL ``n_rows`` rows at ``w``: the mean data
    term (block sums combined in float64) + ``0.5 alpha l2 ||coef||^2``."""
    n = int(X.shape[0] if n_rows is None else n_rows)
    w = jnp.asarray(w, jnp.float32)
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for lo in range(0, n, int(block_rows)):
            hi = min(lo + int(block_rows), n)
            total += float(_loss_sum(w, X[lo:hi], y[lo:hi], loss))
    coef = np.asarray(w[:-1], np.float64)
    return total / n + 0.5 * alpha * l2 * float(coef @ coef)


def decision(X, w):
    """``X w[:-1] + w[-1]`` in float32 at ``highest``."""
    w = jnp.asarray(w, jnp.float32)
    with jax.default_matmul_precision("highest"):
        return jnp.dot(X.astype(jnp.float32), w[:-1]) + w[-1]
