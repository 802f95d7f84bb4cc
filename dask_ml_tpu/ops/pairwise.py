"""Pairwise distance / kernel primitives.

Reference equivalent: ``dask_ml/metrics/pairwise.py``, which maps
sklearn's Cython ``pairwise_distances_argmin_min`` over blocks (SURVEY.md
§3.1). TPU design: one fused XLA expression — the ``x @ y.T`` term rides the
MXU, the norm/argmin epilogue fuses into it, so the "distance + argmin"
pattern the reference pays a Cython call per block for becomes a single
compiled kernel over the whole sharded array.

``y`` (centers / anchor points) is small and replicated; ``x`` may be the
padded row-sharded data — callers mask invalid rows on the results.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def row_norms_sq(x):
    return jnp.sum(x * x, axis=-1)


def euclidean_distances_sq(x, y, mxu_dtype=None):
    """Squared euclidean distances (n, m) via the MXU-friendly expansion
    ||x||^2 - 2 x.y + ||y||^2, clamped at 0 against cancellation.

    ``mxu_dtype`` (e.g. ``jnp.bfloat16``): run ONLY the cross-term
    matmul — where the FLOPs are — at that dtype with f32 accumulation
    (``preferred_element_type``), twice the MXU rate; the norms and the
    epilogue stay at the input precision. Relative distance error is
    bounded by bf16's input rounding (~4e-3) — argmin assignments are
    robust to it, which is why KMeans exposes this through
    ``config.dtype`` while exact-distance APIs default it off."""
    if mxu_dtype is not None:
        xy = jnp.matmul(x.astype(mxu_dtype), y.astype(mxu_dtype).T,
                        preferred_element_type=jnp.float32)
    else:
        xy = x @ y.T
    d2 = (
        row_norms_sq(x)[:, None]
        - 2.0 * xy
        + row_norms_sq(y)[None, :]
    )
    return jnp.maximum(d2, 0.0)


def euclidean_distances(x, y):
    return jnp.sqrt(euclidean_distances_sq(x, y))


def pairwise_distances_argmin_min(x, y):
    """(labels, min_dists) of nearest row of y for each row of x.

    The KMeans hot kernel (SURVEY.md §3.1 🔥): distances + argmin fuse into
    one program instead of the reference's per-block Cython call.
    """
    d2 = euclidean_distances_sq(x, y)
    labels = jnp.argmin(d2, axis=1)
    return labels, jnp.sqrt(jnp.min(d2, axis=1))


def manhattan_distances(x, y):
    """L1 distances (n, m). No MXU path exists for |x-y| sums; the
    broadcasted form below is fine because y (anchors) is small."""
    return jnp.sum(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)


def cosine_distances(x, y):
    xn = x / jnp.maximum(jnp.sqrt(row_norms_sq(x))[:, None], 1e-12)
    yn = y / jnp.maximum(jnp.sqrt(row_norms_sq(y))[:, None], 1e-12)
    return jnp.clip(1.0 - xn @ yn.T, 0.0, 2.0)


def linear_kernel(x, y):
    return x @ y.T


def recentred_distances_sq(x, y, precision=jax.lax.Precision.HIGHEST):
    """Squared euclidean distances (n, m) to the FEW rows ``y``, exact in
    float32 where the expansion is not: with features of order one and 256
    of them ``||x||^2`` is ~256 beside a squared distance of ~2, so the
    expansion subtracts two large numbers, and a TPU's default cross term
    (one bf16 pass) is off by a tenth of that distance. Distances do not
    change when both sides are shifted by ``m = mean(y)``, so the expansion
    is taken of ``x - m`` and ``y - m``, whose norms are of the order of the
    distances themselves: ``||x - m||^2`` as a fused sum of squared
    differences (no shifted copy of x is made) and the cross term as
    ``x @ (y - m)^T - m @ (y - m)^T`` at ``precision``."""
    m = jnp.mean(y, axis=0)
    yc = y - m
    cross = jnp.matmul(x, yc.T, precision=precision) \
        - jnp.matmul(m, yc.T, precision=precision)[None, :]
    d2 = (jnp.sum((x - m) ** 2, axis=-1)[:, None] - 2.0 * cross
          + row_norms_sq(yc)[None, :])
    return jnp.maximum(d2, 0.0)


def rbf_kernel(x, y, gamma=None, precision=None):
    """``exp(-gamma ||x - y||^2)`` by the expansion at the backend's
    default precision; ``precision`` given, by :func:`recentred_distances_sq`
    at that precision (what ``SpectralClustering`` asks for)."""
    if gamma is None:
        gamma = 1.0 / x.shape[-1]
    if precision is not None:
        return jnp.exp(-gamma * recentred_distances_sq(x, y, precision))
    return jnp.exp(-gamma * euclidean_distances_sq(x, y))


def polynomial_kernel(x, y, degree=3, gamma=None, coef0=1.0):
    if gamma is None:
        gamma = 1.0 / x.shape[-1]
    return (gamma * (x @ y.T) + coef0) ** degree


def sigmoid_kernel(x, y, gamma=None, coef0=1.0):
    if gamma is None:
        gamma = 1.0 / x.shape[-1]
    return jnp.tanh(gamma * (x @ y.T) + coef0)


_PAIRWISE_METRICS = {
    "euclidean": euclidean_distances,
    "l2": euclidean_distances,
    "sqeuclidean": euclidean_distances_sq,
    "manhattan": manhattan_distances,
    "l1": manhattan_distances,
    "cityblock": manhattan_distances,
    "cosine": cosine_distances,
}

PAIRWISE_KERNEL_FUNCTIONS = {
    "linear": linear_kernel,
    "rbf": rbf_kernel,
    "polynomial": polynomial_kernel,
    "sigmoid": sigmoid_kernel,
}


def _unwrap_x(x):
    """Padded row-sharded device array; callers mask padding rows of the
    result (slicing here would force a reshard of the big operand)."""
    return x.data if hasattr(x, "data") and hasattr(x, "n_rows") else x


def _unwrap_y(y):
    """y is the small in-memory operand: slice off padding rows so the
    result has no phantom anchor columns."""
    if hasattr(y, "data") and hasattr(y, "n_rows"):
        return y.data[: y.n_rows]
    return y


def pairwise_distances(x, y, metric="euclidean", **kwargs):
    """Distance matrix (n, m) between ``x`` and in-memory ``y``.

    Ref: ``dask_ml/metrics/pairwise.py::pairwise_distances`` — the reference
    maps sklearn's function over blocks with Y held in memory; here the whole
    matrix is one fused XLA program (the dot term rides the MXU). ``x`` may
    be a plain array or a ShardedArray (unwrapped to its padded device array;
    callers mask padding rows of the result — ``y``'s padding IS sliced off).
    ``metric`` may be a name or a callable ``f(x, y, **kwargs)``.
    """
    x, y = _unwrap_x(x), _unwrap_y(y)
    if callable(metric):
        return metric(x, y, **kwargs)
    try:
        fn = _PAIRWISE_METRICS[metric]
    except KeyError:
        raise ValueError(
            f"unsupported metric {metric!r}; one of "
            f"{sorted(_PAIRWISE_METRICS)} or a callable"
        ) from None
    return fn(x, y, **kwargs)


def pairwise_kernels(x, y, metric="linear", **kwargs):
    """Kernel matrix, mirroring sklearn/dask-ml ``pairwise_kernels``."""
    x, y = _unwrap_x(x), _unwrap_y(y)
    if callable(metric):
        return metric(x, y, **kwargs)
    try:
        fn = PAIRWISE_KERNEL_FUNCTIONS[metric]
    except KeyError:
        raise ValueError(
            f"unsupported kernel {metric!r}; one of "
            f"{sorted(PAIRWISE_KERNEL_FUNCTIONS)} or a callable"
        ) from None
    return fn(x, y, **kwargs)
