"""Plain float32 ``jax.numpy`` reference for PCA: the EXACT decomposition
the ``pca`` cells are checked against. A COPY of
``dask_ml_tpu/models/solvers/reference_pca.py`` (PR 25) kept with the
benchmark so that later PRs, which may edit the program, cannot move it.
Nothing the timed path uses is imported from here.

Independent of Halko's algorithm and of the program: no sketch, no QR, no
``shard_map``, no kernels, no masks. Mean, covariance accumulated over row
blocks (so it fits beside a chip-sized X), ``eigh`` of the d x d covariance
on the host in float64:

- ``mean = sum_i x_i / n``;
- ``C = sum_i (x_i - mean)(x_i - mean)^T / (n - 1)`` — each block's scatter
  is an f32 matmul under ``jax.default_matmul_precision("highest")`` (a TPU
  would otherwise multiply in one bf16 pass); the block sums are combined on
  the host in float64, and the rounding of the f32 mean used for centring is
  taken out exactly (``C = (S - n dd^T) / (n - 1)``, ``d`` the mean of the
  centred rows);
- ``lambda_1 >= ... >= lambda_d``, ``V`` from ``numpy.linalg.eigh(C)``;
  ``components = V[:, :k]^T``, ``explained_variance = lambda[:k]``, its ratio
  over ``trace(C)``; ``transform(x) = (x - mean) @ components^T``.

Departures from the published definitions, each on purpose: sklearn and
dask-ml take the SVD of the centred matrix, here the eigendecomposition of
its covariance (the same numbers: ``lambda_i = s_i^2 / (n - 1)``); signs
follow ``ops.linalg.svd_flip`` — the largest-|.| entry of each component is
positive (sklearn >= 1.5's V-based rule; dask-ml and older sklearn decide
from U, which would need an argmax over the sharded rows); no whitening.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def row_blocks(X, block_rows=65536):
    """A callable giving the row blocks of one array anew on every call
    (the covariance needs two passes)."""
    n = int(X.shape[0])
    return lambda: (X[i:i + block_rows] for i in range(0, n, block_rows))


def shard_blocks(arr, block_rows=65536):
    """The same for a row-sharded ``jax.Array``: the row blocks of every
    addressable shard in turn, each cut where it lives (a slice of the
    global array would gather the rows onto every device first). Padding
    rows, if the array has any, are rows like the others."""
    shards = sorted(arr.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    return lambda: (s.data[i:i + block_rows] for s in shards
                    for i in range(0, s.data.shape[0], block_rows))


@jax.jit
def _block_sum(xb):
    return jnp.sum(jnp.asarray(xb, jnp.float32), axis=0)


@jax.jit
def _block_scatter(xb, mean):
    xc = jnp.asarray(xb, jnp.float32) - mean
    with jax.default_matmul_precision("highest"):
        return jnp.sum(xc, axis=0), xc.T @ xc


def mean_cov(blocks):
    """(n, mean (d,), covariance (d, d)), float64, of the rows that
    ``blocks()`` yields: an iterable of (rows, d) arrays, taken twice."""
    n, total = 0, 0.0
    for xb in blocks():
        n += int(xb.shape[0])
        total = total + np.asarray(_block_sum(xb), np.float64)
    mean = total / n
    mean32 = jnp.asarray(mean, jnp.float32)
    resid, scatter = 0.0, 0.0
    for xb in blocks():
        r, s = _block_scatter(xb, mean32)
        resid = resid + np.asarray(r, np.float64)
        scatter = scatter + np.asarray(s, np.float64)
    delta = resid / n          # what centring by the f32 mean left over
    mean = np.asarray(mean32, np.float64) + delta
    cov = (scatter - n * np.outer(delta, delta)) / (n - 1)
    return n, mean, cov


def flip_signs(components):
    """Each row's largest-|.| entry positive (``linalg.svd_flip``'s rule)."""
    components = np.asarray(components, np.float64)
    big = np.argmax(np.abs(components), axis=1)
    signs = np.sign(components[np.arange(len(components)), big])
    return components * np.where(signs == 0, 1.0, signs)[:, None]


def pca_exact(blocks, k):
    """The exact PCA of the rows ``blocks()`` yields. A dict: ``n``,
    ``mean``, ``cov``, ``eigenvalues`` (all d, descending), ``components``
    (k, d), ``explained_variance``, ``explained_variance_ratio``."""
    n, mean, cov = mean_cov(blocks)
    lam, vecs = np.linalg.eigh(cov)
    lam, vecs = lam[::-1], vecs[:, ::-1]
    return {
        "n": n, "mean": mean, "cov": cov, "eigenvalues": lam,
        "components": flip_signs(vecs[:, :k].T),
        "explained_variance": lam[:k],
        "explained_variance_ratio": lam[:k] / np.trace(cov),
    }


@jax.jit
def transform(X, mean, components):
    """``(X - mean) @ components^T``, f32 multiplies."""
    xc = jnp.asarray(X, jnp.float32) - jnp.asarray(mean, jnp.float32)
    with jax.default_matmul_precision("highest"):
        return xc @ jnp.asarray(components, jnp.float32).T
