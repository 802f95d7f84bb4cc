"""Plain float32 ``jax.numpy`` reference for the Nyström spectral embedding:
what every ``SpectralClustering`` fit (any mesh, any row count) is checked
against.

Independent of the program: no kernels, no TSQR, no ``shard_map``, no masks,
no KMeans restarts, and the squared distances by EXACT DIFFERENCES —
``sum_j (x_j - z_j)^2``, never the expansion ``||x||^2 - 2 x.z + ||z||^2``,
whose cancellation is the thing under test. It takes the landmark rows'
indices as data (``SpectralClustering.landmarks_``) and works in row blocks,
so that it fits beside a chip-sized X. The equations (``models/spectral.py``
states the same): Z the c landmark rows, k(x, z) = exp(-gamma ||x - z||^2),

- pass 1: ``B^T 1`` (the column sums of B = k(X, Z));
  A = k(Z, Z) + ``NYSTROM_JITTER`` I, its ``eigh`` in float64 on the host
  gives A^+ and A^(-1/2) (eigenvalues under the jitter count as it);
- pass 2: deg = B A^+ (B^T 1) (a degree at or under ``TINY`` counts as 1),
  G = diag(deg)^(-1/2) B A^(-1/2), and ``G^T G`` accumulated over the blocks
  in float64 on the host; ``eigh`` of that (c, c) Gram in float64 gives the
  right singular vectors W and the singular values S = sqrt(lambda);
- pass 3: E = the rows of ``G W[:, :k] / S[:k]``, each scaled to unit length
  (a row at or under ``TINY`` stays).

Each block's products are f32 matmuls under
``jax.default_matmul_precision("highest")`` (a TPU would otherwise multiply
in one bf16 pass). For labels it has no KMeans: ``cluster_points`` are the
row-normalised means of E over the rows of each given group, and
``nearest_point`` assigns a row to the nearest of them.

The control ``cross="bf16"`` computes the affinity the way a default TPU
matmul would — the expansion, with the cross term's operands rounded to
bfloat16 — and everything else as above: what a program that asked for less
than the precision it states would give. The check must fail it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..spectral import NYSTROM_JITTER, TINY


def row_blocks(X, block_rows=16384):
    """A callable giving the row blocks of one array anew on every call
    (the embedding takes three passes)."""
    n = int(X.shape[0])
    return lambda: (X[i:i + block_rows] for i in range(0, n, block_rows))


def shard_blocks(arr, n_rows=None, block_rows=16384):
    """The same for a row-sharded ``jax.Array``: the row blocks of every
    addressable shard in turn, each cut where it lives (a slice of the
    global array would gather the rows onto every device first), up to the
    first ``n_rows`` rows (the array's padding rows follow them)."""
    shards = sorted(arr.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    total = int(arr.shape[0]) if n_rows is None else int(n_rows)

    def blocks():
        left = total
        for s in shards:
            m = min(int(s.data.shape[0]), left)
            for i in range(0, m, block_rows):
                yield s.data[i:min(i + block_rows, m)]
            left -= m

    return blocks


def take_rows(blocks, idx):
    """Rows ``idx`` (global indices) of the rows ``blocks()`` yields, as one
    host array, in ``idx``'s order."""
    idx = np.asarray(idx, np.int64)
    out, start = {}, 0
    for xb in blocks():
        m = int(xb.shape[0])
        for j in np.flatnonzero((idx >= start) & (idx < start + m)):
            out[int(j)] = np.asarray(xb[int(idx[j]) - start])
        start += m
    if len(out) != len(idx):
        raise ValueError(f"{len(idx) - len(out)} landmark indices lie "
                         f"outside the {start} rows")
    return np.stack([out[j] for j in range(len(idx))])


@functools.partial(jax.jit, static_argnames=("cross",))
def affinity(xb, Z, gamma, cross="exact"):
    """k(x, z) of a block against the landmarks, float32. ``exact``: squared
    distances as sums of squared differences. ``bf16``: the control."""
    xb, Z = jnp.asarray(xb, jnp.float32), jnp.asarray(Z, jnp.float32)
    if cross == "exact":
        d2 = jnp.sum((xb[:, None, :] - Z[None, :, :]) ** 2, axis=-1)
    elif cross == "bf16":
        xz = jnp.matmul(xb.astype(jnp.bfloat16), Z.astype(jnp.bfloat16).T,
                        preferred_element_type=jnp.float32)
        d2 = jnp.maximum(jnp.sum(xb * xb, axis=1)[:, None] - 2.0 * xz
                         + jnp.sum(Z * Z, axis=1)[None, :], 0.0)
    else:
        raise ValueError(f"unknown cross {cross!r}")
    return jnp.exp(-gamma * d2)


@functools.partial(jax.jit, static_argnames=("cross",))
def _col_sums(xb, Z, gamma, cross):
    return jnp.sum(affinity(xb, Z, gamma, cross), axis=0)


def _g_block(xb, Z, gamma, pinv_colsum, inv_sqrt, cross):
    with jax.default_matmul_precision("highest"):
        B = affinity(xb, Z, gamma, cross)
        deg = B @ pinv_colsum
        deg = jnp.where(deg > TINY, deg, 1.0)
        return (B / jnp.sqrt(deg)[:, None]) @ inv_sqrt


@functools.partial(jax.jit, static_argnames=("cross",))
def _gram_block(xb, Z, gamma, pinv_colsum, inv_sqrt, cross):
    G = _g_block(xb, Z, gamma, pinv_colsum, inv_sqrt, cross)
    with jax.default_matmul_precision("highest"):
        return G.T @ G


@functools.partial(jax.jit, static_argnames=("cross",))
def _embed_block(xb, Z, gamma, pinv_colsum, inv_sqrt, proj, cross):
    G = _g_block(xb, Z, gamma, pinv_colsum, inv_sqrt, cross)
    with jax.default_matmul_precision("highest"):
        E = G @ proj
    norms = jnp.linalg.norm(E, axis=1, keepdims=True)
    return E / jnp.where(norms > TINY, norms, 1.0)


def embedding(blocks, landmarks, gamma, k, cross="exact"):
    """The Nyström spectral embedding of the rows ``blocks()`` yields (an
    iterable of (rows, d) arrays, taken four times: the landmark rows, then
    the three passes). A dict: ``n``, ``E`` (n, k) float32 on the host,
    ``singular_values`` (all c, descending, float64), ``gap`` (S[k - 1] /
    S[k]: what makes the k-dimensional subspace well defined)."""
    gamma = np.float32(gamma)
    Z = jnp.asarray(take_rows(blocks, landmarks), jnp.float32)
    c = int(Z.shape[0])
    n, colsum = 0, np.zeros(c)
    for xb in blocks():
        n += int(xb.shape[0])
        colsum += np.asarray(_col_sums(xb, Z, gamma, cross), np.float64)
    A = np.asarray(affinity(Z, Z, gamma, cross), np.float64) \
        + NYSTROM_JITTER * np.eye(c)
    w, V = np.linalg.eigh(A)
    w = np.maximum(w, NYSTROM_JITTER)
    pinv_colsum = jnp.asarray((V / w) @ (V.T @ colsum), jnp.float32)
    inv_sqrt = jnp.asarray((V / np.sqrt(w)) @ V.T, jnp.float32)
    gram = np.zeros((c, c))
    for xb in blocks():
        gram += np.asarray(_gram_block(xb, Z, gamma, pinv_colsum, inv_sqrt,
                                       cross), np.float64)
    lam, W = np.linalg.eigh(gram)
    lam, W = lam[::-1], W[:, ::-1]
    S = np.sqrt(np.maximum(lam, 0.0))
    proj = jnp.asarray(W[:, :k] / S[:k], jnp.float32)
    E = np.concatenate([
        np.asarray(_embed_block(xb, Z, gamma, pinv_colsum, inv_sqrt, proj,
                                cross)) for xb in blocks()])
    return {"n": n, "E": E, "singular_values": S,
            "gap": float(S[k - 1] / S[k]) if k < c else np.inf}


def cluster_points(E, groups, k):
    """(k, k): the mean of E over the rows of each group 0 .. k - 1, scaled
    to unit length (a group without rows gives a zero point)."""
    E, groups = np.asarray(E, np.float64), np.asarray(groups)
    pts = np.stack([E[groups == g].mean(axis=0) if np.any(groups == g)
                    else np.zeros(E.shape[1]) for g in range(k)])
    norms = np.linalg.norm(pts, axis=1, keepdims=True)
    return pts / np.where(norms > TINY, norms, 1.0)


def nearest_point(E, points):
    """The index of the nearest of ``points`` for every row of E, by exact
    differences in float64."""
    E, points = np.asarray(E, np.float64), np.asarray(points, np.float64)
    d2 = ((E[:, None, :] - points[None, :, :]) ** 2).sum(axis=-1)
    return d2.argmin(axis=1)
