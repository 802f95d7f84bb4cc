"""Distributed linear algebra: TSQR and randomized SVD.

Reference equivalent: ``dask/array/linalg.py::tsqr`` /
``svd_compressed`` (SURVEY.md §2b row 2 and §3.3) — the backbone of
PCA/TruncatedSVD/spectral embedding. The TPU design (SURVEY.md §7 B1):

- ``tsqr``: per-shard ``jnp.linalg.qr`` inside ``shard_map``, ``all_gather``
  of the small R factors over ICI, replicated second-stage QR. The reference
  builds the same two-level shape as a task graph with inter-worker shuffles;
  here it is one XLA program.
- ``randomized_svd``: Halko range-finder with power iterations, each pass a
  psum-reduced matmul; the final small SVD is replicated (the reference runs
  it on the client).

Inputs are *padded* row-sharded arrays whose padding rows are exactly zero
(zero rows leave R and the spanned range unchanged), so no masks are needed
here — callers zero padding, e.g. after mean-centering.

Precision: every contraction here asks for ``Precision.HIGHEST``. A TPU
multiplies "f32" operands in one bf16 pass by default, which rounds the
small replicated factors (``q2_i``, ``u_b``, ``qz``) the same way for every
row: Q then loses orthonormality at ~2e-4 and the singular values inherit
it, whatever the row count. The chain is float32 as stated; what each
contraction costs on the v5e is in PERF.md section 6 (PR 25). XLA's own QR
expander already multiplies at ``highest``.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.mesh import DATA_AXIS

_PRECISION = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=_PRECISION)


def tsqr(x: jax.Array, mesh: Mesh, axis_name: str = DATA_AXIS):
    """Tall-skinny QR of a row-sharded (n, d) array; n >> d required.

    Returns (Q, R): Q row-sharded (n, d) with orthonormal columns, R (d, d)
    replicated and upper-triangular.
    """
    d = x.shape[1]

    def _tsqr(xs):
        # reduced QR: local R is (r, d) with r = min(m, d), so shards with
        # fewer rows than columns still compose correctly
        q1, r1 = jnp.linalg.qr(xs)  # (m, r), (r, d)
        r = r1.shape[0]
        rs = jax.lax.all_gather(r1, axis_name)  # (S, r, d) over ICI
        s = rs.shape[0]
        q2, r_final = jnp.linalg.qr(rs.reshape(s * r, d))
        i = jax.lax.axis_index(axis_name)
        q2_i = jax.lax.dynamic_slice_in_dim(q2, i * r, r)
        return _mm(q1, q2_i), r_final

    # check_vma=False, as at every shard_map site in this package: the
    # bodies do their own cross-shard accounting (explicit psum /
    # all_gather into replicated out_specs), which the varying-axes type
    # system would otherwise redo — under check_vma=True the transpose
    # of a replicated input's implicit pvary is a psum, so a body that
    # autodiffs a replicated carry and then psums gets a D-fold gradient
    return jax.shard_map(
        _tsqr,
        mesh=mesh,
        in_specs=P(axis_name, None),
        out_specs=(P(axis_name, None), P()),
        check_vma=False,
    )(x)


def svd_tall(x: jax.Array, mesh: Mesh):
    """Exact SVD of a tall-skinny row-sharded (n, d) array via TSQR.

    Reference: ``da.linalg.svd`` = tsqr + small SVD of R (SURVEY.md §3.3).
    Returns (U row-sharded (n, d), s (d,), Vt (d, d) replicated).
    """
    q, r = tsqr(x, mesh)
    u_r, s, vt = jnp.linalg.svd(r, full_matrices=False)
    return _mm(q, u_r), s, vt


def randomized_range_finder(x, size, key, n_iter, mesh):
    """Orthonormal basis Q (n, size) approximately spanning range(x).

    Halko et al. 2011 randomized range finder with power iterations and
    QR re-orthonormalization each half-iteration, as in
    ``da.linalg.svd_compressed`` (SURVEY.md §3.3).
    """
    d = x.shape[1]
    omega = jax.random.normal(key, (d, size), dtype=x.dtype)
    y = _mm(x, omega)  # psum-reduced matmul pass
    q, _ = tsqr(y, mesh)
    for _ in range(n_iter):
        z = _mm(x.T, q)  # (d, size); XLA inserts the ICI reduction
        qz, _ = jnp.linalg.qr(z)  # replicated small QR
        y = _mm(x, qz)
        q, _ = tsqr(y, mesh)
    return q


def randomized_svd_sweeps(n_iter):
    """Products with ``x`` or ``x.T`` that one :func:`randomized_svd` makes,
    each a pass over all of x: the sketch ``x @ omega``, ``x.T @ q`` and
    ``x @ qz`` per power iteration, and ``q.T @ x``."""
    return 2 + 2 * int(n_iter)


def randomized_svd(x, n_components, key, mesh, n_oversamples=10, n_iter=4):
    """Halko randomized SVD of row-sharded (n, d) x.

    Returns (U (n, k) row-sharded, s (k,), Vt (k, d) replicated).
    """
    size = min(n_components + n_oversamples, min(x.shape))
    q = randomized_range_finder(x, size, key, n_iter, mesh)
    b = _mm(q.T, x)  # (size, d), psum-reduced second data pass
    u_b, s, vt = jnp.linalg.svd(b, full_matrices=False)
    u = _mm(q, u_b)
    k = n_components
    return u[:, :k], s[:k], vt[:k]


# Jitted entry points: the eager versions above dispatch one program per
# op — dozens of launches per SVD. These compile the whole decomposition
# into one program (one launch); mesh/sizes are static.
# count_recompiles is identity when jax.monitoring tracks compiles; on
# runtimes without it, the wrapper counts jit-cache growth instead.
from ..observability import count_recompiles

svd_tall_jit = count_recompiles(jax.jit(svd_tall, static_argnums=(1,)))
randomized_svd_jit = count_recompiles(jax.jit(
    randomized_svd, static_argnums=(1, 3, 4, 5)
))


def svd_flip(u, vt):
    """Deterministic SVD signs, V-based (matches sklearn's
    ``svd_flip(u_based_decision=False)``): flip so each row of Vt has its
    largest-|.| entry positive. V-based avoids an argmax over the sharded
    row axis of U."""
    max_abs = jnp.argmax(jnp.abs(vt), axis=1)
    signs = jnp.sign(vt[jnp.arange(vt.shape[0]), max_abs])
    signs = jnp.where(signs == 0, 1.0, signs).astype(vt.dtype)
    return u * signs[None, :], vt * signs[:, None]
