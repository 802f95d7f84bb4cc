"""Distributed linear algebra: TSQR and randomized SVD.

Reference equivalent: ``dask/array/linalg.py::tsqr`` /
``svd_compressed`` (SURVEY.md §2b row 2 and §3.3) — the backbone of
PCA/TruncatedSVD/spectral embedding. The TPU design (SURVEY.md §7 B1):

- ``tsqr``: a per-shard local factor inside ``shard_map``, ``all_gather``
  of the small R factors over ICI, replicated second-stage QR. The reference
  builds the same two-level shape as a task graph with inter-worker shuffles;
  here it is one XLA program. The local factor (``_local_qr``) is
  CholeskyQR2 — two Gram products, two (d, d) Cholesky factors, two
  products with a small triangular inverse: four passes over the panel —
  behind a guard computed from the small matrices it already has; a shard
  whose panel fails the guard (rank-deficient, zero, ill-conditioned,
  non-finite) takes ``jnp.linalg.qr`` inside the same program, and a shard
  with fewer rows than columns always does. XLA's QR is an unblocked
  Householder loop that re-reads the trailing panel once per column: a
  ``tsqr`` of a 2,097,152 x 74 panel on one v5e took 240.6 ms with it and
  takes 12.9 ms now (PERF.md section 6, PR 26).
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
contraction costs on the v5e is in PERF.md section 6 (PR 25). XLA's own
QR, Cholesky and triangular-solve expanders already multiply at ``highest``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.mesh import DATA_AXIS

_PRECISION = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=_PRECISION)


# CholeskyQR2's second pass restores O(u) orthonormality once the first
# pass leaves ||Q1^T Q1 - I||_2 <= 5/64 (Yamamoto, Nakatsukasa, Yanagisawa,
# Fukaya 2015, "Roundoff error analysis of the CholeskyQR2 algorithm",
# Lemma 3.1 with delta <= 1: in float32 a condition number up to ~1e3). The
# guard reads the Frobenius norm, which bounds the 2-norm from above at any
# width, so the constant is the analysis's own and belongs to no data set.
_CHOLQR2_MAX_DEFECT = 5.0 / 64.0


def _apply_inverse(x, r):
    """``x @ inv(r)`` for a small upper-triangular ``r``: the (d, d) inverse
    by a triangular solve, then one product over the panel."""
    eye = jnp.eye(r.shape[0], dtype=r.dtype)
    r_inv = jax.lax.linalg.triangular_solve(r, eye, left_side=True,
                                            lower=False)
    return _mm(x, r_inv)


def _local_qr(xs):
    """Reduced QR of one shard's (m, d) rows: ``(q, r, fell_back)`` with
    ``q`` (m, min(m, d)), ``r`` (min(m, d), d) upper-triangular.

    m >= d: CholeskyQR2, and Householder (``jnp.linalg.qr``) where its
    guard fails. The guard certifies the result after the fact: the first
    pass's ``q1`` satisfies ``q1 @ r1 = xs`` whatever the conditioning, so
    ``g2 = q1^T q1`` near the identity says that ``q1`` is nearly
    orthonormal and the second pass may finish. A singular Gram gives a
    non-finite ``r1`` or a ``g2`` far from the identity; ``fell_back`` says
    so. Zero rows give zero rows of ``q`` on either route. m < d: a Gram
    route cannot give the reduced shapes, so always Householder.
    """
    m, d = xs.shape
    if m < d:
        q, r = jnp.linalg.qr(xs)
        return q, r, jnp.zeros((), jnp.bool_)
    r1 = jnp.linalg.cholesky(_mm(xs.T, xs)).T
    q1 = _apply_inverse(xs, r1)
    g2 = _mm(q1.T, q1)
    r2 = jnp.linalg.cholesky(g2).T
    defect = jnp.linalg.norm(g2 - jnp.eye(d, dtype=g2.dtype))
    ok = (jnp.isfinite(r1).all() & jnp.isfinite(r2).all()
          & (defect <= _CHOLQR2_MAX_DEFECT))     # NaN compares False
    q, r = jax.lax.cond(
        ok,
        lambda: (_apply_inverse(q1, r2), _mm(r2, r1)),
        lambda: tuple(jnp.linalg.qr(xs)),
    )
    return q, r, ~ok


def tsqr_counted(x: jax.Array, mesh: Mesh, axis_name: str = DATA_AXIS):
    """:func:`tsqr` and, third, whether any shard's local factor fell back
    to Householder (int32 0 / 1, replicated)."""
    d = x.shape[1]

    def _tsqr(xs):
        # reduced QR: local R is (r, d) with r = min(m, d), so shards with
        # fewer rows than columns still compose correctly. Each shard
        # chooses its route alone (no collective inside either), and every
        # local (q1, r1) is a QR of its rows, so shards may differ
        q1, r1, fell_back = _local_qr(xs)  # (m, r), (r, d)
        r = r1.shape[0]
        rs = jax.lax.all_gather(r1, axis_name)  # (S, r, d) over ICI
        s = rs.shape[0]
        q2, r_final = jnp.linalg.qr(rs.reshape(s * r, d))
        i = jax.lax.axis_index(axis_name)
        q2_i = jax.lax.dynamic_slice_in_dim(q2, i * r, r)
        any_fell_back = jax.lax.pmax(fell_back.astype(jnp.int32), axis_name)
        return _mm(q1, q2_i), r_final, any_fell_back

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
        out_specs=(P(axis_name, None), P(), P()),
        check_vma=False,
    )(x)


def tsqr(x: jax.Array, mesh: Mesh, axis_name: str = DATA_AXIS):
    """Tall-skinny QR of a row-sharded (n, d) array; n >> d required.

    Returns (Q, R): Q row-sharded (n, d) with orthonormal columns, R (d, d)
    replicated and upper-triangular. Two levels: a local factor per shard
    (CholeskyQR2 where its guard holds, Householder where not:
    :func:`_local_qr`), then a small replicated QR of the gathered R's.
    """
    return tsqr_counted(x, mesh, axis_name)[:2]


def svd_tall(x: jax.Array, mesh: Mesh):
    """Exact SVD of a tall-skinny row-sharded (n, d) array via TSQR.

    Reference: ``da.linalg.svd`` = tsqr + small SVD of R (SURVEY.md §3.3).
    Returns (U row-sharded (n, d), s (d,), Vt (d, d) replicated, and how
    many of its tall QRs — one — had a shard fall back to Householder).
    """
    q, r, fell_back = tsqr_counted(x, mesh)
    u_r, s, vt = jnp.linalg.svd(r, full_matrices=False)
    return _mm(q, u_r), s, vt, fell_back


def randomized_range_finder(x, size, key, n_iter, mesh):
    """Orthonormal basis Q (n, size) approximately spanning range(x), and
    how many of its ``1 + n_iter`` tall QRs had a shard fall back to
    Householder.

    Halko et al. 2011 randomized range finder with power iterations and
    QR re-orthonormalization each half-iteration, as in
    ``da.linalg.svd_compressed`` (SURVEY.md §3.3).
    """
    d = x.shape[1]
    omega = jax.random.normal(key, (d, size), dtype=x.dtype)
    y = _mm(x, omega)  # psum-reduced matmul pass
    q, _, fallbacks = tsqr_counted(y, mesh)
    for _ in range(n_iter):
        z = _mm(x.T, q)  # (d, size); XLA inserts the ICI reduction
        qz, _ = jnp.linalg.qr(z)  # replicated small QR
        y = _mm(x, qz)
        q, _, fell_back = tsqr_counted(y, mesh)
        fallbacks = fallbacks + fell_back
    return q, fallbacks


def randomized_svd_sweeps(n_iter):
    """Products with ``x`` or ``x.T`` that one :func:`randomized_svd` makes,
    each a pass over all of x: the sketch ``x @ omega``, ``x.T @ q`` and
    ``x @ qz`` per power iteration, and ``q.T @ x``."""
    return 2 + 2 * int(n_iter)


def randomized_svd(x, n_components, key, mesh, n_oversamples=10, n_iter=4):
    """Halko randomized SVD of row-sharded (n, d) x.

    Returns (U (n, k) row-sharded, s (k,), Vt (k, d) replicated, and how
    many of its ``1 + n_iter`` tall QRs had a shard fall back to
    Householder).
    """
    size = min(n_components + n_oversamples, min(x.shape))
    q, fallbacks = randomized_range_finder(x, size, key, n_iter, mesh)
    b = _mm(q.T, x)  # (size, d), psum-reduced second data pass
    u_b, s, vt = jnp.linalg.svd(b, full_matrices=False)
    u = _mm(q, u_b)
    k = n_components
    return u[:, :k], s[:k], vt[:k], fallbacks


# Jitted entry points: the eager versions above dispatch one program per
# op — dozens of launches per SVD. These compile the whole decomposition
# into one program (one launch); mesh/sizes are static.
# count_recompiles is identity when jax.monitoring tracks compiles; on
# runtimes without it, the wrapper counts jit-cache growth instead.
from ..observability import count_recompiles


def _usv(fn):
    """``fn`` without its fallback count: the entry points' (U, s, Vt)."""
    @functools.wraps(fn)
    def usv(*args, **kwargs):
        return fn(*args, **kwargs)[:3]
    return usv


svd_tall_jit = count_recompiles(jax.jit(_usv(svd_tall), static_argnums=(1,)))
randomized_svd_jit = count_recompiles(jax.jit(
    _usv(randomized_svd), static_argnums=(1, 3, 4, 5)
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
