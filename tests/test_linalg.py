"""Distributed linalg tests: TSQR, tall SVD, randomized SVD (SURVEY.md §7 B1).

Oracle = numpy.linalg on the gathered array, the same "small-data parity"
contract the reference uses with sklearn (SURVEY.md §4).
"""

import jax
import numpy as np
import pytest

from dask_ml_tpu.ops import linalg
from dask_ml_tpu.parallel import ShardedArray, default_mesh


def _sharded(n, d, seed=0, dtype=np.float32):
    x = np.random.RandomState(seed).randn(n, d).astype(dtype)
    return x, ShardedArray.from_array(x, default_mesh())


# (rows, columns): the small panel, and panels as tall and as wide (the
# benchmark's 74-column sketch; a width past one 128-lane tile) as a CPU
# test affords — the local factor is XLA's own column loop, whose length is
# the width, so there is no blocking of ours to cross
@pytest.mark.parametrize("n,d", [(96, 6), (20001, 74), (8192, 130)])
def test_tsqr_reconstruction_and_orthonormality(n, d):
    x, sx = _sharded(n, d)
    q, r = linalg.tsqr(sx.data, sx.mesh)
    q, r = np.asarray(q, np.float64)[:n], np.asarray(r, np.float64)
    np.testing.assert_allclose(q @ r, x, atol=1e-4)
    # ||Q^T Q - I||: f32 Householder, ~1e-6 at these heights (2.5e-5 at
    # 2,097,152 rows on the v5e, PERF.md)
    assert np.max(np.abs(q.T @ q - np.eye(d))) <= 2e-5
    assert np.allclose(r, np.triu(r))


def test_randomized_svd_sweeps_counts_the_products_with_x():
    """Counted beside the algorithm: the sketch, two per power iteration,
    the projection."""
    assert [linalg.randomized_svd_sweeps(q) for q in (0, 2, 4)] == [2, 6, 10]
    x, sx = _sharded(256, 24)
    jaxpr = jax.make_jaxpr(
        lambda a, k: linalg.randomized_svd(a, 4, k, sx.mesh, n_iter=2)
    )(sx.data, jax.random.PRNGKey(0))
    big = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"
           and any(v.aval.shape in (sx.data.shape, sx.data.shape[::-1])
                   for v in e.invars)]
    assert len(big) == linalg.randomized_svd_sweeps(2)
    assert all(e.params["precision"] is not None for e in big)


def test_tsqr_with_zero_padding_rows():
    # padded rows are zero; Q rows stay zero and R is unaffected
    mesh = default_mesh()
    x = np.random.RandomState(3).randn(33, 4).astype(np.float32)
    sx = ShardedArray.from_array(x, mesh)
    q, r = linalg.tsqr(sx.data, mesh)
    q = np.asarray(q)
    np.testing.assert_allclose(q[:33] @ np.asarray(r), x, atol=1e-4)
    np.testing.assert_allclose(q[33:], 0.0, atol=1e-5)


def test_svd_tall_matches_numpy():
    x, sx = _sharded(128, 5)
    u, s, vt = linalg.svd_tall(sx.data, sx.mesh)
    s_np = np.linalg.svd(x, compute_uv=False)
    np.testing.assert_allclose(np.asarray(s), s_np, rtol=1e-4)
    rec = np.asarray(u) @ np.diag(np.asarray(s)) @ np.asarray(vt)
    np.testing.assert_allclose(rec, x, atol=1e-3)


@pytest.mark.slow
def test_randomized_svd_low_rank():
    rng = np.random.RandomState(0)
    base = rng.randn(200, 4) @ rng.randn(4, 16)
    x = base.astype(np.float32)
    sx = ShardedArray.from_array(x, default_mesh())
    u, s, vt = linalg.randomized_svd(
        sx.data, 4, jax.random.PRNGKey(0), sx.mesh, n_iter=4
    )
    s_np = np.linalg.svd(x, compute_uv=False)[:4]
    np.testing.assert_allclose(np.asarray(s), s_np, rtol=1e-3)
    rec = np.asarray(u) @ np.diag(np.asarray(s)) @ np.asarray(vt)
    np.testing.assert_allclose(rec, x, atol=2e-2)


def test_svd_flip_deterministic():
    x, sx = _sharded(64, 4, seed=5)
    u, s, vt = linalg.svd_tall(sx.data, sx.mesh)
    u2, vt2 = linalg.svd_flip(u, vt)
    u2, vt2 = np.asarray(u2), np.asarray(vt2)
    # flipped decomposition still reconstructs
    np.testing.assert_allclose(u2 @ np.diag(np.asarray(s)) @ vt2, x, atol=1e-3)
    # largest-|.| entry of each row of Vt is positive
    mx = np.argmax(np.abs(vt2), axis=1)
    assert (vt2[np.arange(4), mx] > 0).all()


def test_tsqr_fewer_rows_than_shards_per_block():
    """n barely above the shard count: per-shard blocks are extremely
    short; TSQR must still produce orthonormal Q and upper R."""
    mesh = default_mesh()
    shards = mesh.devices.size
    n, d = shards + 1, 3  # one shard gets 2 rows, rest get 1 (padded)
    rng = np.random.RandomState(0)
    Xs = ShardedArray.from_array(rng.randn(n, d).astype(np.float32))
    q, r = linalg.tsqr(Xs.data, mesh)
    qh, rh = np.asarray(q)[:n], np.asarray(r)
    np.testing.assert_allclose(qh @ rh, Xs.to_numpy(), atol=1e-4)
    np.testing.assert_allclose(qh.T @ qh, np.eye(d), atol=1e-4)


@pytest.mark.slow
def test_randomized_svd_components_near_rank():
    """k + oversampling exceeding d must clamp, and recover the full
    spectrum of an exactly low-rank matrix."""

    mesh = default_mesh()
    rng = np.random.RandomState(1)
    n, d, true_rank = 512, 12, 4
    A = (rng.randn(n, true_rank) @ rng.randn(true_rank, d)).astype(
        np.float32
    )
    Xs = ShardedArray.from_array(A)
    u, s, vt = linalg.randomized_svd(Xs.data, 8, jax.random.PRNGKey(0), mesh,
                              n_oversamples=10, n_iter=4)
    s = np.asarray(s)
    ref = np.linalg.svd(A.astype(np.float64), compute_uv=False)
    np.testing.assert_allclose(s[:true_rank], ref[:true_rank], rtol=1e-3)
    # spectrum beyond the true rank is numerically zero
    assert np.all(s[true_rank:] < ref[0] * 1e-4)


def test_svd_tall_single_column():
    mesh = default_mesh()
    rng = np.random.RandomState(2)
    x = rng.randn(256, 1).astype(np.float32)
    Xs = ShardedArray.from_array(x)
    u, s, vt = linalg.svd_tall(Xs.data, mesh)
    np.testing.assert_allclose(
        float(s[0]), np.linalg.norm(x), rtol=1e-4
    )
