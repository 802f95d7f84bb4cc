"""Distributed linalg tests: TSQR, tall SVD, randomized SVD (SURVEY.md §7 B1).

Oracle = numpy.linalg on the gathered array, the same "small-data parity"
contract the reference uses with sklearn (SURVEY.md §4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dask_ml_tpu.decomposition import PCA
from dask_ml_tpu.ops import linalg
from dask_ml_tpu.parallel import ShardedArray, default_mesh
from dask_ml_tpu.parallel.mesh import device_mesh


def _sharded(n, d, seed=0, dtype=np.float32):
    x = np.random.RandomState(seed).randn(n, d).astype(dtype)
    return x, ShardedArray.from_array(x, default_mesh())


def _check_qr(q, r, x, orth=2e-5):
    """Q R = X, Q orthonormal, R upper-triangular, all finite; returns the
    float64 factors over X's rows."""
    n, d = x.shape
    q, r = np.asarray(q, np.float64)[:n], np.asarray(r, np.float64)
    assert np.isfinite(q).all() and np.isfinite(r).all()
    np.testing.assert_allclose(q @ r, x, atol=1e-4 * max(1.0, np.abs(x).max()))
    assert np.max(np.abs(q.T @ q - np.eye(d))) <= orth
    assert np.array_equal(r, np.triu(r))
    return q, r


# (rows, columns): the small panel, and panels as tall and as wide (the
# benchmark's 74-column sketch; a width past one 128-lane tile) as a CPU
# test affords. Well-conditioned, so every shard's local factor is
# CholeskyQR2: ||Q^T Q - I|| ~1e-6 (2e-6 at 2,097,152 rows on the v5e,
# where Householder left 2.5e-5; PERF.md)
@pytest.mark.parametrize("n,d", [(96, 6), (20001, 74), (8192, 130)])
def test_tsqr_reconstruction_and_orthonormality(n, d):
    x, sx = _sharded(n, d)
    q, r, fell_back = linalg.tsqr_counted(sx.data, sx.mesh)
    _check_qr(q, r, x)
    assert int(fell_back) == 0


def _householder_only(xs):
    """The local factor before CholeskyQR2: what a fallback must equal."""
    q, r = jnp.linalg.qr(xs)
    return q, r, jnp.ones((), bool)


def _with_kappa(rng, n, d, kappa):
    u = np.linalg.qr(rng.randn(n, d))[0]
    v = np.linalg.qr(rng.randn(d, d))[0]
    return (u * np.logspace(0, -np.log10(kappa), d)) @ v.T


def _panel(kind, n=4096, d=12):
    """Panels the guard must refuse, in every shard of the 8-device mesh."""
    rng = np.random.RandomState(7)
    x = rng.randn(n, d)
    if kind == "duplicated_column":
        x[:, d - 1] = x[:, 2]
    elif kind == "constant_column":   # as svd_tall sees it: centred to zero
        x[:, 5] = 0.0
    elif kind == "rank_4_of_12":
        x = rng.randn(n, 4) @ rng.randn(4, d)
    elif kind == "all_zero":
        x[:] = 0.0
    elif kind == "kappa_1e5":
        x = _with_kappa(rng, n, d, 1e5)
    else:
        raise ValueError(kind)
    return x.astype(np.float32)


DEGENERATE = ["duplicated_column", "constant_column", "rank_4_of_12",
              "all_zero", "kappa_1e5"]


@pytest.mark.parametrize("kind", DEGENERATE)
def test_tsqr_falls_back_to_householder_where_the_guard_fails(
        kind, monkeypatch):
    """A panel CholeskyQR2 cannot factor gets the Householder result —
    inside the program, counted — and exactly what the local factor gave
    before there was a Gram route."""
    x = _panel(kind)
    sx = ShardedArray.from_array(x, default_mesh())
    q, r, fell_back = linalg.tsqr_counted(sx.data, sx.mesh)
    _check_qr(q, r, x)
    assert int(fell_back) == 1
    monkeypatch.setattr(linalg, "_local_qr", _householder_only)
    q_h, r_h = linalg.tsqr(sx.data, sx.mesh)
    np.testing.assert_allclose(np.asarray(q), np.asarray(q_h), atol=1e-6)
    np.testing.assert_allclose(np.asarray(r), np.asarray(r_h), atol=1e-6)


@pytest.mark.parametrize("kind", DEGENERATE)
def test_one_shard_fallback_equals_jnp_qr_up_to_signs(kind):
    """On one device the second stage is a QR of an upper-triangular R
    (column signs only), so the factors are ``jnp.linalg.qr``'s."""
    x = _panel(kind, n=512)
    mesh = device_mesh(devices=jax.devices()[:1])
    sx = ShardedArray.from_array(x, mesh)
    q, r, fell_back = linalg.tsqr_counted(sx.data, mesh)
    assert int(fell_back) == 1
    q_ref, r_ref = jnp.linalg.qr(sx.data)
    np.testing.assert_allclose(np.abs(np.asarray(q)),
                               np.abs(np.asarray(q_ref)), atol=1e-5)
    np.testing.assert_allclose(np.abs(np.asarray(r)),
                               np.abs(np.asarray(r_ref)),
                               atol=1e-5 * max(1.0, np.abs(x).max()))


@pytest.mark.parametrize("kappa,fell_back", [(10.0, 0), (300.0, 0),
                                             (3e4, 1), (1e7, 1)])
def test_local_factor_guard_follows_the_condition_number(kappa, fell_back):
    """float32 CholeskyQR2 holds to a condition number of ~1e3 (the Gram
    squares it against u = 6e-8); beyond, the guard sends the panel to
    Householder. Either way the factors are a QR to working precision."""
    x = _with_kappa(np.random.RandomState(0), 2048, 16, kappa).astype(
        np.float32)
    q, r, fb = jax.jit(linalg._local_qr)(x)
    _check_qr(q, r, x)
    assert int(fb) == fell_back
    if not fell_back:     # a Cholesky factor: positive diagonal
        assert (np.diag(np.asarray(r)) > 0).all()


def test_tsqr_mixed_shards_compose():
    """One shard rank-deficient, the others not: each chooses its route
    alone and the second stage composes them."""
    mesh = default_mesh()
    shards = mesh.devices.size
    m, d = 64, 6
    x = np.random.RandomState(11).randn(shards * m, d).astype(np.float32)
    bad = slice(3 * m, 4 * m)
    x[bad, d - 1] = x[bad, 0]
    routes = [int(linalg._local_qr(x[i * m:(i + 1) * m])[2])
              for i in range(shards)]
    assert routes == [0, 0, 0, 1] + [0] * (shards - 4)
    sx = ShardedArray.from_array(x, mesh)
    q, r, fell_back = linalg.tsqr_counted(sx.data, mesh)
    _check_qr(q, r, x)
    assert int(fell_back) == 1


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


@pytest.mark.parametrize("route", ["cholesky", "householder"])
def test_zero_rows_leave_r_unchanged_and_their_q_rows_zero(route):
    """50 real rows and 14 explicit zero rows over two shards (the second
    holds 18 real rows, so it keeps full rank): R is the real rows' R
    (R^T R = X^T X, whatever the signs) and the zero rows of Q stay zero,
    on the Gram route and on the Householder one."""
    mesh = device_mesh(devices=jax.devices()[:2])
    x = np.zeros((64, 4), np.float32)
    x[:50] = np.random.RandomState(5).randn(50, 4)
    if route == "householder":
        x[:, 3] = x[:, 1]
    sx = ShardedArray.from_array(x, mesh)
    q, r, fell_back = linalg.tsqr_counted(sx.data, mesh)
    assert int(fell_back) == (route == "householder")
    q, r = _check_qr(q, r, x)
    np.testing.assert_allclose(r.T @ r, x[:50].T.astype(np.float64) @ x[:50],
                               atol=1e-4)
    if route == "cholesky":
        assert np.array_equal(q[50:], np.zeros((14, 4)))
    else:
        np.testing.assert_allclose(q[50:], 0.0, atol=1e-6)


def _exact_pca(x, k):
    xc = x.astype(np.float64) - x.astype(np.float64).mean(axis=0)
    _, s, vt = np.linalg.svd(xc, full_matrices=False)
    return vt[:k], s[:k] ** 2 / (len(x) - 1)


@pytest.mark.parametrize("solver", ["randomized", "full"])
@pytest.mark.parametrize("data", ["planted", "rank_4_of_24"])
def test_pca_counts_its_qr_fallbacks(data, solver):
    """``solver_info_["qr_fallbacks"]``: tall QRs of the fit in which a
    shard took Householder. None on a planted subspace over noise; on
    exactly low-rank data the first sketch (13 columns of rank 4) and the
    centred matrix itself, with the exact components either way. (Later
    sketches of the low-rank fit may pass: their surplus columns are
    rounding noise at its own scale, which a Gram factors as it is.)"""
    rng = np.random.RandomState(3)
    n, d, k = 2048, 24, 3
    x = (rng.randn(n, 4) * [8.0, 4.0, 2.0, 1.0]) @ np.linalg.qr(
        rng.randn(d, 4))[0].T + 1.5
    if data == "planted":
        x += 0.25 * rng.randn(n, d)
    x = x.astype(np.float32)
    est = PCA(n_components=k, svd_solver=solver, random_state=0).fit(
        ShardedArray.from_array(x, default_mesh()))
    info = est.solver_info_
    if data == "planted":
        assert info["qr_fallbacks"] == 0
    else:
        assert 1 <= info["qr_fallbacks"] <= 1 + info["n_iter"]
    comp, ev = _exact_pca(x, k)
    np.testing.assert_allclose(np.abs(est.components_ @ comp.T), np.eye(k),
                               atol=1e-3)
    np.testing.assert_allclose(est.explained_variance_, ev, rtol=1e-3)


@pytest.mark.parametrize("solver", ["randomized", "full"])
def test_a_nan_in_x_still_raises_in_pca_fit(solver):
    """A NaN poisons the Gram, the guard refuses it, Householder carries it
    through to the singular values, and ``PCA.fit`` says so."""
    x = np.random.RandomState(0).randn(512, 8).astype(np.float32)
    x[17, 3] = np.nan
    with pytest.raises(FloatingPointError, match="non-finite"):
        PCA(n_components=2, svd_solver=solver).fit(
            ShardedArray.from_array(x, default_mesh()))


def test_svd_tall_matches_numpy():
    x, sx = _sharded(128, 5)
    u, s, vt, _ = linalg.svd_tall(sx.data, sx.mesh)
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
    u, s, vt, _ = linalg.randomized_svd(
        sx.data, 4, jax.random.PRNGKey(0), sx.mesh, n_iter=4
    )
    s_np = np.linalg.svd(x, compute_uv=False)[:4]
    np.testing.assert_allclose(np.asarray(s), s_np, rtol=1e-3)
    rec = np.asarray(u) @ np.diag(np.asarray(s)) @ np.asarray(vt)
    np.testing.assert_allclose(rec, x, atol=2e-2)


def test_svd_flip_deterministic():
    x, sx = _sharded(64, 4, seed=5)
    u, s, vt, _ = linalg.svd_tall(sx.data, sx.mesh)
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
    u, s, vt, _ = linalg.randomized_svd(Xs.data, 8, jax.random.PRNGKey(0), mesh,
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
    u, s, vt, _ = linalg.svd_tall(Xs.data, mesh)
    np.testing.assert_allclose(
        float(s[0]), np.linalg.norm(x), rtol=1e-4
    )
