"""Resident ``PCA(svd_solver="randomized")`` against the plain, exact
reference (``models/solvers/reference_pca.py``) on seeded data: the CPU,
small-size half of what the benchmark's ``pca_rsvd_x512`` cell checks on the
chip at 2,097,152 x 512. Every band is ``benchmark/tolerances_pca.py``'s,
written there beside its reason; this file only says which data it is asked
of."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import sklearn.decomposition as skdec

from benchmark import tolerances_pca as T
from benchmark.families import pca as family
from dask_ml_tpu import config
from dask_ml_tpu.decomposition import PCA
from dask_ml_tpu.models.solvers import reference_pca as ref
from dask_ml_tpu.observability import (programs_snapshot, recent_spans,
                                       reset_recent_spans)
from dask_ml_tpu.parallel import as_sharded
from dask_ml_tpu.parallel.mesh import device_mesh


def planted(n, d, k, seed):
    """The benchmark configuration's data, small: k planted orthonormal
    directions with covariance eigenvalues falling geometrically from 64 to
    16 over unit isotropic noise, and a mean of order one."""
    spec = {"components": k, "eigen_top": 64.0, "eigen_bottom": 16.0,
            "mean_scale": 1.0}
    hp = family.planted_params(np.random.default_rng(seed), d, spec)
    return np.asarray(family.planted_rows(jax.random.PRNGKey(seed), n, d,
                                          hp, spec)[0])


def program_calls():
    return {r["program"]: r["calls"] for r in programs_snapshot()}


def fitted(X, k, devices, seed=0):
    mesh = device_mesh(devices=jax.devices()[:devices])
    Xs = as_sharded(X, mesh=mesh)
    return PCA(n_components=k, svd_solver="randomized",
               random_state=seed).fit(Xs), Xs


def all_readings(est, Xs, X_ref, k):
    """{name: (reading, band)}: the fitted attributes against the exact
    decomposition of ``X_ref``, and ``transform`` against the reference's
    projection of ``X_ref`` at the fit's own components."""
    n = X_ref.shape[0]
    exact = ref.pca_exact(ref.row_blocks(X_ref, 1024), k)
    info = est.solver_info_
    out = T.readings(exact, est.mean_, est.components_,
                     est.explained_variance_, est.explained_variance_ratio_,
                     info["size"], info["n_iter"])
    scores = np.asarray(est.transform(Xs).data)[:n]
    out["transform"] = (T.transform_reading(
        scores, ref.transform(X_ref, est.mean_, est.components_)),
        T.TOL_TRANSFORM)
    return out


# (devices, rows — never a multiple of the shard count above one device —,
# features, components)
SHAPES = [(1, 2048, 512, 64), (8, 3001, 48, 6), (4, 5003, 96, 12),
          (2, 4099, 128, 16)]


@pytest.mark.parametrize("devices,n,d,k", SHAPES)
def test_randomized_pca_within_every_band_of_the_exact_reference(
        devices, n, d, k):
    X = planted(n, d, k, seed=n)
    est, Xs = fitted(X, k, devices)
    assert len(Xs.data.sharding.device_set) == devices
    assert est.solver_info_ == {"solver": "randomized", "size": k + 10,
                                "n_iter": 2, "x_sweeps": 6,
                                "qr_fallbacks": 0}
    assert est.fit_dtype_ == "float32"
    got = all_readings(est, Xs, X, k)
    assert set(got) == {"mean", "orthonormal", "eigenvalue", "angle",
                        "captured", "total_variance", "transform"}
    for name, (value, band) in got.items():
        assert 0.0 <= value <= band, (name, value, band)
    # the bands have teeth at this size: the algorithmic ones are far below
    # what one power iteration fewer would need (rho^2 more)
    assert got["angle"][1] < 0.2 and got["eigenvalue"][1] < 1e-2


def test_a_fit_of_bf16_rounded_rows_fails_the_bands():
    """The guide's "tight enough" clause: the same fit on X rounded to
    bfloat16 — what a bf16 design matrix, or a single-pass MXU multiply of
    the scores, would see — lands outside ``transform``'s band (2^-9 a
    feature, nothing averages over one row's 512 terms) and, at this row
    count, outside the mean's; the subspace's bands do not notice (roundings
    average over the rows), which is why they are not the ones that guard
    the precision."""
    n, d, k = 20000, 512, 64
    X = planted(n, d, k, seed=7)
    Xb = np.asarray(jnp.asarray(X).astype(jnp.bfloat16).astype(jnp.float32))
    est, Xs = fitted(X, k, 1)
    good = all_readings(est, Xs, X, k)
    assert all(v <= b for v, b in good.values()), good
    est_b, Xs_b = fitted(Xb, k, 1)
    bad = all_readings(est_b, Xs_b, X, k)
    assert bad["transform"][0] > 10 * T.TOL_TRANSFORM
    assert bad["mean"][0] > T.TOL_MEAN
    assert bad["angle"][0] <= bad["angle"][1]
    assert bad["captured"][0] <= bad["captured"][1]


def test_a_fit_with_one_power_iteration_less_fails_the_bands(monkeypatch):
    """The algorithmic bands are derived for the program's two power
    iterations; the range finder run with none is outside them."""
    from dask_ml_tpu.ops import linalg

    n, d, k = 2048, 512, 64
    X = planted(n, d, k, seed=11)
    real = linalg.randomized_range_finder
    monkeypatch.setattr(
        linalg, "randomized_range_finder",
        lambda x, size, key, n_iter, mesh: real(x, size, key, 0, mesh))
    jax.clear_caches()
    try:
        est, Xs = fitted(X, k, 1)
        got = all_readings(est, Xs, X, k)
    finally:
        jax.clear_caches()
    assert got["angle"][0] > got["angle"][1]
    assert got["eigenvalue"][0] > got["eigenvalue"][1]


def test_reference_is_the_published_decomposition():
    """The reference against sklearn's full-SVD PCA on the same rows (float64
    there): eigenvalues, ratio, mean, components up to sign, projection."""
    X = planted(1500, 24, 5, seed=3)
    exact = ref.pca_exact(ref.row_blocks(X, 256), 5)
    sk = skdec.PCA(n_components=5, svd_solver="full").fit(X.astype(np.float64))
    np.testing.assert_allclose(exact["mean"], sk.mean_, atol=1e-6)
    np.testing.assert_allclose(exact["explained_variance"],
                               sk.explained_variance_, rtol=1e-5)
    np.testing.assert_allclose(exact["explained_variance_ratio"],
                               sk.explained_variance_ratio_, rtol=1e-5)
    np.testing.assert_allclose(np.abs(exact["components"]),
                               np.abs(sk.components_), atol=1e-4)
    big = np.argmax(np.abs(exact["components"]), axis=1)
    assert (exact["components"][np.arange(5), big] > 0).all()
    assert exact["n"] == 1500 and exact["eigenvalues"].shape == (24,)
    assert np.all(np.diff(exact["eigenvalues"]) <= 0)
    signs = np.sign(np.sum(exact["components"] * sk.components_, axis=1))
    np.testing.assert_allclose(
        np.asarray(ref.transform(X, exact["mean"], exact["components"])),
        sk.transform(X.astype(np.float64)) * signs, atol=2e-4)


def test_reference_blocks_do_not_matter():
    X = planted(1000, 16, 3, seed=5) + 100.0      # mean >> spread
    a = ref.mean_cov(ref.row_blocks(X, 1000))
    b = ref.mean_cov(ref.row_blocks(X, 97))
    x64 = X.astype(np.float64)
    np.testing.assert_allclose(a[1], x64.mean(axis=0), rtol=1e-9)
    np.testing.assert_allclose(b[2], np.cov(x64.T), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(a[2], b[2], rtol=2e-5, atol=2e-5)


def test_the_benchmark_keeps_a_copy_of_the_reference():
    """``benchmark/references/pca.py`` is the reference's code, letter for
    letter below the docstring's first paragraph."""
    import benchmark.references.pca as copy

    def body(mod):
        src = open(mod.__file__).read()
        return src[src.index("Independent of Halko"):]

    assert body(copy) == body(ref)


@pytest.mark.parametrize("entry", ["fit", "fit_transform"])
def test_resident_pca_records_spans_counters_and_programs(entry):
    X = planted(1024, 32, 4, seed=1)
    with config.set(obs_programs=True):
        reset_recent_spans()
        before = program_calls()
        est = PCA(n_components=4, svd_solver="randomized", random_state=0)
        out = getattr(est, entry)(as_sharded(X))
        scores = est.transform(as_sharded(X))
        ring = recent_spans()
        after = program_calls()
    # (a row mask is a tracked program too, where its result cache has none)
    delta = {p: c - before.get(p, 0) for p, c in after.items()
             if c - before.get(p, 0) and p != "sharded.row_mask"}
    assert delta == {"pca.center": 1, "pca.rsvd": 1, "pca.transform": 1}
    roots = [r for r in ring if r["parent_id"] is None]
    assert [(r["span"], r["component"]) for r in roots] == \
        [("fit", "PCA"), ("transform", "PCA")]
    assert roots[0]["n_rows"] == roots[1]["n_rows"] == 1024
    kids = {r["span"]: r for r in ring if r["root_id"] == roots[0]["span_id"]
            and r["parent_id"] is not None}
    assert list(kids) == ["fit.validate", "fit.center", "fit.solve",
                          "fit.finish"]
    assert kids["fit.center"]["x_sweeps"] == 2
    solve = kids["fit.solve"]
    assert (solve["solver"], solve["size"], solve["n_iter"],
            solve["x_sweeps"], solve["qr_fallbacks"]) == \
        ("randomized", 14, 2, 6, 0)
    assert est.solver_info_["x_sweeps"] == 6
    # transform only dispatches: nothing in it waits for the device
    assert roots[1]["sync_s"] == 0.0
    if entry == "fit_transform":
        np.testing.assert_allclose(np.asarray(out.data),
                                   np.asarray(scores.data), atol=5e-2)


def test_full_solver_records_its_program_and_one_sweep():
    X = planted(512, 16, 3, seed=2)
    with config.set(obs_programs=True):
        before = program_calls()
        est = PCA(n_components=3, svd_solver="full").fit(as_sharded(X))
        after = program_calls()
    assert after["pca.svd_tall"] - before.get("pca.svd_tall", 0) == 1
    assert est.solver_info_ == {"solver": "full", "size": 16, "n_iter": 0,
                                "x_sweeps": 1, "qr_fallbacks": 0}
    exact = ref.pca_exact(ref.row_blocks(X), 3)
    np.testing.assert_allclose(est.explained_variance_,
                               exact["explained_variance"], rtol=1e-4)


def test_transform_whiten_and_host_input():
    X = planted(800, 16, 3, seed=4)
    est = PCA(n_components=3, svd_solver="full", whiten=True).fit(X)
    t = est.transform(X).to_numpy()
    want = (X - est.mean_) @ est.components_.T \
        / np.sqrt(est.explained_variance_)
    np.testing.assert_allclose(t, want, atol=1e-4)
    np.testing.assert_allclose(t.std(axis=0, ddof=1), 1.0, rtol=1e-3)
