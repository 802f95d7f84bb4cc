"""KMeans tests (ref: tests/test_kmeans.py in the reference; sklearn is
the oracle per SURVEY.md §4)."""

import jax
import numpy as np
import pytest
from sklearn.cluster import KMeans as SkKMeans
from sklearn.metrics import adjusted_rand_score

from dask_ml_tpu import config, observability as obs
from dask_ml_tpu.cluster import KMeans
from dask_ml_tpu.datasets import make_blobs
from dask_ml_tpu.models import kmeans as KM


@pytest.fixture(scope="module")
def blobs():
    X, y = make_blobs(n_samples=500, n_features=5, centers=4, random_state=0,
                      cluster_std=0.8)
    return X, y


@pytest.mark.parametrize("init", ["k-means||", "k-means++", "random"])
def test_kmeans_recovers_blobs(blobs, init):
    X, y = blobs
    km = KMeans(n_clusters=4, init=init, random_state=0, max_iter=100).fit(X)
    assert km.cluster_centers_.shape == (4, 5)
    ari = adjusted_rand_score(y.to_numpy(), km.labels_.to_numpy())
    # random init has no restarts (n_init, as in the reference) and may hit
    # a local optimum; the smart inits must recover the blobs nearly exactly
    floor = 0.5 if init == "random" else 0.95
    assert ari > floor, f"init={init} ari={ari}"
    assert km.n_iter_ >= 1
    assert km.inertia_ > 0


def test_kmeans_inertia_close_to_sklearn(blobs):
    X, _ = blobs
    Xh = X.to_numpy()
    ours = KMeans(n_clusters=4, random_state=0, max_iter=200).fit(X)
    ref = SkKMeans(n_clusters=4, n_init=10, random_state=0).fit(Xh)
    assert ours.inertia_ <= ref.inertia_ * 1.05


def test_kmeans_explicit_init(blobs):
    X, _ = blobs
    init = X.to_numpy()[:4].copy()
    km = KMeans(n_clusters=4, init=init, max_iter=100).fit(X)
    assert km.inertia_ > 0


def test_kmeans_predict_transform_score(blobs):
    X, _ = blobs
    km = KMeans(n_clusters=4, random_state=0).fit(X)
    labels = km.predict(X)
    np.testing.assert_array_equal(labels.to_numpy(), km.labels_.to_numpy())
    d = km.transform(X).to_numpy()
    assert d.shape == (500, 4)
    np.testing.assert_array_equal(np.argmin(d, axis=1), labels.to_numpy())
    assert km.score(X) == pytest.approx(-km.inertia_, rel=1e-5)


def test_kmeans_numpy_input(blobs):
    X, _ = blobs
    km = KMeans(n_clusters=4, random_state=0).fit(X.to_numpy())
    assert km.cluster_centers_.shape == (4, 5)


def test_kmeans_errors(blobs):
    X, _ = blobs
    with pytest.raises(ValueError, match="n_clusters"):
        KMeans(n_clusters=501).fit(X)
    with pytest.raises(ValueError, match="Unknown init"):
        KMeans(init="bogus").fit(X)
    with pytest.raises(ValueError, match="init array"):
        KMeans(n_clusters=4, init=np.zeros((3, 5))).fit(X)


def test_kmeans_pallas_path_matches_xla(blobs):
    """Fused Pallas Lloyd (interpret mode on CPU) vs the XLA path."""
    X, _ = blobs
    init = X.to_numpy()[:4].copy()
    xla = KMeans(n_clusters=4, init=init, max_iter=50, use_pallas=False).fit(X)
    pls = KMeans(n_clusters=4, init=init, max_iter=50, use_pallas=True).fit(X)
    np.testing.assert_allclose(
        pls.cluster_centers_, xla.cluster_centers_, atol=1e-3
    )
    assert pls.inertia_ == pytest.approx(xla.inertia_, rel=1e-4)
    np.testing.assert_array_equal(
        pls.labels_.to_numpy(), xla.labels_.to_numpy()
    )


def test_fused_assign_update_parity():
    """Interpret-mode parity of the fused Pallas kernel (labels/mind/sums/
    counts/inertia) vs a NumPy reference, across padding and mask cases."""
    from dask_ml_tpu.ops.pallas_fused import fused_assign_update

    rng = np.random.RandomState(0)
    for n, d, k, nvalid in [(256, 8, 4, 256), (137, 7, 3, 130),
                            (1000, 13, 5, 900), (513, 3, 2, 500)]:
        x = rng.randn(n, d).astype(np.float32)
        mask = (np.arange(n) < nvalid).astype(np.float32)
        c = rng.randn(k, d).astype(np.float32)
        lab, mind, sums, counts, inertia = [
            np.asarray(v) for v in fused_assign_update(x, mask, c, interpret=True)
        ]
        # reference uses the same ||x||^2 - 2xc + ||c||^2 expansion so
        # f32 near-ties resolve identically
        d2 = (
            (x * x).sum(1)[:, None]
            - 2.0 * (x @ c.T)
            + (c * c).sum(1)[None, :]
        ).clip(min=0)
        lab_ref = d2.argmin(1)
        mind_ref = d2.min(1) * mask
        # argmin may legitimately differ on f32 near-ties (BLAS vs XLA
        # accumulation order); require the kernel's pick to be within
        # rounding noise of the row minimum instead of bit-equality
        np.testing.assert_allclose(
            d2[np.arange(n), lab], d2[np.arange(n), lab_ref],
            rtol=1e-5, atol=1e-4,
        )
        np.testing.assert_allclose(mind, mind_ref, rtol=1e-4, atol=1e-4)
        sums_ref = np.zeros((k, d), np.float32)
        np.add.at(sums_ref, lab_ref, x * mask[:, None])
        np.testing.assert_allclose(sums, sums_ref, rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(
            counts, np.bincount(lab_ref, weights=mask, minlength=k)
        )
        np.testing.assert_allclose(inertia, mind_ref.sum(), rtol=1e-4)


def test_k_means_functional(blobs):
    """Functional API parity: ref dask_ml/cluster/k_means.py::k_means."""
    from dask_ml_tpu.cluster import k_means

    X, _ = blobs
    centers, labels, inertia, n_iter = k_means(
        X, 4, init="random", random_state=0, max_iter=20, return_n_iter=True
    )
    assert centers.shape[1] == X.shape[1]
    assert centers.shape[0] == 4
    assert inertia > 0 and n_iter >= 1
    centers3 = k_means(X, 4, init="random", random_state=0, max_iter=20)
    assert len(centers3) == 3


def test_kmeans_score_is_negative_inertia(blobs):
    import sklearn.cluster as skc

    X, _ = blobs
    Xh = X.to_numpy() if hasattr(X, "to_numpy") else np.asarray(X)
    init = Xh[:4]
    ours = KMeans(n_clusters=4, init=init, max_iter=20, tol=0.0).fit(X)
    ref = skc.KMeans(n_clusters=4, init=init, n_init=1, max_iter=20,
                     tol=0.0).fit(Xh)
    # sklearn contract: score = -inertia of the assignment
    assert ours.score(X) == pytest.approx(-ours.inertia_, rel=1e-5)
    assert ours.inertia_ == pytest.approx(ref.inertia_, rel=1e-3)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_kmeans_records_what_carried_the_fit(blobs, use_pallas):
    """``solver_info_`` names the kernel flavour that ran (``fused``: the
    Pallas Lloyd kernel; ``lloyd_layout``: the table form it read, lane-dense
    at these 5 features), the iterations and the precision."""
    X, _ = blobs
    km = KMeans(n_clusters=4, init=X.to_numpy()[:4].copy(), max_iter=7,
                tol=0.0, use_pallas=use_pallas).fit(X)
    assert km.solver_info_ == {"n_iter": 7, "fused": use_pallas,
                               "lloyd_layout":
                                   "lanes" if use_pallas else None,
                               "fit_dtype": "float32",
                               "tol_scale_passes": 0,
                               "init_draw": {"draws": 0, "draw": "none"},
                               "init_weights": {"weight_passes": 0,
                                                "weights": "none"}}
    assert km.n_iter_ == 7 and km.fit_dtype_ == "float32"


# -- the weighted draw without a full sort (PR 39) --------------------------

def _plain_draw(weights, key, l):
    """The draw as every fit took it until PR 39: a full ``lax.top_k``."""
    return jax.lax.top_k(KM._gumbel_keys(weights, key), l)[1]


@pytest.fixture(scope="module")
def wide_blobs():
    """Rows enough that every draw of k = 4 takes the tiled path (n_pad >
    2 * l * 128 at l = 8)."""
    X, _ = make_blobs(n_samples=6000, n_features=5, centers=4,
                      random_state=1, cluster_std=2.0)
    return X


@pytest.mark.parametrize("init", ["k-means||", "random"])
def test_the_tiled_draw_fits_what_the_plain_draw_fits(
        monkeypatch, wide_blobs, init):
    new = lambda: KMeans(n_clusters=4, init=init,  # noqa: E731
                         random_state=3, max_iter=30)
    tiled = new().fit(wide_blobs)
    assert tiled.solver_info_["init_draw"]["draw"] == "tiled"
    monkeypatch.setattr(KM, "_gumbel_top_l",
                        jax.jit(_plain_draw, static_argnames=("l",)))
    plain = new().fit(wide_blobs)
    np.testing.assert_array_equal(tiled.cluster_centers_,
                                  plain.cluster_centers_)
    np.testing.assert_array_equal(tiled.labels_.to_numpy(),
                                  plain.labels_.to_numpy())
    assert tiled.inertia_ == plain.inertia_


@pytest.mark.parametrize("n, draw", [(6000, "tiled"), (500, "mixed")])
def test_the_init_span_counts_the_draws_and_their_path(n, draw):
    """k-means‖ dispatches 1 + 5 draws; at 500 rows the first (l = 1) is
    tiled and the rounds' (l = 8) sort."""
    X, _ = make_blobs(n_samples=n, n_features=5, centers=4, random_state=1)
    obs.reset_recent_spans()
    with config.set(obs_programs=True):
        km = KMeans(n_clusters=4, random_state=0, max_iter=5).fit(X)
        ring = {r["span"]: r for r in obs.recent_spans()}
    want = {"draws": 6, "draw": draw}
    assert km.solver_info_["init_draw"] == want
    assert {k: ring["fit.init"][k] for k in want} == want
    # and one candidate-weight pass, counted without a scatter (PR 40)
    weighed = {"weight_passes": 1, "weights": "onehot"}
    assert km.solver_info_["init_weights"] == weighed
    assert {k: ring["fit.init"][k] for k in weighed} == weighed
