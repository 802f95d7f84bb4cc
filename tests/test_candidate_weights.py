"""k-means‖'s candidate weights without a scatter (PR 40,
``ops/reductions.py::small_segment_count`` under ``models/kmeans.py::
_candidate_weights``): the rows nearest each candidate, counted by a
reduction over a one-hot compare that XLA fuses after the argmin, where
``jax.ops.segment_sum`` lowered to a scatter-add that runs near-serially on
the TPU. Every case compares against the scatter formula bit for bit — the
counts are whole numbers, exact in any order — and the fits that read them
against a fit with the scatter put back. The last case lowers the pass at
the ``spectral_nystrom`` cell's shape (its compile for a described v5e is in
``test_kmeans_tol_scale.py``, the one file that describes the chip)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from sklearn.datasets import make_blobs as sk_blobs

from dask_ml_tpu.cluster import KMeans, SpectralClustering
from dask_ml_tpu.datasets import make_blobs
from dask_ml_tpu.models import kmeans as KM
from dask_ml_tpu.ops.pairwise import euclidean_distances_sq
from dask_ml_tpu.ops.reductions import small_segment_count


def _scatter_weights(X, mask, cands, cand_valid):
    """The weights as every fit took them until PR 40: a ``segment_sum``."""
    d2 = euclidean_distances_sq(X, cands)
    d2 = jnp.where(cand_valid[None, :] > 0, d2, jnp.inf)
    labels = jnp.argmin(d2, axis=1)
    return jax.ops.segment_sum(mask, labels, num_segments=cands.shape[0])


_scatter = jax.jit(_scatter_weights)

D = 8
N_RAGGED = 10_012           # four shards of 2,503 rows: no tile divides it


def _inputs(kind, n, c, seed):
    """(X, mask, cands, cand_valid) as numpy: rows drawn near the
    candidates; ``duplicated`` repeats five candidates over every slot (each
    row ties between copies), ``invalid`` leaves a third of the slots
    invalid (slot 0 stays valid, as k-means‖'s first draw is), ``padding``
    masks a tail and scattered rows out."""
    rng = np.random.default_rng(seed)
    cands = rng.standard_normal((c, D)).astype(np.float32) * 3.0
    if kind == "duplicated":
        cands = cands[np.arange(c) % min(c, 5)]
    X = (cands[rng.integers(0, c, n)]
         + rng.standard_normal((n, D)).astype(np.float32)).astype(np.float32)
    mask = np.ones(n, np.float32)
    valid = np.ones(c, np.float32)
    if kind == "invalid":
        valid[1:] = (rng.random(c - 1) > 0.33).astype(np.float32)
    if kind == "padding":
        mask[-(n // 7):] = 0.0
        mask[rng.random(n) < 0.1] = 0.0
    return X, mask, cands, valid


def _both(*args):
    new = np.asarray(KM._candidate_weights(*map(jnp.asarray, args)))
    old = np.asarray(_scatter(*map(jnp.asarray, args)))
    return new, old


@pytest.mark.parametrize("c", [1, 81, 641], ids=["c1", "c81", "c641"])
@pytest.mark.parametrize("kind",
                         ["random", "duplicated", "invalid", "padding"])
def test_the_scatters_weights_bit_for_bit(kind, c):
    X, mask, cands, valid = _inputs(kind, N_RAGGED, c, seed=c)
    new, old = _both(X, mask, cands, valid)
    np.testing.assert_array_equal(new, old)
    assert new.dtype == np.float32 and new.shape == (c,)
    assert new.sum() == mask.sum()
    assert (new[valid == 0] == 0).all()
    if kind == "duplicated" and c > 5:
        # argmin's first-index rule: the copies after the first get nothing
        assert (new[5:] == 0).all()


@pytest.mark.parametrize("n", [1, 7, 129, 1_000_003])
def test_ragged_row_counts(n):
    X, mask, cands, valid = _inputs("padding" if n > 7 else "random", n, 81,
                                    seed=n)
    new, old = _both(X, mask, cands, valid)
    np.testing.assert_array_equal(new, old)


def test_labels_outside_the_bins_count_nowhere():
    """The helper alone: labels below 0 or at ``c`` and above add to no bin,
    as the scatter drops them."""
    rng = np.random.default_rng(3)
    labels = jnp.asarray(rng.integers(-3, 84, 50_000).astype(np.int32))
    w = jnp.asarray((rng.random(50_000) > 0.2).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(small_segment_count(labels, w, 81)),
        np.asarray(jax.ops.segment_sum(w, labels, num_segments=81)))


def test_inside_another_jit():
    X, mask, cands, valid = _inputs("invalid", N_RAGGED, 81, seed=11)

    @jax.jit
    def outer(X, mask, cands, valid):
        return KM._candidate_weights(X * 1.0, mask, cands, valid) + 0.0

    np.testing.assert_array_equal(
        np.asarray(outer(*map(jnp.asarray, (X, mask, cands, valid)))),
        np.asarray(_scatter(*map(jnp.asarray, (X, mask, cands, valid)))))


@pytest.mark.parametrize("kind", ["random", "padding"])
def test_row_sharded_over_four_devices(kind):
    """X and the mask row-sharded over four devices: per-shard counts and
    one all-reduce give the scatter's weights."""
    X, mask, cands, valid = _inputs(kind, N_RAGGED, 81, seed=4)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    Xs = jax.device_put(jnp.asarray(X), NamedSharding(mesh, P("data", None)))
    ms = jax.device_put(jnp.asarray(mask), NamedSharding(mesh, P("data")))
    assert len(Xs.sharding.device_set) == 4
    new = KM._candidate_weights(Xs, ms, jnp.asarray(cands),
                                jnp.asarray(valid))
    np.testing.assert_array_equal(
        np.asarray(new), np.asarray(_scatter(X, mask, cands, valid)))


# -- the fits that read the weights ------------------------------------------

@pytest.fixture
def with_the_scatter(monkeypatch):
    """Put the scatter formula back where ``init_scalable`` calls it."""
    return lambda: monkeypatch.setattr(KM, "_candidate_weights", _scatter)


@pytest.mark.parametrize("n, k", [(3_001, 4), (6_000, 7)])
def test_kmeans_fits_what_the_scatter_fitted(with_the_scatter, n, k):
    X, _ = make_blobs(n_samples=n, n_features=5, centers=k, random_state=n,
                      cluster_std=2.5)
    new = lambda: KMeans(n_clusters=k, init="k-means||",  # noqa: E731
                         random_state=2, max_iter=40)
    onehot = new().fit(X)
    assert onehot.solver_info_["init_weights"] == {"weight_passes": 1,
                                                   "weights": "onehot"}
    with_the_scatter()
    scatter = new().fit(X)
    np.testing.assert_array_equal(onehot.cluster_centers_,
                                  scatter.cluster_centers_)
    np.testing.assert_array_equal(onehot.labels_.to_numpy(),
                                  scatter.labels_.to_numpy())
    assert onehot.inertia_ == scatter.inertia_


def test_spectral_at_the_cells_parameters_fits_what_the_scatter_fitted(
        with_the_scatter):
    """``spectral_nystrom``'s estimator parameters on a small table: 8
    clusters, 100 landmarks, rbf at gamma 1, ten restarts."""
    X, _ = sk_blobs(n_samples=4_000, n_features=16, centers=8,
                    random_state=40, cluster_std=0.4)
    X = (X / 6.0).astype(np.float32)   # where an rbf at gamma 1 still sees
    new = lambda: SpectralClustering(  # noqa: E731
        n_clusters=8, n_components=100, affinity="rbf", gamma=1.0,
        n_init=10, assign_labels="kmeans", persist_embedding=True,
        random_state=7)
    onehot = new().fit(X)
    with_the_scatter()
    scatter = new().fit(X)
    np.testing.assert_array_equal(onehot.labels_.to_numpy(),
                                  scatter.labels_.to_numpy())
    np.testing.assert_array_equal(onehot.assign_labels_.cluster_centers_,
                                  scatter.assign_labels_.cluster_centers_)
    assert onehot.solver_info_["inertias"] == scatter.solver_info_["inertias"]
    np.testing.assert_array_equal(onehot.embedding_.to_numpy(),
                                  scatter.embedding_.to_numpy())
    assert len(set(onehot.labels_.to_numpy().tolist())) == 8


# -- at the cell's shape -----------------------------------------------------

N_CELL, C_CELL = 4_194_304, 81


def _cell_args():
    A = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    return A((N_CELL, D)), A((N_CELL,)), A((C_CELL, D)), A((C_CELL,))


def test_no_scatter_at_the_cells_shape():
    """Lowered, not compiled, at 4,194,304 x 8 with 81 candidates: no
    scatter. The ``segment_sum`` form, lowered the same way, has one — so
    the test sees what it looks for."""
    op = "stablehlo.scatter"        # the op, not a name in the locations
    assert op in _scatter.lower(*_cell_args()).as_text()
    assert op not in KM._candidate_weights.lower(*_cell_args()).as_text()

