"""k-means‖ as one device program a restart (``models/kmeans.py::
_init_scalable_run``, one jitted program): the first draw,
the candidate buffer, the rounds and the weight pass run in one program and
leave in one fetch, where the host used to step each round and wait on its
cost. The round-by-round host loop the package ran until then is kept HERE,
as the reference, and every case asks for the same candidates, validity and
weights bit for bit, then the same centres, labels and inertia."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from sklearn.datasets import make_blobs as sk_blobs

from dask_ml_tpu import config
from dask_ml_tpu import observability as obs
from dask_ml_tpu.cluster import KMeans, SpectralClustering
from dask_ml_tpu.models import kmeans as KM
from dask_ml_tpu.parallel.mesh import device_mesh
from dask_ml_tpu.parallel.sharded import ShardedArray
from dask_ml_tpu.utils.validation import check_array


def _host_loop(X, n_clusters, random_state, max_iter=None,
               oversampling_factor=2):
    """(candidate buffer, validity, weights, rounds kept, centres) as the
    host loop made them: a dispatch, a split, a draw and a blocking read of
    the cost a round, ``break`` at a cost of 0, then the weight pass."""
    data, mask = X.data, X.row_mask(X.dtype)
    d = X.shape[1]
    l = min(max(int(oversampling_factor * n_clusters), 1), data.shape[0])
    key = jax.random.PRNGKey(0 if random_state is None else int(random_state))
    key, k0 = jax.random.split(key)
    first = data[KM._gumbel_top_l(mask, k0, 1)[0]]
    rounds = 5 if max_iter is None else max(int(max_iter), 1)
    c_max = 1 + rounds * l
    cands = jnp.zeros((c_max, d), data.dtype).at[0].set(first)
    cand_valid = jnp.zeros((c_max,), jnp.float32).at[0].set(1.0)
    kept = 0
    for r in range(rounds):
        dmin, phi = KM._cost_to_candidates(data, mask, cands, cand_valid)
        key, kr = jax.random.split(key)
        idx = KM._gumbel_top_l(dmin, kr, l)
        if float(phi) <= 0.0:
            break
        kept += 1
        rows = jnp.take(data, idx, axis=0)
        start = 1 + r * l
        cands = jax.lax.dynamic_update_slice(cands, rows, (start, 0))
        cand_valid = jax.lax.dynamic_update_slice(
            cand_valid, jnp.ones((l,), jnp.float32), (start,))
    weights = KM._candidate_weights(data, mask, cands, cand_valid)
    cands_h = np.asarray(cands)
    valid_h = np.asarray(cand_valid)
    w_h = np.asarray(weights)
    on = valid_h > 0
    w = np.where(w_h[on] > 0, w_h[on], 1e-6)
    centers = KM._reduce_candidates(
        cands_h[on], w, n_clusters,
        None if random_state is None else int(random_state))
    return cands_h, valid_h, w_h, kept, np.asarray(
        jnp.asarray(centers, data.dtype))


def _host_loop_init(X, n_clusters, random_state, max_iter=None,
                    oversampling_factor=2, draws=None, weight_passes=None,
                    rounds_kept=None):
    """``init_scalable``'s signature over the host loop, for the fits."""
    *_, kept, centers = _host_loop(X, n_clusters, random_state, max_iter,
                                   oversampling_factor)
    if rounds_kept is not None:
        rounds_kept.append(kept)
    return jnp.asarray(centers)


def _program(X, n_clusters, random_state, max_iter=None,
             oversampling_factor=2):
    """(buffer, validity, weights) out of the one program's one array."""
    data, mask = X.data, X.row_mask(X.dtype)
    d = X.shape[1]
    l = min(max(int(oversampling_factor * n_clusters), 1), data.shape[0])
    rounds = 5 if max_iter is None else max(int(max_iter), 1)
    packed = np.asarray(KM._init_scalable_run(
        data, mask, jax.random.PRNGKey(int(random_state)), l=l,
        rounds=rounds, draw=KM._gumbel_top_l, weigh=KM._candidate_weights))
    assert packed.shape == (1 + rounds * l, d + 2)
    assert packed.dtype == np.float32
    return packed[:, :d], packed[:, d], packed[:, d + 1]


def _blobs(n, k, seed):
    X, _ = sk_blobs(n_samples=n, n_features=5, centers=k, random_state=seed,
                    cluster_std=2.0)
    return X.astype(np.float32)


def _few_rows(n_distinct, repeats, seed):
    """``n_distinct`` rows, each repeated: once every distinct row is a
    candidate the cost is 0, and the rounds after add nothing. Whole
    numbers, so that a row's distance to its copy is an exact 0."""
    rng = np.random.default_rng(seed)
    base = rng.integers(-6, 7, (n_distinct, 5)).astype(np.float32)
    return base[rng.permutation(np.repeat(np.arange(n_distinct), repeats))]


def _placed(Xh, devices=None):
    mesh = None if devices is None else device_mesh(
        devices=jax.devices()[:devices])
    X = check_array(ShardedArray.from_array(Xh, mesh=mesh), dtype=np.float32)
    if devices is not None:
        assert len(X.data.sharding.device_set) == devices
    return X


CASES = {
    # (rows, clusters, init_max_iter, devices)
    "blobs-3001-k4": (3_001, 4, None, None),
    "blobs-6000-k7-r3": (6_000, 7, 3, None),
    "blobs-500-k4-r8": (500, 4, 8, None),       # the rounds' draws sort
    "blobs-6000-k4-4dev": (6_000, 4, None, 4),
    "blobs-2503-k3-r6-4dev": (2_503, 3, 6, 4),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_program_makes_the_host_loop_s_candidates(case):
    n, k, rounds, devices = CASES[case]
    X = _placed(_blobs(n, k, seed=n + k), devices)
    seed = 2**31 + 7 * n
    cands, valid, w, kept, centers = _host_loop(X, k, seed, rounds)
    p_cands, p_valid, p_w = _program(X, k, seed, rounds)
    np.testing.assert_array_equal(p_cands, cands)
    np.testing.assert_array_equal(p_valid, valid)
    np.testing.assert_array_equal(p_w, w)
    assert kept == (5 if rounds is None else rounds)
    got = []
    new = np.asarray(KM.init_scalable(X, k, seed, rounds, rounds_kept=got))
    np.testing.assert_array_equal(new, centers)
    assert got == [kept]


@pytest.mark.parametrize("n_distinct, repeats, k, devices",
                         [(5, 400, 4, None), (3, 700, 4, 4), (6, 1, 4, None)])
def test_a_cost_of_zero_ends_the_rounds_as_the_break_did(
        n_distinct, repeats, k, devices):
    """At most ``l`` distinct rows: the cost falls to 0 inside the budget,
    the mask keeps the rounds before it and no other."""
    X = _placed(_few_rows(n_distinct, repeats, seed=n_distinct), devices)
    cands, valid, w, kept, centers = _host_loop(X, k, 11)
    assert 1 <= kept < 5
    p_cands, p_valid, p_w = _program(X, k, 11)
    np.testing.assert_array_equal(p_cands, cands)
    np.testing.assert_array_equal(p_valid, valid)
    np.testing.assert_array_equal(p_w, w)
    got = []
    new = np.asarray(KM.init_scalable(X, k, 11, rounds_kept=got))
    np.testing.assert_array_equal(new, centers)
    assert got == [kept]
    km = KMeans(n_clusters=k, random_state=11).fit(X)
    assert km.solver_info_["init_rounds"] == kept
    assert km.solver_info_["init_draw"]["draws"] == 6


def _same_fit(a, b):
    np.testing.assert_array_equal(a.cluster_centers_, b.cluster_centers_)
    np.testing.assert_array_equal(a.labels_.to_numpy(), b.labels_.to_numpy())
    assert a.inertia_ == b.inertia_
    assert a.n_iter_ == b.n_iter_


@pytest.mark.parametrize("n, k, init_max_iter, devices",
                         [(3_001, 4, None, None), (6_000, 7, 2, None),
                          (6_000, 5, None, 4)])
def test_kmeans_fits_what_the_host_loop_fitted(monkeypatch, n, k,
                                               init_max_iter, devices):
    X = _placed(_blobs(n, k, seed=3 * n), devices)
    new = lambda: KMeans(n_clusters=k, random_state=5,  # noqa: E731
                         init_max_iter=init_max_iter, max_iter=40)
    program = new().fit(X)
    assert program.solver_info_["init_rounds"] == (init_max_iter or 5)
    monkeypatch.setattr(KM, "init_scalable", _host_loop_init)
    _same_fit(program, new().fit(X))


def test_spectral_fits_what_the_host_loop_fitted(monkeypatch):
    X, _ = sk_blobs(n_samples=4_000, n_features=16, centers=8,
                    random_state=40, cluster_std=0.4)
    X = (X / 6.0).astype(np.float32)
    new = lambda: SpectralClustering(  # noqa: E731
        n_clusters=8, n_components=100, affinity="rbf", gamma=1.0,
        n_init=10, assign_labels="kmeans", persist_embedding=True,
        random_state=7)
    program = new().fit(X)
    monkeypatch.setattr(KM, "init_scalable", _host_loop_init)
    loop = new().fit(X)
    np.testing.assert_array_equal(program.labels_.to_numpy(),
                                  loop.labels_.to_numpy())
    assert program.solver_info_["inertias"] == loop.solver_info_["inertias"]
    _same_fit(program.assign_labels_, loop.assign_labels_)


# -- what a fit records --------------------------------------------------------

def _calls():
    return {r["program"]: (int(r["calls"]), int(r["compiles"]))
            for r in obs.programs_snapshot()}


def _delta(before):
    return {name: (c - before.get(name, (0, 0))[0],
                   m - before.get(name, (0, 0))[1])
            for name, (c, m) in _calls().items()
            if (c, m) != before.get(name, (0, 0))}


def _recorded(monkeypatch):
    """Calls of ``_init_scalable_run``, recorded; the jit stays the one the
    package compiled, so its cache counts the compiles."""
    run = KM._init_scalable_run
    calls = []

    def recorded(*args, **kwargs):
        calls.append(kwargs["rounds"])
        return run(*args, **kwargs)

    monkeypatch.setattr(KM, "_init_scalable_run", recorded)
    return calls, run._cache_size


def test_one_program_and_one_fetch_a_fit_and_no_compile_for_a_new_seed(
        monkeypatch):
    Xh = _blobs(4_096, 4, seed=1)
    X = _placed(Xh)
    calls, compiled = _recorded(monkeypatch)
    with config.set(obs_programs=True):
        KMeans(n_clusters=4, random_state=0, max_iter=5).fit(X)   # warm
        assert calls == [5]
        obs.reset_recent_spans()
        before, size = _calls(), compiled()
        km = KMeans(n_clusters=4, random_state=1, max_iter=5).fit(X)
        first = _delta(before)
        ring = {r["span"]: r for r in obs.recent_spans()}
        assert calls == [5, 5] and compiled() == size
        before = _calls()
        KMeans(n_clusters=4, random_state=2, max_iter=5).fit(X)
        second = _delta(before)
        assert calls == [5, 5, 5] and compiled() == size
    assert all(m == 0 for _, m in first.values())
    assert all(m == 0 for _, m in second.values())
    # the buffer, the validity and the weights: ONE read
    assert ring["fit.init"]["fetches"] == 1
    assert ring["fit.init"]["init_rounds"] == 5
    assert ring["fit.init"]["draws"] == 6
    assert ring["fit.init"]["weight_passes"] == 1
    assert km.solver_info_["init_rounds"] == 5


def test_spectral_sums_the_rounds_over_its_restarts(monkeypatch):
    """Three restarts, three programs; ``fit.assign`` sums the rounds each
    kept, as many as the host loop kept (some restarts of this embedding
    meet a cost of 0 before the budget)."""
    X, _ = sk_blobs(n_samples=3_000, n_features=4, centers=3,
                    random_state=5, cluster_std=1.5)

    calls, _ = _recorded(monkeypatch)

    def fit():
        obs.reset_recent_spans()
        with config.set(obs_programs=True):
            SpectralClustering(n_clusters=3, n_components=20, n_init=3,
                               gamma=0.2, random_state=4).fit(X)
            ring = {r["span"]: r for r in obs.recent_spans()}
        return len(calls), ring["fit.assign"]

    ran, assign = fit()
    assert ran == 3
    assert assign["draws"] == 18 and assign["weight_passes"] == 3
    monkeypatch.setattr(KM, "init_scalable", _host_loop_init)
    ran_loop, assign_loop = fit()
    assert ran_loop == 3          # the host loop ran no program
    assert 3 <= assign["init_rounds"] == assign_loop["init_rounds"] <= 15
