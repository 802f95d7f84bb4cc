"""``SpectralClustering`` against the plain reference
(``models/solvers/reference_spectral.py``) on seeded data: the CPU,
small-size half of what the benchmark's ``spectral_nystrom`` cell checks on
the chip at 4,194,304 x 256. Every limit is ``benchmark/
tolerances_spectral.py``'s, written there beside its reason; this file only
says which data it is asked of."""

import jax
import numpy as np
import pytest

from benchmark import tolerances_spectral as T
from benchmark.families import spectral as family
from dask_ml_tpu import config
from dask_ml_tpu.cluster import KMeans, SpectralClustering
from dask_ml_tpu.models import spectral as program
from dask_ml_tpu.models.solvers import reference_spectral as ref
from dask_ml_tpu.observability import (programs_snapshot, recent_spans,
                                       reset_recent_spans)
from dask_ml_tpu.parallel import as_sharded
from dask_ml_tpu.parallel.mesh import device_mesh

K = 8


def mixture(n, d, seed):
    """The benchmark configuration's data, small: 8 equal-weight Gaussian
    groups (noise 1/16 a coordinate over 256 features, scaled so that a
    within-group squared distance stays ~2 at any d), centres twice the
    noise, and a common offset of order one. Returns (X, groups)."""
    spec = {"components": K, "noise_scale": 1.0 / np.sqrt(d),
            "center_scale": 2.0 / np.sqrt(d), "offset_scale": 1.0}
    hp = family.mixture_params(np.random.default_rng(seed), d, spec)
    X, g = family.mixture_rows(jax.random.PRNGKey(seed), n, d, hp, spec)
    return np.asarray(X), np.asarray(g).astype(np.int64)


def fitted(X, devices, seed=0, **params):
    mesh = device_mesh(devices=jax.devices()[:devices])
    return SpectralClustering(random_state=seed, persist_embedding=True,
                              **params).fit(as_sharded(X, mesh=mesh))


def all_readings(est, X, groups, cross="exact"):
    """{name: (reading, limit)} of a fitted estimator against the reference
    on the rows ``X`` — or, ``cross="bf16"``, of the CONTROL handed in as
    the estimator's outputs are."""
    n = len(X)
    blocks = ref.row_blocks(X, 512)
    want = ref.embedding(blocks, est.landmarks_, est.gamma, K)
    ref_labels = ref.nearest_point(
        want["E"], ref.cluster_points(want["E"], groups, K))
    if cross == "exact":
        out = family.outputs(est, est.labels_.to_numpy(), n)
    else:
        out = family.control_outputs(
            ref.embedding(blocks, est.landmarks_, est.gamma, K, cross=cross),
            groups, K)
    return T.readings(out, want, ref_labels, n, K), want


@pytest.mark.parametrize("n,d", [(4096, 32), (2048, 256)])
def test_fit_within_every_limit_of_the_reference_and_the_control_outside(
        n, d):
    X, groups = mixture(n, d, seed=n + d)
    est = fitted(X, 1, seed=5)
    got, want = all_readings(est, X, groups)
    assert want["gap"] >= T.MIN_GAP
    for name, (value, limit) in got.items():
        assert value <= limit, (name, value, limit)
    # labels up to a permutation: the groups themselves
    assert T.label_mismatch(est.labels_.to_numpy(), groups, K) == 0.0
    control, _ = all_readings(est, X, groups, cross="bf16")
    failed = [name for name, (value, limit) in control.items()
              if not value <= limit]
    assert failed and set(failed) <= {"embedding_row", "subspace_sine",
                                      "singular_values"}, control


def test_landmarks_are_distinct_valid_rows_and_follow_the_seed():
    X, _ = mixture(1000, 16, seed=1)          # ragged: 1000 rows on 8 shards
    a, b, a2 = (fitted(X, 8, seed=s, n_init=1).landmarks_ for s in (0, 1, 0))
    for idx in (a, b):
        assert idx.shape == (100,) and len(set(idx.tolist())) == 100
        assert idx.min() >= 0 and idx.max() < 1000
    assert np.array_equal(a, a2) and not np.array_equal(a, b)
    few = fitted(X[:60], 1, n_components=100, n_init=1).landmarks_
    assert sorted(few.tolist()) == list(range(60))   # c = min(c, n)


@pytest.mark.parametrize("devices", [1, 4])
def test_ragged_rows_on_any_mesh_give_the_reference_s_answer(devices):
    """1,000 rows do not divide over the shards: the masked tail rows are
    no rows of the embedding, and four devices give what one gives."""
    X, groups = mixture(1000, 32, seed=7)
    est = fitted(X, devices, seed=2)
    assert est.embedding_.shape == (1000, K)
    pad = np.asarray(est.embedding_.data)[1000:]
    assert pad.size == 0 or not pad.any()
    got, _ = all_readings(est, X, groups)
    for name, (value, limit) in got.items():
        assert value <= limit, (name, value, limit)


def test_one_root_span_flat_children_and_the_programs_calls():
    X, _ = mixture(2048, 32, seed=3)
    Xs = as_sharded(X, mesh=device_mesh(devices=jax.devices()[:1]))
    with config.set(obs_programs=True):
        SpectralClustering(random_state=0).fit(Xs)         # compile
        reset_recent_spans()
        before = {r["program"]: r["calls"] for r in programs_snapshot()}
        est = SpectralClustering(random_state=1, n_init=3).fit(Xs)
        KMeans(n_clusters=K, random_state=0).fit(est_embedding(est, Xs))
    ring = recent_spans()
    calls = {r["program"]: r["calls"] - before.get(r["program"], 0)
             for r in programs_snapshot()}
    roots = [r for r in ring if r["parent_id"] is None]
    assert [r["span"] for r in roots] == ["fit", "fit"]
    spectral, kmeans = roots
    assert spectral["component"] == "SpectralClustering"
    kids = [r for r in ring if r["parent_id"] == spectral["span_id"]]
    assert [r["span"] for r in kids] == ["fit.prep", "fit.solve",
                                         "fit.assign", "fit.finish"]
    # flat: nothing lies beneath a child, the restarts opened no span
    assert sum(r["root_id"] == spectral["span_id"] for r in ring) == 5
    walls = sum(r["wall_s"] for r in kids)
    assert walls <= spectral["wall_s"] + 1e-5
    assert spectral["wall_s"] - walls <= 0.02 * spectral["wall_s"] + 2e-3
    assign = kids[2]
    info = est.solver_info_
    assert assign["restarts"] == info["restarts"] == 3
    assert assign["n_iters"] == info["n_iters"] and len(info["n_iters"]) == 3
    assert assign["winner"] == info["winner"] == int(np.argmin(
        info["inertias"]))
    assert spectral["n_iter"] == assign["n_iter"] == info["lloyd_iters"] \
        == sum(info["n_iters"])
    assert spectral["n_landmarks"] == 100 and spectral["n_clusters"] == K
    assert kids[1]["embed"] == info["embed"] == "tsqr"
    assert kids[3]["qr_fallbacks"] == info["qr_fallbacks"] == 0
    # the handoff ledger lands on these spans: one dispatch of the embedding
    # under fit.solve, one fetch (S, the landmarks, the fallback flag) under
    # fit.finish, every restart's under fit.assign
    assert kids[1]["dispatches"] == 1 and kids[1]["fetches"] == 0
    assert kids[3]["dispatches"] == 0 and kids[3]["fetches"] == 1
    assert kids[2]["fetches"] >= 3 and spectral["fetches"] \
        == sum(r["fetches"] for r in kids)
    # KMeans.fit's own spans stay as they are
    assert kmeans["component"] == "KMeans"
    assert [r["span"] for r in ring if r["parent_id"] == kmeans["span_id"]] \
        == ["fit.validate", "fit.init", "fit.tol_scale", "fit.solve",
            "fit.finish"]
    assert calls["spectral.embed"] == 1
    assert calls["kmeans.lloyd"] == calls["kmeans.labels_inertia"] \
        == calls["kmeans.tol_scale"] == 3 + 1
    assert info["assign_fused"] is False and info["precision"] \
        == "float32/highest"


def est_embedding(est, Xs):
    """A (n, K) table for the plain KMeans fit of the span test: the rows'
    first K features."""
    return as_sharded(np.asarray(Xs.data)[:Xs.n_rows, :K], mesh=Xs.mesh)


def test_a_new_random_state_compiles_nothing():
    from benchmark.harness import compile_counter

    X, _ = mixture(1024, 16, seed=4)
    Xs = as_sharded(X, mesh=device_mesh(devices=jax.devices()[:1]))
    SpectralClustering(random_state=0, n_init=2).fit(Xs)
    compiles = compile_counter()
    before = compiles.n
    est = SpectralClustering(random_state=2**31 + 11, n_init=2).fit(Xs)
    assert compiles.n == before
    assert est.solver_info_["restarts"] == 2


def test_reference_is_the_stated_equations_in_float64():
    """The blocked float32 reference against the equations written out
    whole in float64 (n x n never formed there either, but nothing blocked,
    nothing rounded)."""
    X, _ = mixture(600, 24, seed=9)
    idx = np.random.default_rng(0).choice(600, 50, replace=False)
    got = ref.embedding(ref.row_blocks(X, 128), idx, 1.0, K)
    x = X.astype(np.float64)
    z = x[idx]
    B = np.exp(-((x[:, None] - z[None]) ** 2).sum(-1))
    A = np.exp(-((z[:, None] - z[None]) ** 2).sum(-1)) \
        + program.NYSTROM_JITTER * np.eye(50)
    w, V = np.linalg.eigh(A)
    deg = B @ ((V / w) @ V.T @ B.sum(axis=0))
    G = (B / np.sqrt(deg)[:, None]) @ ((V / np.sqrt(w)) @ V.T)
    U, S, _ = np.linalg.svd(G, full_matrices=False)
    E = U[:, :K] / np.linalg.norm(U[:, :K], axis=1, keepdims=True)
    assert got["n"] == 600
    assert np.max(np.abs(got["singular_values"] - S)) < 1e-6
    assert T.row_error(got["E"], E) < 1e-5
    assert T.angle_sine(got["E"], E) < 1e-6
    assert got["gap"] == pytest.approx(S[K - 1] / S[K], rel=1e-5)
    # blocks do not matter, and a sharded array's blocks are its rows
    again = ref.embedding(ref.row_blocks(X, 600), idx, 1.0, K)
    assert T.row_error(again["E"], got["E"]) < 1e-6
    Xs = as_sharded(X, mesh=device_mesh(devices=jax.devices()[:4]))
    sharded = ref.embedding(ref.shard_blocks(Xs.data, 600, 64), idx, 1.0, K)
    assert T.row_error(sharded["E"], got["E"]) < 1e-6


def test_the_benchmark_keeps_a_copy_of_the_reference():
    """The two files differ in their first docstring lines and in where the
    shared constants come from; the constants are equal."""
    import inspect

    from benchmark.references import spectral as copy

    def body(mod):
        src = inspect.getsource(mod)
        return src[src.index("def row_blocks"):]

    assert body(copy) == body(ref)
    assert (copy.NYSTROM_JITTER, copy.TINY) \
        == (program.NYSTROM_JITTER, program.TINY) == (ref.NYSTROM_JITTER,
                                                      ref.TINY)


def test_readings_tell_a_rotation_from_an_error():
    rng = np.random.default_rng(0)
    E = rng.standard_normal((500, K)).astype(np.float32)
    E /= np.linalg.norm(E, axis=1, keepdims=True)
    R = np.linalg.qr(rng.standard_normal((K, K)))[0]
    turned = (E @ R).astype(np.float32)
    assert T.row_error(turned, E) < 1e-6 and T.angle_sine(turned, E) < 1e-6
    off = turned.copy()
    off[7] += 1e-3
    assert 2e-3 < T.row_error(off, E) < 4e-3   # 1e-3 in each of 8
    labels = rng.integers(0, K, 500)
    assert T.label_mismatch((labels + 3) % K, labels, K) == 0.0
    wrong = (labels + 3) % K
    wrong[:5] = (wrong[:5] + 1) % K
    assert T.label_mismatch(wrong, labels, K) == pytest.approx(0.01)
    assert T.label_mismatch(labels + 1, labels, K) == 1.0   # a label of 8
