"""The resident Lloyd pass on a lane-dense table: below 128 features the
Pallas Lloyd loop (``models/kmeans.py::_lloyd_run_pallas``) transposes X once
and each iteration's kernel (``ops/pallas_fused.py::
fused_lloyd_stats_lanes``) reads ``(d, tile)`` blocks, where ``(tile, d)``
row blocks would pad d to 128 lanes. From 128 features up the loop is the
row form's, unchanged.

The kernel cases use whole-number tables: every distance, sum and count is
exact in float32 whatever the order of the sums, so the two forms must agree
bit for bit, ties included (the lower centre index wins)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dask_ml_tpu import config
from dask_ml_tpu.cluster import KMeans, SpectralClustering
from dask_ml_tpu.datasets import make_blobs
from dask_ml_tpu.models import kmeans as KM
from dask_ml_tpu.observability import (programs_snapshot, recent_spans,
                                       reset_recent_spans)
from dask_ml_tpu.ops import pallas_fused as pf
from dask_ml_tpu.parallel.mesh import device_mesh
from dask_ml_tpu.parallel.sharded import as_sharded


def _whole_numbers(n, d, k, seed):
    """Rows and centres drawn from {-2, ..., 2}; the last centre repeats
    the first, so it ties with it at every row and must win none."""
    rng = np.random.default_rng(seed)
    x = rng.integers(-2, 3, size=(n, d)).astype(np.float32)
    c = rng.integers(-2, 3, size=(k, d)).astype(np.float32)
    c[-1] = c[0]
    return x, c


def _numpy_stats(x, n_valid, c):
    d2 = np.maximum((x * x).sum(1)[:, None] - 2.0 * (x @ c.T)
                    + (c * c).sum(1)[None, :], 0.0)
    lab = d2.argmin(1)                       # the first of equal minima
    valid = np.arange(len(x)) < n_valid
    onehot = (lab[:, None] == np.arange(len(c))[None, :]) & valid[:, None]
    return (onehot.T.astype(np.float32) @ x, onehot.sum(0).astype(np.float32),
            np.float32(d2.min(1)[valid].sum()))


@pytest.mark.parametrize("k", [3, 8])
@pytest.mark.parametrize("d", [1, 2, 8, 64, 127])
def test_the_lane_form_sums_what_the_row_form_sums(d, k):
    # 1,000 rows, the first 937 valid: padded lanes and invalid rows both
    n, n_valid = 1_000, 937
    x, c = _whole_numbers(n, d, k, seed=10 * d + k)
    rows = pf.fused_lloyd_stats(x, n_valid, c, interpret=True)
    xt = pf.lloyd_lanes_operand(jnp.asarray(x), k)
    assert xt.shape[0] == d and xt.shape[1] % 128 == 0 and xt.shape[1] > n
    lanes = pf.fused_lloyd_stats_lanes(xt, n_valid, c, interpret=True)
    for got, want, ref in zip(lanes, rows, _numpy_stats(x, n_valid, c)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got), ref)
    assert float(lanes[1][-1]) == 0.0        # the repeated centre won none
    assert float(lanes[1].sum()) == n_valid


def test_the_lane_form_reads_an_unpadded_table():
    """Given ``(d, n)`` with n no multiple of a tile, the kernel pads it
    itself; the statistics are the operand's."""
    x, c = _whole_numbers(3_001, 5, 4, seed=1)
    got = pf.fused_lloyd_stats_lanes(jnp.asarray(x.T), 2_990, c,
                                     interpret=True)
    for g, want in zip(got, _numpy_stats(x, 2_990, c)):
        np.testing.assert_array_equal(np.asarray(g), want)


@pytest.mark.parametrize("n, d, k", [(4_194_304, 8, 8), (4_194_304, 127, 64),
                                     (1_000, 8, 3), (10_012, 1, 8)])
def test_the_lane_tiling(n, d, k):
    tile, chunk, n_pad = pf.lloyd_lane_tiling(n, d, k)
    assert chunk % 128 == 0 and tile % chunk == 0 and n_pad % tile == 0
    assert n <= n_pad < n + (n_pad // tile) * chunk
    # the block and the (d, chunk) and (k, chunk) values fit their budgets
    rows = -(-d // 8) * 8
    assert rows * tile * 4 <= pf._GLM_TILE_BUDGET
    assert max(rows, -(-k // 8) * 8) * chunk * 4 <= pf._LANE_CHUNK_BYTES


def test_the_layout_rule():
    assert [pf.lloyd_layout(d) for d in (1, 8, 127, 128, 256)] \
        == ["lanes", "lanes", "lanes", "rows", "rows"]


# -- the Lloyd program --------------------------------------------------------

def _program_ops(d, n=1_024, k=4):
    """(transposes as (where, permutation), pallas_call x-block shapes) in
    the jaxpr of ``kmeans.lloyd_pallas`` over four devices at d features."""
    mesh = device_mesh(devices=jax.devices()[:4])
    run = KM._lloyd_run_pallas.__wrapped_jit__
    jx = jax.make_jaxpr(
        lambda X, m, c: run(X, m, c, 5, 0.0, mesh, interpret=True))(
        jnp.zeros((n, d)), jnp.ones((n,)), jnp.zeros((k, d)))
    transposes, blocks = [], []

    def walk(jaxpr, where):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                x_block = e.params["grid_mapping"].block_mappings[0]
                blocks.append(tuple(b.block_size
                                    for b in x_block.block_shape))
                continue                   # not into the kernel's body
            if e.primitive.name == "transpose":
                transposes.append((where, e.params["permutation"]))
            for v in e.params.values():
                for sub in v if isinstance(v, (tuple, list)) else [v]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, where + (e.primitive.name,))

    walk(jx.jaxpr, ())
    return transposes, blocks


def test_below_128_the_program_transposes_once_before_its_loop():
    transposes, blocks = _program_ops(8)
    assert len(transposes) == 1
    where, perm = transposes[0]
    assert perm == (1, 0) and "while" not in where
    assert len(blocks) == 1 and blocks[0][0] == 8      # (d, tile)


@pytest.mark.parametrize("d", [128, 256])
def test_from_128_the_program_reads_row_blocks(d):
    transposes, blocks = _program_ops(d)
    assert transposes == []
    assert len(blocks) == 1 and blocks[0][1] == d       # (tile, d)


# -- the estimators ------------------------------------------------------------

@pytest.fixture(scope="module")
def blobs8():
    X, _ = make_blobs(n_samples=2_003, n_features=8, centers=5,
                      random_state=7, cluster_std=1.0)
    return X.to_numpy()


def test_kmeans_on_four_devices_fits_what_xla_fits(blobs8):
    Xs = as_sharded(blobs8, mesh=device_mesh(devices=jax.devices()[:4]))
    assert len(Xs.data.sharding.device_set) == 4
    init = blobs8[:5].copy()
    xla = KMeans(n_clusters=5, init=init, max_iter=50,
                 use_pallas=False).fit(Xs)
    pls = KMeans(n_clusters=5, init=init, max_iter=50,
                 use_pallas=True).fit(Xs)
    assert pls.solver_info_["lloyd_layout"] == "lanes"
    assert xla.solver_info_["lloyd_layout"] is None
    np.testing.assert_allclose(pls.cluster_centers_, xla.cluster_centers_,
                               atol=1e-3)
    assert pls.inertia_ == pytest.approx(xla.inertia_, rel=1e-4)
    np.testing.assert_array_equal(pls.labels_.to_numpy(),
                                  xla.labels_.to_numpy())
    assert pls.n_iter_ == xla.n_iter_


@pytest.mark.parametrize("d, layout", [(8, "lanes"), (128, "rows"),
                                       (256, "rows")])
def test_the_fit_records_the_layout(d, layout):
    rng = np.random.default_rng(d)
    X = rng.standard_normal((300, d)).astype(np.float32)
    reset_recent_spans()
    with config.set(obs_programs=True):
        km = KMeans(n_clusters=3, init=X[:3].copy(), max_iter=3,
                    use_pallas=True).fit(X)
        ring = {r["span"]: r for r in recent_spans()}
    assert km.solver_info_["lloyd_layout"] == layout
    assert ring["fit.solve"]["layout"] == layout
    assert ring["fit.solve"]["fused"] is True


def test_spectral_restarts_run_the_lane_form():
    X, _ = make_blobs(n_samples=1_500, n_features=6, centers=3,
                      random_state=2, cluster_std=1.0)

    def calls():
        return {r["program"]: int(r["calls"]) for r in programs_snapshot()}

    reset_recent_spans()
    with config.set(obs_programs=True):
        before = calls()
        sc = SpectralClustering(n_clusters=3, n_components=30, n_init=3,
                                random_state=0,
                                kmeans_params={"use_pallas": True}).fit(X)
        # the estimators' own programs (a first fit also builds a row mask)
        ran = {name: n - before.get(name, 0) for name, n in calls().items()
               if n - before.get(name, 0)
               and name.startswith(("kmeans.", "spectral."))}
        ring = {r["span"]: r for r in recent_spans()}
    assert sc.solver_info_["assign_fused"] is True
    assert sc.solver_info_["assign_layout"] == "lanes"
    assert ring["fit.assign"]["assign_layout"] == "lanes"
    assert ran == {"spectral.embed": 1, "kmeans.tol_scale": 3,
                   "kmeans.lloyd_pallas": 3, "kmeans.labels_inertia": 3}
