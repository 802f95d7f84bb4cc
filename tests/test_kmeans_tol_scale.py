"""How a resident ``KMeans.fit`` gets its Lloyd threshold (PR 37,
``models/kmeans.py::_lloyd_tol2``): at ``tol == 0`` an exact zero for which
X is not read — no launch, no temporary — and at ``tol > 0`` ONE tracked
program, ``kmeans.tol_scale``, of two fused passes over X. XLA:CPU gives the
counts, the values and what compiled; the tests that compile for a
described v5e hold the programs' memory at the benchmark cells' shapes —
this one and, since PR 40, k-means‖'s candidate-weight pass at
``spectral_nystrom``'s, and the Lloyd program at both cells' tables (this
file is the one that describes the chip)."""

import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from sklearn.cluster import KMeans as SkKMeans

from dask_ml_tpu import config, observability as obs
from dask_ml_tpu.cluster import KMeans
from dask_ml_tpu.models import kmeans as KM
from dask_ml_tpu.ops.reductions import masked_mean_var
from dask_ml_tpu.parallel import as_sharded

K, D = 4, 6
# a row count the 8-way mesh has to pad, and one it shards whole
LAYOUTS = {"padded": 1003, "sharded": 1024}


@pytest.fixture(autouse=True)
def _clean_ring():
    obs.reset_recent_spans()
    yield
    obs.reset_recent_spans()


def _data(layout):
    """Four overlapping blobs far from the origin (a padded zero row that
    was not masked would move every mean by a visible amount), and an init
    that is four of its rows."""
    n = LAYOUTS[layout]
    rng = np.random.RandomState(n)
    centres = rng.randn(K, D).astype(np.float32) * 2.0 + 7.0
    Xh = (centres[rng.randint(K, size=n)]
          + rng.randn(n, D).astype(np.float32)).astype(np.float32)
    X = as_sharded(Xh)
    assert len(X.data.sharding.device_set) == 8
    assert (X.padded_shape[0] > n) == (layout == "padded")
    return Xh, X, Xh[:K].copy()


def _program_calls():
    return {r["program"]: int(r["calls"]) for r in obs.programs_snapshot()}


def _fit_recorded(est, X):
    """(fitted est, the tracked programs the fit ran, its span records)."""
    obs.reset_recent_spans()
    with config.set(obs_programs=True):
        before = _program_calls()
        est.fit(X)
        ran = {k: v - before.get(k, 0) for k, v in _program_calls().items()
               if v - before.get(k, 0)}
        ring = {r["span"]: r for r in obs.recent_spans()}
    return est, ran, ring


def _old_tol2(X, mask, tol):
    """The threshold as every fit took it until PR 37: the eager variance,
    whatever ``tol`` is."""
    _, var = masked_mean_var(X.data, mask, X.n_rows)
    return jnp.asarray(tol, X.dtype) * jnp.mean(var), 2


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("tol", [0.0, 0, np.float32(0)],
                         ids=["float", "int", "f32"])
def test_tol_zero_reads_no_x_and_fits_what_the_old_scale_fitted(
        monkeypatch, tol, use_pallas, layout):
    _, X, init = _data(layout)
    new = lambda: KMeans(n_clusters=K, init=init, max_iter=6,  # noqa: E731
                         tol=tol, use_pallas=use_pallas)
    est, ran, ring = _fit_recorded(new(), X)
    assert "kmeans.tol_scale" not in ran
    assert est.solver_info_["tol_scale_passes"] == 0
    phase = ring["fit.tol_scale"]
    assert phase["passes"] == 0
    assert phase["dispatches"] == 0 and phase["host_operands"] == 0
    assert ring["fit"]["host_operands"] == 0
    assert est.n_iter_ == 6

    monkeypatch.setattr(KM, "_lloyd_tol2", _old_tol2)
    old = new().fit(X)
    assert old.solver_info_["tol_scale_passes"] == 2    # the patch ran
    assert est.n_iter_ == old.n_iter_
    assert est.inertia_ == old.inertia_
    np.testing.assert_array_equal(est.cluster_centers_, old.cluster_centers_)
    np.testing.assert_array_equal(est.labels_.to_numpy(),
                                  old.labels_.to_numpy())


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("use_pallas", [False, True])
def test_tol_positive_is_one_program_and_sklearns_fit(use_pallas, layout):
    Xh, X, init = _data(layout)
    est, ran, ring = _fit_recorded(
        KMeans(n_clusters=K, init=init, max_iter=100, tol=1e-4,
               use_pallas=use_pallas), X)
    assert ran["kmeans.tol_scale"] == 1
    assert est.solver_info_["tol_scale_passes"] == 2
    phase = ring["fit.tol_scale"]
    assert phase["passes"] == 2 and phase["dispatches"] == 1
    # n_rows and tol ride in with the dispatch
    assert phase["host_operands"] == 2 and phase["host_operand_bytes"] == 8

    ref = SkKMeans(n_clusters=K, init=init, n_init=1, algorithm="lloyd",
                   max_iter=100, tol=1e-4).fit(Xh)
    assert 1 < ref.n_iter_ < 100
    assert est.n_iter_ == ref.n_iter_
    np.testing.assert_allclose(est.cluster_centers_, ref.cluster_centers_,
                               atol=1e-3)


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_the_threshold_is_tol_times_the_mean_variance_of_the_logical_rows(
        layout):
    Xh, X, _ = _data(layout)
    tol2, passes = KM._lloyd_tol2(X, X.row_mask(X.dtype), 1e-4)
    want = 1e-4 * np.var(Xh.astype(np.float64), axis=0).mean()
    assert passes == 2
    assert tol2.dtype == jnp.float32 and tol2.shape == ()
    assert float(tol2) == pytest.approx(want, rel=1e-6)
    zero, passes = KM._lloyd_tol2(X, None, 0)    # the mask is not read either
    assert passes == 0 and zero.dtype == jnp.float32 and float(zero) == 0.0


def test_one_program_serves_every_row_count_of_a_padded_shape():
    # n_rows is an operand, not a static
    jit = KM._tol_scale.__wrapped_jit__
    rng = np.random.RandomState(3)
    for n in (1017, 1021, 1024):                 # all pad to 1024 rows
        X = as_sharded(rng.randn(n, 5).astype(np.float32))
        tol2, _ = KM._lloyd_tol2(X, X.row_mask(X.dtype), 1e-3)
        assert float(tol2) == pytest.approx(
            1e-3 * np.var(X.to_numpy().astype(np.float64), axis=0).mean(),
            rel=1e-5)
        if n == 1017:
            size = jit._cache_size()
    assert jit._cache_size() == size


class _CompilesBySpan(logging.Handler):
    """``jax.log_compiles``' "Compiling <name> ..." lines, each with the
    name of the span that was open on the compiling thread."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.seen = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.seen.append((obs.current_span().name, msg.split()[1]))


@pytest.mark.parametrize("tol, compiled", [(1e-4, ["jit(_tol_scale)"]),
                                           (0.0, [])])
def test_a_first_fit_of_a_shape_compiles_only_the_new_program_in_the_phase(
        tol, compiled):
    # shapes no other test of this process fits: every program of the fit
    # compiles, and each compile names the phase it fell into
    n = 777 if tol else 779
    X = np.random.RandomState(n).randn(n, 11).astype(np.float32)
    handler = _CompilesBySpan()
    logger = logging.getLogger("jax")
    logger.addHandler(handler)
    try:
        with jax.log_compiles(), config.set(obs_programs=True):
            KMeans(n_clusters=3, init=X[:3].copy(), max_iter=4, tol=tol,
                   use_pallas=False).fit(X)
    finally:
        logger.removeHandler(handler)
    by_span = {}
    for name, program in handler.seen:
        by_span.setdefault(name, []).append(program)
    assert by_span.get("fit.tol_scale", []) == compiled
    assert "jit(_lloyd_run)" in by_span["fit.solve"]     # the log works


# -- the chip's compiler, no chip attached -----------------------------------

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_at_the_cells_shape_the_chips_compiler_makes_two_passes_and_no_copy(
        one_chip):
    """``kmeans_lloyd``'s X, 4,194,304 x 256 f32 on one v5e: the program is
    two multiply-and-reduce fusions over x and holds nothing X-sized."""
    n, d = 4_194_304, 256
    A = lambda shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=one_chip)
    compiled = KM._tol_scale.__wrapped_jit__.lower(
        A((n, d)), A((n,)), A(()), A(())).compile()
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < n * d * 4 // 8
    reads_x = [line for line in compiled.as_text().splitlines()
               if " fusion(" in line and "%x" in line.split("fusion(")[1]]
    assert len(reads_x) == 2
    assert all("f32[256]" in line.split("=")[1] for line in reads_x)


def test_the_candidate_weights_keep_the_one_hot_out_of_memory(one_chip):
    """``spectral_nystrom``'s k-means‖ weight pass, 4,194,304 x 8 rows
    against 81 candidates on one v5e (PR 40): no scatter, and no temporary
    as large as the int32 labels, let alone the (n, 81) one-hot (1.36 GB in
    float32)."""
    n, d, c = 4_194_304, 8, 81
    A = lambda shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=one_chip)
    compiled = KM._candidate_weights.lower(
        A((n, d)), A((n,)), A((c, d)), A((c,))).compile()
    assert " scatter(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * n


@pytest.mark.parametrize("d, k, kernel", [
    (8, 8, "fused_lloyd_stats_lanes"), (256, 64, "fused_lloyd_stats")])
def test_the_lloyd_program_reads_the_table_where_it_lies(one_chip, d, k,
                                                         kernel):
    """``kmeans.lloyd_pallas`` at the two cells' tables on one v5e:
    ``spectral_nystrom``'s 8-wide E goes to the lane-dense kernel as the
    column-major array it is (a bitcast, no lane-padded 2 GiB copy), and
    ``kmeans_lloyd``'s 256-wide X to the row form, whose kernel keeps the
    name the cell's roofline reader looks for."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n = 4_194_304
    mesh = Mesh(np.array(list(one_chip.device_set)), ("data",))
    A = lambda shape, spec, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa
        shape, dt, sharding=NamedSharding(mesh, spec))
    compiled = KM._lloyd_run_pallas.__wrapped_jit__.lower(
        A((n, d), P("data", None)), A((n,), P("data")), A((k, d), P()),
        A((), P(), jnp.int32), A((), P()), mesh=mesh, interpret=False,
        log=False).compile()
    text = compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < n * 4
    assert f"%{kernel}." in text and "custom-call(" in text
    assert " copy(%X" not in text
    if d < 128:
        assert f"f32[{d},{n}]" in text.split(" bitcast(%X")[0].split("\n")[-1]
