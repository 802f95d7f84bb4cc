"""The per-layer readers the ``pca`` family brought (``center_ms``,
``rsvd_solve_ms``, ``x_sweeps_per_fit``, ``transform_host_ms``: the
program's spans; ``qr_pct``, ``pca_fit_roofline``: the device trace), each
on a recorded ring or a recorded summary with known answers, and the
family's own generator and refusal."""

import json
import re

import jax
import numpy as np
import pytest

from benchmark import harness
from benchmark.metrics import _spans

CELL = "pca_rsvd_x512"
SPAN_READERS = ("center_ms", "rsvd_solve_ms", "x_sweeps_per_fit",
                "transform_host_ms")
TRACE_READERS = ("qr_pct", "pca_fit_roofline")


def _read(name):
    return harness.load_module("metrics", name).read


def _rec(span, span_id, root_id, parent_id, wall_s, **attrs):
    return {"span": span, "span_id": span_id, "root_id": root_id,
            "parent_id": parent_id, "wall_s": wall_s, "sync_s": 0.0,
            "t_start_ns": 0, "t_end_ns": 0, **attrs}


def _ring():
    """A warm-up fit and transform, then two cycles of the window; the
    second window fit's centring took 3 ms, its solve 700."""
    ring, sid = [], 0
    for center, solve, tr_wall, tr_sync in ((0.9, 0.5, 0.9, 0.0),
                                            (0.001, 0.8, 0.004, 0.001),
                                            (0.003, 0.7, 0.002, 0.0005)):
        root = sid = sid + 1
        ring += [
            _rec("fit.validate", sid + 1, root, root, 1e-4),
            _rec("fit.center", sid + 2, root, root, center, x_sweeps=2),
            _rec("fit.solve", sid + 3, root, root, solve, solver="randomized",
                 size=74, n_iter=2, x_sweeps=6, sync_s=solve - 0.001),
            _rec("fit.finish", sid + 4, root, root, 1e-4),
            _rec("fit", root, root, None, center + solve + 2e-4,
                 component="PCA", n_rows=2048, n_iter=2)]
        sid += 5
        ring.append(_rec("transform", sid, sid, None, tr_wall,
                         component="PCA", n_rows=2048) | {"sync_s": tr_sync})
    return ring


def test_span_readers_on_a_recorded_ring(monkeypatch):
    monkeypatch.setattr(_spans, "_ring", _ring)
    ctx = {"fits": [{"fit_s": 0.8}, {"fit_s": 0.7}],
           "cycles": [{"predict_s": [0.01]}, {"predict_s": [0.01]}]}
    assert _read("center_ms")(ctx) == pytest.approx(2.0)        # (1 + 3) / 2
    assert _read("rsvd_solve_ms")(ctx) == pytest.approx(750.0)
    assert _read("x_sweeps_per_fit")(ctx) == pytest.approx(8.0)  # 6 + 2
    # wall less the wait: (4 - 1 + 2 - 0.5) / 2 ms
    assert _read("transform_host_ms")(ctx) == pytest.approx(2.25)
    # the family's cell also reports the shared fit_prep_ms from these spans
    assert _read("fit_prep_ms")(ctx) is not None


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_find_nothing_in_another_family_s_ring(name, monkeypatch):
    """An empty ring, a GLM's spans (no ``fit.center``, a ``fit.solve``
    without ``x_sweeps``, ``predict`` for ``transform``): None, no raise."""
    ctx = {"fits": [{"fit_s": 0.1}], "cycles": [{"predict_s": [0.1]}]}
    monkeypatch.setattr(_spans, "_ring", lambda: [])
    assert _read(name)(ctx) is None
    glm = [_rec("fit.prepare", 2, 1, 1, 0.01),
           _rec("fit.solve", 3, 1, 1, 0.05, n_evals=9),
           _rec("fit", 1, 1, None, 0.06, component="LogisticRegression"),
           _rec("predict", 4, 4, None, 0.08)]
    monkeypatch.setattr(_spans, "_ring", lambda: glm)
    assert _read(name)(ctx) is None


def _trace_ctx(summary):
    cell = harness.load_cell(CELL)
    return {"trace": summary, "cell": cell, "n_rows": 2097152, "chips": 1,
            "d": 512, "peaks": lambda: harness.peaks_for("TPU v5 lite"),
            "kernel_cost": lambda: harness.load_module(
                "kernels", cell.config["main_kernel"]["cost"]).cost}


def test_trace_readers_on_the_recorded_v5e_summary():
    """Three traced cycles of the cell on one v5e (PR 25): three tall QR
    loops a fit at 0.2297 s each; 4.29 GB of X over 819 GB/s against 0.771 s
    of device time a fit."""
    summary = harness.load_json(harness.HERE, "testdata",
                                "summary_pca_v5e.json")
    ctx = _trace_ctx(summary)
    pattern = ctx["cell"].config["qr_ops"]["pattern"]
    loops = [n for n in summary["ops"] if re.search(pattern, n)]
    assert sorted(n.split(" = ")[0] for n in loops) == \
        ["while.26", "while.27", "while.28"]
    assert _read("qr_pct")(ctx) == pytest.approx(88.869, abs=1e-3)
    share = _read("pca_fit_roofline")(ctx)
    assert share == pytest.approx(0.68033, abs=1e-4)
    busy = 2.326626364 * (1 - 0.6080280537902327 / 100) / 3
    assert share == pytest.approx(100 * (2097152 * 512 * 4 / 819e9) / busy)
    assert 0 < share <= 100


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_readers_find_nothing_without_their_operations(name):
    assert _read(name)(_trace_ctx(None)) is None
    kmeans = harness.load_json(harness.HERE, "testdata",
                               "trace_kmeans_v5e.json")
    from benchmark import trace_reduce

    summary = trace_reduce.reduce(kmeans)
    if name == "qr_pct":     # no tall QR loop in a Lloyd trace
        assert _read(name)(_trace_ctx(summary)) is None
    else:                    # the fit's floor is read off any fit's trace
        assert 0 < _read(name)(_trace_ctx(summary)) <= 100


def test_cost_is_one_read_of_x():
    cost = harness.load_module("kernels", "pca_fit").cost
    assert cost(2097152, 512, {"n_components": 64}) == \
        {"bytes": 4 * 2097152 * 512, "flops": 0}


def test_planted_generator_is_seeded_sharded_and_planted():
    """The family's own distribution through the benchmark's born-sharded
    program: the same seed gives the same rows, two chips draw different
    rows, and the rows' covariance has the planted spectrum."""
    from benchmark import datagen
    from benchmark.families import pca as fam  # noqa: F401  (registers it)
    from dask_ml_tpu.parallel.mesh import device_mesh

    cfg = harness.load_cell(CELL).config
    assert cfg["data"]["generator"] == "planted_subspace"
    mesh = device_mesh(devices=jax.devices()[:2])
    d, n = 64, 16384
    gen = dict(cfg["data"], components=4)
    hp = datagen.host_params(gen, d, 5)
    np.testing.assert_allclose(hp["basis"].T @ hp["basis"], np.eye(4),
                               atol=1e-6)
    np.testing.assert_allclose(hp["scale"] ** 2 + 1.0,
                               64.0 * 0.25 ** (np.arange(4) / 3), rtol=1e-6)
    a, y = datagen.make_resident(gen, n, d, 5, mesh, hp)
    b, _ = datagen.make_resident(gen, n, d, 5, mesh, hp)
    c, _ = datagen.make_resident(gen, n, d, 6, mesh,
                                 datagen.host_params(gen, d, 6))
    assert y is None and len(a.sharding.device_set) == 2
    a = np.asarray(a)
    assert np.array_equal(a, np.asarray(b))
    assert not np.array_equal(a, np.asarray(c))
    assert not np.array_equal(a[:n // 2], a[n // 2:])
    lam = np.linalg.eigvalsh(np.cov(a.T.astype(np.float64)))[::-1]
    np.testing.assert_allclose(lam[:4], 64.0 * 0.25 ** (np.arange(4) / 3),
                               rtol=0.1)
    assert lam[4] < 1.3 and abs(np.mean(a.mean(axis=0) - hp["mean"])) < 0.05


def test_family_refuses_a_program_from_before_the_resident_pca(monkeypatch):
    """On the parent of PR 25 (no ``solver_info_``, no ``pca.rsvd``) the
    cell fails cleanly, before any data is made."""
    from benchmark.families import pca as fam
    from dask_ml_tpu.ops import linalg

    cell = harness.load_cell(CELL).with_traffic(rows_per_chip=2048)
    monkeypatch.delattr(linalg, "randomized_svd_sweeps")
    with pytest.raises(harness.BenchmarkError, match="before PR 25"):
        fam.make_data(cell.config, cell.traffic, 1, 0, None)


def test_configuration_states_its_deployment_and_widths():
    cfg = harness.load_cell(CELL).config
    dep = cfg["deployment"]
    assert (dep["rows"], dep["chips"], dep["rows_per_chip"]) == \
        (1_000_000_000, 256, 3_906_250)
    assert dep["rows_per_chip"] * dep["chips"] == dep["rows"]
    assert cfg["n_features"] == dep["n_features"] == 512
    assert cfg["estimator"]["params"] == {"n_components": 64,
                                          "svd_solver": "randomized"}
    assert cfg["reduced"] == ["rows_per_chip"]
    assert set(cfg["assumed"]) == {"n_components", "deployment", "data"}
    assert cfg["expect"] == {"fit_dtype": "float32", "program": "pca.rsvd",
                             "solver": "randomized"}
    traffic = harness.load_cell(CELL).traffic
    assert traffic["rows_per_chip"] == 2_097_152
    assert traffic["cycle"] == ["fit", "predict"]
    json.dumps(cfg)
