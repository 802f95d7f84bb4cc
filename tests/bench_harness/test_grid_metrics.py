"""The ``gridsearch`` family's own pieces, rehearsed on the CPU: the five
readers of the fold-stacked search (``grid_solve_ms``,
``grid_evals_per_fit``, ``grid_score_ms``, ``grid_refit_ms``,
``grid_fit_roofline``), the one-evaluation floor, the benchmark's entries
found BY NAME, the check failing a search whose models or scores are wrong,
and readers that return None — and raise nothing — on a program without
the spans. A CPU run gives counts and correctness, never a time worth
writing down."""

import dataclasses

import jax
import numpy as np
import pytest

from benchmark import harness
from benchmark.families import gridsearch as fam
from dask_ml_tpu import observability as obs
from dask_ml_tpu.parallel.mesh import device_mesh, use_mesh

from .test_rehearsal import _tiny

CELL = "gridsearch_logreg"
READERS = ("grid_solve_ms", "grid_evals_per_fit", "grid_score_ms",
           "grid_refit_ms", "grid_fit_roofline")


def _read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


def test_grid_readers_in_a_traced_rehearsal(tmp_path):
    cell, devices = _tiny(harness.load_cell(CELL))
    obs.reset_recent_spans()
    lines = []
    res = harness.run_cell(cell, seed=5, seconds=0.2, trace=1,
                           devices=devices, interpret=True,
                           dump=str(tmp_path), log=lines.append)
    assert res["correct"] is True, lines
    m = {k: v["value"] for k, v in res["metrics"].items()}
    dumped = harness.load_json(tmp_path, f"{CELL}_trace1_s5.json")
    fits = [f for c in dumped["cycles"] for f in c["fits"]]
    evals = [f["facts"]["n_evals"] for f in fits]
    assert m["grid_evals_per_fit"] == pytest.approx(sum(evals) / len(evals))
    assert all(f["facts"]["path"] == "stacked-folds"
               and f["facts"]["fold_copies"] == 0 for f in fits)
    assert all(e > f["facts"]["n_iter"] for e, f in zip(evals, fits))
    for name in ("grid_solve_ms", "grid_score_ms", "grid_refit_ms"):
        assert 0 < m[name] < 1e3 * max(f["fit_s"] for f in fits)
    assert "grid_fit_roofline" not in m           # no device plane here
    assert m["fit_prep_ms"] > 0 and m["compiles_in_window"] == 0.0
    want = dict(harness.load_cell(CELL).config["expect"]["programs"])
    want["glm.prepare"] -= 1        # an f32 design off the chip: no cast
    assert all(f["programs"] == want for f in fits)
    facts = dumped["facts"]
    assert facts["models_checked"] == 50
    assert facts["score_rows_over_near_max"] <= 0
    assert facts["grad_over_band_max"] <= 1.0
    assert facts["excess_band"] >= facts["excess_max"] >= -1e-5
    obs.reset_recent_spans()


def test_readers_find_nothing_without_the_spans(monkeypatch):
    """An empty ring, a program from before the ring, a program whose
    search opens no such spans (its roots are a plain fit's: ``fit.solve``
    without ``n_models``, no ``fit.score``, no ``fit.refit``), no device
    trace: None, and no raise."""
    obs.reset_recent_spans()
    cell = harness.load_cell(CELL)
    ctx = {"fits": [{"fit_s": 0.5, "facts": {"n_iter": 9}}] * 3,
           "cycles": [{"predict_s": [0.1]}], "trace": None, "cell": cell}
    for name in READERS:
        assert _read(name, ctx) is None
    root = {"span": "fit", "parent_id": None, "span_id": 1, "root_id": 1}
    solve = {"span": "fit.solve", "parent_id": 1, "span_id": 2, "root_id": 1,
             "wall_s": 0.1, "n_iter": 9, "n_evals": 11}
    monkeypatch.setattr(obs, "recent_spans", lambda: [root, solve] * 3)
    ctx["trace"] = {"kinds": {"bench.fit": {"calls": 3, "seconds": 1.5,
                                            "idle_pct": 10.0}}}
    for name in READERS:
        assert _read(name, ctx) is None
    monkeypatch.delattr(obs, "recent_spans")
    for name in READERS:
        assert _read(name, ctx) is None


def _made_up(cell):
    root = {"span": "fit", "parent_id": None, "span_id": 1, "root_id": 1}
    kids = [{"span": "fit.solve", "wall_s": 0.3, "n_iter": 20,
             "n_evals": 24, "n_models": 50},
            {"span": "fit.score", "wall_s": 0.01, "scored": "program"},
            {"span": "fit.refit", "wall_s": 0.05, "nested": []}]
    ring = [root] + [{**k, "parent_id": 1, "span_id": i + 2, "root_id": 1}
                     for i, k in enumerate(kids)]
    return ring, {
        "cell": cell, "fits": [{"fit_s": 0.4, "facts": {"n_iter": 20}}],
        "cycles": [], "n_rows": 4194304, "d": 256, "chips": 1,
        "trace": {"kinds": {"bench.fit": {"calls": 1, "seconds": 0.4,
                                          "idle_pct": 10.0}}},
        "peaks": lambda: harness.peaks_for("TPU v5 lite"),
        "kernel_cost": lambda: harness.load_module(
            "kernels", cell.config["main_kernel"]["cost"]).cost}


def test_the_readers_on_a_made_up_ring_and_trace(monkeypatch):
    """24 evaluations of the 2.62 ms floor over 0.36 s of busy chip."""
    cell = harness.load_cell(CELL)
    ring, ctx = _made_up(cell)
    monkeypatch.setattr(obs, "recent_spans", lambda: ring)
    assert _read("grid_solve_ms", ctx) == pytest.approx(300.0)
    assert _read("grid_evals_per_fit", ctx) == 24.0
    assert _read("grid_score_ms", ctx) == pytest.approx(10.0)
    assert _read("grid_refit_ms", ctx) == pytest.approx(50.0)
    one_read = 4194304 * 256 * 2 / 819e9
    assert _read("grid_fit_roofline", ctx) == pytest.approx(
        100 * 24 * one_read / 0.36)
    assert 0 < _read("grid_fit_roofline", ctx) < 100


def test_the_floor_at_the_cells_shapes():
    """One read of the bf16 design (2.147 GB, 2.62 ms at 819 GB/s) bounds an
    evaluation; 50 models' two products are 2.15e11 FLOP, 1.09 ms of the
    MXU's bf16 peak."""
    cell = harness.load_cell(CELL)
    cost = harness.load_module("kernels", "grid_fit").cost
    need = cost(4194304, 256, cell.config["main_kernel"])
    assert need == {"bytes": 4194304 * 256 * 2,
                    "flops": 4 * 4194304 * 256 * 50}
    peaks = harness.peaks_for("TPU v5 lite")
    assert need["bytes"] / peaks["hbm_bytes_per_s"] == pytest.approx(
        2.621e-3, rel=1e-3)
    assert need["flops"] / peaks["flops_bf16"] == pytest.approx(
        1.090e-3, rel=1e-3)


ENTRIES = [
    ("grid_solve_ms", "ms", "program_span", "Resident solver"),
    ("grid_evals_per_fit", "count", "program_counter", "Resident solver"),
    ("grid_score_ms", "ms", "program_span", "Estimator entry"),
    ("grid_refit_ms", "ms", "program_span", "Estimator entry"),
    ("grid_fit_roofline", "%", "device_trace", "Kernels"),
]


@pytest.mark.parametrize("name,unit,source,layer", ENTRIES)
def test_the_cell_s_entries_by_name(name, unit, source, layer):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit,
                     "better": "higher" if unit == "%" else "lower",
                     "source": source, "layer": layer, "moves": "fit_s",
                     "workloads": [CELL]}
    harness.load_module("metrics", name)          # its reader is there


def test_the_cell_and_its_configuration_by_name():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "gridsearch_logreg_c10_cv5_1b_x256", "resident_4m", 1)
    (c,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    assert c["reduced"] == ["rows_per_chip"]
    cfg = harness.load_json(harness.ROOT, c["file"])
    assert cfg["source"] == c["source"] and len(c["source"]) <= 200
    assert cfg["n_features"] == 256
    assert np.allclose(fam.grid(cfg), np.logspace(-4, 4, 10))
    assert cfg["estimator"]["inner"]["params"] == {
        "solver": "lbfgs", "tol": 0.001, "max_iter": 50, "warm_start": False}
    mine = {m["name"] for m in bench["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]}
    assert {"iter_ms", "fit_prep_ms", "compiles_in_window", "fit_idle_pct",
            "predict_idle_pct", "peak_hbm", *READERS} == mine


def test_a_program_without_the_stacked_path_is_refused(monkeypatch):
    from dask_ml_tpu.model_selection import _search

    cell, _ = _tiny(harness.load_cell(CELL))
    monkeypatch.delattr(_search, "_FoldIds")
    with pytest.raises(harness.BenchmarkError, match="no fold-stacked"):
        fam.make_data(cell.config, cell.traffic, 1, 3, None)


@pytest.fixture(scope="module")
def fitted():
    """One search of the cell at a rehearsal size, its data, and its
    predict_proba."""
    cell = harness.load_cell(CELL).with_traffic(rows_per_chip=4096,
                                                sample_rows=1024)
    mesh = device_mesh(devices=jax.devices()[:1])
    with use_mesh(mesh):
        data = fam.make_data(cell.config, cell.traffic, 1, 11, mesh)
        fam.vary(cell, data, 1)
        est = fam.make_estimator(cell, data, True)
        fam.fit(est, data)
        predicted = fam.predict(est, data)
    return cell, data, mesh, est, predicted


def test_the_check_passes_the_search(fitted):
    cell, data, mesh, est, predicted = fitted
    with use_mesh(mesh):
        chk = fam.check(cell, est, data, predicted)
        eng = fam.engaged(cell, est, data)
    assert not chk.failures and not eng.failures
    assert chk.facts["best_C"] == est.best_params_["C"]


def _faulty(est, **info):
    """A copy of the fitted search with ``search_info_`` entries
    replaced."""
    import copy

    bad = copy.copy(est)
    bad.search_info_ = {**est.search_info_, **info}
    return bad


@pytest.mark.parametrize("fault,failure", [
    ("bf16_scores", "recorded test score"),
    ("train_rows", "recorded test score"),
    ("wrong_folds", "reference gradient"),
])
def test_the_check_fails_each_fault(fitted, fault, failure):
    """``tools/grid_faults.py``'s faults: the test scores at the nearest
    precision below the program's (one bfloat16 pass of the product) fail
    by the near-tie band alone; scores of the training rows by the same;
    fold f's models moved to fold f + 1 by their gradients on their own
    training rows."""
    from benchmark.tools import grid_faults

    cell, data, mesh, est, predicted = fitted
    with use_mesh(mesh):
        bad = grid_faults.faults(est, data)[fault]
        failures = fam.check(cell, bad, data, predicted).failures
    assert any(failure in f for f in failures), failures
    if fault == "bf16_scores":
        assert all("recorded test score" in f for f in failures)


def test_the_check_fails_a_winner_off_the_rule(fitted):
    cell, data, mesh, est, predicted = fitted
    bad = _faulty(est)
    other = (est.best_index_ + 5) % 10
    bad.best_index_ = other
    bad.best_params_ = est.cv_results_["params"][other]
    with use_mesh(mesh):
        failures = fam.check(cell, bad, data, predicted).failures
    assert any("best_index_" in f for f in failures), failures


def test_engaged_fails_the_fold_copies_path(fitted):
    cell, data, mesh, est, predicted = fitted
    bad = _faulty(est, path="fold-copies", fold_copies=10)
    failures = fam.engaged(cell, bad, data, {"glm.lbfgs_lam_grid": 5}).failures
    assert any("'path'" in f for f in failures)
    assert any("'fold_copies'" in f for f in failures)
    assert any("glm.lbfgs_lam_grid" in f for f in failures)
    assert not fam.engaged(dataclasses.replace(cell), est, data).failures
