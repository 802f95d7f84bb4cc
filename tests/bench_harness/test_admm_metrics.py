"""The ``glm_admm`` family's own pieces, rehearsed on the CPU: the three
readers that take ADMM's counters (``admm_newton_steps_per_fit``,
``admm_outer_ms``, ``admm_newton_roofline``), the one-step cost function,
the benchmark's entries found BY NAME, the check passing the reference run
rightly and failing it run wrongly, ``engaged`` failing a program whose
defaults are not the stated ones, and readers that return None — and raise
nothing — on a program without the counters. A CPU run gives counts and
correctness, never a time worth writing down."""

import jax
import pytest

from benchmark import harness
from benchmark.families import glm_admm as fam
from benchmark.tools import admm_faults
from dask_ml_tpu import observability as obs
from dask_ml_tpu.parallel.mesh import device_mesh, use_mesh

from .test_rehearsal import _tiny

CELL = "logreg_admm_l1"
READERS = ("admm_newton_steps_per_fit", "admm_outer_ms",
           "admm_newton_roofline")


def _read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


def test_admm_readers_in_a_traced_rehearsal(tmp_path):
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
    steps = [f["facts"]["local_steps"] for f in fits]
    iters = [f["facts"]["n_iter"] for f in fits]
    assert m["admm_newton_steps_per_fit"] == pytest.approx(
        sum(steps) / len(steps))
    assert m["iters_per_fit"] == pytest.approx(sum(iters) / len(iters))
    # a solve ends early once warm: between one and eight steps an iteration
    assert all(i <= s < 8 * i for s, i in zip(steps, iters))
    assert 0 < m["admm_outer_ms"] <= m["iter_ms"]
    assert "admm_newton_roofline" not in m        # no device plane here
    assert m["launches_per_fit"] == 2.0           # glm.prepare, glm.admm
    assert all(f["programs"] == {"glm.prepare": 1, "glm.admm": 1}
               for f in fits)
    assert m["fit_prep_ms"] > 0 and m["compiles_in_window"] == 0.0
    facts = dumped["facts"]
    # the TPU's choice, requested in the rehearsal (interpret mode)
    assert facts["local_step"] == cell.config["expect"]["local_step"] \
        == "pallas_newton_stats"
    assert facts["support_mismatch"] == 0 and facts["nnz"] < 256
    assert facts["kkt_max"] <= facts["kkt_band"] == 4e-5
    assert all(f["facts"]["dual_residual"] <= 1e-4 for f in fits)
    obs.reset_recent_spans()


def test_readers_find_nothing_without_the_counters(monkeypatch):
    """An empty ring, a program from before the ring (the parent of the PR
    that adds it), a ``fit.solve`` without ``local_steps`` (another solver's,
    or the parent's ADMM), no device trace: None, and no raise."""
    obs.reset_recent_spans()
    cell = harness.load_cell(CELL)
    ctx = {"fits": [{"fit_s": 0.1, "facts": {"n_iter": 18}}] * 3,
           "cycles": [{"predict_s": [0.1]}], "trace": None, "cell": cell}
    for name in READERS:
        assert _read(name, ctx) is None
    root = {"span": "fit", "parent_id": None, "span_id": 1, "root_id": 1}
    solve = {"span": "fit.solve", "parent_id": 1, "span_id": 2, "root_id": 1,
             "wall_s": 0.5, "n_iter": 18}
    monkeypatch.setattr(obs, "recent_spans", lambda: [root, solve] * 3)
    ctx["trace"] = {"kinds": {"bench.fit": {"calls": 3, "seconds": 3.0,
                                            "idle_pct": 1.0}}}
    for name in READERS:
        assert _read(name, ctx) is None
    monkeypatch.delattr(obs, "recent_spans")
    for name in READERS:
        assert _read(name, ctx) is None


def test_the_roofline_reader_on_a_made_up_trace(monkeypatch):
    """44 steps of the 5.24 ms floor over 0.99 s of busy chip a fit."""
    cell = harness.load_cell(CELL)
    root = {"span": "fit", "parent_id": None, "span_id": 1, "root_id": 1}
    solve = {"span": "fit.solve", "parent_id": 1, "span_id": 2, "root_id": 1,
             "wall_s": 0.9, "n_iter": 18, "local_steps": 44}
    monkeypatch.setattr(obs, "recent_spans", lambda: [root, solve])
    ctx = {"cell": cell, "fits": [{"fit_s": 1.0, "facts": {"n_iter": 18}}],
           "cycles": [], "n_rows": 4194304, "d": 256, "chips": 1,
           "trace": {"kinds": {"bench.fit": {"calls": 1, "seconds": 1.0,
                                             "idle_pct": 1.0}}},
           "peaks": lambda: harness.peaks_for("TPU v5 lite"),
           "kernel_cost": lambda: harness.load_module(
               "kernels", cell.config["main_kernel"]["cost"]).cost}
    assert _read("admm_newton_steps_per_fit", ctx) == 44.0
    assert _read("admm_outer_ms", ctx) == pytest.approx(50.0)
    one_read = 4194304 * 256 * 4 / 819e9
    assert _read("admm_newton_roofline", ctx) == pytest.approx(
        100 * 44 * one_read / 0.99)
    assert _read("admm_newton_roofline", ctx) < 100


def test_step_cost_at_the_cells_shapes():
    """One read of the float32 X (5.24 ms at 819 GB/s) bounds a step; the
    Gram's symmetric half is 1.40 ms of the MXU's bf16 peak."""
    cell = harness.load_cell(CELL)
    cost = harness.load_module("kernels", "admm_newton").cost
    need = cost(4194304, 256, cell.config["main_kernel"])
    assert need == {"bytes": 4194304 * 256 * 4,
                    "flops": 4194304 * 256 * 257}
    peaks = harness.peaks_for("TPU v5 lite")
    assert need["bytes"] / peaks["hbm_bytes_per_s"] == pytest.approx(
        5.244e-3, rel=1e-3)
    assert need["flops"] / peaks["flops_bf16"] == pytest.approx(
        1.401e-3, rel=1e-3)
    assert cost(1024, 256, {"design_itemsize": 2})["bytes"] == 1024 * 512


def test_a_program_from_before_pr_36_is_refused_before_any_data(monkeypatch):
    from dask_ml_tpu.models.solvers import solvers as S

    cell, _ = _tiny(harness.load_cell(CELL))
    monkeypatch.delattr(S, "ADMM_BALANCE_RATIO")
    with pytest.raises(harness.BenchmarkError, match="before PR 36"):
        fam.make_data(cell.config, cell.traffic, 1, 3, None)


ENTRIES = [
    ("admm_newton_steps_per_fit", "count", "program_counter",
     "Resident solver"),
    ("admm_outer_ms", "ms", "program_span", "Resident solver"),
    ("admm_newton_roofline", "%", "device_trace", "Kernels"),
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
        "logreg_admm_l1_1b_x256", "resident_4m", 1)
    (c,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    assert c["reduced"] == ["rows_per_chip", "chips"]
    cfg = harness.load_json(harness.ROOT, c["file"])
    assert cfg["source"] == c["source"] and len(c["source"]) <= 200
    assert cfg["n_features"] == 256 and cfg["architecture"] is None
    assert cfg["penalty"]["lam"] == 2.0 ** -9
    assert cfg["penalty"]["C_at_4194304_rows"] == 2.0 ** -13
    # the cell reports what every cell must, and the GLM counts
    mine = {m["name"] for m in bench["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]}
    assert {"iter_ms", "fit_prep_ms", "iters_per_fit", "launches_per_fit",
            "compiles_in_window", "fit_idle_pct", "predict_idle_pct",
            "peak_hbm", *READERS} == mine


@pytest.fixture(scope="module")
def placed():
    """The cell's data at a rehearsal size, labels number 1 drawn."""
    cell = harness.load_cell(CELL).with_traffic(rows_per_chip=16384,
                                                sample_rows=1024)
    mesh = device_mesh(devices=jax.devices()[:1])
    with use_mesh(mesh):
        data = fam.make_data(cell.config, cell.traffic, 1, 7, mesh)
        fam.vary(cell, data, 1)
    return cell, data, mesh


def test_labels_come_from_a_sparse_teacher_with_an_offset(placed):
    import numpy as np

    cell, data, _ = placed
    beta = data["hp"]["beta"]
    assert np.count_nonzero(beta) == 32
    assert np.allclose(np.abs(beta[beta != 0]), 32 ** -0.5)
    y = np.asarray(data["y"].data)
    assert 0.55 < y.mean() < 0.60          # logits 2 x.t + 0.5
    before = y.copy()
    fam.vary(cell, data, 2)
    assert not np.array_equal(before, np.asarray(data["y"].data))
    fam.vary(cell, data, 1)
    assert np.array_equal(before, np.asarray(data["y"].data))


@pytest.fixture(scope="module")
def right_run(placed):
    """The reference's own 4-block ADMM run rightly on the placed data, in
    the shape the check reads."""
    cell, data, mesh = placed
    with use_mesh(mesh):
        return admm_faults.reference_outputs(cell, data, None, 4)


def test_the_check_passes_the_reference_run_rightly(placed, right_run):
    cell, data, mesh = placed
    with use_mesh(mesh):
        chk = fam.check_outputs(cell, right_run, data)
    assert not chk.failures
    assert chk.facts["support_mismatch"] == 0
    assert right_run["n_iter"] < right_run["local_steps"]


@pytest.mark.parametrize("fault", ["penalised_intercept", "no_1_over_n"])
def test_the_check_fails_a_wrong_fixed_point(placed, fault):
    """A penalised intercept rests where its gradient entry is lam, fifty
    times the limit; a threshold without the 1 / N is four times too
    large."""
    cell, data, mesh = placed
    with use_mesh(mesh):
        out = admm_faults.reference_outputs(cell, data, fault, 4)
        chk = fam.check_outputs(cell, out, data)
    assert any("KKT residual" in f for f in chk.failures), chk.failures
    assert chk.facts["kkt_max"] > 10 * chk.facts["kkt_band"]


def test_the_check_fails_a_fit_on_the_bfloat16_staircase(placed, right_run):
    """What a fit computed in bfloat16 can reach at best: the right run's
    coefficients rounded to bfloat16 (the control itself, ``bf16_design``,
    never meets the stop and takes minutes: ``admm_faults.py`` runs it on
    the chip). With ``predict_proba`` computed from the same point, as the
    check computes the program's, the limits on the FIT fail it — the KKT
    residual among them — and ``TOL_PROBA`` does not."""
    import jax.numpy as jnp
    import numpy as np

    cell, data, mesh = placed
    out = dict(right_run)
    out["coef"] = np.asarray(jnp.asarray(out["coef"]).astype(
        jnp.bfloat16).astype(jnp.float32))
    with use_mesh(mesh):
        out["predicted"] = admm_faults.reference_proba(
            out["coef"], out["intercept"], data["X"].data)
        chk = fam.check_outputs(cell, out, data)
    assert any("KKT residual" in f for f in chk.failures), chk.failures
    assert not any("predict_proba" in f for f in chk.failures)
    assert chk.facts["kkt_max"] > 2 * chk.facts["kkt_band"]


def test_one_local_step_fails_by_the_count_alone(placed, right_run):
    """One Newton step a solve heals itself: the fixed point is the right
    one, and only the count of local steps tells."""
    cell, data, mesh = placed
    with use_mesh(mesh):
        out = admm_faults.reference_outputs(cell, data, "one_local_step", 4,
                                            max_iter=right_run["n_iter"])
        (failure,) = fam.check_outputs(cell, out, data).failures
    assert "local Newton steps" in failure
    assert out["local_steps"] == out["n_iter"]


def test_engaged_holds_the_program_to_the_stated_defaults(placed,
                                                          monkeypatch):
    from dask_ml_tpu.models.solvers import solvers as S

    cell, data, mesh = placed
    with use_mesh(mesh):
        est = fam.make_estimator(cell, data, True)
        fam.fit(est, data)
        assert est.C == pytest.approx(1.0 / (2.0 ** -9 * 16384))
        assert not fam.engaged(cell, est, data, {"glm.prepare": 1,
                                                 "glm.admm": 1}).failures
        twice = fam.engaged(cell, est, data, {"glm.admm": 2}).failures
        assert any("not once" in f for f in twice)
        monkeypatch.setattr(S, "ADMM_BALANCE_RATIO", 5.0)
        assert any("ADMM_BALANCE_RATIO" in f
                   for f in fam.engaged(cell, est, data).failures)
        monkeypatch.undo()
        est.solver_info_ = {"n_iter": 3, "intercept": "column"}
        failures = fam.engaged(cell, est, data).failures
        assert any("'intercept'" in f for f in failures)
        assert any("'local_step'" in f for f in failures)
