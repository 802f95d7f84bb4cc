"""The ``hyperband`` family's own pieces, rehearsed on the CPU: the five
readers that take a search's fit (``search_dispatches_per_fit``,
``search_control_ms``, ``search_score_ms``, ``search_step_ms``,
``search_fit_roofline``), the fit's cost function at the cell's shapes, the
clean refusal of a program from before ``search_info_``, and the cell's
entries in ``BENCHMARK.json``. A CPU run gives counts and correctness, never
a time worth writing down."""

import pytest

from benchmark import harness
from benchmark.metrics import _search, _spans
from dask_ml_tpu import observability as obs

from .test_rehearsal import _tiny

CELL = "hyperband_sgd"
READERS = ("search_dispatches_per_fit", "search_control_ms",
           "search_score_ms", "search_step_ms", "search_fit_roofline")


def _read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


def test_search_readers_in_a_traced_rehearsal(tmp_path):
    cell, devices = _tiny(harness.load_cell(CELL))
    obs.reset_recent_spans()
    lines = []
    res = harness.run_cell(cell, seed=7, seconds=0.2, trace=1,
                           devices=devices, interpret=True,
                           dump=str(tmp_path), log=lines.append)
    assert res["correct"] is True, lines
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # split_x, split_y (the encoded labels), a scan a group, a score a
    # round
    assert m["search_dispatches_per_fit"] == 1 + 1 + 11 + 5
    assert m["search_control_ms"] > 0 and m["search_score_ms"] > 0
    assert m["search_step_ms"] > 0
    assert "search_fit_roofline" not in m          # no device plane here
    assert m["iter_ms"] > 0 and m["fit_prep_ms"] > 0
    assert m["compiles_in_window"] == 0.0
    for absent in ("evals_per_fit", "eval_ms", "predict_host_ms"):
        assert absent not in m
    dumped = harness.load_json(tmp_path, f"{CELL}_trace1_s7.json")
    fits = [f for c in dumped["cycles"] for f in c["fits"]]
    assert all(f["facts"] == {"n_iter": 5, "groups": 11, "scan_steps": 321,
                              "dispatches": 16} for f in fits)
    assert all(f["programs"] == {"search.split_x": 1, "search.split_y": 1,
                                 "sgd.cohort_scan": 11,
                                 "sgd.cohort_score": 5} for f in fits)
    facts = dumped["facts"]
    assert (facts["block_rows"], facts["n_test"]) == (224, 256)
    assert facts["plane"] == "grid" and facts["stated"] <= facts["f32"]
    # the readers' sums are the solve's own: a fit's four parts make up its
    # solve, and the solve carries the schedule's counts
    calls = _spans.calls("fit", len(fits))
    assert len(calls) == len(fits) == 3
    for root, kids in calls:
        assert set(kids) == {"fit.validate", "fit.prepare", "fit.solve",
                             "fit.finish"}
        solve = kids["fit.solve"]
        assert (solve["rounds"], solve["groups"], solve["scan_steps"],
                solve["model_steps"]) == (5, 11, 321, 1581)
        parts = sum(solve[k] for k in ("train_s", "score_s", "publish_s",
                                       "control_s"))
        assert parts == pytest.approx(solve["wall_s"], rel=0.02, abs=2e-3)
    solves = [kids["fit.solve"] for _, kids in calls]
    assert m["search_step_ms"] == pytest.approx(
        sum(1e3 * s["train_s"] / 321 for s in solves) / 3)
    assert m["search_control_ms"] == pytest.approx(
        sum(1e3 * (s["control_s"] + s["publish_s"]) for s in solves) / 3)
    predicts = _spans.calls("predict", 1)
    assert predicts[0][1]["predict.decision"]["link"] == "device"
    obs.reset_recent_spans()


def test_readers_find_nothing_without_a_search(monkeypatch):
    """An empty ring, fits without a registry delta, a solve that carries no
    sums (another family's), no device trace: None, and no raise."""
    obs.reset_recent_spans()
    cell = harness.load_cell(CELL)
    ctx = {"fits": [{"fit_s": 0.1, "facts": {"n_iter": 5}}] * 3,
           "cycles": [{"predict_s": [0.1]}], "trace": None, "cell": cell}
    for name in READERS:
        assert _read(name, ctx) is None
    monkeypatch.setattr(_spans, "fits", lambda ctx: [
        ({"span": "fit"}, {"fit.solve": {"n_evals": 9, "wall_s": 0.1}})])
    assert _search.solves(ctx, "train_s") == []
    for name in READERS:
        assert _read(name, ctx) is None


def test_fit_cost_at_the_cells_shapes():
    """The floor of one fit, by hand: 81 block reads of 458,752 x 256 bf16
    entries and one read of the float32 X; 1,581 model steps of two
    products, 206 recorded scores of one."""
    cell = harness.load_cell(CELL)
    mod = harness.load_module("kernels", "search_fit")
    sched = cell.config["main_kernel"]["schedule"]
    assert mod.schedule(81, 3) == (1581, 206)
    assert 206 == (81 + 27 + 9 + 3 + 1) + (34 + 11 + 3 + 1) \
        + (15 + 5 + 1) + (8 + 2) + 5
    need = mod.cost(4194304, 256, sched)
    block = 458752 * 256
    assert need == {
        "bytes": 81 * block * 2 + 4194304 * 256 * 4,
        "flops": 1581 * 4 * block + 206 * 2 * 524288 * 256}
    want = cell.config["expect"]
    assert (want["partial_fit_calls"], want["block_rows"]) == (1581, 458752)
    ctx = {"cell": cell, "n_rows": 4194304, "chips": 1, "d": 256,
           "trace": {"kinds": {"bench.fit": {"calls": 9, "seconds": 4.5,
                                             "idle_pct": 40.0}}},
           "peaks": lambda: harness.peaks_for("TPU v5 lite"),
           "kernel_cost": lambda: mod.cost}
    floor = need["bytes"] / 819e9                  # the bytes bind
    assert floor == pytest.approx(28.474e-3, rel=1e-4)
    assert need["flops"] / 197e12 == pytest.approx(4.0507e-3, rel=1e-4)
    assert _read("search_fit_roofline", ctx) == pytest.approx(
        100 * floor / 0.3)
    ctx["trace"]["kinds"]["bench.fit"]["idle_pct"] = 100.0
    assert _read("search_fit_roofline", ctx) is None


def test_a_program_without_search_info_is_refused_before_any_data(
        monkeypatch):
    from dask_ml_tpu.model_selection import HyperbandSearchCV

    cell, devices = _tiny(harness.load_cell(CELL))
    fam = harness.load_module("families", cell.config["family"])
    monkeypatch.delattr(
        HyperbandSearchCV.__mro__[1], "_search_sums")
    with pytest.raises(harness.BenchmarkError, match="search_info_"):
        fam.make_data(cell.config, cell.traffic, 1, 3, None)


def test_the_cells_entries_by_name():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": "hyperband_sgd_1b_x256",
                           "traffic": "resident_4m", "chips": 1}
    cfg = {c["name"]: c for c in bench["configs"]}["hyperband_sgd_1b_x256"]
    assert cfg["reduced"] == ["rows_per_chip", "chips"]
    per = {m["name"]: m for m in bench["per_layer"]}
    for name, unit, source, layer in (
            ("search_dispatches_per_fit", "count", "program_counter",
             "Device programs"),
            ("search_control_ms", "ms", "program_span", "Estimator entry"),
            ("search_score_ms", "ms", "program_span", "Resident solver"),
            ("search_step_ms", "ms", "program_span", "Resident solver"),
            ("search_fit_roofline", "%", "device_trace", "Kernels")):
        assert per[name] == {
            "name": name, "unit": unit, "source": source, "layer": layer,
            "better": "higher" if unit == "%" else "lower",
            "moves": "fit_s", "workloads": [CELL]}
    for name in ("iter_ms", "fit_prep_ms"):
        assert CELL in per[name]["workloads"]
    for name in ("evals_per_fit", "eval_ms", "predict_host_ms"):
        assert CELL not in per[name]["workloads"]
    # the new entries close the list, in the order the issue gives them
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(READERS)
