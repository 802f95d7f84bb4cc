"""The ``sgd`` family's own pieces, rehearsed on the CPU: the four readers
that take the wrapper's passes (``sgd_pass_ms``, ``sgd_grid_ms``,
``sgd_dispatches_per_pass``, ``sgd_fit_roofline``), the one-pass cost
function, the check biting where it must, and the clean refusal of a program
from before the pass record. A CPU run gives counts and correctness, never a
time worth writing down."""

import jax
import pytest

from benchmark import harness
from benchmark.metrics import _sgd_passes
from dask_ml_tpu import observability as obs

from .test_rehearsal import _tiny

CELL = "sgd_incremental"
READERS = ("sgd_pass_ms", "sgd_grid_ms", "sgd_dispatches_per_pass",
           "sgd_fit_roofline")


def _read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


def test_pass_readers_in_a_traced_rehearsal(tmp_path):
    cell, devices = _tiny(harness.load_cell(CELL))
    obs.reset_recent_spans()
    lines = []
    res = harness.run_cell(cell, seed=5, seconds=0.2, trace=1,
                           devices=devices, interpret=True,
                           dump=str(tmp_path), log=lines.append)
    assert res["correct"] is True, lines
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["sgd_pass_ms"] > 0 and m["sgd_grid_ms"] > 0
    assert m["sgd_grid_ms"] < m["sgd_pass_ms"]
    assert m["sgd_dispatches_per_pass"] == 3.0    # grid_x, grid_y, the epoch
    assert "sgd_fit_roofline" not in m            # no device plane here
    assert m["iter_ms"] > 0 and m["compiles_in_window"] == 0.0
    dumped = harness.load_json(tmp_path, f"{CELL}_trace1_s5.json")
    fits = [f for c in dumped["cycles"] for f in c["fits"]]
    assert all(f["facts"] == {"n_iter": 40, "passes": 5,
                              "path": "fused_epoch", "dispatches": 3}
               for f in fits)
    assert all(f["programs"] == {"sgd.grid_x": 5, "sgd.grid_y": 5,
                                 "sgd.fused_epoch": 5} for f in fits)
    assert dumped["facts"]["t_end"] == 40
    assert dumped["facts"]["stated"] <= dumped["facts"]["f32"]
    # five roots a fit — fit, then partial_fit x 4 — whose walls lie inside
    # the harness's own clock around the five calls
    passes = _sgd_passes.passes({"fits": fits})
    assert len(passes) == 5 * len(fits)
    names = [root["span"] for root, _ in passes]
    assert names == ["fit", "partial_fit", "partial_fit", "partial_fit",
                     "partial_fit"] * len(fits)
    for i, fit in enumerate(fits):
        mine = passes[5 * i:5 * i + 5]
        assert [root["t_end"] for root, _ in mine] == [8, 16, 24, 32, 40]
        walls = sum(root["wall_s"] for root, _ in mine)
        assert walls <= fit["fit_s"]
        assert fit["fit_s"] - walls <= 0.05 * fit["fit_s"] + 2e-3
        for root, kids in mine:
            assert set(kids) == {"pass.validate", "pass.grid", "pass.solve"}
            inside = sum(r["wall_s"] for r in kids.values())
            assert inside <= root["wall_s"] + 1e-5
    obs.reset_recent_spans()


def test_readers_find_nothing_without_passes(monkeypatch):
    """An empty ring, fits that count no passes (another family's), a
    program from before the ring, no device trace: None, and no raise."""
    obs.reset_recent_spans()
    cell = harness.load_cell(CELL)
    ctx = {"fits": [{"fit_s": 0.1, "facts": {"passes": 5}}] * 3,
           "cycles": [{"predict_s": [0.1]}], "trace": None, "cell": cell}
    for name in READERS:
        assert _read(name, ctx) is None
    ctx["fits"] = [{"fit_s": 0.1, "facts": {"n_iter": 8}}]
    for name in READERS:
        assert _read(name, ctx) is None
    monkeypatch.delattr(obs, "recent_spans")
    assert _sgd_passes.passes({"fits": [{"facts": {"passes": 5}}]}) == []


def test_fit_roofline_known_answer():
    """Nine fits in 0.9 s of calls, the chip busy 60 % of them: 60 ms a fit
    against five passes of 2.6426 ms."""
    cell = harness.load_cell(CELL)
    cost = harness.load_module("kernels", "sgd_pass").cost
    need = cost(4194304, 256, {})
    assert need == {"bytes": 4194304 * 256 * 2 + 4194304 * 4,
                    "flops": 4 * 4194304 * 256}
    ctx = {"cell": cell, "n_rows": 4194304, "chips": 1, "d": 256,
           "trace": {"kinds": {"bench.fit": {"calls": 9, "seconds": 0.9,
                                             "idle_pct": 40.0}}},
           "peaks": lambda: harness.peaks_for("TPU v5 lite"),
           "kernel_cost": lambda: cost}
    floor = need["bytes"] / 819e9
    assert floor == pytest.approx(2.6426e-3, rel=1e-4)
    assert _read("sgd_fit_roofline", ctx) == pytest.approx(
        100 * 5 * floor / 0.06)
    ctx["trace"]["kinds"]["bench.fit"]["idle_pct"] = 100.0
    assert _read("sgd_fit_roofline", ctx) is None


def _fitted(seed=2):
    cell, devices = _tiny(harness.load_cell(CELL))
    fam = harness.load_module("families", cell.config["family"])
    from dask_ml_tpu.parallel.mesh import device_mesh, use_mesh

    mesh = device_mesh(devices=devices)
    with use_mesh(mesh):
        data = fam.make_data(cell.config, cell.traffic, 1, seed, mesh)
        fam.vary(cell, data, 0)
        est = fam.make_estimator(cell, data, interpret=True)
        fam.fit(est, data)
        return cell, fam, data, est, fam.predict(est, data), mesh


def test_the_check_passes_a_true_fit_and_fails_another_schedule():
    """The same data, the same wrapper: the check holds; told another block
    order than the one that ran, or handed the other labels, it does not."""
    from dask_ml_tpu.parallel.mesh import use_mesh

    cell, fam, data, est, labels, mesh = _fitted()
    with use_mesh(mesh):
        good = fam.check(cell, est, data, labels)
        assert good.failures == [] and fam.engaged(cell, est, data).failures == []
        assert good.facts["stated"] <= 1e-5
        est.random_state += 1                 # not the order that ran
        bad = fam.check(cell, est, data, labels)
        assert any("stated precision" in f for f in bad.failures)
        est.random_state -= 1
        flipped = fam.check(cell, est, data, 1 - labels)
        assert any("predict" in f for f in flipped.failures)
        est.pass_info_["path"] = "block_loop"
        assert any("block_loop" in f
                   for f in fam.engaged(cell, est, data).failures)


def test_a_program_without_the_pass_record_is_refused_before_any_data(
        monkeypatch):
    from dask_ml_tpu.wrappers import Incremental

    cell, devices = _tiny(harness.load_cell(CELL))
    fam = harness.load_module("families", cell.config["family"])
    monkeypatch.delattr(Incremental, "_pass")
    with pytest.raises(harness.BenchmarkError, match="pass_info_"):
        fam.make_data(cell.config, cell.traffic, 1, 0, None)


def test_the_cell_s_entries_in_the_benchmark():
    """The cell's entries, found by NAME: its four readers are its own and
    no other cell's, ``iter_ms`` lists it once, and every entry the
    benchmark had is still there, in the order it had, letter for letter
    but for that one list."""
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "fit_s"
    assert by_name["iter_ms"]["workloads"].count(CELL) == 1
    assert CELL not in by_name["fit_prep_ms"]["workloads"]
    assert CELL not in by_name["predict_host_ms"]["workloads"]
    entry = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == ("sgd_incremental_1b_x256", "resident_4m", 1)
    mine = {m["name"] for m in harness.load_cell(CELL).per_layer}
    assert set(READERS) | {"iter_ms", "compiles_in_window", "peak_hbm",
                           "fit_idle_pct", "predict_idle_pct"} == mine
    for other in (w["name"] for w in bench["workloads"]
                  if w["name"] != CELL):
        theirs = {m["name"] for m in harness.load_cell(other).per_layer}
        assert not set(READERS) & theirs
    # PR 26's entry, which test_qr_fallbacks_metric.py looks for at the END
    # of the list (where this PR's entries now stand): as it was written
    assert by_name["qr_fallbacks_per_fit"] == {
        "name": "qr_fallbacks_per_fit", "unit": "count", "better": "lower",
        "source": "program_span", "layer": "Factorisation",
        "moves": "fit_s", "workloads": ["pca_rsvd_x512"]}
