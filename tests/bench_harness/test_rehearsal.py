"""CPU rehearsal of every benchmark cell (``benchmark/run.py`` has no CPU
mode): ``harness.run_cell`` at tiny rows on the suite's virtual CPU devices,
interpret-mode kernels and the choices the TPU's auto-gates make requested
explicitly — so the control flow, the stats each metric reads, the check
against the plain reference and the result line's keys are exercised before
any chip time is spent. The
four-chip cell runs on a mesh of 4 of the 8 virtual devices. A CPU run gives
counts and correctness, never a time worth writing down."""

import json

import jax
import pytest

from benchmark import harness

CELLS = [w["name"] for w in harness.load_json(
    harness.ROOT, "BENCHMARK.json")["workloads"]]


def _tiny(cell):
    """(the cell at rehearsal size, the devices it runs on)."""
    if cell.config["family"] == "kmeans":
        # enough rows that 20 Lloyd iterations reach no exact fixed point;
        # the sample is all of them, so the reference's own Lloyd run sees
        # the system's data
        return cell.with_traffic(rows_per_chip=16384, sample_rows=16384,
                                 check_rows=16384, trace_cycles=1), \
            jax.devices()[:cell.chips]
    return cell.with_traffic(rows_per_chip=2048, sample_rows=1024,
                             trace_cycles=1), jax.devices()[:cell.chips]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal(name, trace, tmp_path):
    cell, devices = _tiny(harness.load_cell(name))
    lines = []
    res = harness.run_cell(cell, seed=3, seconds=0.2, trace=trace,
                           devices=devices, interpret=True,
                           dump=str(tmp_path), log=lines.append)
    assert res["correct"] is True, lines
    assert res["failed"] == 0 and res["attempted"] >= 2
    # the fixed keys of the last line, and no other
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(res) == want        # no device plane on the CPU: no breakdown
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    json.dumps(res)
    declared = {m["name"]: m for m in
                (cell.per_layer if trace else cell.end_to_end)}
    assert set(res["metrics"]) <= set(declared)
    for k, v in res["metrics"].items():
        assert v["unit"] == declared[k]["unit"]
        assert isinstance(v["value"], float)
    if trace:
        # what a CPU run can count: no compile inside the window, and the
        # counts each cell's readers take from the program
        assert res["metrics"]["compiles_in_window"]["value"] == 0.0
        for k in ("fit_idle_pct", "predict_idle_pct", "collective_pct",
                  "glm_value_grad_roofline", "lloyd_stats_roofline"):
            assert k not in res["metrics"]    # nothing to read: left out
        assert res["metrics"]["iter_ms"]["value"] > 0
    else:
        assert set(res["metrics"]) == {"fit_s", "predict_rate", "setup_s"}
        assert all(v["value"] > 0 for v in res["metrics"].values())
    dumped = harness.load_json(tmp_path, f"{name}_trace{trace}_s3.json")
    assert dumped["result"]["correct"] is True


def test_four_chip_cell_uses_a_mesh_of_four():
    cell, devices = _tiny(harness.load_cell("logreg_resident_x4"))
    assert cell.chips == 4 and len(devices) == 4


def test_same_seed_same_inputs():
    """The data is a function of the seed alone."""
    import numpy as np

    from benchmark import datagen
    from dask_ml_tpu.parallel.mesh import device_mesh

    cfg = harness.load_cell("logreg_resident").config
    mesh = device_mesh(devices=jax.devices()[:2])
    hp = datagen.host_params(cfg["data"], 256, 5)
    assert abs(float(np.linalg.norm(hp["beta"])) - 1.0) < 1e-6
    a = datagen.make_resident(cfg["data"], 4096, 256, 5, mesh, hp)
    b = datagen.make_resident(cfg["data"], 4096, 256, 5, mesh, hp)
    c = datagen.make_resident(cfg["data"], 4096, 256, 6, mesh,
                              datagen.host_params(cfg["data"], 256, 6))
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))
    # the two chips drew different rows
    Xa = np.asarray(a[0])
    assert not np.array_equal(Xa[:2048], Xa[2048:])


def test_sample_rows_come_from_the_first_shard():
    """On four chips ``X.data[:m]`` would all-gather all 16 GiB of X onto
    every chip before slicing (the first four-chip run of PR 22 died of it):
    the reference's sample is cut from the first shard alone."""
    import numpy as np

    from benchmark import datagen
    from benchmark.families import _common as C
    from dask_ml_tpu.parallel import as_sharded
    from dask_ml_tpu.parallel.mesh import device_mesh

    cfg = harness.load_cell("logreg_resident_x4").config
    mesh = device_mesh(devices=jax.devices()[:4])
    hp = datagen.host_params(cfg["data"], 256, 1)
    X, y = datagen.make_resident(cfg["data"], 8192, 256, 1, mesh, hp)
    for arr in (as_sharded(X, mesh=mesh), as_sharded(y, mesh=mesh)):
        got = C.device_rows(arr, 1024)
        assert len(got.sharding.device_set) == 1
        assert np.array_equal(np.asarray(got), np.asarray(arr.data[:1024]))
        assert C.device_rows(arr) is arr.data
    with pytest.raises(ValueError, match="first shard holds 2048"):
        C.device_rows(as_sharded(X, mesh=mesh), 4096)
