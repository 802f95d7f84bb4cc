"""The benchmark's own contracts: ``BENCHMARK.json`` against the rules the
driver checks before any run, the harness driven by data alone (a new
configuration + traffic mix + per-layer metric dropped in as NEW files run
with no edit to an existing one), the trace reduction on tables with known
answers, and ``run.py`` refusing to run off-chip or without the program."""

import json
import os
import re
import shutil
import subprocess
import sys

import jax
import pytest

from benchmark import harness, trace_reduce

ROOT = harness.ROOT
BENCH = harness.load_json(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


# -- BENCHMARK.json -----------------------------------------------------------

def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check with all 24 cells must fit into 43,200 s
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert 1 <= len(word) <= 200 and "\n" not in word and "\t" not in word
        assert not word.startswith("/") and ".." not in word.split("/")
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])


def test_configs():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        # never a width
        assert not any(k.endswith(("_dim", "_rank")) or "features" in k
                       or "clusters" in k for k in c["reduced"])
        body = harness.load_json(ROOT, c["file"])
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"]
        assert set(body["reduced_why"]) == set(c["reduced"])
        # its plain reference sits beside it
        assert os.path.isfile(os.path.join(harness.HERE, body["reference"]))


def test_workloads():
    cells = BENCH["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in BENCH["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        harness.load_cell(w["name"])       # every file it names is there
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


def test_metrics():
    e2e, layer = BENCH["end_to_end"], BENCH["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in BENCH["workloads"]}
    assert "setup_s" in {m["name"] for m in e2e}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in {e["name"] for e in e2e}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
        # every metric is a reader of its own, found by its name
        assert callable(harness.load_module("metrics", m["name"]).read)
    for w in cells:
        cell = harness.load_cell(w)
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
        assert cell.per_layer


def test_files_under_paths_are_named_from_name_characters():
    for p in BENCH["paths"]:
        for d, _, files in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


# -- driven by data: add a cell by files alone --------------------------------

def test_new_config_traffic_and_metric_by_new_files_only(tmp_path,
                                                         monkeypatch):
    """A later PR may add files and entries and may not edit a file that is
    there. Copy the benchmark, ADD a configuration, a traffic mix, a
    per-layer metric and a cell — new files, new entries — and run it."""
    root = tmp_path / "checkout"
    bench_dir = root / os.path.relpath(harness.HERE, ROOT)
    shutil.copytree(harness.HERE, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}

    cfg = harness.load_json(bench_dir, "configs", "logreg_1b_x256.json")
    cfg.update(name="logreg_c10", n_features=128)
    cfg["estimator"]["params"]["C"] = 10.0
    (bench_dir / "configs" / "logreg_c10.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "resident_tiny.json").write_text(json.dumps({
        "what": "a rehearsal-sized resident mix", "residency": "device",
        "rows_per_chip": 2048, "cycle": ["fit", "predict"],
        "sample_rows": 1024, "check_rows": "all", "trace_cycles": 1}))
    (bench_dir / "metrics" / "grad_norm_at_stop.py").write_text(
        '"""The solver\'s own gradient norm at its last iterate."""\n\n\n'
        "def read(ctx):\n"
        "    est = ctx['cycles'][-1]['est']\n"
        "    return est.solver_info_['grad_norm']\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "logreg_c10", "source": "a test", "reduced": [],
        "file": os.path.relpath(bench_dir / "configs" / "logreg_c10.json",
                                root), "why": "a test"})
    bench["workloads"].append({"name": "c10_tiny", "config": "logreg_c10",
                               "traffic": "resident_tiny", "chips": 1,
                               "why": "a test"})
    bench["per_layer"].append({
        "name": "grad_norm_at_stop", "unit": "1", "better": "lower",
        "source": "program_counter", "layer": "Resident solver",
        "moves": "fit_s", "workloads": ["c10_tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("c10_tiny", root=str(root))
    assert cell.config["n_features"] == 128
    res = harness.run_cell(cell, seed=1, seconds=0.1, trace=1,
                           devices=jax.devices()[:1], interpret=True,
                           log=lambda s: None)
    assert res["correct"] is True
    assert 0 < res["metrics"]["grad_norm_at_stop"]["value"] <= 1e-3
    assert "compiles_in_window" in res["metrics"]      # the old ones too
    assert "collective_pct" not in res["metrics"]      # not this cell's
    after = {p: p.read_bytes() for p in bench_dir.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert {p: b for p, b in after.items() if p in before} == before
    assert len(after) == len(before) + 3


def test_unknown_names_are_errors():
    with pytest.raises(harness.BenchmarkError, match="unknown workload"):
        harness.load_cell("no_such_cell")
    with pytest.raises(harness.BenchmarkError, match="no metrics file"):
        harness.load_module("metrics", "no_such_metric")
    with pytest.raises(harness.BenchmarkError, match="no published peaks"):
        harness.peaks_for("cpu")
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


# -- the trace reduction ------------------------------------------------------

def _table(device_lines, host_events):
    return {"planes": [
        {"name": f"/device:TPU:{i}",
         "lines": [{"name": "XLA Ops", "events": evs},
                   {"name": "Steps", "events": [["step", 0.0, 1e12]]}]}
        for i, evs in enumerate(device_lines)
    ] + [{"name": "/host:CPU",
          "lines": [{"name": "python", "events": host_events}]}],
        "names": {}}


def test_reduce_known_answer():
    """Traced window 0..100 ms; a fit call 5-60 ms, a predict call 70-90 ms.
    Chip 0: a 40 ms while that contains two 10 ms kernels and a 6 ms
    all-reduce in the fit, 10 ms in the predict. Chip 1: 20 ms in the fit.
    What runs between the calls (the benchmark's own label draw) and before
    the window counts nowhere."""
    ms = 1e6
    host = [["bench.window", 0.0, 100 * ms], ["bench.fit", 5 * ms, 55 * ms],
            ["bench.predict", 70 * ms, 20 * ms],
            ["not.ours", 0.0, 100 * ms]]
    chip0 = [["while.1", 10 * ms, 40 * ms], ["kernel.7", 12 * ms, 10 * ms],
             ["kernel.7", 30 * ms, 10 * ms],
             ["all-reduce.2", 52 * ms, 6 * ms],
             ["relabel.9", 62 * ms, 6 * ms],
             ["fusion.9", 75 * ms, 10 * ms],
             ["before.window", -50 * ms, 20 * ms]]
    chip1 = [["fusion.3", 20 * ms, 20 * ms], ["relabel.9", 62 * ms, 6 * ms]]
    s = trace_reduce.reduce(_table([chip0, chip1], host))
    assert s["chips"] == 2
    assert s["window_s"] == pytest.approx(0.075)         # the calls alone
    assert s["busy_s_by_chip"] == pytest.approx([0.056, 0.020])
    assert s["busy_s"] == pytest.approx(0.038)
    assert s["idle_pct"] == pytest.approx(100 * (1 - 20 / 75))  # worst chip
    fit, predict = s["kinds"]["bench.fit"], s["kinds"]["bench.predict"]
    assert (fit["calls"], predict["calls"]) == (1, 1)
    assert fit["seconds"] == pytest.approx(0.055)
    assert fit["idle_pct"] == pytest.approx(100 * (1 - 20 / 55))
    assert predict["idle_pct"] == pytest.approx(100.0)   # chip 1 did nothing
    assert fit["collective_s"] == pytest.approx(0.003)   # mean over chips
    assert predict["collective_s"] == 0.0
    assert s["collective_s"] == pytest.approx(0.003)
    assert s["ops"]["while.1"]["self_s"] == pytest.approx(0.010)  # 20 / 2
    assert s["ops"]["kernel.7"]["count"] == 2
    assert trace_reduce.matching(s, r"^kernel") == pytest.approx([0.01] * 2)
    assert not {"before.window", "relabel.9"} & set(s["ops"])
    # the worst chip's gaps, each inside one call
    assert s["gaps"][:3] == [
        ["bench.predict after its start", pytest.approx(0.020)],
        ["bench.fit after fusion.3", pytest.approx(0.020)],
        ["bench.fit after its start", pytest.approx(0.015)]]
    b = trace_reduce.breakdown(s)
    assert dict(b["device_ops"]) == pytest.approx(
        {"while.1": 0.010, "kernel.7": 0.010, "fusion.3": 0.010,
         "fusion.9": 0.005, "all-reduce.2": 0.003})
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    json.dumps(b)
    # the readers of the per-kind numbers
    ctx = {"trace": s}
    read = lambda name: harness.load_module("metrics", name).read  # noqa: E731
    assert read("fit_idle_pct")(ctx) == pytest.approx(100 * (1 - 20 / 55))
    assert read("predict_idle_pct")(ctx) == pytest.approx(100.0)
    assert read("collective_pct")(ctx) == pytest.approx(100 * 3 / 55)
    for name in ("fit_idle_pct", "predict_idle_pct", "collective_pct"):
        assert read(name)({"trace": None}) is None


def test_reduce_recorded_v5e_trace():
    """A trace recorded on the chip (PR 22: three traced cycles of KMeans
    at the PR's first shape, 8,388,608 x 128, one v5e; device event names
    already shortened by ``load``). 60 calls of the fused Lloyd kernel, 20 a
    fit."""
    import statistics

    table = harness.load_json(harness.HERE, "testdata",
                              "trace_kmeans_v5e.json")
    s = trace_reduce.reduce(table)
    assert s["chips"] == 1
    assert s["window_s"] == pytest.approx(0.871158236)
    assert s["busy_s"] == pytest.approx(0.797247818)
    assert s["idle_pct"] == pytest.approx(8.484155340063825)
    assert s["kinds"]["bench.fit"]["idle_pct"] == pytest.approx(8.2122546)
    assert s["kinds"]["bench.predict"]["idle_pct"] == pytest.approx(14.1138695)
    assert s["collective_s"] == 0.0
    assert [n for n, _, _ in s["spans"]] == ["bench.fit", "bench.predict"] * 3
    cfg = harness.load_cell("kmeans_lloyd").config
    durs = trace_reduce.matching(s, cfg["main_kernel"]["pattern"])
    assert len(durs) == 60
    assert statistics.median(durs) == pytest.approx(0.009290016)
    b = trace_reduce.breakdown(s)
    assert b["device_ops"][0][0].startswith("fused_lloyd_stats.5 = "
                                            "custom-call")
    assert b["device_ops"][0][1] == pytest.approx(0.557385573)
    assert b["idle_gaps"][0] == ["bench.fit after copy.1",
                                 pytest.approx(0.028955826)]
    # self times never exceed the busy union by more than overlap allows
    assert sum(o["self_s"] for o in s["ops"].values()) \
        == pytest.approx(s["busy_s"], rel=0.02)
    # the roofline reader on it: 4.3 GB a call over 9.29 ms of 819 GB/s
    ctx = {"trace": s, "cell": harness.load_cell("kmeans_lloyd"),
           "n_rows": 8388608, "chips": 1, "d": 128,
           "peaks": lambda: harness.peaks_for("TPU v5 lite"),
           "kernel_cost": lambda: harness.load_module(
               "kernels", "lloyd_stats").cost}
    share = harness.load_module("metrics", "lloyd_stats_roofline").read(ctx)
    assert share == pytest.approx(56.45, abs=0.05)


def test_short_name():
    long = ('%fused_lloyd_stats.5 = (f32[64,128]{1,0:T(8,128)S(1)}, '
            'f32[1,64]{1,0:T(1,128)S(1)}) custom-call(f32[8388608,128]'
            '{1,0:T(8,128)} %get-tuple-element.199), custom_call_target='
            '"tpu_custom_call"')
    assert trace_reduce.short_name(long) == \
        "fused_lloyd_stats.5 = custom-call (f32[64,128], f32[1,64])"
    assert trace_reduce.short_name(
        "%copy.2 = f32[4194304,1]{1,0:T(8,128)} copy(f32[4194304,1]"
        "{0,1:T(1,128)} %bitcast.8)") == "copy.2 = copy f32[4194304,1]"
    assert trace_reduce.short_name("bench.fit") == "bench.fit"
    assert len(trace_reduce.short_name("%x = " + "f32[1]{0} " * 50
                                       + "add(a, b)")) <= 96


def test_reduce_without_device_plane_reads_nothing():
    t = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["bench.window", 0.0, 1e6]]}]}],
        "names": {}}
    assert trace_reduce.reduce(t) is None


def test_load_reads_a_profile_written_here(tmp_path):
    """``load`` on a real ``.xplane.pb`` (a CPU one: host annotations only):
    the benchmark's annotations come through, other host events do not."""
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.fit"):
            jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    table = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    seen = {e[0] for p in table["planes"] for ln in p["lines"]
            for e in ln["events"]}
    assert seen == {"bench.window", "bench.fit"}
    assert trace_reduce.reduce(table) is None


# -- run.py -------------------------------------------------------------------

def _run(cwd, *args, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), *args],
        capture_output=True, text=True, timeout=300, cwd=cwd,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


ARGS = ("--workload", "logreg_resident", "--seed", "0", "--seconds", "1",
        "--trace", "0")


def test_run_refuses_off_chip():
    proc = _run(ROOT, *ARGS)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_run_refuses_an_unknown_cell_and_a_bare_directory(tmp_path):
    proc = _run(ROOT, "--workload", "nope", *ARGS[2:])
    assert proc.returncode != 0 and "unknown workload" in proc.stderr
    # only BENCHMARK.json and the files under paths: no program to measure
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path), *ARGS)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_pin_malloc_keeps_the_temporaries_of_a_call():
    """After ``run.pin_malloc`` a round of three 16 MiB temporaries faults
    its pages in once; a fresh process left to glibc's own adjustment faults
    them in on every round (what made ``predict_rate`` depend on whether the
    process had compiled)."""
    code = (
        "import resource, sys, numpy as np\n"
        "sys.path.insert(0, %r)\n"
        "if sys.argv[1] == 'pin':\n"
        "    from benchmark import run\n"
        "    run.pin_malloc()\n"
        "def faults():\n"
        "    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt\n"
        "def round_():\n"
        "    a = np.ones(1 << 22, np.float32); b = a + 1; c = b * 2\n"
        "    del a, b, c\n"
        "for _ in range(3): round_()\n"
        "f = faults(); round_(); print(faults() - f)\n" % ROOT)
    env = dict(os.environ, NUMPY_MADVISE_HUGEPAGE="0")
    for var in ("MALLOC_MMAP_THRESHOLD_", "MALLOC_TRIM_THRESHOLD_"):
        env.pop(var, None)
    got = {}
    for mode in ("pin", "free"):
        out = subprocess.run([sys.executable, "-c", code, mode], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        got[mode] = int(out.stdout.split()[-1])
    assert got["pin"] < 100 and got["free"] > 10000, got   # 3 x 4096 pages


def test_measure_tool_reads_spread_as_the_driver_does():
    """The distance between the quartiles over the median."""
    tool = harness.load_module("tools", "measure")
    assert tool.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(2.0 / 3.0)
    assert tool.spread([10.0] * 6) == 0.0
    assert tool.spread([0.98, 1.0, 1.0, 1.0, 1.0, 1.02]) == pytest.approx(0.0)
