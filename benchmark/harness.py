"""The harness: one cell (a configuration under a traffic mix) → set-up,
a measured window of fit-then-predict cycles, the check against the plain
reference, and — in a traced run — the per-layer metrics and ``breakdown``.

Driven by data: a cell names a configuration and a traffic mix in
``BENCHMARK.json``; each is a JSON file found by that name
(``configs/<config>.json``, ``traffic/<traffic>.json``); the configuration
names its estimator family (``families/<family>.py``), its data generator
(``datagen.GENERATORS``) and its main kernel's cost function
(``kernels/<cost>.py``); every per-layer metric is a reader of its own
(``metrics/<name>.py`` with ``read(ctx)``; None = nothing to read, the
metric is left out). Nothing here switches on a cell's name.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import json
import os
import shutil
import statistics
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchmarkError(Exception):
    """The benchmark cannot run as asked (unknown cell, missing file, wrong
    machine): no result line, a non-zero exit."""


def load_module(kind, name, bench_dir=HERE):
    """``<bench_dir>/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchmarkError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    path = os.path.join(*parts)
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise BenchmarkError(f"cannot read {path}: {e}") from e


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # the metric entries of BENCHMARK.json, this cell's
    per_layer: list
    bench_dir: str = HERE  # where traffic/, families/, metrics/, ... live

    def with_traffic(self, **overrides):
        """A copy at other row counts — the CPU rehearsal's tiny sizes."""
        return dataclasses.replace(self, traffic={**self.traffic, **overrides})


def load_cell(workload, root=ROOT):
    """The cell ``workload`` of ``<root>/BENCHMARK.json``, its files found
    by name under the benchmark's directory in ``root``."""
    bench = load_json(root, "BENCHMARK.json")
    bench_dir = os.path.join(root, os.path.relpath(HERE, ROOT))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchmarkError(f"unknown workload {workload!r}; BENCHMARK.json "
                             f"has {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(entries):
        return [m for m in entries
                if "workloads" not in m or workload in m["workloads"]]

    return Cell(
        name=workload, chips=int(w["chips"]),
        config=load_json(root, cfg_entry["file"]),
        traffic=load_json(bench_dir, "traffic", f"{w['traffic']}.json"),
        end_to_end=mine(bench["end_to_end"]),
        per_layer=mine(bench["per_layer"]), bench_dir=bench_dir,
    )


def peaks_for(kind):
    """The published peaks of the device, by its exact ``device_kind``; an
    unknown device is an error, never a default."""
    table = load_json(HERE, "peaks.json")
    if kind not in table:
        raise BenchmarkError(f"no published peaks for device kind {kind!r} "
                             f"in peaks.json (has {sorted(table)})")
    return table[kind]


class _Compiles:
    """Programs compiled or fetched from the persistent cache, from jax's own
    monitoring events. Inside the window both must be zero."""

    def __init__(self):
        self.n = 0
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            self.n += 1

    def _event(self, name, **kw):
        if name.endswith("compilation_cache/cache_hits"):
            self.n += 1


@functools.cache
def compile_counter():
    """The process's one counter (jax keeps every listener registered)."""
    return _Compiles()


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest chip; 0 where the backend reports
    none (the CPU rehearsal)."""
    stats = [d.memory_stats() for d in devices]
    return max((int(s.get("peak_bytes_in_use", 0)) for s in stats if s),
               default=0)


def trimmed_mean(xs, cut=0.1):
    """Mean without the lowest and highest tenth. A fit's time takes a few
    discrete values (one per count of objective evaluations), and a median
    over such values jumps when their mix shifts; a mean moves smoothly, and
    the trim keeps one stalled cycle from moving it."""
    if not xs:
        return None
    xs = sorted(xs)
    k = int(len(xs) * cut)
    return statistics.fmean(xs[k:len(xs) - k])


def _programs():
    from dask_ml_tpu.observability import programs_snapshot

    return {r["program"]: int(r["calls"]) for r in programs_snapshot()}


def _cycle(fam, cell, data, interpret, annotate, traced, k):
    """Cycle ``k`` of the traffic's recipe (``cycle``: a list of ``fit`` and
    ``predict`` steps). A ``fit`` step is {what the traffic varies (untimed),
    a NEW estimator, ``fit``}; ``predict`` runs on the newest estimator. A
    call that raised is in ``failed`` and ends the cycle."""
    rec = {"attempted": 0, "failed": 0, "est": None, "predicted": None,
           "fits": [], "predict_s": []}
    recipe = cell.traffic["cycle"]
    n_fits = recipe.count("fit")
    done = 0
    for step in recipe:
        rec["attempted"] += 1
        try:
            if step == "fit":
                fam.vary(cell, data, k * n_fits + done)
                est = fam.make_estimator(cell, data, interpret)
                before = _programs() if traced else None
                t = time.perf_counter()
                with annotate("bench.fit"):
                    fam.fit(est, data)
                fit = {"fit_s": time.perf_counter() - t,
                       "facts": fam.fit_facts(est)}
                if traced:
                    fit["programs"] = {
                        name: n - before.get(name, 0)
                        for name, n in _programs().items()
                        if n - before.get(name, 0)}
                rec["fits"].append(fit)
                rec["est"] = est
                done += 1
            elif step == "predict":
                t = time.perf_counter()
                with annotate("bench.predict"):
                    rec["predicted"] = fam.predict(rec["est"], data)
                rec["predict_s"].append(time.perf_counter() - t)
            else:
                raise BenchmarkError(f"unknown step {step!r} in the "
                                     f"traffic's cycle")
        except BenchmarkError:
            raise
        except Exception:
            traceback.print_exc()
            rec["failed"] += 1
            return rec
    return rec


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # the Python tracer slows the host
    opts.host_tracer_level = 2         # TraceAnnotation spans
    return opts


def _null(name):
    return contextlib.nullcontext()


def _measure(cell, fam, data, seconds, trace, trace_dir, interpret, t0,
             parts):
    """Warm-up, then the window. Returns (cycles, setup_s, window_s,
    programs compiled or loaded inside the window)."""
    import jax

    compiles = compile_counter()
    warm = _cycle(fam, cell, data, interpret, _null, trace, 0)
    if warm["failed"]:
        raise BenchmarkError("the warm-up cycle raised (above)")
    parts.update(warm_fit_s=warm["fits"][0]["fit_s"],
                 warm_predict_s=warm["predict_s"][0])
    del warm
    compiled_before = compiles.n
    cycles = []
    annotate, budget = _null, None
    if trace:
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
        annotate = jax.profiler.TraceAnnotation
        budget = int(cell.traffic["trace_cycles"])
    setup_s = time.perf_counter() - t0
    t_win = time.perf_counter()
    try:
        with annotate("bench.window"):
            # start cycles until the time (traced: the cycle budget) is up;
            # finish the one in flight
            while not cycles or (
                    time.perf_counter() - t_win < seconds
                    and (budget is None or len(cycles) < budget)):
                cycles.append(_cycle(fam, cell, data, interpret, annotate,
                                     trace, len(cycles) + 1))
                if len(cycles) > 1:    # keep the newest outputs only
                    cycles[-2]["est"] = cycles[-2]["predicted"] = None
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_s = time.perf_counter() - t_win
    return cycles, setup_s, window_s, compiles.n - compiled_before


def _check(cell, fam, data, last, in_window):
    """(problems, facts) of the last cycle's estimator and outputs."""
    problems, facts = [], {}
    if not last["failed"]:
        eng = fam.engaged(cell, last["est"], data,
                          last["fits"][-1].get("programs"))
        chk = fam.check(cell, last["est"], data, last["predicted"])
        problems = eng.failures + chk.failures
        facts = {**eng.facts, **chk.facts}
    if in_window:
        problems.append(f"{in_window} programs compiled or loaded inside "
                        f"the window")
    return problems, facts


def run_cell(cell, seed, seconds, trace, t0=None, devices=None,
             interpret=False, dump=None, log=print):
    """Run one cell and return the result line's dict.

    ``devices`` (default: the first ``cell.chips`` of ``jax.devices()``) and
    ``interpret`` exist for the CPU rehearsal in the tests; ``run.py`` passes
    neither. ``dump`` is a directory that receives the run's details (and,
    traced, the trace's table) for reading by hand."""
    import jax

    import dask_ml_tpu  # noqa: F401  (places the compile cache first)
    from dask_ml_tpu import config as pconfig
    from dask_ml_tpu.parallel.mesh import default_mesh, device_mesh, use_mesh

    from benchmark import trace_reduce

    t0 = time.perf_counter() if t0 is None else t0
    parts = {"import_s": time.perf_counter() - t0}   # where set-up went
    trace = bool(trace)
    devices = list(devices or jax.devices()[:cell.chips])
    mesh = default_mesh() if len(devices) == len(jax.devices()) \
        else device_mesh(devices=devices)
    fam = load_module("families", cell.config["family"], cell.bench_dir)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    summary = table = None
    try:
        with contextlib.ExitStack() as stack:
            stack.enter_context(use_mesh(mesh))
            if trace:
                stack.enter_context(pconfig.set(obs_programs=True))
            t = time.perf_counter()
            data = fam.make_data(cell.config, cell.traffic, len(devices),
                                 seed, mesh)
            jax.block_until_ready(data["X"].data)
            parts["data_s"] = time.perf_counter() - t
            cycles, setup_s, window_s, in_window = _measure(
                cell, fam, data, seconds, trace, trace_dir, interpret, t0,
                parts)
            peak = memory_peak_bytes(devices)     # before the check
            problems, facts = _check(cell, fam, data, cycles[-1], in_window)
        if trace:
            table = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
            summary = trace_reduce.reduce(table)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    attempted = sum(c["attempted"] for c in cycles)
    # a cycle whose post-window check failed counts as failed
    failed = sum(c["failed"] for c in cycles) + bool(problems)
    fits = [f for c in cycles for f in c["fits"]]
    predicts = [t for c in cycles for t in c["predict_s"]]
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    ctx = {
        "cell": cell, "cycles": cycles, "fits": fits, "trace": summary,
        "n_rows": data["n_rows"], "d": data["d"], "x_bytes": data["x_bytes"],
        "chips": len(devices), "setup_s": setup_s,
        "fit_s": trimmed_mean([f["fit_s"] for f in fits]),
        "predict_s": statistics.median(predicts) if predicts else None,
        "compiles_in_window": in_window,
        "memory_peak_bytes": peak,
        "peaks": lambda: peaks_for(dev0.device_kind),
        "kernel_cost": lambda: load_module(
            "kernels", cell.config["main_kernel"]["cost"],
            cell.bench_dir).cost,
    }
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_module("metrics", m["name"], cell.bench_dir).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    log(f"benchmark: {cell.name} seed {seed}: {len(cycles)} cycles "
        f"({len(fits)} fits, {len(predicts)} predicts) in "
        f"{window_s:.2f}s window, set-up {setup_s:.2f}s "
        f"{json.dumps({k: round(v, 2) for k, v in parts.items()})}, "
        f"{in_window} compiles in window; check facts {json.dumps(facts)}")
    for p in problems:
        log(f"benchmark: CHECK FAILED: {p}")
    result = {"correct": not problems and failed == 0,
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = trace_reduce.breakdown(summary)
    if dump:
        os.makedirs(dump, exist_ok=True)
        stem = os.path.join(dump, f"{cell.name}_trace{int(trace)}_s{seed}")
        with open(stem + ".json", "w") as f:
            json.dump({
                "result": result, "facts": facts, "problems": problems,
                "window_s": window_s, "setup_parts": parts,
                "cycles": [{k: v for k, v in c.items()
                            if k not in ("est", "predicted")}
                           for c in cycles],
                "summary": summary,
            }, f, indent=1, default=str)
        if table is not None:
            with open(stem + "_table.json", "w") as f:
                json.dump(table, f)
    return result
