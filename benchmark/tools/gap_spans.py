"""Put each idle gap of the device down to the host span it lies under, by
hand, on the machine with the chip:

    python3 benchmark/tools/gap_spans.py <workload> [<workload> ...]
        [--cycles 3] [--seed 7] [--top 10]

For each cell: a warm-up cycle, then ``--cycles`` cycles of the traffic's
recipe under this tool's own ``jax.profiler`` trace with ``obs_programs`` on,
so that every span of the program is also a ``dmt.<span>`` annotation on the
profiler's timeline (``dask_ml_tpu/observability/_spans.py``). The xplane is
loaded with the benchmark's own loader, keeping the ``dmt.*`` host events
beside the ``bench.*`` ones, and the tool prints:

- the ``--top`` longest idle gaps of the worst chip inside ``dmt.fit`` /
  ``dmt.predict``: seconds, the call, the device operation that ended where
  the gap begins, and the innermost ``dmt.*`` span open on the host at the
  gap's midpoint; then ALL of that chip's idle time inside those calls, by
  the innermost host span open over it, in milliseconds a call;
- per traced fit, the phases' walls, their sum over the root ``fit`` span
  and the root over the harness's own ``fit_s`` of that call;
- whether the profiler's clock and ``time.time_ns()`` are one clock: the
  start of the first ``dmt.fit`` event minus its ring record's
  ``t_start_ns``, and how far the two moved apart over the later fits;
- the solver's own count of objective evaluations (``n_evals`` of the
  window's ``fit.solve`` spans) beside the trace's count of the main
  kernel's events inside ``bench.fit`` on the first chip.

Everything printed is also written to ``chiprun_out/gap_spans/<workload>.json``.
One process: it holds the chip. The labelling belongs in
``trace_reduce.reduce`` (so that the ledger's ``idle_gaps`` carry span
names); that edit is a ``benchmark`` PR's, this tool is what it would move.
"""

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, trace_reduce  # noqa: E402
from benchmark.metrics import _spans  # noqa: E402

PROGRAM = "dmt."
ROOTS = ("dmt.fit", "dmt.predict")


def host_spans(table, prefix=PROGRAM):
    """[(name, start_ns, end_ns)] of the host events named ``prefix*``."""
    return sorted(
        ((n, s, s + d) for plane in table["planes"]
         if not trace_reduce.DEVICE_PLANE.match(plane["name"])
         for line in plane["lines"] for n, s, d in line["events"]
         if n.startswith(prefix)), key=lambda sp: sp[1])


def innermost(spans, t):
    """The span open at ``t`` that started last (of two that started
    together, the one that ends first); None outside every span."""
    open_at = [sp for sp in spans if sp[1] <= t < sp[2]]
    return max(open_at, key=lambda sp: (sp[1], -sp[2]))[0] if open_at \
        else None


def worst_chip_gaps(table):
    """(spans, gaps) — the program's host spans and every idle gap of the
    worst chip inside its root spans, ``(start_ns, end_ns, root, the device
    operation that ended where it begins)``. None when the trace has no
    device plane (a CPU run)."""
    chips = [sorted(line["events"], key=lambda e: e[1])
             for plane in table["planes"]
             if trace_reduce.DEVICE_PLANE.match(plane["name"])
             for line in plane["lines"]
             if line["name"] == trace_reduce.OPS_LINE and line["events"]]
    if not chips:
        return None
    spans = host_spans(table)
    per_chip = []
    for events in chips:
        busy, gaps = 0.0, []
        for root, c0, c1 in (sp for sp in spans if sp[0] in ROOTS):
            clipped = [(n, max(s, c0), min(s + d, c1))
                       for n, s, d in events if s < c1 and s + d > c0]
            merged = trace_reduce._union([(s, e) for _, s, e in clipped])
            busy += sum(e - s for s, e in merged)
            ended = {e: n for n, _, e in clipped}
            edges = [c0] + [t for iv in merged for t in iv] + [c1]
            gaps += [(edges[i], edges[i + 1], root,
                      ended.get(edges[i], "its start").split(" = ")[0])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
        per_chip.append((busy, gaps))
    return spans, min(per_chip, key=lambda bg: bg[0])[1]


def attribute(table, top=10):
    """The ``top`` longest idle gaps of the worst chip inside the program's
    root spans, longest first: ``{"s", "in", "after", "span"}`` — ``span``
    is the innermost host span open at the gap's midpoint."""
    found = worst_chip_gaps(table)
    if found is None:
        return None
    spans, gaps = found
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return [{"s": (t1 - t0) / 1e9, "in": root, "after": prev,
             "span": innermost(spans, (t0 + t1) / 2)}
            for t0, t1, root, prev in longest]


def idle_by_span(table):
    """ALL the worst chip's idle time inside the program's root spans, by
    the innermost host span open over it (a gap that outlasts a span is cut
    at the span's edges): ``{span: {"calls", "idle_ms_a_call"}}`` — calls of
    that span in the trace, idle milliseconds under it per call."""
    found = worst_chip_gaps(table)
    if found is None:
        return None
    spans, gaps = found
    idle = {}
    for t0, t1, _, _ in gaps:
        cuts = sorted({t0, t1} | {t for _, s, e in spans for t in (s, e)
                                  if t0 < t < t1})
        for a, b in zip(cuts, cuts[1:]):
            name = innermost(spans, (a + b) / 2)
            idle[name] = idle.get(name, 0.0) + (b - a)
    calls = {}
    for name, _, _ in spans:
        calls[name] = calls.get(name, 0) + 1
    return {name: {"calls": calls[name],
                   "idle_ms_a_call": ns / 1e6 / calls[name]}
            for name, ns in sorted(idle.items(), key=lambda kv: -kv[1])}


def fit_rows(ring, fits):
    """Per window fit (the last ``len(fits)`` ``fit`` roots of the ring):
    the phases' walls in ms, their sum over the root, the root over the
    harness's ``fit_s`` of that call."""
    rows = []
    for (root, kids), fit in zip(_spans.calls("fit", len(fits), ring), fits):
        phases = {n: 1e3 * r["wall_s"] for n, r in kids.items()
                  if r["parent_id"] == root["span_id"]}
        root_ms = 1e3 * root["wall_s"]
        rows.append({
            "phases_ms": phases, "sync_ms": {
                n: 1e3 * r["sync_s"] for n, r in kids.items() if r["sync_s"]},
            "root_ms": root_ms, "fit_s_ms": 1e3 * fit["fit_s"],
            "phases_over_root": sum(phases.values()) / root_ms,
            "root_over_fit_s": root["wall_s"] / fit["fit_s"],
            "n_evals": kids.get("fit.solve", {}).get("n_evals")})
    return rows


def clock_check(table, ring, n):
    """The profiler's clock against ``time.time_ns()``, over the last ``n``
    ``fit`` roots: ``first_ns`` is the start of the first one's ``dmt.fit``
    event minus its ring record's ``t_start_ns`` (0 within microseconds if
    the two are one clock; the xplane counts from the session's start, so it
    is minus that start); ``drift_ns`` is, for each later fit, how far the
    two clocks moved apart since the first."""
    events = [s for name, s, _ in host_spans(table) if name == "dmt.fit"][-n:]
    starts = [root["t_start_ns"] for root, _ in _spans.calls("fit", n, ring)]
    if not events or len(events) != len(starts):
        return None
    return {"first_ns": int(events[0]) - starts[0],
            "drift_ns": [int(e - events[0]) - (s - starts[0])
                         for e, s in zip(events[1:], starts[1:])]}


def run(cell, seed, cycles, devices=None, interpret=False):
    """(table, fits, ring) of ``cycles`` traced cycles of ``cell`` after one
    warm-up cycle; ``devices`` and ``interpret`` are the CPU rehearsal's."""
    import jax

    import dask_ml_tpu  # noqa: F401  (places the compile cache first)
    from dask_ml_tpu import config as pconfig
    from dask_ml_tpu.observability import recent_spans, reset_recent_spans
    from dask_ml_tpu.parallel.mesh import default_mesh, device_mesh, use_mesh

    devices = list(devices or jax.devices()[:cell.chips])
    if len(devices) != cell.chips:
        raise harness.BenchmarkError(
            f"{cell.name} asks for {cell.chips} chips; jax shows "
            f"{len(devices)}")
    mesh = default_mesh() if len(devices) == len(jax.devices()) \
        else device_mesh(devices=devices)
    fam = harness.load_module("families", cell.config["family"],
                              cell.bench_dir)
    cell = cell.with_traffic(trace_cycles=cycles)
    trace_dir = tempfile.mkdtemp(prefix="gap_spans_")
    try:
        with contextlib.ExitStack() as stack:
            stack.enter_context(use_mesh(mesh))
            stack.enter_context(pconfig.set(obs_programs=True))
            data = fam.make_data(cell.config, cell.traffic, len(devices),
                                 seed, mesh)
            jax.block_until_ready(data["X"].data)
            reset_recent_spans()
            done, *_ = harness._measure(
                cell, fam, data, float("inf"), True, trace_dir, interpret,
                time.perf_counter(), {})
            ring = recent_spans()
        table = trace_reduce.load(trace_reduce.find_xplane(trace_dir),
                                  host_prefix=(trace_reduce.PREFIX, PROGRAM))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if any(c["failed"] for c in done):
        raise harness.BenchmarkError("a traced call raised (above)")
    return table, [f for c in done for f in c["fits"]], ring


def report(cell, table, fits, ring, top):
    """What the tool prints for one cell, as a dict."""
    out = {"workload": cell.name, "gaps": attribute(table, top),
           "idle_by_span": idle_by_span(table),
           "fits": fit_rows(ring, fits),
           "clock": clock_check(table, ring, len(fits))}
    summary = trace_reduce.reduce(table)
    if summary is not None:
        pattern = cell.config["main_kernel"]["pattern"]
        out["kernel_events_in_bench_fit"] = len(
            trace_reduce.matching(summary, pattern))
        out["idle_gaps_as_the_ledger_names_them"] = summary["gaps"][:top]
    evals = [f["n_evals"] for f in out["fits"]]
    out["n_evals_sum"] = sum(evals) if all(evals) else None
    return out


def show(out):
    print(f"== {out['workload']}")
    if out["gaps"] is None:
        print("no device plane in the trace (a CPU run): no gaps to "
              "attribute")
    else:
        print(f"{'seconds':>9}  {'in':<12} {'after device op':<28} "
              f"host span at the midpoint")
        for g in out["gaps"]:
            print(f"{g['s']:9.6f}  {g['in']:<12} {g['after'][:28]:<28} "
                  f"{g['span']}")
        print("all idle time of that chip, by innermost host span "
              "(calls, idle ms a call):")
        for name, row in out["idle_by_span"].items():
            print(f"  {name:<24} {row['calls']:3d}  "
                  f"{row['idle_ms_a_call']:9.3f}")
    for i, f in enumerate(out["fits"]):
        phases = " ".join(f"{n.removeprefix('fit.')}={ms:.3f}"
                          for n, ms in f["phases_ms"].items())
        syncs = " ".join(f"{n.removeprefix('fit.')}={ms:.3f}"
                         for n, ms in f["sync_ms"].items())
        print(f"fit {i}: {phases} | sync {syncs} | root {f['root_ms']:.3f} "
              f"ms, phases/root {f['phases_over_root']:.4f}, root/fit_s "
              f"{f['root_over_fit_s']:.4f}, n_evals {f['n_evals']}")
    print(f"dmt.fit start on the profiler's clock minus t_start_ns "
          f"(time.time_ns): {out['clock']}")
    if "kernel_events_in_bench_fit" in out:
        print(f"n_evals over the window's fits: {out['n_evals_sum']}; main "
              f"kernel events inside bench.fit, first chip: "
              f"{out['kernel_events_in_bench_fit']}")
    sys.stdout.flush()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workloads", nargs="+")
    ap.add_argument("--cycles", type=int, default=3)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args()
    # as the benchmark's runs: malloc's thresholds where a long-lived
    # process ends up, or predict's host half depends on this process's past
    from benchmark.run import pin_malloc

    pin_malloc()
    dump = os.path.join(ROOT, "chiprun_out", "gap_spans")
    os.makedirs(dump, exist_ok=True)
    for name in args.workloads:
        cell = harness.load_cell(name)
        table, fits, ring = run(cell, args.seed, args.cycles)
        out = report(cell, table, fits, ring, args.top)
        show(out)
        with open(os.path.join(dump, f"{name}.json"), "w") as f:
            json.dump({**out, "ring": ring}, f, indent=1, default=str)


if __name__ == "__main__":
    main()
