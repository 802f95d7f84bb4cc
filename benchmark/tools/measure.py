"""Measure cells as the driver does, by hand, on the machine with the chip:

    python3 benchmark/tools/measure.py <tag> <workload> [<workload> ...]
        [--sets 2] [--runs 6] [--traced 1] [--cold-cache 0]

For each cell: ``--traced`` traced runs (``--trace 1``), then ``--sets`` sets
of ``--runs`` plain runs, every run a new process of ``benchmark/run.py`` with
another ``--seed`` (900.. traced; 1000.., 2000.. the sets) and
``BENCHMARK.json``'s ``run_seconds``. Prints, per set and end-to-end metric,
the median and the spread (the distance between the quartiles over the
median, as the driver reads it). Each run's last line goes to
``chiprun_out/<tag>/<workload>.jsonl``, its log to ``<workload>.log`` and its
details (``run.py --dump``) beside them. ``--cold-cache 1`` makes the first
run of each cell compile, by pointing ``JAX_COMPILATION_CACHE_DIR`` at a new
directory under the output for that cell.

This process never imports jax: a parent that touched it would hold the chip.
"""

import argparse
import json
import os
import statistics
import subprocess
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(xs):
    q = statistics.quantiles(xs, n=4, method="inclusive")
    return (q[2] - q[0]) / statistics.median(xs)


def one_run(bench, name, seed, trace, out, env):
    cmd = bench["command"] + [
        "--workload", name, "--seed", str(seed), "--seconds",
        str(bench["run_seconds"]), "--trace", str(trace), "--dump", out]
    t = time.perf_counter()
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True)
    wall = time.perf_counter() - t
    with open(os.path.join(out, f"{name}.log"), "a") as f:
        f.write(f"--- seed {seed} trace {trace} rc {proc.returncode} "
                f"wall {wall:.1f}s\n{proc.stdout}{proc.stderr[-4000:]}\n")
    lines = proc.stdout.strip().splitlines()
    if proc.returncode or not lines:
        print(f"{name} seed {seed} trace {trace}: rc {proc.returncode}\n"
              f"{proc.stderr[-2000:]}", flush=True)
        return None
    res = json.loads(lines[-1])
    with open(os.path.join(out, f"{name}.jsonl"), "a") as f:
        f.write(json.dumps({"seed": seed, "trace": trace, "wall_s": wall,
                            **res}) + "\n")
    print(f"{name} seed {seed} trace {trace} wall {wall:.1f}s correct "
          f"{res['correct']} " + " ".join(
              f"{k}={v['value']:.6g}" for k, v in res["metrics"].items())
          + f" peak={res['device']['memory_peak_bytes'] / 2**30:.2f}GiB",
          flush=True)
    if len(lines) > 1:
        print("   " + lines[-2][:1500], flush=True)
    return res


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("tag")
    ap.add_argument("workloads", nargs="+")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--traced", type=int, default=1)
    ap.add_argument("--cold-cache", type=int, default=0)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = os.path.join(ROOT, "chiprun_out", args.tag)
    os.makedirs(out, exist_ok=True)
    thp = "/sys/kernel/mm/transparent_hugepage/enabled"
    if os.path.exists(thp):
        print("transparent_hugepage:", open(thp).read().strip(), flush=True)
    for name in args.workloads:
        env = dict(os.environ)
        if args.cold_cache:
            env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
                out, f"jax_cache_{name}")
        for i in range(args.traced):
            one_run(bench, name, 900 + i, 1, out, env)
        for s in range(args.sets):
            got = [one_run(bench, name, 1000 * (s + 1) + i, 0, out, env)
                   for i in range(args.runs)]
            got = [r for r in got if r]
            for m in (got[0]["metrics"] if len(got) > 1 else ()):
                xs = [r["metrics"][m]["value"] for r in got]
                print(f"== {name} set {s + 1} {m}: median "
                      f"{statistics.median(xs):.6g} spread "
                      f"{100 * spread(xs):.2f}% of {len(xs)} runs "
                      f"{[float(f'{x:.5g}') for x in xs]}", flush=True)


if __name__ == "__main__":
    main()
