"""Kernel-only timing of the resident Lloyd statistics pass in its two table
forms: ``fused_lloyd_stats`` over ``(n, d)`` row blocks and
``fused_lloyd_stats_lanes`` over the lane-dense ``(d, n)`` table.

For each (d, k) one jitted program makes ``--iters`` dependent Lloyd
iterations (each one kernel call and the centre update), and the same
program with twice the iterations; the difference over the extra
iterations is the time of one iteration, and what is left of the shorter
program is the set-up it pays once (the row form's relayout of X into
lane-padded row blocks, the lane form's transpose). Prints one JSON line a
case and writes them all to ``chiprun_out/lloyd_layout_probe.json``.

Run on a TPU (it exits non-zero elsewhere):

    python scripts/lloyd_layout_probe.py [--rows 4194304] [--iters 20]

``--variants`` times the lane form at the first d under other chunk and
block sizes as well; ``--interpret`` rehearses the script on the CPU
(interpret-mode kernels, tiny ``--rows``; its times mean nothing).
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dask_ml_tpu.ops import pallas_fused as pf  # noqa: E402


def _program(form, iters, interpret):
    def update(x, nv, c):
        if form == "rows":
            sums, counts, _ = pf.fused_lloyd_stats(x, nv, c,
                                                   interpret=interpret)
        else:
            sums, counts, _ = pf.fused_lloyd_stats_lanes(
                x, nv, c, interpret=interpret)
        return jnp.where(counts[:, None] > 0, sums / counts[:, None], c)

    @jax.jit
    def run(x, nv, c0):
        if form == "lanes":
            x = pf.lloyd_lanes_operand(x, c0.shape[0])
        return jax.lax.fori_loop(0, iters, lambda _, c: update(x, nv, c),
                                 c0)
    return run


def _seconds(fn, *args, repeats):
    jax.block_until_ready(fn(*args))          # compile and warm
    out = []
    for _ in range(repeats):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t)
    return statistics.median(out)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=4_194_304)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--repeats", type=int, default=5)
    p.add_argument("--d", type=int, nargs="+", default=[8, 32, 64, 127])
    p.add_argument("--k", type=int, nargs="+", default=[8, 64])
    p.add_argument("--variants", action="store_true")
    p.add_argument("--interpret", action="store_true")
    a = p.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.interpret:
        sys.exit(f"needs a TPU, found {dev.platform}")
    lines = []

    def case(form, x, nv, c0, **facts):
        run = [_seconds(_program(form, n, a.interpret), x, nv, c0,
                        repeats=a.repeats) for n in (a.iters, 2 * a.iters)]
        per_iter = (run[1] - run[0]) / a.iters
        d, n = x.shape[1], x.shape[0]
        line = {"form": form, "rows": n, "d": d, "k": c0.shape[0],
                "iter_ms": 1e3 * per_iter,
                "setup_ms": 1e3 * (run[0] - a.iters * per_iter),
                "device": dev.device_kind, **facts}
        if form == "lanes":
            line["tiling"] = pf.lloyd_lane_tiling(n, d, c0.shape[0])
        print(json.dumps(line), flush=True)
        lines.append(line)

    for j, d in enumerate(a.d):
        for k in a.k:
            kx, kc = jax.random.split(jax.random.PRNGKey(d * 1000 + k))
            x = jax.random.normal(kx, (a.rows, d), jnp.float32)
            c0 = x[:k] + 0.01 * jax.random.normal(kc, (k, d), jnp.float32)
            nv = jnp.int32(a.rows)
            for form in ("rows", "lanes"):
                case(form, x, nv, c0)
            if a.variants and j == 0:
                chunk0, block0 = pf._LANE_CHUNK_BYTES, pf._GLM_TILE_BUDGET
                for chunk_b in (128 << 10, 512 << 10, 1 << 20, 2 << 20):
                    for block_b in (2 << 20, 4 << 20):
                        pf._LANE_CHUNK_BYTES = chunk_b
                        pf._GLM_TILE_BUDGET = block_b
                        jax.clear_caches()
                        case("lanes", x, nv, c0, chunk_bytes=chunk_b,
                             block_bytes=block_b)
                pf._LANE_CHUNK_BYTES, pf._GLM_TILE_BUDGET = chunk0, block0
                jax.clear_caches()
            del x
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "lloyd_layout_probe.json"),
              "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
