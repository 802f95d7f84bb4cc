"""The search fit's share of its roofline, %: the stacked solve's objective
evaluations (``grid_evals_per_fit``, the program's own counter) times the
least time ONE evaluation must take on one chip (``kernels/grid_fit.py``:
the larger of one read of the bf16 design over the peak HBM bytes/s and
``4 n d`` FLOP for each of the 50 models over the peak bf16 FLOP/s) over the
device-busy seconds of one ``bench.fit`` call — ``admm_newton_roofline``'s
arithmetic. The fold ids, the prep, the scoring program and the refit are
counted in the busy time and not in the floor, so it stays a floor. None
without a device trace or without the counter."""
from benchmark.metrics import grid_evals_per_fit
from benchmark.metrics._lib import call_kind


def read(ctx):
    kind = call_kind(ctx, "bench.fit")
    evals = grid_evals_per_fit.read(ctx)
    if kind is None or not kind["calls"] or not evals:
        return None
    busy = kind["seconds"] * (1.0 - kind["idle_pct"] / 100.0) / kind["calls"]
    if busy <= 0:
        return None
    need = ctx["kernel_cost"]()(ctx["n_rows"] // ctx["chips"], ctx["d"],
                                ctx["cell"].config["main_kernel"])
    peaks = ctx["peaks"]()
    least = max(need["bytes"] / peaks["hbm_bytes_per_s"],
                need["flops"] / peaks["flops_bf16"])
    return 100.0 * evals * least / busy
