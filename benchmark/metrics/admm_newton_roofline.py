"""The fit's share of its roofline, %: the local Newton steps the fit ran
(``admm_newton_steps_per_fit``, the program's own counter) times the least
time ONE step must take on one chip (``kernels/admm_newton.py``: the larger
of one read of this chip's X at the stated design precision over the peak
HBM bytes/s and the Gram's ``n d (d + 1)`` FLOP over the peak FLOP/s) over
the device-busy seconds of one ``bench.fit`` call — ``pca_fit_roofline``'s
arithmetic on a per-step cost. It reads the same work whatever carries the
step. None without a device trace or without the counter."""
from benchmark.metrics import admm_newton_steps_per_fit
from benchmark.metrics._lib import call_kind


def read(ctx):
    kind = call_kind(ctx, "bench.fit")
    steps = admm_newton_steps_per_fit.read(ctx)
    if kind is None or not kind["calls"] or not steps:
        return None
    busy = kind["seconds"] * (1.0 - kind["idle_pct"] / 100.0) / kind["calls"]
    if busy <= 0:
        return None
    need = ctx["kernel_cost"]()(ctx["n_rows"] // ctx["chips"], ctx["d"],
                                ctx["cell"].config["main_kernel"])
    peaks = ctx["peaks"]()
    least = max(need["bytes"] / peaks["hbm_bytes_per_s"],
                need["flops"] / peaks["flops_bf16"])
    return 100.0 * steps * least / busy
