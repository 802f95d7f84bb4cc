"""The search fit's share of its roofline, %: the least time ANY
implementation of one fit must take on one chip (``kernels/search_fit.py``
from the configuration's ``main_kernel.schedule``: ``max_iter`` block reads
and one read of X over the peak HBM bytes/s, or the model steps' and scores'
products over the peak FLOP/s, whichever is larger) over the device-busy
seconds of one ``bench.fit`` call — ``pca_fit_roofline``'s arithmetic. None
without a device trace."""
from benchmark.metrics._lib import call_kind


def read(ctx):
    kind = call_kind(ctx, "bench.fit")
    if kind is None or not kind["calls"]:
        return None
    busy = kind["seconds"] * (1.0 - kind["idle_pct"] / 100.0) / kind["calls"]
    if busy <= 0:
        return None
    need = ctx["kernel_cost"]()(
        ctx["n_rows"] // ctx["chips"], ctx["d"],
        ctx["cell"].config["main_kernel"]["schedule"])
    peaks = ctx["peaks"]()
    least = max(need["bytes"] / peaks["hbm_bytes_per_s"],
                need["flops"] / peaks["flops_bf16"])
    return 100.0 * least / busy
