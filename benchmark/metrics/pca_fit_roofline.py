"""The PCA fit's share of its roofline, %: the least time any fit must take
on one chip (``kernels/pca_fit.py``: this chip's X read once, over the peak
HBM bytes/s) over the device-busy seconds of one ``bench.fit`` call (the
busy union inside the fit calls on the worst chip, over their count)."""
from benchmark.metrics._lib import call_kind


def read(ctx):
    kind = call_kind(ctx, "bench.fit")
    if kind is None or not kind["calls"]:
        return None
    busy = kind["seconds"] * (1.0 - kind["idle_pct"] / 100.0) / kind["calls"]
    if busy <= 0:
        return None
    cfg = ctx["cell"].config
    need = ctx["kernel_cost"]()(ctx["n_rows"] // ctx["chips"], ctx["d"],
                                cfg["estimator"]["params"])
    peaks = ctx["peaks"]()
    least = max(need["bytes"] / peaks["hbm_bytes_per_s"],
                need["flops"] / peaks["flops_bf16"])
    return 100.0 * least / busy
