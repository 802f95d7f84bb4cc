"""Arithmetic the per-layer readers share. Every reader takes the run's
``ctx`` (see ``harness.run_cell``) and returns a number, or None when there is
nothing to read — the harness then leaves the metric out of the line."""

import statistics


def mean_fact(ctx, key):
    """Mean over the window's fits: a count that differs from draw to draw
    (solver iterations) has no stable median."""
    vals = [f["facts"][key] for f in ctx["fits"]
            if f["facts"].get(key) is not None]
    return statistics.fmean(vals) if vals else None


def call_kind(ctx, name):
    """The trace's numbers inside the calls annotated ``name``
    (``bench.fit``, ``bench.predict``): calls, seconds, idle_pct (worst
    chip), collective_s (mean over chips); None without a device trace."""
    trace = ctx["trace"]
    return None if trace is None else trace["kinds"].get(name)


def kernel_roofline(ctx):
    """The main kernel's share of its roofline, %: the least time the chip
    could take for what one call must move and compute (the larger of bytes
    over peak HBM bytes/s and FLOP over peak FLOP/s; ``kernels/<cost>.py``
    from shapes) over the median device duration of the kernel's events in
    the trace. The event-name pattern is data, in the configuration's file."""
    trace = ctx["trace"]
    if trace is None:
        return None
    from benchmark import trace_reduce

    cfg = ctx["cell"].config
    durs = trace_reduce.matching(trace, cfg["main_kernel"]["pattern"])
    if not durs:
        return None
    need = ctx["kernel_cost"]()(ctx["n_rows"] // ctx["chips"], ctx["d"],
                                cfg["estimator"]["params"])
    peaks = ctx["peaks"]()
    least = max(need["bytes"] / peaks["hbm_bytes_per_s"],
                need["flops"] / peaks["flops_bf16"])
    return 100.0 * least / statistics.median(durs)
