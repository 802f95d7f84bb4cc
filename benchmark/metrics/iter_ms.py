"""Fit wall time over ``n_iter_``, ms, mean over the fits — measured
from outside, so prep, label scan and host fetch are spread over the
iterations (the tracing issue replaces it with a span)."""
import statistics


def read(ctx):
    vals = [1e3 * f["fit_s"] / f["facts"]["n_iter"] for f in ctx["fits"]
            if f["facts"].get("n_iter")]
    return statistics.fmean(vals) if vals else None
