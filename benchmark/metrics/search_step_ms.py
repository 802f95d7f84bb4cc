"""One scan step of a cohort program, ms: ``fit.solve``'s ``train_s`` (first
dispatch of a round's cohort programs to the sync of the stacked weights)
over its ``scan_steps``; mean over the window's fits."""
from benchmark.metrics import _search, _spans


def read(ctx):
    return _spans.mean(1e3 * s["train_s"] / s["scan_steps"]
                       for s in _search.solves(ctx, "train_s", "scan_steps")
                       if s["scan_steps"])
