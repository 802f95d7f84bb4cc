"""The host's own count of the time the chip had nothing to run in one fit,
ms: ``host_gap_s`` of the fit's root span(s) — the time inside the call with
no tracked dispatch in flight (from the root's open, or a wait's return, to
the end of the next tracked dispatch, or to the close). It assumes that a
wait drains the queue and sees no untracked launch: an upper bound of the
trace's idle time a fit. Mean over the window's fits."""
from benchmark.metrics import _handoffs


def read(ctx):
    return _handoffs.per_fit(ctx, "host_gap_s", 1e3)
