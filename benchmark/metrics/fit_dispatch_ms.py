"""Host time inside the tracked programs' calls of one fit, ms:
``dispatch_s`` of the fit's root span(s) (the wall of every jitted call:
argument handling, the placement of host operands, the enqueue). Mean over
the window's fits."""
from benchmark.metrics import _handoffs


def read(ctx):
    return _handoffs.per_fit(ctx, "dispatch_s", 1e3)
