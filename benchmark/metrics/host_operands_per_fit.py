"""Operands the tracked programs of one fit took from the HOST (numpy arrays
and scalars, Python numbers that are not static): ``host_operands`` of the
fit's root span(s). Each is placed on every device of the mesh with the
dispatch, while the chip waits. Mean over the window's fits."""
from benchmark.metrics import _handoffs


def read(ctx):
    return _handoffs.per_fit(ctx, "host_operands")
