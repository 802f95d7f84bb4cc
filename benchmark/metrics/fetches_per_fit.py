"""Blocking device-to-host reads in one fit: ``fetches`` of the fit's root
span(s) (every ``to_host`` of a device value and every solver ``_fetch``).
Mean over the window's fits."""
from benchmark.metrics import _handoffs


def read(ctx):
    return _handoffs.per_fit(ctx, "fetches")
