"""Inside the ``predict`` calls alone: 1 - union of device-op intervals /
their summed length, worst chip, %."""
from benchmark.metrics._lib import call_kind


def read(ctx):
    kind = call_kind(ctx, "bench.predict")
    return None if kind is None else kind["idle_pct"]
