"""Device time in collective operations (all-reduce and kin, self time, mean
over chips) over the summed length of the ``fit`` calls, %."""
from benchmark.metrics._lib import call_kind


def read(ctx):
    kind = call_kind(ctx, "bench.fit")
    return None if kind is None else \
        100.0 * kind["collective_s"] / kind["seconds"]
