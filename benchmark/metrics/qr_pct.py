"""Device time in the tall QR factorisations over the summed length of the
``fit`` calls, %. XLA lowers a tall QR to a ``while`` over the panel's
columns whose body operations carry unstable names (``fusion.657``), so the
loops themselves are what a name can find: the WHOLE durations (first chip)
of the events matching the configuration's ``qr_ops.pattern`` — the
``while`` operations that carry an f32 panel of at least 100,000 rows. The
blocked formation of Q after each loop is not in it. None where nothing
matches: the metric is then left out."""
from benchmark import trace_reduce
from benchmark.metrics._lib import call_kind


def read(ctx):
    kind = call_kind(ctx, "bench.fit")
    pattern = ctx["cell"].config.get("qr_ops", {}).get("pattern")
    if kind is None or not pattern:
        return None
    qr_s = sum(trace_reduce.matching(ctx["trace"], pattern))
    return 100.0 * qr_s / kind["seconds"] if qr_s else None
