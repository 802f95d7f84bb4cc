"""The result's way to the host in one predict, ms: ``fetch_s`` of the
predict's root span (the wall of the device-to-host read; the wait for the
decision program is the span's ``sync_s`` and is not in it). Mean over the
window's predicts."""
from benchmark.metrics import _handoffs


def read(ctx):
    return _handoffs.per_predict(ctx, "fetch_s", 1e3)
