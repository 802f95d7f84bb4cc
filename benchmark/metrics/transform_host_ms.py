"""The host's part of ``PCA.transform``, ms: the root ``transform`` span
without the time it waited for the device (``sync_s``) — validation, the
components' way to the device, the dispatch. Mean over the window's calls."""
from benchmark.metrics import _spans


def read(ctx):
    n = sum(len(c["predict_s"]) for c in ctx["cycles"])
    return _spans.mean(1e3 * (root["wall_s"] - root["sync_s"])
                       for root, _ in _spans.calls("transform", n))
