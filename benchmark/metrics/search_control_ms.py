"""The host's own work between the cohort programs of one fit, ms:
``fit.solve``'s ``control_s`` (the controller's decisions, the records, the
scans' operands) + ``publish_s`` (scores and weights to the host); mean over
the window's fits."""
from benchmark.metrics import _search, _spans


def read(ctx):
    return _spans.mean(1e3 * (s["control_s"] + s["publish_s"])
                       for s in _search.solves(ctx, "control_s", "publish_s"))
