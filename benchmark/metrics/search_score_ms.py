"""Scoring the candidates on the held-out block in one fit, ms:
``fit.solve``'s ``score_s`` (every round's score program, dispatch to sync);
mean over the window's fits."""
from benchmark.metrics import _search, _spans


def read(ctx):
    return _spans.mean(1e3 * s["score_s"]
                       for s in _search.solves(ctx, "score_s"))
