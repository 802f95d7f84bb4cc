"""The refit of one search, ms: the ``fit.refit`` span, a plain
``LogisticRegression(C=best).fit(X, y)`` (``glm.prepare``, ``glm.lbfgs``)
whose own spans are kept in it (``nested``). Mean over the window's fits;
None where no fit has such a span."""
from benchmark.metrics import _grid, _spans


def read(ctx):
    return _spans.mean(1e3 * s["wall_s"]
                       for s in _grid.phases(ctx, "fit.refit", "nested"))
