"""Scoring one search, ms: the ``fit.score`` span — the one scoring program
(``glm.grid_score``: every model's accuracy on its test fold) and its one
fetch, then ``cv_results_`` and the winner. Mean over the window's fits;
None where no fit has such a span."""
from benchmark.metrics import _grid, _spans


def read(ctx):
    return _spans.mean(1e3 * s["wall_s"]
                       for s in _grid.phases(ctx, "fit.score", "scored"))
