"""The fold-stacked solve of one search, ms: the ``fit.solve`` span, from
the dispatch of ``glm.lbfgs_lam_grid`` (every (fold, C) model of the grid in
one L-BFGS program) to the fetch that waits for its coefficients. Mean over
the window's fits; None where no fit has a stacked solve."""
from benchmark.metrics import _grid, _spans


def read(ctx):
    return _spans.mean(1e3 * s["wall_s"]
                       for s in _grid.phases(ctx, "fit.solve", "n_models"))
