"""Objective evaluations of one search's stacked solve (each one pass over
the design for all 50 models: the first ``value_and_grad`` plus every step
of the zoom line search), the int32 counter in the L-BFGS loop's carry, read
from the ``fit.solve`` span's ``n_evals``. Mean over the window's fits; None
where no fit has a stacked solve."""
from benchmark.metrics import _grid, _spans


def read(ctx):
    return _spans.mean(s.get("n_evals")
                       for s in _grid.phases(ctx, "fit.solve", "n_models"))
