"""Objective evaluations of one fit (each one pass over X: the first
``value_and_grad`` plus every step of the zoom line search), counted inside
the solver's ``while_loop`` and read from the ``fit.solve`` span's
``n_evals``; mean over the window's fits."""
from benchmark.metrics import _spans


def read(ctx):
    return _spans.mean(kids["fit.solve"].get("n_evals")
                       for _, kids in _spans.fits(ctx) if "fit.solve" in kids)
