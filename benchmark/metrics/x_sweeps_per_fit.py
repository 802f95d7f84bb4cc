"""Passes over all of X that one PCA fit makes: the solver program's
products with X or X^T (``solver_info_["x_sweeps"]``, on the ``fit.solve``
span) plus the centring program's (on ``fit.center``), each counted where
the program is built. Mean over the window's fits."""
from benchmark.metrics import _spans


def read(ctx):
    return _spans.mean(
        kids["fit.solve"]["x_sweeps"]
        + kids.get("fit.center", {}).get("x_sweeps", 0)
        for _, kids in _spans.fits(ctx)
        if "x_sweeps" in kids.get("fit.solve", {}))
