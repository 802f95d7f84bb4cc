"""The solver phase of a PCA fit, ms: the ``fit.solve`` span, from the
dispatch of the solver program through the fetch of ``s``, ``vt``, the mean
and the variance (it waits for the centring program too). Mean over the
window's fits."""
from benchmark.metrics import _spans


def read(ctx):
    return _spans.mean(1e3 * kids["fit.solve"]["wall_s"]
                       for _, kids in _spans.fits(ctx)
                       if "x_sweeps" in kids.get("fit.solve", {}))
