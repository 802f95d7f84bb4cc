"""The centring phase of a PCA fit on the host's clock, ms: the
``fit.center`` span (dispatch of the mean/variance program; the centred copy
is made inside the solver program, which waits for both). Mean over the
window's fits."""
from benchmark.metrics import _spans


def read(ctx):
    return _spans.mean(1e3 * kids["fit.center"]["wall_s"]
                       for _, kids in _spans.fits(ctx)
                       if "fit.center" in kids)
