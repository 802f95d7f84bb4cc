"""The embedding of a spectral fit, ms: the ``fit.solve`` span of a fit whose
span says what carried it (``embed``): from the dispatch of
``spectral.embed`` (landmark draw, both affinities, degrees, G, the TSQR
SVD, the row scaling) to the end of the wait for its (n, k) table. Mean over
the window's fits. None where no fit has such a span, as with a program from
before it."""
from benchmark.metrics import _spans


def read(ctx):
    return _spans.mean(1e3 * kids["fit.solve"]["wall_s"]
                       for _, kids in _spans.fits(ctx)
                       if "embed" in kids.get("fit.solve", {}))
