"""The assignment stage of a spectral fit, ms: the ``fit.assign`` span, ONE
span over all ``n_init`` KMeans restarts on the (n, k) table (each:
k-means|| rounds, a host k-means++ on the candidates, the tol scale, the
Lloyd loop, the labels pass and their fetches). Mean over the window's fits.
None where no fit has such a span."""
from benchmark.metrics import _spans


def read(ctx):
    return _spans.mean(1e3 * kids["fit.assign"]["wall_s"]
                       for _, kids in _spans.fits(ctx)
                       if "restarts" in kids.get("fit.assign", {}))
