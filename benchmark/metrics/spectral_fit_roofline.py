"""The spectral fit's share of its roofline, %: the least time any Nyström
fit must take on one chip (``kernels/spectral_fit.py``: this chip's X read
once over the peak HBM bytes/s, or the affinity's cross term at one bf16
pass over the peak FLOP/s, whichever is longer) over the device-busy seconds
of one ``bench.fit`` call — ``pca_fit_roofline``'s arithmetic on this
configuration's cost file. Only where the program's fits say what carried
the embedding (``fit.solve`` with ``embed``): a program without it gives
None."""
from benchmark.metrics import _spans
from benchmark.metrics.pca_fit_roofline import read as floor_over_busy


def read(ctx):
    if not any("embed" in kids.get("fit.solve", {})
               for _, kids in _spans.fits(ctx)):
        return None
    return floor_over_busy(ctx)
