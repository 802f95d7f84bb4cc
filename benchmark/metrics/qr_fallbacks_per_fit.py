"""Tall QRs of one PCA fit in which a shard's local factor failed
CholeskyQR2's guard and took Householder (``solver_info_["qr_fallbacks"]``,
the ``qr_fallbacks`` attribute of the ``fit.solve`` span; 0 .. 1 + n_iter).
Mean over the window's fits. None where the span has no such attribute, as
with a program from before the guarded factor: the metric is left out."""
from benchmark.metrics import _spans


def read(ctx):
    return _spans.mean(
        kids["fit.solve"]["qr_fallbacks"]
        for _, kids in _spans.fits(ctx)
        if "qr_fallbacks" in kids.get("fit.solve", {}))
