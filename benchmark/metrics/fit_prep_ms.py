"""What a fit spends before its solver starts, ms: the start of the
``fit.solve`` span minus the start of the root ``fit`` span (validate +
prepare for the GLMs, validate + init + tol_scale for KMeans). Host time:
device work a phase only queues lands in ``fit.solve``, which waits for it.
Mean over the window's fits."""
from benchmark.metrics import _spans


def read(ctx):
    return _spans.mean(
        1e-6 * (kids["fit.solve"]["t_start_ns"] - root["t_start_ns"])
        for root, kids in _spans.fits(ctx) if "fit.solve" in kids)
