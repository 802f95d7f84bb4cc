"""The fit's share of its roofline, %: the least time its passes must take
on one chip (``kernels/sgd_pass.py``: every row's design read once at the
fit dtype's width and its label once, a pass; times the configuration's
passes) over the device-busy seconds of one ``bench.fit`` call —
``pca_fit_roofline``'s arithmetic on this cell's one-pass cost. None without
a device trace."""
from benchmark.metrics import pca_fit_roofline


def read(ctx):
    one_pass = pca_fit_roofline.read(ctx)
    return None if one_pass is None \
        else int(ctx["cell"].config["fit"]["passes"]) * one_pass
