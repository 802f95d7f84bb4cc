"""What a pass spends before its solver starts, ms: the start of the
``pass.solve`` span minus the start of the pass's root span (validation, the
label encoding with its fetch, the DISPATCH of the two grid programs — their
device time lands in ``pass.solve``, which waits for them), as
``fit_prep_ms`` reads a fit. Mean over the window's passes."""
from benchmark.metrics import _sgd_passes, _spans


def read(ctx):
    return _spans.mean(
        1e-6 * (kids["pass.solve"]["t_start_ns"] - root["t_start_ns"])
        for root, kids in _sgd_passes.passes(ctx) if "pass.solve" in kids)
