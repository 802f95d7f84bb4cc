"""Tracked-program calls in one pass: ``dispatches`` of the pass record
(the program registry's delta over the pass; the fused path makes three:
``sgd.grid_x``, ``sgd.grid_y``, ``sgd.fused_epoch``), on the pass's root
span. Mean over the window's passes."""
from benchmark.metrics import _sgd_passes, _spans


def read(ctx):
    return _spans.mean(root.get("dispatches")
                       for root, _ in _sgd_passes.passes(ctx))
