"""One ``partial_fit`` pass of the wrapper, ms: the wall of the pass's root
span (``fit`` for the first pass of a fit, ``partial_fit`` for the others),
validation, grid build, the epoch program and the weights' way to the host.
Mean over the window's passes."""
from benchmark.metrics import _sgd_passes, _spans


def read(ctx):
    return _spans.mean(1e3 * root["wall_s"]
                       for root, _ in _sgd_passes.passes(ctx))
