"""What the ``gridsearch`` family's span readers share: the children of the
window's fits (``_spans.fits``) that only the fold-stacked search writes —
``fit.solve`` with ``n_models`` (the one stacked program and its counters),
``fit.score`` with ``scored``, ``fit.refit`` with ``nested`` (the refit's own
spans, kept in it). A program whose search opens no such span (one from
before the stacked path: its roots are the per-fold grids' and the refit's
own ``fit``) gives an empty list, and the reader None."""
from benchmark.metrics import _spans


def phases(ctx, name, key):
    """The window's ``name`` records that carry ``key``."""
    return [kids[name] for _, kids in _spans.fits(ctx)
            if key in kids.get(name, {})]
