"""What the ``hyperband`` family's span readers share: the ``fit.solve``
child of every fit of the window (``_spans.fits``). A search's fit is ONE
public call under one root ``fit`` span, and its solve carries the sums of
``search_info_`` (``rounds``, ``groups``, ``scan_steps``, ``train_s``,
``score_s``, ``publish_s``, ``control_s``). Nothing to read — an empty ring,
a program whose search opens no such span or carries no such sum — gives an
empty list."""
from benchmark.metrics import _spans


def solves(ctx, *keys):
    """The window's ``fit.solve`` records that carry every one of ``keys``."""
    return [kids["fit.solve"] for _, kids in _spans.fits(ctx)
            if all(kids.get("fit.solve", {}).get(k) is not None
                   for k in keys)]
