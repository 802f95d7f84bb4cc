"""What the handoff readers share (PR 34): the program's own ledger of what
the host handed the device and took back, kept on its spans
(``dask_ml_tpu/observability/_spans.py``: ``dispatches``, ``dispatch_s``,
``host_operands``, ``host_operand_bytes``, ``fetches``, ``fetch_bytes``,
``fetch_s``, ``host_gap_s``). The totals are inclusive, so the ROOT record of
a call carries the call's. A harness fit is one root ``fit`` span
(``_spans.fits``) or, where ``fit["facts"]["passes"]`` says so, that many
roots in a row (``_sgd_passes.passes``), whose attributes are summed. Nothing
to read — an empty ring, a program whose spans keep no ledger — gives None."""
from benchmark.metrics import _sgd_passes, _spans


def _fit_roots(ctx):
    """[the root records of each fit of the window], oldest first."""
    counts = [int(f.get("facts", {}).get("passes") or 0)
              for f in ctx["fits"]]
    if not any(counts):
        return [[root] for root, _ in _spans.fits(ctx)]
    roots = [root for root, _ in _sgd_passes.passes(ctx)]
    fits = []
    for n in reversed(counts):       # cut from the newest: the ring's oldest
        cut = len(roots) - n         # records may be gone
        fits.append(roots[max(cut, 0):])
        roots = roots[:max(cut, 0)]
    return [f for f, n in zip(reversed(fits), counts) if len(f) == n]


def per_fit(ctx, key, scale=1.0):
    """Mean over the window's fits of ``key`` summed over a fit's roots."""
    return _spans.mean(scale * sum(root[key] for root in roots)
                       for roots in _fit_roots(ctx)
                       if roots and all(key in root for root in roots))


def per_predict(ctx, key, scale=1.0):
    """Mean over the window's predicts of the root's ``key``."""
    return _spans.mean(scale * root[key]
                       for root, _ in _spans.predicts(ctx) if key in root)
