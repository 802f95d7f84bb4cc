"""Wall time of one objective evaluation, ms: the ``fit.solve`` span (the
solver's dispatch through the scalar fetch that waits for it; no prep, no
final fetch) over its ``n_evals``; mean over the window's fits."""
from benchmark.metrics import _spans


def read(ctx):
    solves = [kids["fit.solve"] for _, kids in _spans.fits(ctx)
              if kids.get("fit.solve", {}).get("n_evals")]
    return _spans.mean(1e3 * s["wall_s"] / s["n_evals"] for s in solves)
