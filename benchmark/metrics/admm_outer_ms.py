"""Wall time of one outer ADMM iteration, ms: the ``fit.solve`` span (the
dispatch of ``glm.admm`` through the one fetch that waits for it) over the
``n_iter`` it ran — local Newton steps, the ``(d + 1, d + 1)`` solves, the
soft threshold, the residuals; mean over the window's fits. Read only where
the span says ADMM ran (it carries ``local_steps``); None otherwise."""
from benchmark.metrics import _spans


def read(ctx):
    solves = [kids["fit.solve"] for _, kids in _spans.fits(ctx)
              if kids.get("fit.solve", {}).get("local_steps")
              and kids["fit.solve"].get("n_iter")]
    return _spans.mean(1e3 * s["wall_s"] / s["n_iter"] for s in solves)
