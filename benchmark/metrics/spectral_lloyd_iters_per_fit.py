"""Lloyd iterations one spectral fit ran, summed over its ``n_init``
restarts: each restart's count is the int32 its Lloyd program's loop
carries, and the ``fit.assign`` span keeps their sum as ``n_iter`` (the
harness's ``fit_facts`` reports the same number from ``solver_info_``). Mean
over the window's fits. None where the program has no such counter."""
from benchmark.metrics import _spans


def read(ctx):
    return _spans.mean(kids["fit.assign"]["n_iter"]
                       for _, kids in _spans.fits(ctx)
                       if "restarts" in kids.get("fit.assign", {}))
