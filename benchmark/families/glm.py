"""Driver for the ``glm`` family (``LogisticRegression``): how a cell's data
is placed, what one fit and one predict are, what must have engaged, and the
comparison with the plain reference that decides ``correct``."""

from __future__ import annotations

import numpy as np

from benchmark import tolerances as T
from benchmark.families import _common as C
from benchmark.references import logreg as ref

make_data = C.place


def make_estimator(cell, data, interpret):
    """On the chip nothing is added to the stated parameters. In the CPU
    rehearsal the choices the TPU's auto-gates make are REQUESTED."""
    extra = {}
    if interpret:
        extra = {"fit_dtype": "bfloat16",
                 "solver_kwargs": {"use_pallas": True,
                                   "pallas_interpret": True}}
    return C.new_estimator(cell.config, **extra)


def vary(cell, data, k):
    """What changes from cycle to cycle (the traffic's ``vary_per_cycle``):
    ``labels`` — cycle ``k`` fits labels drawn from teacher ``k`` of the seed
    over the same rows. A fit's work depends on its labels (the line search
    takes 10, 11 or 12 objective evaluations by the draw, PERF.md section 6),
    so one draw a run would make ``fit_s`` a lottery of the seed; the window
    averages over its draws instead. Not timed."""
    if cell.traffic.get("vary_per_cycle") != "labels":
        return
    import jax

    from benchmark import datagen
    from dask_ml_tpu.parallel import as_sharded

    y = datagen.relabel(cell.config["data"], data["X"].data, data["seed"], k,
                        data["d"])
    data["y"] = as_sharded(jax.block_until_ready(y), mesh=data["mesh"])


def fit(est, data):
    import jax

    est.fit(data["X"], data["y"])
    jax.block_until_ready((est.coef_, est.intercept_))


def predict(est, data):
    """``predict_proba`` over the whole X; ends when the host array the API
    returns is in hand."""
    return est.predict_proba(data["X"])


def fit_facts(est):
    return {"n_iter": int(est.n_iter_)}


def engaged(cell, est, data, programs=None):
    """What must have carried the fit; a fallback is a failure."""
    chk = C.Check()
    info = dict(est.solver_info_)
    want = cell.config["expect"]["fit_dtype"]
    chk.need(getattr(est, "fit_dtype_", None) == want,
             f"fit_dtype_ is {getattr(est, 'fit_dtype_', None)!r}, "
             f"not {want!r}")
    chk.need(info.get("fused") is True,
             f"the fused GLM kernel was not selected: {info}")
    on = len(data["X"].data.sharding.device_set)
    chk.need(on == data["chips"], f"X lives on {on} of {data['chips']} chips")
    return chk


def check(cell, est, data, predicted):
    """The last fitted estimator and its last ``predict_proba`` against the
    reference; see ``tolerances.py`` for every band."""
    chk = C.Check()
    facts = chk.facts
    p = cell.config["estimator"]["params"]
    n, d = data["n_rows"], data["d"]
    m = min(int(cell.traffic["sample_rows"]), n)
    lam = 1.0 / (float(p["C"]) * n)
    coef = np.asarray(est.coef_, np.float32).ravel()
    b0 = np.float32(np.ravel(est.intercept_)[0])

    facts["n_iter"] = int(est.n_iter_)
    chk.need(est.n_iter_ < p["max_iter"],
             f"not converged: n_iter_ {est.n_iter_} hit max_iter")
    chk.need(np.isfinite(coef).all() and np.isfinite(b0), "non-finite coef_")

    # loss and gradient on the check rows: all of them where the reference
    # can hold them, else the sample
    sampled = cell.traffic["check_rows"] != "all"
    rows = m if sampled else None
    Xc = C.device_rows(data["X"], rows)
    yc = C.device_rows(data["y"], rows)
    mc = int(Xc.shape[0])

    def ref_at(c, b):
        v, gc, gb = ref.value_and_grad(c, b, Xc, yc, lam)
        return float(v), np.r_[np.asarray(gc), float(gb)]

    v0, g0 = ref_at(np.zeros_like(coef), 0.0)
    v1, g1 = ref_at(coef, b0)
    scale = float(np.max(np.abs(g0)))
    band = T.logreg_grad_band(float(p["tol"]), scale, mc, sampled)
    facts.update(check_rows=mc, loss_at_zero=v0, loss_at_fit=v1,
                 grad_scale=scale, grad_max_at_fit=float(np.max(np.abs(g1))),
                 grad_band=band)
    chk.need(v1 < v0, f"the loss did not fall: {v0} -> {v1}")
    chk.need(facts["grad_max_at_fit"] <= band,
             f"reference gradient at coef_ has an entry "
             f"{facts['grad_max_at_fit']:.3e} > band {band:.3e}")

    # the reference's own optimum on the sample
    Xs, ys = C.device_rows(data["X"], m), C.device_rows(data["y"], m)
    c_opt, b_opt = ref.optimum(Xs, ys, lam, coef, b0)
    at_fit = float(ref.objective(coef, b0, Xs, ys, lam))
    at_opt = float(ref.objective(c_opt, b_opt, Xs, ys, lam))
    ex_band = T.logreg_excess_band(d, m, float(p["tol"]))
    facts.update(sample_rows=m, excess_over_sample_optimum=at_fit - at_opt,
                 excess_band=ex_band)
    chk.need(np.isfinite(at_opt) and -1e-5 <= at_fit - at_opt <= ex_band,
             f"reference loss at coef_ {at_fit} vs its optimum on the "
             f"sample {at_opt}: excess outside [0, {ex_band:.3e}]")

    # predict_proba on the sample rows
    ok = chk.need(isinstance(predicted, np.ndarray)
                  and predicted.shape == (n, 2),
                  f"predict_proba returned shape "
                  f"{getattr(predicted, 'shape', None)}, not {(n, 2)}")
    if ok:
        ref_p = np.asarray(ref.proba(coef, b0, Xs))
        err = float(np.max(np.abs(predicted[:m, 1] - ref_p)))
        facts["proba_max_err"] = err
        chk.need(np.isfinite(predicted[:m]).all()
                 and bool(np.all(np.abs(predicted[:m].sum(axis=1) - 1.0)
                                 <= 1e-6)),
                 "probabilities are not finite rows summing to 1")
        chk.need(err <= T.TOL_PROBA, f"predict_proba off by {err:.3e}")
        facts["accuracy_on_sample"] = float(
            np.mean((predicted[:m, 1] > 0.5) == (np.asarray(ys) > 0.5)))
        chk.need(facts["accuracy_on_sample"] > 0.6,
                 f"accuracy {facts['accuracy_on_sample']} is chance level")
    return chk
