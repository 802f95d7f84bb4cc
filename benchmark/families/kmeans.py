"""Driver for the ``kmeans`` family (Lloyd ``KMeans`` from an explicit seeded
init): data placement, one fit and one predict, what must have engaged, and
the comparison with the plain reference that decides ``correct``."""

from __future__ import annotations

import numpy as np

from benchmark import tolerances as T
from benchmark.families import _common as C
from benchmark.references import kmeans as ref

make_data = C.place


def make_estimator(cell, data, interpret):
    """``init`` is the generator's seeded (k, d) array: the program receives
    arrays, never the seed. In the CPU rehearsal the fused kernel the TPU's
    auto-gate picks is REQUESTED (the program then interprets it)."""
    extra = {"init": np.asarray(data["hp"]["init"], np.float32)}
    if interpret:
        extra["use_pallas"] = True
    return C.new_estimator(cell.config, **extra)


def vary(cell, data, k):
    """Nothing varies: 20 Lloyd iterations are the same work on any draw."""


def fit(est, data):
    import jax

    est.fit(data["X"])
    jax.block_until_ready(est.labels_.data)


def predict(est, data):
    import jax

    out = est.predict(data["X"])
    jax.block_until_ready(out.data)
    return out


def fit_facts(est):
    return {"n_iter": int(est.n_iter_)}


def engaged(cell, est, data, programs=None):
    """KMeans records no flag for its kernel: the traced run reads the
    program registry's call counts (``programs``: name -> calls in one fit);
    the untraced run can only see where X lives."""
    chk = C.Check()
    on = len(data["X"].data.sharding.device_set)
    chk.need(on == data["chips"], f"X lives on {on} of {data['chips']} chips")
    want = cell.config["expect"]["fit_dtype"]
    chk.need(getattr(est, "fit_dtype_", None) == want,
             f"fit_dtype_ is {getattr(est, 'fit_dtype_', None)!r}, "
             f"not {want!r}")
    if programs is not None:
        name = cell.config["expect"]["program"]
        chk.need(programs.get(name, 0) >= 1,
                 f"program {name!r} did not run in the fit: {programs}")
    return chk


def check(cell, est, data, predicted):
    """The system's OWN outputs against the reference on the sample rows (a
    reference Lloyd run on a sample cannot reproduce full-data centres)."""
    chk = C.Check()
    facts = chk.facts
    p = cell.config["estimator"]["params"]
    n = data["n_rows"]
    m = min(int(cell.traffic["sample_rows"]), n)
    Xs = C.device_rows(data["X"], m)
    centers = np.asarray(est.cluster_centers_, np.float32)
    init = np.asarray(data["hp"]["init"], np.float32)

    facts["n_iter"] = int(est.n_iter_)
    chk.need(est.n_iter_ == p["max_iter"],
             f"n_iter_ {est.n_iter_} != {p['max_iter']}: the tol=0 loop "
             f"left early")
    chk.need(centers.shape == init.shape and np.isfinite(centers).all(),
             f"cluster_centers_ shape {centers.shape} / non-finite")

    d2 = np.asarray(ref.distances_sq(Xs, centers), np.float64)
    ref_lab = d2.argmin(axis=1)
    dmin = d2.min(axis=1)
    rows = np.arange(m)
    for name, lab in (("labels_", est.labels_), ("predict", predicted)):
        lab = np.asarray(C.device_rows(lab, m))
        gap = d2[rows, lab] - dmin
        share = float(np.mean(lab != ref_lab))
        worst = float(np.max(gap / np.maximum(dmin, 1e-30)))
        facts[f"{name}_mismatch_share"] = share
        facts[f"{name}_worst_tie_gap"] = worst
        chk.need(worst <= T.TOL_KMEANS_TIE,
                 f"{name}: a row is assigned to a centre {worst:.3e} "
                 f"(relative squared distance) worse than the reference's")
        chk.need(share <= T.TOL_KMEANS_MISMATCH_SHARE,
                 f"{name}: {share:.3%} of sample rows differ from the "
                 f"reference's labels")

    per_row_ref = float(dmin.mean())
    per_row_sys = float(est.inertia_) / n
    facts.update(inertia_per_row=per_row_sys,
                 ref_inertia_per_row_on_sample=per_row_ref)
    chk.need(abs(per_row_sys - per_row_ref)
             <= T.TOL_KMEANS_INERTIA * per_row_ref,
             f"inertia_/n {per_row_sys} vs reference on the sample "
             f"{per_row_ref}")

    i_init = float(ref.labels_inertia(Xs, init)[2]) / m
    i_ref = float(ref.labels_inertia(
        Xs, ref.lloyd(Xs, init, int(p["max_iter"])))[2]) / m
    gain = (i_init - per_row_ref) / max(i_init - i_ref, 1e-30)
    facts.update(ref_inertia_init=i_init, ref_inertia_after_ref_lloyd=i_ref,
                 gain_share=gain)
    chk.need(i_ref < i_init and gain >= T.KMEANS_MIN_GAIN_SHARE,
             f"final centres earn {gain:.3f} of the reference Lloyd run's "
             f"inertia gain on the sample ({i_init} -> {i_ref}; system "
             f"{per_row_ref})")
    return chk
