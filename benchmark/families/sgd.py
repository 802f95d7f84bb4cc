"""Driver for the ``sgd`` family (``Incremental(SGDClassifier)`` trained by
``partial_fit`` passes over a resident, row-sharded X): how the cell's data
is placed, what one fit (a NEW wrapper, ``fit``, then further passes by
``partial_fit``) and one predict are, what must have engaged, and the
comparison with the plain reference that decides ``correct``. Only the
wrapper's public ``fit`` / ``partial_fit`` / ``predict`` are called."""

from __future__ import annotations

import numpy as np

from benchmark import tolerances_sgd as T
from benchmark.families import _common as C, glm
from benchmark.references import sgd as ref


def make_data(cfg, traffic, chips, seed, mesh):
    """The cell's rows, placed as every family's. A program whose wrapper
    keeps no record of its pass (``Incremental.pass_info_``: PR 30) cannot
    run this family's cells: say so before any data is made."""
    from dask_ml_tpu.wrappers import Incremental

    if not hasattr(Incremental, "_pass"):
        from benchmark.harness import BenchmarkError

        raise BenchmarkError(
            "this program's Incremental records no pass_info_ (path, steps, "
            "dispatches; it is from before PR 30): the sgd cells cannot run "
            "on it")
    data = C.place(cfg, traffic, chips, seed, mesh)
    data["fit"] = cfg["fit"]
    return data


# -- one cycle -----------------------------------------------------------------

def vary(cell, data, k):
    """What changes from fit to fit: the cycle's ``random_state`` (the block
    order) and, where the traffic says ``"vary_per_cycle": "labels"``, labels
    drawn from teacher ``k`` of the seed over the same rows. Not timed."""
    data["random_state"] = (int(data["seed"]) * 7919 + int(k)) % (2**31 - 1)
    glm.vary(cell, data, k)


def make_estimator(cell, data, interpret):
    """A NEW wrapper around a NEW estimator, as the configuration states
    them. In the CPU rehearsal the TPU's dtype choice is REQUESTED."""
    est = cell.config["estimator"]
    extra = {"fit_dtype": "bfloat16"} if interpret else {}
    inner = C.load_class(est["inner"]["class"])(
        **{**est["inner"]["params"], **extra})
    return C.load_class(est["class"])(
        estimator=inner, random_state=data["random_state"], **est["params"])


def fit(est, data):
    """The deployment's loop: ``fit`` (pass 1), then ``partial_fit`` for
    every further pass; ends when the weights are ready."""
    import jax

    spec = data["fit"]
    est.fit(data["X"], data["y"], classes=list(spec["classes"]))
    for _ in range(int(spec["passes"]) - 1):
        est.partial_fit(data["X"], data["y"])
    jax.block_until_ready((est.estimator_.coef_, est.estimator_.intercept_))


def predict(est, data):
    """``predict`` over the whole X; ends with the host labels in hand."""
    return est.predict(data["X"])


def fit_facts(est):
    """``n_iter`` is the minibatch steps of the whole fit (the clock after
    the last pass), so ``iter_ms`` reads ms a step."""
    info = est.pass_info_
    steps = int(info["steps"])
    return {"n_iter": int(info["t_end"]),
            "passes": int(info["t_end"]) // max(steps, 1),
            "path": info["path"], "dispatches": info["dispatches"]}


def engaged(cell, est, data, programs=None):
    """What must have carried the fit; a fallback is a failure."""
    chk = C.Check()
    want = cell.config["expect"]
    info = dict(getattr(est, "pass_info_", {}))
    inner = est.estimator_
    chk.need(info.get("path") == want["path"],
             f"the last pass took {info.get('path')!r}, not "
             f"{want['path']!r}: {info}")
    chk.need(getattr(inner, "fit_dtype_", None) == want["fit_dtype"],
             f"fit_dtype_ is {getattr(inner, 'fit_dtype_', None)!r}, "
             f"not {want['fit_dtype']!r}")
    # (fit_dtype is held to by its resolution, above: the rehearsal
    # requests what the TPU's "auto" chooses)
    stated = {k: v for k, v in cell.config["program_config"].items()
              if k not in ("note", "fit_dtype")}
    have = {k: inner.get_params().get(k) for k in stated}
    chk.need(have == stated,
             f"the estimator's parameters {have} are not the stated "
             f"{stated}")
    on = len(data["X"].data.sharding.device_set)
    chk.need(on == data["chips"], f"X lives on {on} of {data['chips']} chips")
    chk.facts.update(path=info.get("path"), headroom=info.get("headroom"),
                     grid_bytes=info.get("grid_bytes"))
    if programs is not None:
        passes = int(cell.config["fit"]["passes"])
        chk.need(programs.get(want["program"], 0) == passes,
                 f"program {want['program']!r} ran "
                 f"{programs.get(want['program'], 0)} times in the fit, "
                 f"not {passes}: {programs}")
    return chk


# -- the check -----------------------------------------------------------------

def block_rows(n_rows, blocks, chips):
    """Rows of one of ``blocks`` contiguous blocks covering ``n_rows``, a
    multiple of the chips: the deployment's partition, from its numbers."""
    s = -(-int(n_rows) // int(blocks))
    return -(-s // int(chips)) * int(chips)


def orders(random_state, blocks, passes, shuffle):
    """The block order of every pass: the wrapper seeds a NEW
    ``RandomState(random_state)`` per call, so every pass draws the same."""
    out = []
    for _ in range(int(passes)):
        order = list(range(int(blocks)))
        if shuffle:
            np.random.RandomState(random_state).shuffle(order)
        out.append(order)
    return out


def weights(inner):
    return np.r_[np.asarray(inner.coef_, np.float32).ravel(),
                 np.float32(np.ravel(inner.intercept_)[0])]


def check(cell, est, data, predicted):
    """The last fitted wrapper against the reference's own run of the same
    steps over ALL the cell's rows, and its last ``predict`` on the sample
    rows; ``tolerances_sgd.py`` gives every band."""
    chk = C.Check()
    facts = chk.facts
    cfg = cell.config
    n, d = data["n_rows"], data["d"]
    spec, stated, want = cfg["fit"], cfg["program_config"], cfg["expect"]
    inner = est.estimator_
    if cell.traffic["check_rows"] != "all":
        raise ValueError("the sgd check repeats the fit over all rows")

    # (e) the clock and the classes
    passes, blocks = int(spec["passes"]), int(spec["blocks"])
    t_end = est.pass_info_["t_end"]
    facts.update(t_end=t_end, blocks=est.pass_info_["blocks"])
    chk.need(t_end == passes * blocks == want["steps"]
             and est.pass_info_["blocks"] == blocks,
             f"the clock reads {t_end} after {passes} passes of "
             f"{est.pass_info_['blocks']} blocks, not {want['steps']}")
    chk.need(list(np.asarray(est.classes_)) == list(spec["classes"]),
             f"classes_ is {est.classes_!r}, not {spec['classes']}")
    w = weights(inner)
    if not chk.need(w.shape == (d + 1,) and np.isfinite(w).all(),
                    f"coef_ / intercept_ of shape {w.shape} or non-finite"):
        return chk

    # (a), (b): the same 40 steps by the reference, at the stated precision
    # and in float32
    X, y = data["X"].data, data["y"].data
    S = block_rows(n, blocks, data["chips"])
    hyper = dict(loss=cfg["estimator"]["inner"]["params"]["loss"],
                 alpha=stated["alpha"], eta0=stated["eta0"],
                 power_t=stated["power_t"], schedule=stated["learning_rate"],
                 fit_intercept=stated["fit_intercept"])
    order = orders(est.random_state, blocks, passes,
                   cfg["estimator"]["params"]["shuffle_blocks"])
    design = want["fit_dtype"] if want["fit_dtype"] != "float32" else None
    w_stated, _ = ref.fit(X, y, order, S, n, design_dtype=design, **hyper)
    w_f32, _ = ref.fit(X, y, order, S, n, **hyper)
    facts.update(check_rows=n, block_rows=S,
                 stated=T.distance(w, w_stated), f32=T.distance(w, w_f32),
                 f32_of_stated=T.distance(w_stated, w_f32))
    chk.need(facts["stated"] <= T.TOL_STATED,
             f"coef_ is {facts['stated']:.3e} of ||w|| from the reference at "
             f"the stated precision (band {T.TOL_STATED:.0e})")
    facts["f32_band"] = T.f32_band(S)
    chk.need(facts["f32"] <= facts["f32_band"],
             f"coef_ is {facts['f32']:.3e} of ||w|| from the float32 "
             f"reference (band {facts['f32_band']:.2e})")

    # (c) the reference loss over all rows fell
    at = lambda v: ref.objective(v, X, y, S, n, loss=hyper["loss"],  # noqa: E731
                                 alpha=stated["alpha"])
    v0, v1 = at(np.zeros_like(w)), at(w)
    facts.update(loss_at_zero=v0, loss_at_fit=v1)
    chk.need(v1 < v0, f"the loss did not fall: {v0} -> {v1}")

    # (d) predict on the sample rows
    m = min(int(cell.traffic["sample_rows"]), n)
    ok = chk.need(isinstance(predicted, np.ndarray)
                  and predicted.shape == (n,),
                  f"predict returned {type(predicted).__name__} of shape "
                  f"{getattr(predicted, 'shape', None)}, not {(n,)}")
    if ok:
        eta = np.asarray(ref.decision(C.device_rows(data["X"], m), w),
                         np.float64)
        ref_lab = np.asarray(spec["classes"])[(eta > 0).astype(int)]
        differ = predicted[:m] != ref_lab
        rms = float(np.sqrt(np.mean(eta ** 2)))
        worst = float(np.max(np.abs(eta[differ]), initial=0.0) / rms)
        ys = np.asarray(C.device_rows(data["y"], m))
        facts.update(sample_rows=m, predict_mismatch_share=float(
            differ.mean()), predict_worst_tie=worst, decision_rms=rms,
            accuracy_on_sample=float(np.mean(
                predicted[:m] == np.asarray(spec["classes"])[
                    (ys > 0.5).astype(int)])))
        chk.need(worst <= T.TOL_TIE,
                 f"predict: a row {worst:.3e} of the decision scale from the "
                 f"boundary has the other label than the reference's")
        chk.need(facts["predict_mismatch_share"] <= T.TOL_MISMATCH_SHARE,
                 f"predict: {facts['predict_mismatch_share']:.3%} of the "
                 f"sample rows differ from the reference's labels")
        chk.need(facts["accuracy_on_sample"] > 0.6,
                 f"accuracy {facts['accuracy_on_sample']} is chance level")
    return chk
