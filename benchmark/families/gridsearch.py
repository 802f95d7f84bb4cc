"""Driver for the ``gridsearch`` family (``GridSearchCV`` over
``LogisticRegression``'s ``C`` on a resident, row-sharded X): how the cell's
data is placed, what one fit (a NEW search, ``fit(X, y)``) and one predict
(``search.predict_proba(X)`` through ``best_estimator_``) are, what must
have engaged, and the comparison with the plain reference
(``references/gridsearch.py``) that decides ``correct``; ``tolerances.py``
and ``tolerances_grid.py`` give every band. Only the search's public
``fit`` / ``predict_proba`` are called."""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark import tolerances as T0, tolerances_grid as T
from benchmark.families import _common as C, glm as glm_family
from benchmark.references import gridsearch as ref

vary = glm_family.vary      # new labels from the seed's next teacher
predict = glm_family.predict


def make_data(cfg, traffic, chips, seed, mesh):
    """The cell's rows, placed as every family's. A program whose search
    has no fold-stacked path (no fold ids, no ``search_info_``) cannot run
    this cell at one chip's share — it copies every fold of X — so it is
    refused before any data is made."""
    from dask_ml_tpu.model_selection import _search

    if not hasattr(_search, "_FoldIds"):
        from benchmark.harness import BenchmarkError

        raise BenchmarkError(
            "this program's GridSearchCV has no fold-stacked C grid (it "
            "gathers a copy of X's rows for every fold): the gridsearch "
            "cells cannot run on it")
    return C.place(cfg, traffic, chips, seed, mesh)


def grid(cfg):
    """The searched C values, as the configuration states them."""
    return [float(c) for c in
            np.logspace(*cfg["estimator"]["param_grid"]["C"]["logspace"])]


def make_estimator(cell, data, interpret):
    """A NEW search around a NEW estimator, as the configuration states
    them, on the chip and in the CPU rehearsal alike. The rehearsal does
    NOT request the TPU's bfloat16 design: at its 1,638 training rows a
    fold the bf16 objective is a staircase the zoom line search cannot
    descend (a plain ``LogisticRegression(C=1e-3)`` fit of one fold stops
    at max_iter with a gradient of 3.2e-3), so the CPU's own choice,
    float32, carries it; ``engaged`` holds each backend to its own."""
    est = cell.config["estimator"]
    inner = C.load_class(est["inner"]["class"])(**est["inner"]["params"])
    return C.load_class(est["class"])(inner, {"C": grid(cell.config)},
                                      **est["params"])


def fit(est, data):
    """The one public call; it returns with the winner refitted and its
    coefficients on the host."""
    est.fit(data["X"], data["y"])


def fit_facts(est):
    """``n_iter`` is the stacked solve's joint iterations (the slowest
    model's), so ``iter_ms`` reads ms an iteration of all 50 models."""
    info = est.search_info_
    return {k: info.get(k) for k in ("n_iter", "n_evals", "n_iter_min",
                                     "n_iter_max", "path", "fold_copies")} \
        | {"best_C": float(est.best_params_["C"])}


def engaged(cell, est, data, programs=None):
    """What must have carried the fit; a fallback is a failure."""
    import jax

    chk = C.Check()
    tpu = jax.default_backend() == "tpu"
    want = dict(cell.config["expect"])
    if not tpu:    # the auto policy's choice off the chip: an f32 design,
        want["fit_dtype"] = "float32"        # which the search never casts
        want["programs"] = {**want["programs"], "glm.prepare":
                            want["programs"]["glm.prepare"] - 1}
    info = dict(getattr(est, "search_info_", {}))
    for key in ("path", "fold_copies", "n_models", "n_folds", "fit_dtype",
                "intercept", "scored"):
        chk.need(info.get(key) == want[key],
                 f"search_info_[{key!r}] is {info.get(key)!r}, not "
                 f"{want[key]!r}: {({k: v for k, v in info.items() if k != 'betas'})}")
    chk.need(len(est.cv_results_["params"]) == want["n_candidates"],
             f"{len(est.cv_results_['params'])} candidates, not "
             f"{want['n_candidates']}")
    stated = {**cell.config["estimator"]["params"]}
    have = {k: getattr(est, k) for k in stated}
    chk.need(have == stated, f"the search's parameters {have} are not the "
             f"stated {stated}")
    inner = cell.config["estimator"]["inner"]["params"]
    best = est.best_estimator_
    have = {k: best.get_params().get(k) for k in inner}
    chk.need(have == inner, f"the refit's parameters {have} are not the "
             f"stated {inner}")
    sinfo = dict(getattr(best, "solver_info_", {}))
    chk.need(sinfo.get("intercept") == want["intercept"]
             and getattr(best, "fit_dtype_", None) == want["fit_dtype"],
             f"the refit kept the intercept as {sinfo.get('intercept')!r} "
             f"at {getattr(best, 'fit_dtype_', None)!r}")
    if tpu:
        chk.need(sinfo.get("fused") is True,
                 f"the refit's fused GLM kernel was not selected: {sinfo}")
    on = len(data["X"].data.sharding.device_set)
    chk.need(on == data["chips"], f"X lives on {on} of {data['chips']} chips")
    if programs is not None:
        for name, n in want["programs"].items():
            chk.need(programs.get(name, 0) == n,
                     f"program {name!r} ran {programs.get(name, 0)} times "
                     f"in the fit, not {n}: {programs}")
    chk.facts.update({k: info.get(k) for k in (
        "path", "fold_copies", "fold_id_bytes", "n_iter", "n_evals",
        "n_iter_min", "n_iter_max")})
    return chk


def check(cell, est, data, predicted):
    """The last fitted search against the reference: every (fold, C) model
    at its own fold, every test-fold score, the winner, then the refit and
    ``predict_proba`` as the ``glm`` family checks a fit."""
    if cell.traffic["check_rows"] != "all":
        raise ValueError("the gridsearch check reads every fold's rows")
    chk = C.Check()
    facts = chk.facts
    cfg = cell.config
    inner = cfg["estimator"]["inner"]["params"]
    tol = float(inner["tol"])
    Cs = grid(cfg)
    K = len(Cs)
    n, d = data["n_rows"], data["d"]
    F = int(cfg["expect"]["n_folds"])
    info = est.search_info_
    betas = np.asarray(info.get("betas"), np.float64)
    if not chk.need(betas.shape == (K, F, d + 1) and np.isfinite(betas).all(),
                    f"search_info_['betas'] has shape {betas.shape} or is "
                    f"not finite, not ({K}, {F}, {d + 1})"):
        return chk
    # a model the budget cut off is held to the same bands as the rest (on
    # the CPU rehearsal's 1,638 training rows a fold is nearly separable and
    # the weakest penalties take all 50 iterations)
    facts["n_iter_max"] = info.get("n_iter_max")
    X, y = C.device_rows(data["X"]), C.device_rows(data["y"])
    starts, stops = ref.folds(n, F)
    m = min(int(cell.traffic["sample_rows"]), n - int(max(stops - starts)))
    res = est.cv_results_
    right = np.zeros((K, F), np.int64)
    near = np.zeros((K, F), np.int64)
    off = np.zeros((K, F), np.int64)
    needed = np.zeros((K, F))
    grad_over = excess_worst = -np.inf
    excess_low = np.inf
    for f in range(F):
        lo, hi = int(starts[f]), int(stops[f])
        n_test, n_train = hi - lo, n - (hi - lo)
        lams = [1.0 / (c * n_train) for c in Cs]
        W, b = betas[:, f, :d], betas[:, f, d]
        st = ref.fold_stats(X, y, W, b, lo, hi, lams, T.NEAR_TIE)
        chk.need(st["n_train"] == n_train,
                 f"fold {f}: {st['n_train']} training rows, not {n_train}")
        scale = float(np.max(np.abs(ref.grad_at_zero(X, y, lo, hi))))
        band = T0.logreg_grad_band(tol, scale, n_train, False)
        gmax = np.max(np.abs(st["grad"]), axis=1)
        grad_over = max(grad_over, float(np.max(gmax / band)))
        worst = int(np.argmax(gmax))
        chk.need(bool(np.all(gmax <= band)),
                 f"fold {f}, C={Cs[worst]:.3g}: the reference gradient at "
                 f"the model has an entry {gmax[worst]:.3e} > band {band:.3e}")
        recorded = np.asarray(res[f"split{f}_test_score"], np.float64)
        hits = np.rint(recorded * n_test).astype(np.int64)
        right[:, f], near[:, f] = st["right"], st["near"]
        off[:, f] = np.abs(hits - st["right"])
        needed[:, f] = [ref.near_band_needed(st["smallest"][c], off[c, f])
                        for c in range(K)]
        # the reference's own optimum on the fold's first m training rows
        idx = np.r_[0:lo, hi:n][:m]
        Xs, ys = X[idx], y[idx]
        ex_band = T0.logreg_excess_band(d, m, tol)
        for c in range(K):
            excess, _ = ref.sample_excess(Xs, ys, lams[c], W[c], b[c])
            excess_worst = max(excess_worst, excess)
            excess_low = min(excess_low, excess)
            chk.need(np.isfinite(excess) and -1e-5 <= excess <= ex_band,
                     f"fold {f}, C={Cs[c]:.3g}: reference loss at the model "
                     f"is {excess:.3e} above its optimum on the fold's "
                     f"first {m} training rows, outside [0, {ex_band:.3e}]")
    n_test = (stops - starts).astype(np.float64)[None, :]
    facts.update(models_checked=K * F, grad_over_band_max=grad_over,
                 excess_max=excess_worst, excess_min=excess_low,
                 excess_band=T0.logreg_excess_band(d, m, tol), sample_rows=m,
                 score_rows_off_max=int(off.max()),
                 score_rows_over_near_max=int((off - near).max()),
                 near_tie_rows_max=int(near.max()),
                 near_band_needed_max=float(needed.max()))
    worst = np.unravel_index(int(np.argmax(off - near)), off.shape)
    chk.need(bool(np.all(off <= near)),
             f"C={Cs[worst[0]]:.3g} on fold {worst[1]}: the recorded test "
             f"score is {off[worst]} rows from the reference's accuracy, "
             f"{near[worst]} of its test rows within {T.NEAR_TIE} of the "
             f"boundary")

    # the winner: the program's rule on its own recorded means, and the
    # reference's choice on its own scores unless a near-tie can flip it
    prog_means = np.asarray(res["mean_test_score"], np.float64)
    ref_means = (right / n_test).mean(axis=1)
    slack = float((near / n_test).mean(axis=1).max())
    best = int(est.best_index_)
    ref_best = ref.winner(ref_means, T.TIE_TOL)
    ambiguous = bool(np.any(
        np.abs(ref_means - (ref_means.max() - T.TIE_TOL)) <= 2 * slack))
    facts.update(best_index=best, ref_best_index=ref_best,
                 best_C=float(Cs[best]), winner_ambiguous=ambiguous)
    chk.need(best == ref.winner(prog_means, T.TIE_TOL)
             and est.best_params_ == {"C": est.cv_results_["params"][best]
                                      ["C"]},
             f"best_index_ {best} is not the tie rule's choice on the "
             f"recorded means {prog_means}")
    chk.need(np.isclose(est.best_params_["C"], Cs[best]),
             f"best_params_ {est.best_params_} is not C = {Cs[best]}")
    chk.need(best == ref_best or ambiguous,
             f"best_index_ {best} (C={Cs[best]:.3g}); the reference chooses "
             f"{ref_best} (C={Cs[ref_best]:.3g}) on its means {ref_means}")

    # the refit and predict_proba: the glm family's check at the winner's C
    refit_cell = dataclasses.replace(cell, config={
        **cfg, "estimator": {"params": {**inner, "C": Cs[best]}}})
    sub = glm_family.check(refit_cell, est.best_estimator_, data, predicted)
    chk.failures += [f"refit: {msg}" for msg in sub.failures]
    facts.update({f"refit_{k}": v for k, v in sub.facts.items()})
    return chk
