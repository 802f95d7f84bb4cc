"""Driver for the ``hyperband`` family (``HyperbandSearchCV`` over
``SGDClassifier`` on a resident, row-sharded X): how the cell's data is
placed, what one fit (a NEW search, ``fit(X, y, classes=...)``) and one
predict (``search.predict(X)``) are, what must have engaged, and the
comparison with the plain reference that decides ``correct``. Only the
search's public ``fit`` / ``predict`` are called."""

from __future__ import annotations

import numpy as np

from benchmark import tolerances_search as T
from benchmark.families import _common as C, sgd as sgd_family
from benchmark.references import hyperband as ref, sgd as ref_sgd


def make_data(cfg, traffic, chips, seed, mesh):
    """The cell's rows, placed as every family's. A program whose search
    keeps no record of what it ran (``search_info_``: PR 32) cannot run this
    family's cells: say so before any data is made."""
    from dask_ml_tpu.model_selection import HyperbandSearchCV

    if not hasattr(HyperbandSearchCV, "_search_sums"):
        from benchmark.harness import BenchmarkError

        raise BenchmarkError(
            "this program's HyperbandSearchCV records no search_info_ "
            "(plane, rounds, groups, paths; it is from before PR 32): the "
            "hyperband cells cannot run on it")
    return C.place(cfg, traffic, chips, seed, mesh)


# -- one cycle -----------------------------------------------------------------

vary = sgd_family.vary      # the fit's random_state, and new labels


def parameters(cfg):
    """The searched distributions, as the configuration states them."""
    return {k: np.logspace(*v["logspace"])
            for k, v in cfg["estimator"]["parameters"].items()}


def make_estimator(cell, data, interpret):
    """A NEW search around a NEW estimator, as the configuration states
    them. In the CPU rehearsal the TPU's dtype choice is REQUESTED."""
    est = cell.config["estimator"]
    extra = {"fit_dtype": "bfloat16"} if interpret else {}
    inner = C.load_class(est["inner"]["class"])(
        **{**est["inner"]["params"], **extra})
    return C.load_class(est["class"])(
        inner, parameters(cell.config), random_state=data["random_state"],
        **est["params"])


def fit(est, data):
    """The one public call; it returns with the winner's weights on the
    host."""
    est.fit(data["X"], data["y"], classes=[0, 1])


def predict(est, data):
    """``predict`` over the whole X; ends with the host labels in hand."""
    return est.predict(data["X"])


def fit_facts(est):
    """``n_iter`` is the adaptive rounds of the fit, so ``iter_ms`` reads ms
    a round."""
    info = est.search_info_
    return {"n_iter": int(info["n_rounds"]), "groups": info["groups"],
            "scan_steps": info["scan_steps"],
            "dispatches": info["dispatches"]}


def engaged(cell, est, data, programs=None):
    """What must have carried the fit; a fallback is a failure."""
    chk = C.Check()
    want = cell.config["expect"]
    info = dict(getattr(est, "search_info_", {}))
    chk.need(info.get("plane") == want["plane"],
             f"the fit's data plane was {info.get('plane')!r}, not "
             f"{want['plane']!r}: gate {info.get('gate')}")
    groups = [g for r in info.get("rounds", []) for g in r["groups"]]
    off = [g for g in groups if g["path"] != want["path"]
           or g["program"] != want["program"]]
    chk.need(groups and not off,
             f"{len(off)} of {len(groups)} groups left the path "
             f"{want['path']!r} / {want['program']!r}: {off[:3]}")
    chk.need(info.get("fit_dtype") == want["fit_dtype"],
             f"fit_dtype is {info.get('fit_dtype')!r}, not "
             f"{want['fit_dtype']!r}")
    # (fit_dtype is held to by its resolution, above: the rehearsal
    # requests what the TPU's "auto" chooses)
    stated = {k: v for k, v in cell.config["program_config"].items()
              if k not in ("note", "fit_dtype")}
    inner = est.best_estimator_
    have = {k: inner.get_params().get(k) for k in stated}
    chk.need(have == stated,
             f"the estimator's parameters {have} are not the stated {stated}")
    on = len(data["X"].data.sharding.device_set)
    chk.need(on == data["chips"], f"X lives on {on} of {data['chips']} chips")
    chk.facts.update(plane=info.get("plane"), gate=info.get("gate"),
                     grid_bytes=info.get("grid_bytes"),
                     groups=len(groups), scan_steps=info.get("scan_steps"))
    if programs is not None:
        for name, n in ((want["program"], len(groups)),
                        (want["score_program"], info.get("n_rounds")),
                        ("search.split_x", 1)):
            chk.need(programs.get(name, 0) == n,
                     f"program {name!r} ran {programs.get(name, 0)} times "
                     f"in the fit, not {n}: {programs}")
    return chk


# -- the check -----------------------------------------------------------------

weights = sgd_family.weights


def outputs(est, predicted):
    """What the check reads of a fitted search, as plain data (the faults'
    script builds the same from the reference run wrongly)."""
    res = est.cv_results_
    return {
        "metadata": est.metadata_, "metadata_before": est.metadata(),
        "params": list(res["params"]),
        "calls": [int(c) for c in res["partial_fit_calls"]],
        "history": [{k: r[k] for k in ("model_id", "partial_fit_calls",
                                       "score")} for r in est.history_],
        "n_rounds": est.search_info_["n_rounds"],
        "best_index": int(est.best_index_),
        "best_score": float(est.best_score_),
        "best_params": est.best_params_,
        "w_best": weights(est.best_estimator_),
        "classes": list(np.asarray(est.classes_)),
        "predicted": predicted, "random_state": est.random_state,
    }


def problem(cell, data, random_state, **kw):
    """The reference's own view of the cell's data for one search."""
    cfg = cell.config
    est, stated = cfg["estimator"], cfg["program_config"]
    hyper = dict(loss=est["inner"]["params"]["loss"], l2=1.0, l1=0.0,
                 power_t=stated["power_t"],
                 schedule=stated["learning_rate"],
                 fit_intercept=stated["fit_intercept"])
    return ref.Problem(
        data["X"].data, data["y"].data, data["n_rows"], data["chips"],
        parameters=parameters(cfg), max_iter=est["params"]["max_iter"],
        eta=est["params"]["aggressiveness"],
        test_size=est["params"]["test_size"], random_state=random_state,
        hyper=hyper, **kw)


def check(cell, est, data, predicted):
    """The last fitted search against the reference; ``tolerances_search.py``
    gives every band."""
    if cell.traffic["check_rows"] != "all":
        raise ValueError("the hyperband check repeats the search over all "
                         "rows")
    return check_outputs(cell, outputs(est, predicted), data)


def check_outputs(cell, out, data, cache=None):
    """``cache`` (a dict): the reference's problem and its own search, kept
    from one call to the next of the same ``random_state`` (the faults'
    tool checks many outputs of one problem)."""
    chk = C.Check()
    facts = chk.facts
    cfg = cell.config
    want = cfg["expect"]
    n, d = data["n_rows"], data["d"]
    max_iter = cfg["estimator"]["params"]["max_iter"]
    eta = cfg["estimator"]["params"]["aggressiveness"]
    cache = {} if cache is None else cache
    if cache.get("random_state") != out["random_state"]:
        cache.clear()
        cache.update(random_state=out["random_state"],
                     p=problem(cell, data, out["random_state"]))
    p = cache["p"]
    n_test = len(p.test_idx)
    n_models = len(p.params)

    # (A) the schedule
    meta = ref.metadata(max_iter, eta)
    for name in ("metadata", "metadata_before"):
        got = {k: out[name].get(k) for k in meta}
        chk.need(got == meta, f"{name} is {got}, not {meta}")
    if cell.traffic["rows_per_chip"] * data["chips"] == n:
        full = {k: want[k] for k in meta}
        chk.need(meta == full, f"the file's expect {full} is not the "
                 f"schedule's {meta}")
    chk.need(out["params"] == p.params,
             "cv_results_['params'] is not the reference's own draw")
    chk.need(out["n_rounds"] == want["rounds"],
             f"{out['n_rounds']} adaptive rounds, not {want['rounds']}")
    chk.need(out["classes"] == list(cfg["fit"]["classes"]),
             f"classes_ is {out['classes']}, not {cfg['fit']['classes']}")
    by_rung = {}                 # (model id, calls) -> recorded score
    for r in out["history"]:
        by_rung[(r["model_id"], r["partial_fit_calls"])] = float(r["score"])
    last = {}
    for (m, c), v in by_rung.items():
        if c >= last.get(m, (0, None))[0]:
            last[m] = (c, v)
    ok = chk.need(sorted(last) == list(range(n_models))
                  and len(out["calls"]) == n_models,
                  f"history_ scores {len(last)} of {n_models} models")
    if not ok:
        return chk
    chk.need(all(last[m][0] == out["calls"][m] for m in last),
             "a model's partial_fit_calls differs from its last record's")
    for s, n_s, r_s in ref.brackets(max_iter, eta):
        plan = ref.rungs(n_s, r_s, max_iter, eta)
        wanted = sorted(c for (alive, c), (nxt, _) in zip(
            plan, plan[1:] + [(0, 0)]) for _ in range(alive - nxt))
        got = sorted(out["calls"][m] for m in p.models[s])
        chk.need(got == wanted, f"bracket {s}: final calls {got} are not "
                 f"the schedule's {wanted}")

        # (B) every promotion follows the recorded scores
        alive = list(p.models[s])
        for (_, calls), (n_next, calls_next) in zip(plan, plan[1:]):
            scores = {m: by_rung.get((m, calls)) for m in alive}
            if not chk.need(None not in scores.values(),
                            f"bracket {s}: a model alive at {calls} calls "
                            f"has no score there"):
                break
            kept = sorted(ref.keep(scores, n_next))
            went_on = sorted(m for m in p.models[s]
                             if (m, calls_next) in by_rung)
            if not chk.need(went_on == kept,
                            f"bracket {s} at {calls} calls: {went_on} went "
                            f"on, the recorded scores keep {kept}"):
                break
            alive = kept

    # (C) every recorded final score is the model's accuracy: the
    # reference's own whole search at the stated precision first (its
    # weights serve wherever it trained a model as far as the system did)
    design = want["fit_dtype"] if want["fit_dtype"] != "float32" else None
    calls = {m: c for m, (c, _) in last.items()}
    if "own" not in cache:
        cache["own"] = ref.search(p, design_dtype=design)
    own = cache["own"]
    order = sorted(calls)
    Ws = ref.replay(p, calls, design_dtype=design, known=own)
    Wf = ref.replay(p, calls)
    recorded = np.asarray([last[m][1] for m in order])
    for tag, W, tie, dt in (("stated", Ws, T.TIE_STATED, design),
                            ("f32", Wf, T.TIE_F32, None)):
        acc, near = p.scores(np.stack([W[m] for m in order]), tie, dt)
        rows_off = np.abs(recorded - acc) * n_test
        over = rows_off - near
        facts[f"score_rows_off_{tag}"] = float(rows_off.max())
        facts[f"score_rows_over_near_{tag}"] = float(over.max())
        facts[f"score_rows_off_median_{tag}"] = float(np.median(rows_off))
        facts[f"score_near_median_{tag}"] = float(np.median(near))
        worst = int(np.argmax(over))
        if tag == "stated":
            chk.need(np.median(rows_off) <= T.MEDIAN_ROWS,
                     f"the models' recorded scores lie a median of "
                     f"{np.median(rows_off):.1f} rows from the stated "
                     f"replay's (band {T.MEDIAN_ROWS})")
        chk.need(bool(np.all(over <= T.SLACK_ROWS + 1e-6)),
                 f"model {order[worst]}'s recorded score {recorded[worst]} "
                 f"is {rows_off[worst]:.1f} rows from the {tag} replay's "
                 f"{acc[worst]} ({near[worst]} rows within {tie} of the "
                 f"boundary)")

    # (D) the winner
    best = int(np.nanargmax(recorded))
    chk.need(out["best_index"] == best
             and out["best_score"] == recorded[best]
             and out["best_params"] == p.params[best],
             f"best_index_ {out['best_index']} / best_score_ "
             f"{out['best_score']} are not the largest recorded score's "
             f"({best}, {recorded[best]})")
    w = np.asarray(out["w_best"], np.float32)
    if not chk.need(w.shape == (d + 1,) and np.isfinite(w).all(),
                    f"coef_ / intercept_ of shape {w.shape} or non-finite"):
        return chk
    b = out["best_index"]
    # the nearest precision below the stated one, for THIS model (both
    # products' results rounded to bfloat16): a fact beside ``stated``
    Wl = ref.replay(p, {b: calls[b]}, design_dtype=design,
                    lower="accumulate")
    lower = T.distance(Wl[b], Ws[b])
    facts.update(check_rows=n, block_rows=p.S, n_test=n_test,
                 best_calls=calls[b], best_score=out["best_score"],
                 best_alpha=float(p.params[b]["alpha"]),
                 best_eta0=float(p.params[b]["eta0"]),
                 stated=T.distance(w, Ws[b]), f32=T.distance(w, Wf[b]),
                 f32_of_stated=T.distance(Ws[b], Wf[b]), lower=lower,
                 f32_band=T.f32_band(p.S))
    chk.need(facts["stated"] <= T.TOL_STATED,
             f"the winner's weights are {facts['stated']:.3e} of ||w|| from "
             f"the replay at the stated precision (band {T.TOL_STATED:.0e})")
    chk.need(facts["f32"] <= facts["f32_band"],
             f"the winner's weights are {facts['f32']:.3e} of ||w|| from "
             f"the float32 replay (band {facts['f32_band']:.2e})")
    predicted = out["predicted"]
    m = min(int(cell.traffic["sample_rows"]), n)
    if chk.need(isinstance(predicted, np.ndarray) and predicted.shape == (n,),
                f"predict returned {type(predicted).__name__} of shape "
                f"{getattr(predicted, 'shape', None)}, not {(n,)}"):
        dec = np.asarray(ref_sgd.decision(C.device_rows(data["X"], m), w),
                         np.float64)
        ref_lab = np.asarray(cfg["fit"]["classes"])[(dec > 0).astype(int)]
        differ = predicted[:m] != ref_lab
        rms = float(np.sqrt(np.mean(dec ** 2)))
        tie = float(np.max(np.abs(dec[differ]), initial=0.0) / rms)
        facts.update(sample_rows=m, predict_worst_tie=tie,
                     predict_mismatch_share=float(differ.mean()))
        chk.need(tie <= T.TOL_TIE,
                 f"predict: a row {tie:.3e} of the decision scale from the "
                 f"boundary has the other label than the reference's")
        chk.need(facts["predict_mismatch_share"] <= T.TOL_MISMATCH_SHARE,
                 f"predict: {facts['predict_mismatch_share']:.3%} of the "
                 f"sample rows differ from the reference's labels")

    # (E) the search found something: the reference's own search
    ref_best = max(own["score"].values())
    facts.update(ref_best_score=ref_best, best_band=T.best_band(n_test),
                 cut_band=T.cut_band(n_test))
    chk.need(abs(out["best_score"] - ref_best) <= facts["best_band"],
             f"best_score_ {out['best_score']} is not within "
             f"{facts['best_band']:.2e} of the reference's own best "
             f"{ref_best}")
    compared = same = 0
    split_at = set()             # brackets where a narrow cut fell apart
    for cut in own["cuts"]:
        s, at = cut["bracket"], cut["calls"]
        if s in split_at:
            continue
        mine = sorted(m for m in cut["scores"] if (m, at) in by_rung)
        if mine != sorted(cut["scores"]):
            split_at.add(s)
            continue
        ranked = sorted(cut["scores"].values(), reverse=True)
        k = len(cut["kept"])
        margin = ranked[k - 1] - ranked[k]
        went_on = sorted(m for m in mine if calls[m] > at)
        if margin > facts["cut_band"]:
            compared += 1
            same += went_on == cut["kept"]
            chk.need(went_on == cut["kept"],
                     f"bracket {s} at {at} calls: the reference keeps "
                     f"{cut['kept']} by a margin of {margin:.2e}, the "
                     f"search kept {went_on}")
        elif went_on != cut["kept"]:
            split_at.add(s)
    facts.update(cuts=len(own["cuts"]), cuts_compared=compared,
                 cuts_same=same)
    return chk
