"""Driver for the ``glm_admm`` family: ``LogisticRegression(solver="admm",
penalty="l1")`` on a resident table. How the cell's data is placed (Gaussian
rows from ``datagen``'s own program, labels from a SPARSE teacher with an
intercept, drawn here), what one fit and one predict are, what must have engaged, and the
comparison with the plain reference (``references/logreg_l1.py``) that
decides ``correct``; ``tolerances_l1.py`` gives every band beside its
reason. ``fit`` and ``predict`` are ``families/glm.py``'s."""

from __future__ import annotations

import functools
import inspect

import numpy as np

from benchmark import datagen, tolerances as T0, tolerances_l1 as T
from benchmark.families import _common as C
from benchmark.families.glm import fit, predict  # noqa: F401
from benchmark.references import logreg_l1 as ref


def sparse_teacher(rng, d, gen):
    """A unit-norm teacher with ``teacher_nonzero`` entries of
    ``+-1 / sqrt(nonzero)``, support and signs from ``rng``: every draw is
    the same problem up to a signed permutation of the features, under
    which the l1 penalty is invariant."""
    k = min(int(gen["teacher_nonzero"]), d)
    beta = np.zeros(d, np.float32)
    beta[rng.choice(d, k, replace=False)] = \
        rng.choice([-1.0, 1.0], k) / np.sqrt(k)
    return {"beta": beta}


@functools.cache
def _label_program():
    """Bernoulli labels for rows ``X`` from a teacher WITH an intercept
    (``datagen``'s relabel program has none): one jitted pass over X where
    it lives."""
    import jax
    import jax.numpy as jnp

    def labels(X, beta, key, scale, intercept):
        eta = jnp.dot(X, beta, precision=jax.lax.Precision.HIGHEST)
        u = jax.random.uniform(key, (X.shape[0],))
        return (u < jax.nn.sigmoid(scale * eta + intercept)
                ).astype(jnp.float32)

    return jax.jit(labels, static_argnums=(3, 4))


def _draw_labels(cfg, data, k):
    """Labels number ``k`` of the seed over the cell's rows, from sparse
    teacher ``k``: sets ``data["hp"]`` and ``data["y"]``."""
    import jax

    from dask_ml_tpu.parallel import as_sharded

    gen = cfg["data"]
    hp = sparse_teacher(
        np.random.default_rng([int(data["seed"]), 2, int(k)]), data["d"], gen)
    key = jax.random.fold_in(jax.random.PRNGKey(int(data["seed"])),
                             1_000_003 + k)
    y = _label_program()(data["X"].data, hp["beta"], key,
                         float(gen["logit_scale"]),
                         float(gen["teacher_intercept"]))
    data["hp"] = hp
    data["y"] = as_sharded(jax.block_until_ready(y), mesh=data["mesh"])


def make_data(cfg, traffic, chips, seed, mesh):
    """``_common.place``'s record: the rows from ``datagen``'s own resident
    program (the labels it draws beside them, from a teacher without an
    intercept, are dropped), then labels number 0 from this file. A program
    from before PR 36 is refused before any data is made (measured once on
    the chip, PERF.md section 6: its fit runs, five times slower under 8.8
    GB, and names nothing this family's ``engaged`` reads)."""
    from dask_ml_tpu.models.solvers import solvers as S
    from dask_ml_tpu.parallel import as_sharded

    if not hasattr(S, "ADMM_BALANCE_RATIO"):
        from benchmark.harness import BenchmarkError

        raise BenchmarkError(
            "this program's ADMM states neither its defaults nor what "
            "carried its local step (solvers.ADMM_BALANCE_RATIO, "
            "solver_info_['local_step']; it is from before PR 36, when a fit "
            "copied X into a 257-column design): the glm_admm cells cannot "
            "run on it")
    d = int(cfg["n_features"])
    n = int(traffic["rows_per_chip"]) * int(chips)
    hp = sparse_teacher(np.random.default_rng([int(seed), 0]), d, cfg["data"])
    gen = {k: cfg["data"][k] for k in ("generator", "logit_scale")}
    X, _ = datagen.make_resident(gen, n, d, seed, mesh, hp)
    data = {"n_rows": n, "d": d, "x_bytes": 4 * n * d, "seed": seed,
            "chips": int(chips), "mesh": mesh, "X": as_sharded(X, mesh=mesh)}
    _draw_labels(cfg, data, 0)
    return data


def vary(cell, data, k):
    """``vary_per_cycle: labels`` — fit ``k`` sees labels drawn from sparse
    teacher ``k`` of the seed over the same rows. Not timed."""
    if cell.traffic.get("vary_per_cycle") == "labels":
        _draw_labels(cell.config, data, k)


_REHEARSAL_KWARGS = {"use_pallas": True, "pallas_interpret": True}


def make_estimator(cell, data, interpret):
    """The stated parameters and ``C`` from the stated ``lam`` and the rows
    actually placed, so that the CPU rehearsal at 2,048 rows fits the same
    ``lam``. On the chip nothing else is added. In the CPU rehearsal the
    choice the TPU's auto-gate makes is REQUESTED: the fused kernel for the
    local step, in interpret mode."""
    lam = float(cell.config["penalty"]["lam"])
    extra = {"solver_kwargs": dict(_REHEARSAL_KWARGS)} if interpret else {}
    return C.new_estimator(cell.config, C=1.0 / (lam * data["n_rows"]),
                           **extra)


def fit_facts(est):
    info = est.solver_info_
    return {"n_iter": int(est.n_iter_),
            **{k: info[k] for k in ("local_steps", "primal_residual",
                                    "dual_residual", "rho", "nnz")
               if k in info}}


def _stated_defaults(cfg):
    """Failures where the program's ADMM defaults are not the stated ones."""
    from dask_ml_tpu.models.solvers import solvers as S

    stated = cfg["solver_kwargs_defaults"]
    sig = inspect.signature(S.admm).parameters
    out = []
    for k in ("rho", "local_iter"):
        have = sig[k].default if k in sig else "<no such parameter>"
        if have != stated[k]:
            out.append(f"solvers.admm's default {k} is {have!r}, the "
                       f"configuration states {stated[k]!r}")
    for k, name in (("balance_ratio", "ADMM_BALANCE_RATIO"),
                    ("balance_factor", "ADMM_BALANCE_FACTOR")):
        if getattr(S, name, None) != stated[k]:
            out.append(f"solvers.{name} is {getattr(S, name, None)!r}, the "
                       f"configuration states {stated[k]!r}")
    return out


def engaged(cell, est, data, programs=None):
    """What must have carried the fit; a fallback is a failure."""
    chk = C.Check()
    info = dict(est.solver_info_)
    want = cell.config["expect"]
    p = cell.config["estimator"]["params"]
    for k in ("solver", "penalty", "tol", "max_iter", "warm_start"):
        chk.need(getattr(est, k) == p[k],
                 f"the estimator's {k} is {getattr(est, k)!r}, not {p[k]!r}")
    chk.need(not est.solver_kwargs or est.solver_kwargs == _REHEARSAL_KWARGS,
             f"solver_kwargs {est.solver_kwargs!r}: the cell runs the "
             f"program's defaults")
    for msg in _stated_defaults(cell.config):
        chk.need(False, msg)
    chk.need(getattr(est, "fit_dtype_", None) == want["fit_dtype"],
             f"fit_dtype_ is {getattr(est, 'fit_dtype_', None)!r}, "
             f"not {want['fit_dtype']!r}")
    for k in ("intercept", "local_step"):
        chk.need(info.get(k) == want[k],
                 f"solver_info_[{k!r}] is {info.get(k)!r}, not {want[k]!r}")
    chk.need("primal_residual" in info and "local_steps" in info,
             f"solver_info_ does not name ADMM's counters: {info}")
    on = len(data["X"].data.sharding.device_set)
    chk.need(on == data["chips"], f"X lives on {on} of {data['chips']} chips")
    if programs is not None:
        chk.need(programs.get(want["program"]) == 1,
                 f"{want['program']} ran {programs.get(want['program'])} "
                 f"times in the fit, not once: {programs}")
    chk.facts.update(local_step=info.get("local_step"))
    return chk


def outputs(est, predicted):
    """What the check reads of a fitted estimator, as plain values (the
    faults tool hands in a reference run in the same shape)."""
    info = est.solver_info_
    return {"coef": np.asarray(est.coef_, np.float32).ravel(),
            "intercept": np.float32(np.ravel(est.intercept_)[0]),
            "n_iter": int(est.n_iter_),
            "primal_residual": info.get("primal_residual"),
            "dual_residual": info.get("dual_residual"),
            "local_steps": info.get("local_steps"),
            "predicted": predicted}


def check(cell, est, data, predicted):
    return check_outputs(cell, outputs(est, predicted), data)


def check_outputs(cell, out, data):
    """``out`` (:func:`outputs`) against the reference over ALL the cell's
    rows; every band is ``tolerances_l1.py``'s."""
    chk = C.Check()
    facts = chk.facts
    p = cell.config["estimator"]["params"]
    tol = float(p["tol"])
    lam = float(cell.config["penalty"]["lam"])
    n, d = data["n_rows"], data["d"]
    m = min(int(cell.traffic["sample_rows"]), n)
    coef, b0 = out["coef"], out["intercept"]

    facts.update(n_iter=out["n_iter"], local_steps=out["local_steps"],
                 primal_residual=out["primal_residual"],
                 dual_residual=out["dual_residual"])
    chk.need(out["n_iter"] < p["max_iter"],
             f"not converged: n_iter_ {out['n_iter']} hit max_iter")
    chk.need(out["primal_residual"] is not None
             and out["primal_residual"] <= tol
             and out["dual_residual"] <= tol,
             f"stopped with primal {out['primal_residual']} / dual "
             f"{out['dual_residual']} above tol {tol}")
    ceiling = int(cell.config["solver_kwargs_defaults"]["local_iter"])
    chk.need(out["local_steps"] is not None
             and out["n_iter"] < out["local_steps"] <= ceiling * out["n_iter"],
             f"{out['local_steps']} local Newton steps in {out['n_iter']} "
             f"outer iterations: not a count the stated local rule gives "
             f"(a cold first solve takes more than one step, none more "
             f"than {ceiling})")
    if not chk.need(np.isfinite(coef).all() and np.isfinite(b0),
                    "non-finite coef_"):
        return chk

    if cell.traffic["check_rows"] != "all":
        chk.need(False, "the l1 check reads ALL rows: check_rows must be "
                        "'all'")
    Xc, yc = C.device_rows(data["X"]), C.device_rows(data["y"])

    # stationarity of the penalised problem at (coef_, intercept_)
    g, gb = ref.gradient(coef, b0, Xc, yc)
    kkt = ref.kkt_from_gradient(coef, g, gb, lam)
    band = T.KKT_BAND
    facts.update(check_rows=int(Xc.shape[0]), kkt_max=float(kkt.max()),
                 kkt_band=band, kkt_argmax=int(kkt.argmax()))
    chk.need(facts["kkt_max"] <= band,
             f"reference KKT residual at coef_ has an entry "
             f"{facts['kkt_max']:.3e} > band {band:.3e}")

    # the reference's own optimum over the same rows, from coef_
    c_opt, b_opt, info = ref.optimum(Xc, yc, lam, coef, b0)
    facts.update(optimum_iters=info["n_iter"], optimum_kkt=info["kkt"])
    chk.need(info["kkt"] <= 0.05 * band,
             f"the reference's own optimum stopped at a KKT residual "
             f"{info['kkt']:.3e}, not far below the band")
    at_fit = ref.objective(coef, b0, Xc, yc, lam)
    at_opt = ref.objective(c_opt, b_opt, Xc, yc, lam)
    at_zero = ref.objective(np.zeros_like(coef), 0.0, Xc, yc, lam)
    ex_band = T.EXCESS_BAND
    facts.update(objective_at_zero=at_zero, objective_at_fit=at_fit,
                 excess_over_optimum=at_fit - at_opt, excess_band=ex_band,
                 coef_dist_max=float(np.max(np.abs(coef - c_opt))))
    chk.need(at_fit < at_zero, f"the objective did not fall: {at_zero} -> "
                               f"{at_fit}")
    chk.need(abs(at_fit - at_opt) <= ex_band,
             f"reference objective at coef_ {at_fit} vs its optimum "
             f"{at_opt}: excess {at_fit - at_opt:.3e} outside "
             f"+-{ex_band:.3e}")

    # the support
    g_opt, _ = ref.gradient(c_opt, b_opt, Xc, yc)
    near = np.where(c_opt == 0, np.abs(g_opt) >= lam - band,
                    np.abs(c_opt) <= band / T.SUPPORT_MU)
    differ = (coef != 0) != (c_opt != 0)
    facts.update(nnz=int(np.count_nonzero(coef)),
                 nnz_optimum=int(np.count_nonzero(c_opt)),
                 support_near=int(near.sum()),
                 support_mismatch=int((differ & ~near).sum()),
                 teacher_support_found=bool(np.array_equal(
                     coef != 0, data["hp"]["beta"] != 0)))
    chk.need(facts["nnz"] < d, "coef_ holds no exact zero")
    chk.need(facts["support_mismatch"] == 0,
             f"{facts['support_mismatch']} coefficients are zero on one side "
             f"and not on the other, away from the threshold")

    # predict_proba on the sample rows
    pred = out["predicted"]
    ok = chk.need(isinstance(pred, np.ndarray) and pred.shape == (n, 2),
                  f"predict_proba returned shape "
                  f"{getattr(pred, 'shape', None)}, not {(n, 2)}")
    if ok:
        Xs, ys = C.device_rows(data["X"], m), C.device_rows(data["y"], m)
        ref_p = np.asarray(ref.proba(coef, b0, Xs))
        err = float(np.max(np.abs(pred[:m, 1] - ref_p)))
        facts.update(sample_rows=m, proba_max_err=err)
        chk.need(np.isfinite(pred[:m]).all()
                 and bool(np.all(np.abs(pred[:m].sum(axis=1) - 1.0) <= 1e-6)),
                 "probabilities are not finite rows summing to 1")
        chk.need(err <= T0.TOL_PROBA, f"predict_proba off by {err:.3e}")
        facts["accuracy_on_sample"] = float(
            np.mean((pred[:m, 1] > 0.5) == (np.asarray(ys) > 0.5)))
        chk.need(facts["accuracy_on_sample"] > 0.6,
                 f"accuracy {facts['accuracy_on_sample']} is chance level")
    return chk
