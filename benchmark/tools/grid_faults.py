"""The gridsearch cell's check against a search run rightly and against the
same search's outputs made wrong, one fault at a time, by hand:

    python benchmark/tools/grid_faults.py [--seed n [n ...]] [--rows r]

One NEW search of the cell (``families/gridsearch.py``: labels number 1 of
the seed, the configuration's estimator), then ``check`` of it as it is and
of five faults: ``bf16_scores`` — every recorded test score recomputed from
the search's own models with eta at the nearest precision below the
program's (ONE bfloat16 pass of the product, f32 accumulation): the control
the near-tie band ``tolerances_grid.NEAR_TIE`` is set against;
``fp8_coef`` — every model's coefficients one precision rung below the
bf16 beta the stacked loss multiplies (float8_e4m3fn, 3 mantissa bits), the
rung ``tolerances.py`` names for the gradient and excess bands;
``loose_tol`` — a NEW search whose estimator stops at ten times the stated
``tol``, the stop ``logreg_excess_band`` names; ``wrong_folds`` — each
fold's models moved to the next fold; ``train_rows`` — each model scored on
its training rows. Prints each one's failures and, as one JSON object a
line, the facts that decide them (``near_band_needed_max``: the least
near-tie band that would let every score through). On the chip it runs at
the cell's rows; ``--rows 16384`` rehearses it on the CPU."""

import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from benchmark import harness  # noqa: E402
from benchmark.families import gridsearch as fam  # noqa: E402

FACTS = ("score_rows_off_max", "score_rows_over_near_max",
         "near_tie_rows_max", "near_band_needed_max", "grad_over_band_max",
         "excess_max", "best_index", "ref_best_index")


def bf16_scores(est, data):
    """``cv_results_``'s test scores of ``est``'s own models with eta from
    one bfloat16 pass of the product (f32 accumulation, the intercept in
    f32): {split key: (K,) scores}."""
    import jax
    import jax.numpy as jnp

    betas = est.search_info_["betas"]
    F = betas.shape[1]
    starts, stops = fam.ref.folds(data["n_rows"], F)
    X, y = data["X"].data, data["y"].data

    @jax.jit
    def hits(Xf, yf, W, b):
        eta = jnp.dot(Xf.astype(jnp.bfloat16), W.astype(jnp.bfloat16).T,
                      preferred_element_type=jnp.float32) + b[None, :]
        return jnp.sum((eta > 0) == (yf[:, None] > 0.5), axis=0)

    out = {}
    for f in range(F):
        lo, hi = int(starts[f]), int(stops[f])
        h = hits(X[lo:hi], y[lo:hi], jnp.asarray(betas[:, f, :-1],
                                                 jnp.float32),
                 jnp.asarray(betas[:, f, -1], jnp.float32))
        out[f"split{f}_test_score"] = np.asarray(h, np.float64) / (hi - lo)
    return out


def faults(est, data):
    """{name: a copy of the fitted search made wrong that way}."""
    def faulty(**info):
        bad = copy.copy(est)
        bad.search_info_ = {**est.search_info_, **info}
        bad.cv_results_ = dict(est.cv_results_)
        return bad

    import ml_dtypes

    from dask_ml_tpu.base import clone

    out = {"bf16_scores": faulty()}
    out["bf16_scores"].cv_results_.update(bf16_scores(est, data))
    betas = np.asarray(est.search_info_["betas"], np.float32)
    out["fp8_coef"] = faulty(betas=betas.astype(
        ml_dtypes.float8_e4m3fn).astype(np.float64))
    loose = clone(est.estimator).set_params(tol=10 * est.estimator.tol)
    out["loose_tol"] = type(est)(loose, est.param_grid).fit(data["X"],
                                                            data["y"])
    out["wrong_folds"] = faulty(
        betas=np.roll(est.search_info_["betas"], 1, axis=1))
    train = type(est)(est.estimator, est.param_grid,
                      return_train_score=True).fit(data["X"], data["y"])
    out["train_rows"] = faulty()
    for f in range(est.search_info_["betas"].shape[1]):
        out["train_rows"].cv_results_[f"split{f}_test_score"] = \
            train.cv_results_[f"split{f}_train_score"]
    return out


def faults_of_seed(cell, mesh, seed):
    """The search of ``seed``'s labels number 1 and its faults, each
    checked and printed."""
    data = fam.make_data(cell.config, cell.traffic, 1, seed, mesh)
    fam.vary(cell, data, 1)
    est = fam.make_estimator(cell, data, False)
    fam.fit(est, data)
    predicted = fam.predict(est, data)
    runs = {"right": est, **faults(est, data)}
    for name, e in runs.items():
        chk = fam.check(cell, e, data, fam.predict(e, data)
                        if name == "loose_tol" else predicted)
        print(f"{name}: {len(chk.failures)} failures", flush=True)
        for msg in chk.failures[:4]:
            print(f"   {msg[:300]}", flush=True)
        print(json.dumps({"run": name, "seed": seed, "rows": data["n_rows"],
                          "correct": not chk.failures,
                          **{k: chk.facts.get(k) for k in FACTS}}),
              flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, nargs="+", default=[1234567891])
    ap.add_argument("--rows", type=int, default=None)
    args = ap.parse_args(argv)
    from dask_ml_tpu.parallel.mesh import default_mesh, use_mesh

    cell = harness.load_cell("gridsearch_logreg")
    if args.rows:
        cell = cell.with_traffic(rows_per_chip=args.rows,
                                 sample_rows=min(args.rows // 4, 65536))
    mesh = default_mesh()
    with use_mesh(mesh):
        for seed in args.seed:
            faults_of_seed(cell, mesh, seed)


if __name__ == "__main__":
    main()
