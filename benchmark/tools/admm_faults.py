"""Show, by hand on the machine with the chip, that the ``glm_admm`` cell's
check fails what it must fail:

    python3 benchmark/tools/admm_faults.py <workload> --seed <n> [--rows R]

The cell's data is placed from the seed as ``run.py`` places it, labels
number 1 drawn. The plain reference's consensus ADMM (``references/
logreg_l1.py::admm``, over ``--blocks`` blocks of the rows: four, so that the
``1 / N`` of the threshold is there to be left out) then runs once rightly
and once in each of its named wrong ways; each run's result is handed to the
cell's own check (``families/glm_admm.py::check_outputs``) in place of the
program's, with the reference's own float32 ``predict_proba`` of it, as the
check computes it for the program: a run fails by what the check reads of the
FIT. The right run must pass. Every run stops at ``STOP_SHARE`` of the
configuration's ``tol``: the check's limits are set from the one-block cell's
readings, whose stop the primal residual decides with the dual one at a tenth
of ``tol``; four blocks stopped at the stated ``tol`` stop on the DUAL
residual and read a KKT entry of 4.7e-5 (my chip run, PR 36), inside the stop
rule's ceiling and outside the cell's limit. A wrong fixed point does not
move with the stop. One JSON object a run, to standard output and to
``chiprun_out/<tag>/admm_faults_<seed>.jsonl``; ``tolerances_l1.py`` quotes
the readings. ``one_local_step`` is run to the RIGHT run's outer count."""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


# the share of the configuration's tol at which the reference's runs stop
STOP_SHARE = 0.1


def reference_proba(coef, intercept, X):
    """The reference's ``(n, 2)`` float32 ``predict_proba`` at a point."""
    import numpy as np

    from benchmark.references import logreg_l1 as ref

    p1 = np.asarray(ref.proba(coef, intercept, X))
    return np.stack([1.0 - p1, p1], axis=1)


def reference_outputs(cell, data, fault, blocks, max_iter=None):
    """One reference ADMM run in the shape ``families/glm_admm.py::outputs``
    gives a fitted estimator."""
    from benchmark.references import logreg_l1 as ref

    p = cell.config["estimator"]["params"]
    X, y = data["X"].data, data["y"].data
    res = ref.admm(X, y, float(cell.config["penalty"]["lam"]), blocks,
                   rho=float(cell.config["solver_kwargs_defaults"]["rho"]),
                   tol=STOP_SHARE * float(p["tol"]),
                   max_iter=int(max_iter or p["max_iter"]), fault=fault)
    return {"coef": res["coef"], "intercept": res["intercept"],
            "n_iter": res["n_iter"],
            "primal_residual": res["primal_residual"],
            "dual_residual": res["dual_residual"],
            "local_steps": res["local_steps"],
            "predicted": reference_proba(res["coef"], res["intercept"], X)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=None,
                    help="rows a chip (default: the traffic's)")
    ap.add_argument("--blocks", type=int, default=4)
    ap.add_argument("--tag", default="faults")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args(argv)

    import jax

    import dask_ml_tpu  # noqa: F401
    from benchmark import harness
    from benchmark.families import glm_admm as fam
    from benchmark.references import logreg_l1 as ref
    from dask_ml_tpu.parallel.mesh import default_mesh, use_mesh

    cell = harness.load_cell(args.workload)
    if jax.default_backend() != "tpu" and not args.rows:
        sys.exit("admm_faults: the cell's size needs the chip (--rows for a "
                 "rehearsal)")
    if args.rows:
        cell = cell.with_traffic(rows_per_chip=args.rows,
                                 sample_rows=min(args.rows, 1024))
    out_dir = os.path.join(harness.ROOT, "chiprun_out", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    mesh = default_mesh()
    keys = ("n_iter", "local_steps", "primal_residual", "dual_residual",
            "kkt_max", "kkt_band", "excess_over_optimum", "excess_band",
            "coef_dist_max", "nnz", "nnz_optimum", "support_near",
            "support_mismatch", "proba_max_err")
    with use_mesh(mesh):
        data = fam.make_data(cell.config, cell.traffic, cell.chips,
                             args.seed, mesh)
        fam.vary(cell, data, 1)
        right_iters = None
        for name in ("right", *ref.FAULTS):
            if args.only and name not in args.only:
                continue
            t = time.perf_counter()
            out = reference_outputs(
                cell, data, None if name == "right" else name, args.blocks,
                max_iter=right_iters if name == "one_local_step" else None)
            if name == "right":
                right_iters = out["n_iter"]
            chk = fam.check_outputs(cell, out, data)
            line = {"run": name, "seed": args.seed, "blocks": args.blocks,
                    "correct": not chk.failures,
                    "failed_by": [f[:160] for f in chk.failures][:6],
                    **{k: chk.facts.get(k) for k in keys},
                    "seconds": round(time.perf_counter() - t, 1)}
            print(json.dumps(line), flush=True)
            with open(os.path.join(out_dir,
                                   f"admm_faults_{args.seed}.jsonl"),
                      "a") as f:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
