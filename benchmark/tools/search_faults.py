"""Show, by hand on the machine with the chip, that the ``hyperband`` cell's
check fails what it must fail:

    python3 benchmark/tools/search_faults.py <workload> --seed <n> [--rows R]

The cell's data is placed from the seed as ``run.py`` places it, labels
number 1 drawn. The plain reference (``references/hyperband.py``) then runs
the whole search once rightly and once in each of the named wrong ways; each
run's results are handed to the cell's own check (``families/hyperband.py::
check_outputs``) in place of the program's. The right run must pass; every
wrong one must fail, and the line says by which comparison. One JSON object a
run, to standard output and to ``chiprun_out/<tag>/faults_<seed>.jsonl``.
``tolerances_search.py`` quotes the readings."""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

# (name, search's fault, search's lower)
RUNS = [("right", None, None),
        ("bottom_third_promoted", "bottom", None),
        ("scored_on_training_rows", "train_rows", None),
        ("one_call_short", "short", None),
        ("one_block_partition", "one_block", None),
        ("blocks_reversed", "reversed", None),
        ("weights_bf16", None, "update"),
        ("products_bf16", None, "accumulate")]


def outputs_of(cell, p, res, data, random_state):
    """A reference run in the shape ``families/hyperband.py::outputs`` gives
    a fitted search."""
    import numpy as np

    from benchmark.references import hyperband as ref, sgd as ref_sgd

    est = cell.config["estimator"]["params"]
    meta = ref.metadata(est["max_iter"], est["aggressiveness"])
    finals = [res["score"][m] for m in sorted(res["score"])]
    best = int(np.argmax(finals))
    w = np.asarray(res["W"][best], np.float32)
    labels = np.asarray(cell.config["fit"]["classes"])[np.asarray(
        ref_sgd.decision(data["X"].data, w) > 0).astype(int)]
    return {"metadata": meta, "metadata_before": meta,
            "params": list(p.params),
            "calls": [res["calls"][m] for m in sorted(res["calls"])],
            "history": res["history"],
            "n_rounds": cell.config["expect"]["rounds"],
            "best_index": best, "best_score": finals[best],
            "best_params": p.params[best], "w_best": w,
            "classes": list(cell.config["fit"]["classes"]),
            "predicted": labels, "random_state": random_state}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=None,
                    help="rows a chip (default: the traffic's)")
    ap.add_argument("--tag", default="faults")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args(argv)

    import jax

    import dask_ml_tpu  # noqa: F401
    from benchmark import harness
    from benchmark.families import hyperband as fam
    from benchmark.references import hyperband as ref
    from dask_ml_tpu.parallel.mesh import default_mesh, use_mesh

    cell = harness.load_cell(args.workload)
    if jax.default_backend() != "tpu" and not args.rows:
        sys.exit("search_faults: the cell's size needs the chip (--rows for "
                 "a rehearsal)")
    if args.rows:
        cell = cell.with_traffic(rows_per_chip=args.rows,
                                 sample_rows=min(args.rows, 1024))
    out_dir = os.path.join(harness.ROOT, "chiprun_out", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    mesh = default_mesh()
    with use_mesh(mesh):
        data = fam.make_data(cell.config, cell.traffic, cell.chips,
                             args.seed, mesh)
        fam.vary(cell, data, 1)
        rs = data["random_state"]
        design = cell.config["expect"]["fit_dtype"]
        cache = {"random_state": rs, "p": fam.problem(cell, data, rs)}
        for name, fault, lower in RUNS:
            if args.only and name not in args.only:
                continue
            t = time.perf_counter()
            res = ref.search(cache["p"], design_dtype=design, lower=lower,
                             fault=fault)
            chk = fam.check_outputs(
                cell, outputs_of(cell, cache["p"], res, data, rs), data,
                cache)
            keys = ("stated", "lower", "f32", "score_rows_off_stated",
                    "score_rows_over_near_stated",
                    "score_rows_off_median_stated",
                    "score_near_median_stated", "score_rows_off_f32",
                    "score_rows_over_near_f32", "best_score",
                    "ref_best_score", "cuts_compared", "cuts_same",
                    "predict_mismatch_share")
            line = {"run": name, "seed": args.seed, "random_state": rs,
                    "correct": not chk.failures,
                    "failed_by": [f[:160] for f in chk.failures][:6],
                    "n_failures": len(chk.failures),
                    **{k: chk.facts.get(k) for k in keys},
                    "seconds": round(time.perf_counter() - t, 1)}
            print(json.dumps(line), flush=True)
            with open(os.path.join(out_dir, f"faults_{args.seed}.jsonl"),
                      "a") as f:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
