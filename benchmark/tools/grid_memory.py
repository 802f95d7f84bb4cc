"""Compile-only account of the gridsearch cell's programs, by hand, before
chip time:

    python benchmark/tools/grid_memory.py [workload]

AOT-compiles what one search dispatches — ``search.fold_ids`` (the
contiguous fold ids), ``glm.prepare`` (the bf16 design and the label scan,
no ones column), ``glm.lbfgs_lam_grid`` (every (fold, C) model in one
stacked L-BFGS program, the intercept each block's last entry) and
``glm.grid_score`` (every model's hits on its test fold over the f32 X) —
at the cell's REAL shapes for a described v5e:2x2 (no chip attached: the
topology is described through the ``TPU_*`` variables set below) and prints ``memory_analysis()`` of
each: arguments, outputs, temporaries. The refit is ``logreg_resident``'s
``glm.prepare`` and ``glm.lbfgs``, which ``compile_rehearsal.py`` covers.
It reaches into the program's internals, so a PR that renames them updates
this tool, not the benchmark."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from benchmark import harness  # noqa: E402
from benchmark.tools.compile_rehearsal import report  # noqa: E402


def rehearse(cell, topo):
    from dask_ml_tpu.model_selection import _search
    from dask_ml_tpu.models import glm
    from dask_ml_tpu.models.solvers import solvers as S
    from dask_ml_tpu.parallel.mesh import DATA_AXIS

    chips, d = cell.chips, int(cell.config["n_features"])
    n = int(cell.traffic["rows_per_chip"]) * chips
    exp = cell.config["expect"]
    K, F = int(exp["n_candidates"]), int(exp["n_folds"])
    m, w = K * F, d + 1
    p = cell.config["estimator"]["inner"]["params"]
    mesh = Mesh(np.asarray(topo.devices[:chips]), (DATA_AXIS,))

    def A(shape, dt=jnp.float32, spec=P()):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    print(f"{cell.name}: {n} x {d} over {chips} chip(s), {m} models",
          flush=True)
    X, v = A((n, d), spec=P(DATA_AXIS, None)), A((n,), spec=P(DATA_AXIS))
    ids = A((n,), jnp.int32, P(DATA_AXIS))
    report("search.fold_ids", _search._contiguous_fold_ids.__wrapped_jit__
           .lower(n, A((F - 1,), jnp.int32),
                  NamedSharding(mesh, P(DATA_AXIS))).compile())
    report("glm.prepare", glm._prepare_fit.__wrapped_jit__.lower(
        X, v, v, fit_intercept=False, to_bf16=True, encode=True).compile())
    Xb = A((n, d), jnp.bfloat16, P(DATA_AXIS, None))
    report("glm.lbfgs_lam_grid", S._lam_grid_chunk.__wrapped_jit__.lower(
        Xb, v, v, ids, A((F,)), (A((m * w,)),), A((m,)), A((w,)),
        A((), jnp.int32), A(()), family="logistic", reg=p.get("penalty",
                                                              "l2"),
        k=K, n_folds=F, intercept=True).compile())
    report("glm.grid_score", glm._grid_hits.__wrapped_jit__.lower(
        X, v, v, ids, A((m, w)), n_folds=F).compile())


def main(argv):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in argv or ["gridsearch_logreg"]:
        rehearse(harness.load_cell(name), topo)


if __name__ == "__main__":
    main(sys.argv[1:])
