"""Compile-only account of the ADMM cell's programs, by hand, before chip
time:

    python benchmark/tools/admm_memory.py [workload]

AOT-compiles ``glm.admm`` (``solvers._admm_run``, the scalar-intercept form
a fit of ``LogisticRegression(solver="admm")`` runs: X as wide as its
features; once with the fused kernel carrying the local step, once with
XLA's blocked loop) and ``glm.prepare`` (the label scan alone: an ADMM fit hands it no
X) at the cell's REAL shapes for a described v5e:2x2 — no chip attached, the
`on-chip-measurement` guide's section 2 — and prints ``memory_analysis()``
of each: arguments, outputs, temporaries. ``compile_rehearsal.py`` does the
same for the cells it knows; this file is the ADMM cell's, so that tool
stays as it is. It reaches into the program's internals, so a PR that
renames them updates this tool, not the benchmark."""

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
    from dask_ml_tpu.models import glm
    from dask_ml_tpu.models.solvers import solvers as S
    from dask_ml_tpu.parallel.mesh import DATA_AXIS

    chips, d = cell.chips, int(cell.config["n_features"])
    n = int(cell.traffic["rows_per_chip"]) * chips
    p = cell.config["estimator"]["params"]
    mesh = Mesh(np.asarray(topo.devices[:chips]), (DATA_AXIS,))

    def A(shape, dt=jnp.float32, spec=P()):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    print(f"{cell.name}: {n} x {d} over {chips} chip(s)", flush=True)
    X, v = A((n, d), spec=P(DATA_AXIS, None)), A((n,), spec=P(DATA_AXIS))
    report("glm.prepare (labels only)", glm._prepare_fit.__wrapped_jit__
           .lower(None, v, v, fit_intercept=False, to_bf16=False,
                  encode=True).compile())
    w = d + 1
    for use_pallas, step in ((True, "pallas_newton_stats"),
                             (False, "xla_blocked")):
        report(f"glm.admm ({step})", S._admm_run.__wrapped_jit__.lower(
            X, v, v, n, A((w,)), A(()), A((w,)), 0.5, A(()),
            A((), jnp.int32), A(()), family="logistic",
            reg=p["penalty"], local_iter=8, mesh=mesh, log=False,
            intercept=True, use_pallas=use_pallas).compile())


def main(argv):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in argv or ["logreg_admm_l1"]:
        rehearse(harness.load_cell(name), topo)


if __name__ == "__main__":
    main(sys.argv[1:])
