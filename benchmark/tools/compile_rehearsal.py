"""Compile-only rehearsal, by hand, before chip time:

    python benchmark/tools/compile_rehearsal.py [workload ...]

AOT-compiles each cell's main programs at the cell's REAL shapes for
a described v5e:2x2 (no chip attached; the `on-chip-measurement` guide,
section 2) and prints ``memory_analysis()`` per program: what the chip's
compiler refuses, it refuses here, at no chip time. It counts one program at
a time, not what else the process keeps on the device, and not the eager
(un-jitted) operations of a fit. It reaches into the program's internals
(``_lbfgs_chunk``, ``_lloyd_run_pallas``), so a later PR that renames them
must update this tool, not the benchmark.
"""

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

from benchmark import datagen, harness  # noqa: E402

GiB = 2.0 ** 30


def report(name, compiled):
    m = compiled.memory_analysis()
    arg, out, tmp = (m.argument_size_in_bytes, m.output_size_in_bytes,
                     m.temp_size_in_bytes)
    alias = getattr(m, "alias_size_in_bytes", 0)
    print(f"  {name:<28} args {arg / GiB:6.2f}  out {out / GiB:6.2f}  "
          f"temp {tmp / GiB:6.2f}  alias {alias / GiB:5.2f}  "
          f"live {(arg + out + tmp - alias) / GiB:6.2f} GiB a chip",
          flush=True)


def rehearse(cell, topo):
    from dask_ml_tpu.parallel.mesh import DATA_AXIS

    chips, d = cell.chips, int(cell.config["n_features"])
    n = int(cell.traffic["rows_per_chip"]) * chips
    mesh = Mesh(np.asarray(topo.devices[:chips]), (DATA_AXIS,))
    rows = lambda nd: NamedSharding(  # noqa: E731
        mesh, P(*((DATA_AXIS,) + (None,) * (nd - 1))))
    repl = NamedSharding(mesh, P())

    def A(shape, dt, sh=repl):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    print(f"{cell.name}: {n} x {d} over {chips} chip(s)", flush=True)
    hp = datagen.host_params(cell.config["data"], d, 0)
    gen, _ = datagen.resident_program(cell.config["data"], n, d, mesh, hp)
    report("datagen", gen.lower(
        A((2,), jnp.uint32),
        {k: A(v.shape, v.dtype) for k, v in hp.items()}).compile())
    X = A((n, d), jnp.float32, rows(2))
    v = lambda dt=jnp.float32: A((n,), dt, rows(1))  # noqa: E731
    s = lambda dt=jnp.float32: A((), dt)  # noqa: E731
    fam = cell.config["family"]
    if fam == "glm":
        import optax

        from dask_ml_tpu.models import glm
        from dask_ml_tpu.models.solvers import solvers as S

        report("_prepare_fit", glm._prepare_fit.lower(
            X, v(), v(), fit_intercept=True, to_bf16=True,
            encode=True).compile())
        w = d + 1
        state = jax.eval_shape(optax.lbfgs(memory_size=10).init,
                               jnp.zeros((w,), jnp.float32))
        state = jax.tree.map(lambda a: A(a.shape, a.dtype), state)
        carry = (A((w,), jnp.float32), state, s(), s(jnp.int32))
        report("glm.lbfgs", S._lbfgs_chunk.__wrapped_jit__.lower(
            A((n, w), jnp.bfloat16, rows(2)), v(), v(), n, carry, s(),
            A((w,), jnp.float32), 0.5, s(jnp.int32), s(),
            family="logistic", reg="l2", memory=10, log=False,
            use_pallas=True, mesh=mesh, interpret=False).compile())
        report("_matvec_eta (predict)", glm._matvec_eta.lower(
            X, A((d,), jnp.float32), s()).compile())
    elif fam == "kmeans":
        from dask_ml_tpu.models import kmeans as KM

        k = int(cell.config["estimator"]["params"]["n_clusters"])
        c = A((k, d), jnp.float32)
        report("kmeans.lloyd_pallas", KM._lloyd_run_pallas.__wrapped_jit__
               .lower(X, v(), c, s(jnp.int32), s(), mesh=mesh,
                      interpret=False, log=False).compile())
        report("kmeans.labels_inertia", KM._labels_inertia.__wrapped_jit__
               .lower(X, v(), c).compile())
        print("  (KMeans.fit also runs masked_mean_var EAGERLY: "
              f"x - mean, * mask, squared — up to three X-sized buffers, "
              f"{3 * 4 * n * d / chips / GiB:.2f} GiB a chip)")


def main(argv):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = argv or [w["name"] for w in harness.load_json(
        harness.ROOT, "BENCHMARK.json")["workloads"]]
    for name in names:
        rehearse(harness.load_cell(name), topo)


if __name__ == "__main__":
    main(sys.argv[1:])
