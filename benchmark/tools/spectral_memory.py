"""Compile-only account of the spectral cell's programs, by hand, before chip
time:

    python benchmark/tools/spectral_memory.py [workload]

AOT-compiles ``spectral.embed`` (``models/spectral.py::_embed``: the
landmark draw, both affinities, the degrees, G, the TSQR, the small SVD, the
row scaling) and the programs the ``n_init`` KMeans restarts run over its
(n, k) output (the k-means|| cost and weight passes, the weighted draw, the
tol scale, the Lloyd loop in both flavours, the labels pass) at the cell's
REAL shapes for a described v5e:2x2 — no chip attached, the
`on-chip-measurement` guide's section 2 — and prints ``memory_analysis()``
of each: arguments, outputs, temporaries, and for the embedding the
temporaries in (n, c) float32 panels, dense and lane-padded. What the chip's
compiler refuses (the fused Lloyd kernel at an 8-wide table, say), it
refuses here. ``compile_rehearsal.py`` does the same for the cells it knows;
this file is the spectral cell's, so that tool stays as it is. It reaches
into the program's internals, so a PR that renames them updates this tool,
not the benchmark."""

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
from benchmark.tools.compile_rehearsal import GiB, report  # noqa: E402


def _try(name, lower):
    try:
        compiled = lower().compile()
    except Exception as e:                       # what the compiler refuses
        print(f"  {name:<28} REFUSED: {type(e).__name__}: "
              f"{str(e).splitlines()[0][:160]}", flush=True)
        return None
    report(name, compiled)
    return compiled


def rehearse(cell, topo):
    from dask_ml_tpu.models import kmeans as KM, spectral as SP
    from dask_ml_tpu.parallel.mesh import DATA_AXIS

    chips, d = cell.chips, int(cell.config["n_features"])
    n = int(cell.traffic["rows_per_chip"]) * chips
    p = cell.config["estimator"]["params"]
    c, k = int(p["n_components"]), int(p["n_clusters"])
    mesh = Mesh(np.asarray(topo.devices[:chips]), (DATA_AXIS,))

    def A(shape, dt=jnp.float32, spec=P()):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    print(f"{cell.name}: {n} x {d} over {chips} chip(s), {c} landmarks, "
          f"{k} clusters", flush=True)
    X, v = A((n, d), spec=P(DATA_AXIS, None)), A((n,), spec=P(DATA_AXIS))
    got = _try("spectral.embed", lambda: SP._embed.__wrapped_jit__.lower(
        X, v, A((), jnp.uint32), c=c, k=k, mesh=mesh, affinity=p["affinity"],
        gamma=float(p["gamma"]), degree=3, coef0=1, kernel_params=None))
    if got is not None:
        tmp = got.memory_analysis().temp_size_in_bytes
        dense, padded = 4 * n * c / chips, 4 * n * 128 * -(-c // 128) / chips
        print(f"  its temporaries are {tmp / dense:.2f} dense / "
              f"{tmp / padded:.2f} lane-padded (n, {c}) float32 panels of "
              f"{dense / GiB:.2f} / {padded / GiB:.2f} GiB", flush=True)
    E = A((n, k), spec=P(DATA_AXIS, None))
    l = int(cell.config["kmeans_defaults"]["oversampling_factor"] * k)
    cands = A((1 + 5 * l, k))
    _try("kmeans || cost", lambda: KM._cost_to_candidates.lower(
        E, v, cands, A((1 + 5 * l,))))
    _try("kmeans || draw (top_k)", lambda: KM._gumbel_top_l.lower(
        v, A((2,), jnp.uint32), l=l))
    _try("kmeans || weights", lambda: KM._candidate_weights.lower(
        E, v, cands, A((1 + 5 * l,))))
    _try("kmeans.tol_scale", lambda: KM._tol_scale.__wrapped_jit__.lower(
        E, v, A(()), A(())))
    cen = A((k, k))
    _try("kmeans.lloyd_pallas", lambda: KM._lloyd_run_pallas.__wrapped_jit__
         .lower(E, v, cen, A((), jnp.int32), A(()), mesh=mesh,
                interpret=False, log=False))
    _try("kmeans.lloyd (XLA)", lambda: KM._lloyd_run.__wrapped_jit__.lower(
        E, v, cen, A((), jnp.int32), A(()), log=False, mxu_dtype=None))
    _try("kmeans.labels_inertia", lambda: KM._labels_inertia
         .__wrapped_jit__.lower(E, v, cen))


def main(argv):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in argv or ["spectral_nystrom"]:
        rehearse(harness.load_cell(name), topo)


if __name__ == "__main__":
    main(sys.argv[1:])
