"""Device-mesh management — the substrate every estimator runs on.

In the reference (dask-ml), data lives as row-chunked ``dask.array`` blocks
scheduled over workers connected by TCP (``distributed/comm``); here the
equivalent substrate is a ``jax.sharding.Mesh`` over TPU chips, with XLA
collectives over ICI replacing the comm layer entirely (SURVEY.md §5,
"Distributed communication backend").

The default mesh is 1-D over all visible devices with axis name ``"data"``
(pure data-parallel — the reference's row-chunking model, SURVEY.md §2c).
A 2-D ``("data", "model")`` mesh is supported for wide-feature problems
where sharding the feature axis pays (the reference's nearest analog is
dask.array 2-D blockwise matmul).
"""

from __future__ import annotations

import contextlib
import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"

_state = threading.local()


def device_mesh(shape=None, axis_names=(DATA_AXIS,), devices=None,
                topology_order=None) -> Mesh:
    """Build a mesh over ``devices`` (default: all of ``jax.devices()``).

    ``shape=None`` gives a 1-D mesh over every device. ``shape`` may use -1
    for one axis (inferred), e.g. ``device_mesh((-1, 2), ("data", "model"))``.

    On TPU the device order is TOPOLOGY-AWARE (``mesh_utils``): mesh
    neighbors are ICI neighbors, and on multi-host runs the slow DCN hop
    is the OUTER factor of the data axis — collectives then ride ICI
    rings within a host/slice and cross DCN once, instead of ping-ponging
    over DCN in enumeration order. CPU/GPU keep plain enumeration order.

    ``topology_order`` — None (default): reorder only when ``devices`` is
    omitted (explicit lists keep the caller's order, e.g. disjoint search
    submeshes); True: force reordering even for an explicit full-device
    list (``global_mesh``/``local_mesh`` pass this); False: never.
    """
    explicit = devices is not None
    if devices is None:
        devices = jax.devices()
    devices = np.asarray(devices, dtype=object)
    n = devices.size
    if shape is None:
        shape = (n,)
    shape = tuple(shape)
    if len(shape) != len(axis_names):
        raise ValueError(f"shape {shape} does not match axis_names {axis_names}")
    if -1 in shape:
        known = int(np.prod([s for s in shape if s != -1]))
        if n % known:
            raise ValueError(f"cannot infer -1 in {shape} from {n} devices")
        shape = tuple(n // known if s == -1 else s for s in shape)
    if int(np.prod(shape)) != n:
        raise ValueError(f"mesh shape {shape} needs {int(np.prod(shape))} devices, have {n}")
    if topology_order is None:
        topology_order = not explicit
    if topology_order and devices.flat[0].platform == "tpu":
        return Mesh(_topology_mesh(shape, list(devices.flat)), axis_names)
    return Mesh(devices.reshape(shape), axis_names)


def _topology_mesh(shape, devices):
    """TPU device array in torus-aware order. A shape the topology
    helpers refuse raises — a mesh silently built in enumeration order
    would put ICI non-neighbours next to each other and only show up as
    slow collectives."""
    from jax.experimental import mesh_utils

    n_procs = len({d.process_index for d in devices})
    if n_procs > 1 and len(devices) % n_procs == 0:
        if shape[0] % n_procs == 0:
            # DCN outer on the (leading) data axis, ICI inner
            ici = (shape[0] // n_procs,) + tuple(shape[1:])
            dcn = (n_procs,) + (1,) * (len(shape) - 1)
            # granule = process (we factor by process count), not the
            # default slice granule — a multi-host single slice would
            # otherwise mismatch dcn and raise
            return mesh_utils.create_hybrid_device_mesh(
                ici, dcn, devices=devices, process_is_granule=True
            )
    return mesh_utils.create_device_mesh(shape, devices=devices)


def default_mesh() -> Mesh:
    """The ambient mesh: the one set by :func:`use_mesh`, else a cached 1-D
    data mesh over all devices."""
    mesh = getattr(_state, "mesh", None)
    if mesh is not None:
        return mesh
    cached = getattr(_state, "cached_default", None)
    if cached is None or cached.devices.size != len(jax.devices()):
        cached = device_mesh()
        _state.cached_default = cached
    return cached


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Context manager: make ``mesh`` the ambient mesh for estimators that
    don't receive one explicitly."""
    prev = getattr(_state, "mesh", None)
    _state.mesh = mesh
    try:
        yield mesh
    finally:
        _state.mesh = prev


def resolve_mesh(mesh=None) -> Mesh:
    return mesh if mesh is not None else default_mesh()


def data_shard_spec(a, lead: int = 0) -> P:
    """PartitionSpec sharding axis ``lead`` of ``a`` over the "data"
    axis, every other axis replicated — the ONE spec builder the
    sharded superblock scan programs (GLM reducers, SGD scan, KMeans
    assign-stats) use for their block operands, so a future mesh-shape
    change lands in one place."""
    return P(*((None,) * lead + (DATA_AXIS,)
               + (None,) * (a.ndim - lead - 1)))


def parse_mesh_shape(s, n_devices: int):
    """Parse a ``config.mesh_shape`` string against ``n_devices``.

    Returns ``None`` for "auto"/""/"1d", else ``(D, M)``. A bare "D"
    normalizes to ``(D, 1)``; M == 1 means the caller must build a plain
    1-D data mesh over D devices (the trivial model axis COLLAPSES so
    the 1-D programs stay jaxpr-byte-identical — asserted in
    perf_smoke). Either factor may be -1 (inferred from ``n_devices``);
    D*M may undershoot ``n_devices`` (the first D*M devices are used)
    but never exceed it."""
    s = str(s or "auto").strip().lower()
    if s in ("auto", "", "1d"):
        return None
    parts = s.split("x")
    if len(parts) not in (1, 2):
        raise ValueError(
            f"mesh_shape {s!r}: expected 'auto', 'D', or 'DxM'"
        )
    try:
        dims = [int(p) for p in parts]
    except ValueError:
        raise ValueError(
            f"mesh_shape {s!r}: expected 'auto', 'D', or 'DxM'"
        ) from None
    if len(parts) == 1:
        dims = dims + [1]
    d, m = dims
    if d == -1 and m == -1:
        raise ValueError(f"mesh_shape {s!r}: only one axis may be -1")
    if d == -1:
        if m < 1 or n_devices % m:
            raise ValueError(
                f"mesh_shape {s!r}: cannot infer data axis from "
                f"{n_devices} devices"
            )
        d = n_devices // m
    elif m == -1:
        if d < 1 or n_devices % d:
            raise ValueError(
                f"mesh_shape {s!r}: cannot infer model axis from "
                f"{n_devices} devices"
            )
        m = n_devices // d
    if d < 1 or m < 1:
        raise ValueError(f"mesh_shape {s!r}: axes must be >= 1 (or -1)")
    if d * m > n_devices:
        raise ValueError(
            f"mesh_shape {s!r} needs {d * m} devices, have {n_devices}"
        )
    return (d, m)


# t5x-style logical-axis rules: named LOGICAL array axes map onto mesh
# axes — batch-like axes shard over "data", feature/embedding axes over
# "model", anything else replicates. The ONE table `to_sharded` /
# `ShardedArray.from_array` and `BlockStream._put_sharded` consult, so
# a future mesh-shape change (or a third axis) lands in one place.
LOGICAL_AXIS_RULES = (
    ("batch", DATA_AXIS),
    ("feature", MODEL_AXIS),
    ("embed", MODEL_AXIS),
)


def logical_axis_spec(logical_axes, mesh: Mesh) -> P:
    """PartitionSpec for an array whose axes carry the LOGICAL names in
    ``logical_axes`` (None entries replicate), resolved through
    :data:`LOGICAL_AXIS_RULES` against ``mesh``: a rule only engages
    when its mesh axis exists on ``mesh`` (so "feature" degrades to
    replicated on a 1-D data mesh and the same call site serves both
    shapes)."""
    rules = dict(LOGICAL_AXIS_RULES)
    names = set(mesh.axis_names)
    spec = []
    for name in logical_axes:
        axis = rules.get(name)
        spec.append(axis if axis in names else None)
    return P(*spec)


def stream_data_mesh() -> Mesh:
    """The mesh streamed (out-of-core) fits shard over, resolved from
    ``config.stream_mesh`` x ``config.mesh_shape``. ``stream_mesh``
    restricts the device POOL: 0 = all local devices, 1 = a single
    device (the sharded superblock flavor never engages), N = the first
    N local devices. ``mesh_shape`` then SHAPES the pool: "auto"/"D"/
    "Dx1" give the 1-D data mesh (today's behavior, byte-identical
    programs), "DxM" a 2-D ("data", "model") mesh over the first D*M
    pool devices. Cached per resolved (knobs, device set) so every
    BlockStream of a fit sees the SAME Mesh object (scan programs are
    lru-cached with the mesh in their key)."""
    from ..config import get_config

    cfg = get_config()
    n = int(cfg.stream_mesh)
    shape_s = str(getattr(cfg, "mesh_shape", "auto"))
    if n <= 0:
        pool = jax.devices()
    else:
        pool = jax.devices()[: max(min(n, len(jax.devices())), 1)]
    dm = parse_mesh_shape(shape_s, len(pool))
    if dm is None:
        if n <= 0:
            return default_mesh()
        devices = pool
    elif dm[1] == 1:
        # trivial model axis: COLLAPSE to the plain 1-D data mesh so the
        # 1-D scan programs stay jaxpr-byte-identical
        devices = pool[: dm[0]]
        if n <= 0 and len(devices) == len(jax.devices()):
            return default_mesh()
        dm = None
    else:
        devices = pool[: dm[0] * dm[1]]
    key = (n, shape_s, len(devices), tuple(d.id for d in devices))
    cached = getattr(_state, "stream_meshes", None)
    if cached is None:
        cached = _state.stream_meshes = {}
    mesh = cached.get(key)
    if mesh is None:
        if dm is None:
            mesh = device_mesh(devices=devices)
        else:
            mesh = device_mesh(dm, (DATA_AXIS, MODEL_AXIS),
                               devices=devices)
        cached[key] = mesh
    return mesh


def data_shards(mesh: Mesh) -> int:
    """Number of shards along the data (row) axis."""
    return mesh.shape[DATA_AXIS] if DATA_AXIS in mesh.shape else 1


def model_shards(mesh: Mesh) -> int:
    """Number of shards along the model (feature) axis; 1 on 1-D meshes."""
    return mesh.shape[MODEL_AXIS] if MODEL_AXIS in mesh.shape else 1


def mesh_str(mesh: Mesh) -> str:
    """Render a mesh as "DxM" — the report CLI / status form (a 1-D
    data mesh over 4 devices renders "4x1")."""
    return f"{data_shards(mesh)}x{model_shards(mesh)}"


def row_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """NamedSharding for an array whose leading axis is row-sharded."""
    spec = (DATA_AXIS,) + (None,) * (ndim - 1)
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
