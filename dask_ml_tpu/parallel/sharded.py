"""Row-sharded array substrate — the TPU-native replacement for the
reference's chunked ``dask.array`` data model (SURVEY.md §2b, row 1:
``dask/array/core.py`` blockwise collections).

Design (SURVEY.md §7 B0): a :class:`ShardedArray` is a padded ``jax.Array``
laid out with ``NamedSharding(P("data", ...))`` over a device mesh, plus the
*logical* row count. Global-view GSPMD programming replaces dask's per-block
task graphs: ``jnp`` ops on the padded array are traced once under ``jit``
and XLA inserts the ICI collectives that dask would have expressed as
tree-reduce task graphs.

Padding: XLA needs equal shards, so rows are padded to a multiple of the
data-axis size. Padded rows are zero; every reduction in ``ops/`` is
mask-aware (``row_mask``) so they never contribute. This replaces dask's
ragged-final-chunk handling.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import to_host
from ..observability import track_program
from .mesh import (
    DATA_AXIS, MODEL_AXIS, data_shards, logical_axis_spec, resolve_mesh,
)


def _padded_rows(n_rows: int, n_shards: int) -> int:
    return max(n_shards, math.ceil(n_rows / n_shards) * n_shards)


def _scatter(x, mesh: Mesh, spec) -> jax.Array:
    """Place an array onto ``mesh`` with ``spec`` — the ONE placement
    primitive for host and device inputs, single- and multi-host meshes.

    Multi-host meshes can't be reached by ``device_put`` (it only places
    onto this process's devices): every process holds the same full array
    (SPMD discipline) and materializes ONLY its addressable shards via
    ``make_array_from_callback`` — the reference's scatter step with no
    bytes over sockets beyond the runtime's own control plane.
    """
    sharding = NamedSharding(mesh, spec)
    if not sharding.is_fully_addressable:  # mesh spans other processes
        if isinstance(x, jax.Array):
            if x.sharding == sharding:  # already placed as requested
                return x
            if not x.is_fully_addressable:
                raise NotImplementedError(
                    "re-placing an already cross-process array onto a "
                    "different multi-host sharding is not supported; "
                    "gather to host first (to_numpy)"
                )
            x = np.asarray(x)
        return jax.make_array_from_callback(
            x.shape, sharding, lambda idx: x[idx]
        )
    return jax.device_put(x, sharding)


@functools.lru_cache(maxsize=32)
def _replicator(mesh: Mesh):
    """Cached replicating identity per mesh: the cross-host all-gather
    program ``to_numpy`` uses — a fresh lambda per call would retrace and
    recompile every time."""
    return jax.jit(lambda v: v, out_shardings=NamedSharding(mesh, P()))


class ShardedArray:
    """A logically (n_rows, *feature_dims) array, row-sharded over a mesh.

    Parameters
    ----------
    data : jax.Array
        Padded device array, leading axis divisible by the mesh's data size.
    n_rows : int
        Logical (unpadded) number of rows.
    mesh : Mesh
    """

    __slots__ = ("data", "n_rows", "mesh")

    def __init__(self, data: jax.Array, n_rows: int, mesh: Mesh):
        self.data = data
        self.n_rows = int(n_rows)
        self.mesh = mesh

    # -- construction -----------------------------------------------------
    @classmethod
    def from_array(cls, x, mesh: Mesh | None = None, dtype=None,
                   shard_features: bool = False) -> "ShardedArray":
        """Place a host (numpy) or device array onto the mesh, row-sharded.

        Equivalent of ``da.from_array`` + scatter in the reference; here it
        is one ``device_put`` with a NamedSharding (no serialization layer —
        SURVEY.md §5 comm row).

        ``shard_features=True`` additionally shards axis 1 over the mesh's
        ``"model"`` axis (2-D tensor-parallel layout for wide-feature
        problems, SURVEY.md §2c TP row) — GSPMD then inserts the psum for
        feature-contracted matmuls automatically.
        """
        if isinstance(x, ShardedArray):
            return x if dtype is None else cls(x.data.astype(dtype), x.n_rows, x.mesh)
        import scipy.sparse as sp

        if sp.issparse(x):
            # densify-on-placement: correct for BLOCK-sized sparse inputs
            # (an Incremental partial_fit block). Whole-corpus sparse fits
            # never reach here — estimator fit paths route sparse through
            # stream_plan/BlockStream, which densifies one block at a time
            from .streaming import _csr_dense

            x = _csr_dense(x.tocsr(), 0, x.shape[0],
                           x.dtype if dtype is None else dtype)
        mesh = resolve_mesh(mesh)
        on_device = isinstance(x, jax.Array) and not isinstance(
            x, jax.core.Tracer
        )
        if on_device:
            # pad + reshard on device — never round-trip through host
            # memory (the PCIe hop dominates at scale)
            xp = jnp
            if dtype is not None:
                x = x.astype(dtype)
        else:
            xp = np
            x = np.asarray(x)
            if dtype is not None:
                x = x.astype(dtype, copy=False)
        n = x.shape[0]
        n_pad = _padded_rows(n, data_shards(mesh))
        if n_pad != n:
            pad_widths = [(0, n_pad - n)] + [(0, 0)] * (x.ndim - 1)
            x = xp.pad(x, pad_widths)
        feat = "feature" if shard_features and x.ndim >= 2 else None
        axes = (("batch", feat) + (None,) * (x.ndim - 2))[: x.ndim]
        spec = logical_axis_spec(axes, mesh)
        data = _scatter(x, mesh, spec)
        return cls(data, n, mesh)

    # -- basic properties -------------------------------------------------
    @property
    def shape(self):
        return (self.n_rows,) + tuple(self.data.shape[1:])

    @property
    def padded_shape(self):
        return tuple(self.data.shape)

    @property
    def ndim(self):
        return self.data.ndim

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def sharding(self) -> NamedSharding:
        return self.data.sharding

    def __len__(self):
        return self.n_rows

    def __repr__(self):
        return (
            f"ShardedArray(shape={self.shape}, padded={self.padded_shape}, "
            f"dtype={self.dtype}, shards={data_shards(self.mesh)})"
        )

    # -- masks ------------------------------------------------------------
    def row_mask(self, dtype=jnp.float32) -> jax.Array:
        """(n_padded,) mask: 1 for logical rows, 0 for padding. Sharded the
        same way as ``data``'s rows so masked reductions stay local."""
        return row_mask(self.padded_shape[0], self.n_rows, self.mesh, dtype)

    # -- host round-trip --------------------------------------------------
    def to_numpy(self) -> np.ndarray:
        if not self.data.is_fully_addressable:
            # multi-host mesh: replicate via an in-program all-gather
            # (ICI/DCN), then read the local copy — np.asarray on a
            # cross-process array would raise
            rep = _replicator(self.mesh)(self.data)
            return np.asarray(rep)[: self.n_rows]
        return to_host(self.data)[: self.n_rows]

    def astype(self, dtype) -> "ShardedArray":
        return ShardedArray(self.data.astype(dtype), self.n_rows, self.mesh)

    # -- pickling ---------------------------------------------------------
    def __getstate__(self):
        """Pickle as the logical HOST array (devices and meshes don't
        pickle); unpickling re-shards onto the ambient mesh — a model
        saved on an 8-chip slice loads on a 1-chip box and vice versa.
        Fitted estimators holding ShardedArray attributes (KMeans.labels_
        et al) become persistable exactly like the reference's estimators
        holding dask arrays."""
        if not self.data.is_fully_addressable:
            # to_numpy on a multi-host array launches a COLLECTIVE; a
            # rank-0-only pickle (the normal save pattern) would deadlock
            # waiting for peers mid-pickle. Make the caller gather first,
            # where every process can participate.
            raise ValueError(
                "cannot pickle a cross-process ShardedArray directly: "
                "call to_numpy() on ALL processes first and pickle the "
                "host array"
            )
        from .mesh import MODEL_AXIS

        spec = getattr(self.data.sharding, "spec", ())
        model_sharded = len(spec) > 1 and spec[1] == MODEL_AXIS
        return {"host": self.to_numpy(), "n_rows": self.n_rows,
                "model_sharded": model_sharded}

    def __setstate__(self, state):
        restored = ShardedArray.from_array(
            state["host"], shard_features=state.get("model_sharded", False)
        )
        self.data = restored.data
        self.n_rows = int(state["n_rows"])
        self.mesh = restored.mesh




@track_program("sharded.row_mask")
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _row_mask(n_padded: int, n_rows: int, sharding, dtype) -> jax.Array:
    idx = jnp.arange(n_padded)
    return jax.lax.with_sharding_constraint((idx < n_rows).astype(dtype), sharding)


# result cache is bounded by SIZE, not just count: a cached (n,) f32 mask
# pins n*4 bytes of device memory for the process lifetime
_MASK_CACHE_MAX_ROWS = 4_194_304  # <= 16 MB per entry, 8 entries


@functools.lru_cache(maxsize=8)
def _row_mask_cached(n_padded: int, n_rows: int, mesh: Mesh, dtype):
    return _row_mask(n_padded, n_rows, NamedSharding(mesh, P(DATA_AXIS)), dtype)


def row_mask(n_padded: int, n_rows: int, mesh: Mesh, dtype=jnp.float32) -> jax.Array:
    # RESULT-cached for small/medium masks (they are requested several
    # times per fit; each rebuild is a program launch); huge masks are
    # rebuilt rather than pinned in HBM
    if n_padded <= _MASK_CACHE_MAX_ROWS:
        return _row_mask_cached(n_padded, n_rows, mesh, dtype)
    return _row_mask(n_padded, n_rows, NamedSharding(mesh, P(DATA_AXIS)), dtype)


def as_sharded(x, mesh: Mesh | None = None, dtype=None) -> ShardedArray:
    """Canonicalize numpy / jax / ShardedArray input to ShardedArray."""
    return ShardedArray.from_array(x, mesh=mesh, dtype=dtype)


def reshard(x: ShardedArray, mesh: Mesh | None = None) -> ShardedArray:
    """Move a ShardedArray onto a different mesh — the rechunk-parity
    primitive (ref ``dask/array/rechunk.py``, SURVEY.md §5 long-context
    row). The repartition lowers to XLA collective-permute/all-to-all over
    ICI when the device sets overlap; no task graph, no serialization.

    Padding is recomputed for the target mesh's data-axis size (old
    padding rows are zero, so slicing/padding on device preserves the
    masked-reduction invariant).
    """
    mesh = resolve_mesh(mesh)
    if mesh is x.mesh or mesh == x.mesh:
        return x
    # slice off the old padding on device, then reuse from_array's
    # on-device pad + placement path for the target mesh
    return ShardedArray.from_array(x.data[: x.n_rows], mesh=mesh)


def take_rows(x: ShardedArray, idx) -> ShardedArray:
    """New ShardedArray of x's rows at (host) integer indices ``idx``.

    The resharding primitive behind train/test splits and CV fold
    extraction — the reference's rechunk/shuffle task graphs
    (``dask/array/rechunk.py``, SURVEY.md §5 long-context row) become one
    gather that XLA lowers to an all-to-all over ICI."""
    idx = np.asarray(idx)
    if idx.ndim != 1:
        raise ValueError(f"idx must be 1-D, got shape {idx.shape}")
    if idx.size and ((idx < 0).any() or (idx >= x.n_rows).any()):
        raise IndexError(
            f"indices out of bounds for {x.n_rows} rows: "
            f"[{idx.min()}, {idx.max()}] (jnp.take would clamp silently)"
        )
    n_out = idx.shape[0]
    shards = data_shards(x.mesh)
    n_pad = _padded_rows(n_out, shards)
    # pad with index 0 (any valid row): padded rows are masked by n_rows
    idx_padded = np.zeros(n_pad, np.int32)
    idx_padded[:n_out] = idx
    spec = P(*((DATA_AXIS,) + (None,) * (x.ndim - 1)))
    sharding = NamedSharding(x.mesh, spec)
    idx_dev = _scatter(idx_padded, x.mesh, P(DATA_AXIS))

    @jax.jit
    def gather(data, indices):
        out = jnp.take(data, indices, axis=0)
        return jax.lax.with_sharding_constraint(out, sharding)

    out = gather(x.data, idx_dev)
    # re-zero rows that came from padding of the source or of the output
    out_arr = ShardedArray(out, n_out, x.mesh)
    mask = out_arr.row_mask(out.dtype)
    out_arr.data = out * (mask.reshape((n_pad,) + (1,) * (x.ndim - 1)))
    return out_arr
