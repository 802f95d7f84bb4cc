"""Host→device block streaming for larger-than-HBM datasets.

Reference equivalent: dask's chunk scheduling — blocks materialize on
workers as tasks run (SURVEY.md §2b row 1). TPU design (SURVEY.md §7
design stance #1, "the heart of the system"): the working set lives in
host RAM (numpy / np.memmap); fixed-shape blocks are placed onto the mesh
with ``jax.device_put`` AHEAD of compute (device_put is async — issuing
the next transfer before consuming the current block overlaps DMA with
compute, the double-buffer pattern). A consumed block's HBM is released
when its Python reference drops at the next loop iteration, so peak
footprint is ≈ (prefetch + 1) blocks.

Blocks have a fixed padded shape (static shapes for jit); the final
partial block carries its logical row count and a mask.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import (
    DATA_AXIS, MODEL_AXIS, data_shards, mesh_str, model_shards,
    resolve_mesh,
)


class SparseBlocks:
    """Row-concatenated view over a list of scipy sparse (CSR) blocks —
    the shape a blocked vectorizer naturally produces — WITHOUT the
    ``sp.vstack`` copy. Only supports what streaming needs: ``shape``,
    ``dtype`` and contiguous row-range densification.

    Ref: dask_ml/feature_extraction/text.py produces a dask array of CSR
    chunks; this is its host-side analog feeding BlockStream.
    """

    def __init__(self, blocks):
        blocks = [b.tocsr() if not sp.isspmatrix_csr(b) else b
                  for b in blocks]
        if not blocks:
            raise ValueError("SparseBlocks needs at least one block")
        d = blocks[0].shape[1]
        for b in blocks:
            if b.shape[1] != d:
                raise ValueError("blocks have inconsistent widths")
        self.blocks = blocks
        self.offsets = np.cumsum([0] + [b.shape[0] for b in blocks])
        self.shape = (int(self.offsets[-1]), d)
        self.dtype = blocks[0].dtype
        self.ndim = 2

    def tocsr(self):
        """Materialize as one CSR (O(nnz)) — for host consumers that
        need arbitrary row slicing (e.g. host-estimator block loops)."""
        return sp.vstack(self.blocks).tocsr()

    def slice_dense(self, lo, hi, dtype=np.float32):
        """Densify rows [lo, hi) — touches only the blocks they span."""
        if hi <= lo:
            return np.empty((0, self.shape[1]), dtype)
        i = int(np.searchsorted(self.offsets, lo, side="right") - 1)
        parts = []
        while lo < hi and i < len(self.blocks):
            b_lo, b_hi = self.offsets[i], self.offsets[i + 1]
            take = min(hi, b_hi) - lo
            parts.append(
                _csr_dense(self.blocks[i], lo - b_lo, lo - b_lo + take,
                           dtype)
            )
            lo += take
            i += 1
        return parts[0] if len(parts) == 1 \
            else np.concatenate(parts, axis=0)


def _is_sparse_source(a) -> bool:
    return sp.issparse(a) or isinstance(a, SparseBlocks)


def _n_rows_of(a) -> int:
    # len() raises on scipy sparse ("length is ambiguous")
    return int(a.shape[0]) if _is_sparse_source(a) else len(a)


def _csr_dense(a, lo, hi, dtype):
    """Densify CSR rows [lo, hi) straight into ``dtype`` — casting the
    nnz values first, so the transient is ONE dense block, not a
    float64 block plus its cast copy."""
    blk = a[lo:hi]
    if blk.dtype != dtype:
        blk = blk.astype(dtype)
    return blk.toarray()


def as_row_sliceable(a):
    """Normalize a sparse source to a row-sliceable form (CSR) ONCE —
    call this before a loop of ``_slice_dense`` calls; ``tocsr()`` is
    identity for CSR but O(nnz) for COO/CSC/BSR."""
    return a.tocsr() if sp.issparse(a) and not sp.isspmatrix_csr(a) else a


def as_row_indexable(a):
    """Normalize a sparse source to a form supporting fancy ROW
    indexing (``a[idx_array]``): scipy sparse → CSR; the
    ``SparseBlocks`` view (which only supports contiguous-range
    densify) materializes as one CSR. The single normalization point
    behind the search/split fold-extraction paths — sparse folds stay
    sparse, never densified."""
    a = as_row_sliceable(a)
    return a.tocsr() if isinstance(a, SparseBlocks) else a


def _slice_dense(a, lo, hi, dtype):
    """One host block of ``a`` as a dense array — the single densify
    point for sparse sources (O(block) host memory, never the corpus).
    Non-CSR sparse is converted defensively (COO/BSR cannot row-slice);
    loops should pre-normalize with ``as_row_sliceable``."""
    if isinstance(a, SparseBlocks):
        return a.slice_dense(lo, hi, dtype)
    if sp.issparse(a):
        return _csr_dense(a.tocsr(), lo, hi, dtype)
    return np.asarray(a[lo:hi], dtype=dtype)


class StreamBudgetExceeded(ValueError):
    """A streamed fit's PER-DEVICE staged super-block slab exceeds the
    simulated ``config.stream_device_byte_budget`` — the typed refusal
    (sibling of ``DenseBudgetExceeded``) that stands in for a real
    per-chip HBM OOM on CPU. The fix is a mesh with more shards on the
    axis that's over budget: a wide-d fit that a 1-D data mesh refuses
    fits once ``config.mesh_shape`` adds a model axis (X slabs then
    stage as (rows/D, d/M) tiles — per-device bytes flat in d)."""


class Block:
    """One streamed block: device data + logical row count."""

    __slots__ = ("arrays", "n_rows", "mask")

    def __init__(self, arrays, n_rows, mask):
        self.arrays = arrays
        self.n_rows = n_rows
        self.mask = mask


class SuperBlock:
    """K stacked streamed blocks: ONE dispatch's worth of data.

    ``arrays[i]`` is the stream's i-th array as a device
    ``(K, block_rows, ...)`` stack and ``counts`` the device ``(K,)``
    int32 valid-row counts (a consumer derives each step's prefix mask
    from them). The FINAL
    super-block of a pass is padded to the same K — missing block slots
    carry ``counts == 0`` and all-zero data, so every dispatch compiles
    once — and ``n_blocks`` says how many slots are real. ``n_rows`` is
    the super-block's total valid rows.

    On a >1-device stream mesh (ISSUE 9) every array is BATCH-SHARDED
    over the mesh's "data" axis (each device owns a contiguous
    ``block_rows / D`` row slab of every block) and ``shard_counts``
    holds the device ``(D, K)`` per-shard valid-row counts — row ``s``
    lives on shard ``s``'s device, so a shard_map consumer reads its
    own ragged-tail counts locally (a block's trailing shards see 0).
    ``shard_counts`` is None on a single-device mesh."""

    __slots__ = ("arrays", "counts", "n_blocks", "n_rows",
                 "shard_counts")

    def __init__(self, arrays, counts, n_blocks, n_rows,
                 shard_counts=None):
        self.arrays = arrays
        self.counts = counts
        self.n_blocks = n_blocks
        self.n_rows = n_rows
        self.shard_counts = shard_counts


_PUT_ALIASES = None


def _device_put_aliases() -> bool:
    """One-time semantic probe: does this backend's ``device_put``
    alias (zero-copy) host numpy memory? Where it does, a reused staging
    buffer would be mutated under a still-queued consumer computation
    (block_until_ready only covers the transfer, not later reads of an
    aliased buffer), so the super-block ring switches to fresh
    per-super-block buffers there. The probe is the direct hazard:
    mutate the source after the put and see whether the device array
    changed. The source is 64-byte aligned because that is the case a
    runtime aliases (XLA:CPU does, and copies anything less aligned: a
    probe left to malloc's 16 bytes reads "copies" by luck)."""
    global _PUT_ALIASES
    if _PUT_ALIASES is None:
        try:
            raw = np.zeros(8 + 16, np.float32)
            lead = (-raw.ctypes.data % 64) // 4
            probe = raw[lead:lead + 8]
            dev = jax.block_until_ready(jax.device_put(probe))
            probe[:] = 1.0
            _PUT_ALIASES = bool(float(np.asarray(dev)[0]) == 1.0)
        except Exception:
            _PUT_ALIASES = True  # cannot prove safety: assume aliasing
    return _PUT_ALIASES


# auto block budget: bytes of ONE block's X on device. Fixed bytes (not a
# fraction of n) so an arbitrarily large memmap still streams in
# HBM-bounded blocks; peak device footprint ≈ (prefetch + 1) blocks.
_AUTO_BLOCK_BYTES = 256 << 20

# byte budget of ONE super-block (K stacked blocks) on device: caps the
# auto K and the K autotuner so super-blocking never defeats the HBM
# bound the per-block budget establishes (peak ≈ (prefetch + 1)
# super-blocks while a pass is in flight)
_SUPERBLOCK_BYTES = 512 << 20

# training-profile sample budget in VALUES (rows x features): the
# first-pass fold must stay a rounding error next to the pass compute
# at ANY design width
_PROFILE_VALUE_BUDGET = 1 << 20

# widest feature count the training profile sketches: past this the
# per-feature histogram matrix (d x ~80 int64 buckets) and the fold's
# O(block x d) temporaries stop being "free on the staging path" —
# wide/hashed feature spaces are served by the serving-side sketches'
# own cap instead
_PROFILE_MAX_FEATURES = 1024

# auto K: dispatch amortization saturates quickly — 8 blocks per
# dispatch removes ~7/8 of the per-block launch+sync overhead; beyond
# that the stacked buffer's footprint grows for single-digit-% returns
_AUTO_SUPERBLOCK_K = 8


def auto_block_rows(n_rows: int, row_bytes: int = 4) -> int:
    """Block size from config: ``stream_block_rows`` if set, else an
    HBM byte budget divided by the bytes-per-row of the streamed data."""
    from ..config import get_config

    br = get_config().stream_block_rows
    if br and br > 0:
        return int(br)
    return max(_AUTO_BLOCK_BYTES // max(int(row_bytes), 1), 1)


def grid_partition(n_pad: int, D: int) -> tuple[int, int]:
    """(n_blocks B, rows-per-block S) for ``n_pad`` rows on a D-way data
    axis: at least max(D, 8) blocks — the epoch must yield multiple
    minibatch steps even on a 1-device mesh (a D-only split would
    collapse a single-chip host fit to ONE gradient step per epoch) —
    with S rounded up to a multiple of D so a (B, S, d) block grid's row
    axis shards evenly. The one partition formula behind the fused-epoch
    grid, the Incremental wrapper's block loops, and the SGD host fit —
    device- and host-input fits of the same data train identical
    minibatches."""
    n_pad = max(n_pad, 1)
    target = max(D, 8)
    s = -(-n_pad // target)
    S = max(-(-s // D) * D, 1)
    return -(-n_pad // S), S


def resolve_stream_mesh(mesh=None):
    """The mesh a host-streamed fit runs over: an explicit ``mesh``
    wins; under a live multi-process runtime blocks are PROCESS-LOCAL
    data (they shard over this process's devices only — a global-mesh
    device_put asserts value equality across processes, and the
    cross-process merge is the consumer's explicit psum_host); else
    ``config.stream_mesh`` x ``config.mesh_shape`` pick the local
    device set and its 1-D/2-D shape (see ``mesh.stream_data_mesh`` —
    "Dx1" collapses to the plain 1-D mesh, "DxM" gives the 2-D
    ("data", "model") mesh). The ONE resolution point shared by
    ``BlockStream`` and ``fit_block_rows`` so block partitions,
    staging shardings and the lru'd scan-program mesh keys always
    agree — every BlockStream of a fit sees the SAME Mesh object."""
    if mesh is not None:
        return mesh
    from . import distributed as dist

    if dist.process_count() > 1:
        local = dist.local_mesh()
        from ..config import get_config

        n = int(get_config().stream_mesh)
        if n <= 0 or n >= local.devices.size:
            return local
        # config.stream_mesh still applies per process: N restricts to
        # the first N LOCAL devices, and stream_mesh=1 remains the
        # documented single-device escape hatch (the sharded flavor
        # never engages) even under a live multi-host runtime — the
        # exact environment where an un-validated path most needs an
        # opt-out
        from .mesh import device_mesh

        return device_mesh(devices=list(local.devices.flat)[:n])
    from .mesh import stream_data_mesh

    return stream_data_mesh()


def fit_block_rows(X, mesh=None) -> int:
    """Rows per block for an epoch-style fit over host data: the
    ``grid_partition`` size for the resolved mesh, capped by
    ``stream_plan``'s byte budget when X is a source that must stream in
    bounded dense blocks (sparse, memmap, configured block rows) — the
    ONE block-size policy shared by the SGD fit loop and
    ``Incremental._block_size``."""
    n = int(X.shape[0]) if hasattr(X, "shape") else len(X)
    D = max(data_shards(resolve_stream_mesh(mesh)), 1)
    S = max(grid_partition(-(-max(n, 1) // D) * D, D)[1], 1)
    budget = stream_plan(X)
    return S if budget is None else max(min(S, budget), 1)


def stream_plan(X) -> int | None:
    """Rows-per-block when ``X`` should be fitted out-of-core, else None.

    Streams when X is host-resident and either (a) an ``np.memmap`` —
    its backing file may exceed host AND device memory, so it must never
    be materialized whole — or (b) larger than a configured
    ``config.stream_block_rows``. Device-resident inputs (ShardedArray /
    jax.Array) always take the resident path.
    """
    from ..config import get_config

    if _is_sparse_source(X):
        # sparse ALWAYS streams: the device representation is dense, so
        # the only scalable bridge is one densified block at a time
        # (VERDICT r4 missing #2; ref text.py CSR chunks → per-block fit)
        n = X.shape[0]
        if n == 0:
            return None
        row_bytes = 4 * int(np.prod(X.shape[1:], dtype=np.int64) or 1)
        return min(auto_block_rows(n, row_bytes), n)
    if not isinstance(X, np.ndarray) or isinstance(X, np.generic):
        return None
    n = X.shape[0] if X.ndim else 0
    if n == 0:
        return None
    if isinstance(X, np.memmap):
        # blocks stream as float32 regardless of the memmap dtype
        row_bytes = 4 * int(np.prod(X.shape[1:], dtype=np.int64) or 1)
        return min(auto_block_rows(n, row_bytes), n)
    br = get_config().stream_block_rows
    if br and 0 < br < n:
        return br
    return None


class BlockStream:
    """Prefetched epoch iterator over host arrays.

    Parameters
    ----------
    arrays : tuple of host arrays (np.ndarray / np.memmap), equal length.
    block_rows : rows per block (rounded up to a multiple of the mesh's
        data-axis size); None reads ``config.stream_block_rows``, falling
        back to an HBM byte budget divided by the arrays' combined
        bytes-per-row.
    shuffle : shuffle block order each epoch (the reference's
        ``shuffle_blocks``); rows within a block keep locality.
    prefetch : transfers kept in flight ahead of compute (1 = classic
        double buffering); None reads ``config.stream_prefetch``.
    """

    def __init__(self, arrays, block_rows=None, mesh=None, shuffle=False,
                 seed=None, dtype=np.float32, prefetch=None,
                 profile=True, nonfinite=None):
        # stream_mesh / multi-process resolution lives in ONE place so
        # the data-parallel superblock flavor, the block partition and
        # the staging shardings can never disagree
        self.mesh = resolve_stream_mesh(mesh)
        # sparse sources normalize to CSR once: COO/BSR don't support
        # row slicing at all and CSC slices rows in O(nnz)
        self.arrays = tuple(
            a.tocsr() if sp.issparse(a) and not sp.isspmatrix_csr(a)
            else a
            for a in arrays
        )
        n = _n_rows_of(self.arrays[0])
        for a in self.arrays:
            if _n_rows_of(a) != n:
                raise ValueError("arrays have inconsistent lengths")
        self.n_rows = n
        # dense bytes-per-row of everything this stream puts on device —
        # sizes the auto block AND caps autotune growth at the same
        # byte budget (growth must not defeat the HBM bound)
        self._row_bytes = sum(
            4 * int(np.prod(a.shape[1:], dtype=np.int64) or 1)
            for a in self.arrays
        )
        # ... and of the X position alone: the unit BOTH auto budgets
        # count in. ``stream_plan`` sizes a block as _AUTO_BLOCK_BYTES of
        # X, so a super-block budget that also counted y could never
        # hold two of them (512 MiB // (256 MiB + 4 B/row) == 1): at the
        # auto block size super-blocks — and with them the fused
        # kernels — silently never engaged (first seen on the v5e, PR 21)
        self._x_row_bytes = 4 * int(np.prod(
            self.arrays[0].shape[1:], dtype=np.int64) or 1)
        if block_rows is None:
            block_rows = min(auto_block_rows(n, self._row_bytes), n)
        if prefetch is None:
            from ..config import get_config

            prefetch = get_config().stream_prefetch
        self.prefetch = max(int(prefetch), 1)
        shards = data_shards(self.mesh)
        self.block_rows = max(
            int(np.ceil(block_rows / shards)) * shards, shards
        )
        self.shuffle = shuffle
        self.rng = np.random.RandomState(seed)
        self.dtype = dtype
        self.n_blocks = int(np.ceil(n / self.block_rows))
        # 2-D mesh feature tiling (logical-axis rules, mesh.py): on a
        # ("data", "model") mesh ONLY the X position (arrays[0], dense,
        # ndim >= 2, d divisible by M — shard_map needs even tiles)
        # stages as (rows/D, d/M) per-device tiles; y/aux/masks and the
        # per-shard valid-row counts stay data-only (counts replicate
        # over "model" for free via P("data", None)). A non-tileable X
        # records the reason and stages data-only — the 1-D sharded
        # programs stay correct on a 2-D mesh (their specs name only
        # "data", so compute is model-replicated).
        m_shards = model_shards(self.mesh)
        self.model_tiled = False
        self.model_tile_reason = None
        if m_shards > 1:
            a0 = self.arrays[0]
            d0_tile = getattr(a0, "shape", (0,))[1] if getattr(
                a0, "ndim", 1) >= 2 else 0
            if _is_sparse_source(a0):
                self.model_tile_reason = "sparse-source"
            elif getattr(a0, "ndim", 1) != 2:
                self.model_tile_reason = "x-not-2d"
            elif d0_tile % m_shards:
                self.model_tile_reason = (
                    f"d-not-divisible({d0_tile}%{m_shards})"
                )
            else:
                self.model_tiled = True

        def _feat(i, a):
            return (MODEL_AXIS if i == 0 and self.model_tiled
                    else None,) + (None,) * (a.ndim - 2) \
                if a.ndim >= 2 else ()

        self._shardings = tuple(
            NamedSharding(self.mesh, P(*((DATA_AXIS,) + _feat(i, a))))
            for i, a in enumerate(self.arrays)
        )
        self._mask_sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        # super-block stacks shard their ROW axis (axis 1); the block
        # axis is the scan axis and stays unsharded
        self._sb_shardings = tuple(
            NamedSharding(self.mesh,
                          P(*((None, DATA_AXIS) + _feat(i, a))))
            for i, a in enumerate(self.arrays)
        )
        self._counts_sharding = NamedSharding(self.mesh, P())
        # per-shard valid-row counts of the sharded superblock flavor:
        # a (D, K) matrix whose row s lives on shard s's device
        self._shard_counts_sharding = NamedSharding(
            self.mesh, P(DATA_AXIS, None)
        )
        # set by the K autotuner — and by the adaptive-search cohort
        # plane (ISSUE 14), which wants finer dispatch granularity than
        # a plain fit so each dispatch's slot RUNG can track the live
        # bracket instead of the round's widest moment
        self._superblock_k_override = None
        # device-resident sparse staging (ISSUE 13): when opted in
        # (config.stream_sparse) and the source stays under the density
        # threshold, a sparse X streams as bucketed-nnz COO triples
        # through _superblocks_sparse instead of densifying per block.
        # The plan (capacities, per-block nnz rungs, fallback reason)
        # is built ONCE here from indptr alone
        self.sparse_plan = None
        self.sparse_reason = None
        if any(_is_sparse_source(a) for a in self.arrays):
            from ..config import get_config as _gc

            _cfg = _gc()
            if not _cfg.stream_sparse:
                self.sparse_reason = "stream-sparse-off"
            elif not _is_sparse_source(self.arrays[0]) or any(
                    _is_sparse_source(a) for a in self.arrays[1:]):
                # only the X position streams sparse; sparse targets
                # have no kernel story
                self.sparse_reason = "sparse-operand-layout"
            else:
                from .sparse_stream import plan_sparse_stream

                plan = plan_sparse_stream(
                    self.arrays[0], self.block_rows,
                    data_shards(self.mesh),
                    float(_cfg.stream_sparse_max_density),
                )
                self.sparse_reason = plan.reason
                if plan.engaged:
                    self.sparse_plan = plan
        from ..config import get_config
        from ..observability.live import ensure_telemetry

        # reliability plane (ISSUE 11), captured once: bounded-backoff
        # IO retry budget and the non-finite block policy
        cfg_rel = get_config()
        self._io_retries = max(int(cfg_rel.stream_io_retries), 0)
        nf = (cfg_rel.stream_nonfinite if nonfinite is None
              else nonfinite)
        if nf not in ("off", "raise", "quarantine"):
            raise ValueError(
                f"stream_nonfinite={nf!r} is not supported; accepted: "
                "'off', 'raise', 'quarantine'"
            )
        self._nonfinite = nf
        # the plan SPEC is captured (not re-read per site): super-block
        # staging runs on a worker thread whose thread-local config does
        # not carry the creator's config.set overrides
        self._fault_spec = cfg_rel.fault_plan

        # per-feature training profile (observability/sketch.py): the
        # staging path folds a strided row sample of the FIRST pass's
        # host slabs — pure numpy on buffers already in hand, so it can
        # never add a device sync or touch a jaxpr. Consumers attach the
        # snapshot to the fitted estimator (training_profile_); serving
        # scores live traffic against it (drift.py). `profile=False`
        # opts inference streams (streamed_map) out — a predict stream's
        # distribution is not a training profile.
        self.profile = None
        # WIDE sparse sources opt out: a hashed-text corpus is 2**16+
        # wide, and a per-feature sketch there is O(d * buckets) memory
        # (tens of MB) on a path whose whole point is O(block)
        # footprint. NARROW sparse (d <= _PROFILE_MAX_FEATURES) folds a
        # densified strided sample under the same per-VALUE budget as
        # dense streams — drift monitoring works on sparse fits that
        # can afford it, and the opt-out reason is on record
        sparse_src = any(_is_sparse_source(a) for a in self.arrays)
        d_prof = int(np.prod(
            getattr(self.arrays[0], "shape", (0, 1))[1:], dtype=np.int64
        ) or 1)
        self.profile_reason = None
        if sparse_src and d_prof > _PROFILE_MAX_FEATURES:
            self.profile_reason = f"sparse-wide(d={d_prof})"
        self._profile_enabled = bool(
            profile and get_config().obs_drift
            and self.profile_reason is None
        )
        # VALUE budget for the profile sample: bounds the fold cost per
        # fit regardless of dataset size AND width (the profile is a
        # uniform strided sample either way). A row budget alone let
        # wide designs blow the first-pass fold up proportionally to d
        # (d=128 folded 7.3M values, ~0.5s on the staging worker's
        # critical path — measured as a streamed-SGD throughput
        # regression); a value budget keeps the fold ~0.1s at any
        # width. 1M values = the old 64k rows at d=16.
        d0 = int(np.prod(
            getattr(self.arrays[0], "shape", (0, 1))[1:], dtype=np.int64
        ) or 1)
        budget_rows = max(_PROFILE_VALUE_BUDGET // max(d0, 1), 1024)
        self._profile_stride = max(
            int(np.ceil(self.n_rows / budget_rows)), 1
        )

        # streamed fits are the long-running workload the live exporter
        # exists for: arm /metrics//status (no-op when obs_http_port
        # is 0)
        ensure_telemetry()

    def _native_plan(self):
        """(per-array ok flags, reason) — which arrays the C++ readahead
        reader can serve, and when none can, why (recorded in the pass
        stats as ``native_reader_reason``). An eligible memmap is
        verified by comparing the reader's block 0 against the numpy
        slice, which catches sliced / re-offset memmap views whose
        ``.offset`` no longer describes them; a reader that fails to
        build, open or read raises."""
        from ..io.native import NativeBlockReader, native_available

        eligible = [
            type(a) is np.memmap and a.flags["C_CONTIGUOUS"]
            and getattr(a, "filename", None) is not None
            for a in self.arrays
        ]
        if not any(eligible):
            return [False] * len(self.arrays), "not-a-memmap"
        if not native_available():
            return [False] * len(self.arrays), "no-c++-compiler"
        oks = []
        for a, el in zip(self.arrays, eligible):
            ok = False
            if el:
                # the offset/contiguity property is independent of
                # block size: verify with a SMALL block instead of
                # double-reading a full (possibly 256 MB) one.
                # equal_nan: datasets with missing values must not
                # lose the readahead path
                vb = min(self.block_rows, len(a), 4096)
                r = NativeBlockReader(a, vb)
                try:
                    blk = r.next()
                    ok = blk is not None and np.array_equal(
                        blk, np.asarray(a[: len(blk)]),
                        equal_nan=np.issubdtype(a.dtype, np.floating),
                    )
                finally:
                    r.close()
            oks.append(ok)
        return oks, None if any(oks) else "memmap-view-offset"

    def _native_readers(self, sequential=True):
        """(per-array readahead readers, reason): readers for a
        SEQUENTIAL pass (None entries where inapplicable), or
        ``(None, why)`` when no array takes the native path. The reader
        thread pread()s blocks ahead of the consumer, overlapping disk
        latency with device transfer/compute
        (native/block_reader.cpp)."""
        if not sequential:
            return None, "non-sequential-order"
        if getattr(self, "_native_ok", None) is None:
            self._native_ok, self._native_reason = self._native_plan()
        if not any(self._native_ok):
            return None, self._native_reason
        from ..io.native import NativeBlockReader

        return [
            NativeBlockReader(a, self.block_rows) if ok else None
            for ok, a in zip(self._native_ok, self.arrays)
        ], None

    def _profile_fold(self, blk, strided=False) -> None:
        """Fold one host X slab (valid rows only, pre-padding) into the
        training profile — first pass only (later passes re-stream the
        same rows), strided to the row budget, never raising into the
        stream. Called from the per-block path and the super-block
        staging worker alike (the sketch is thread-safe). ``strided``
        marks a sample the caller already strided (the sparse staging
        path densifies ONLY the sampled rows)."""
        if not self._profile_enabled or getattr(self, "_passes", 0):
            return
        try:
            if blk.ndim != 2 or blk.shape[0] == 0 \
                    or blk.shape[1] > _PROFILE_MAX_FEATURES:
                self._profile_enabled = (
                    blk.ndim == 2 and blk.shape[1] <= _PROFILE_MAX_FEATURES
                )
                return
            prof = self.profile
            if prof is None:
                from ..observability.sketch import FeatureSketch

                prof = self.profile = FeatureSketch(blk.shape[1])
            prof.fold(blk if strided else blk[:: self._profile_stride])
        except Exception:
            self._profile_enabled = False  # diagnostics never kill a fit

    def _profile_fold_sparse(self, a, lo, hi) -> None:
        """The sparse staging path's profile fold: densify ONLY the
        strided sample rows of [lo, hi) (the sparse path never builds a
        dense block, and narrow-sparse profiling must not reintroduce
        one) and fold them pre-strided. No-op when profiling is off
        (wide sparse keeps the recorded opt-out)."""
        if not self._profile_enabled or getattr(self, "_passes", 0):
            return
        try:
            step = self._profile_stride
            if sp.isspmatrix_csr(a):
                blk = np.asarray(a[lo:hi:step].toarray(), self.dtype)
            else:
                # SparseBlocks: scatter ONLY the strided rows' nonzeros
                # into the sample buffer — O(block nnz) work, O(sample)
                # dense memory, never the block_rows x d temp this path
                # exists to avoid
                from .sparse_stream import coo_rows

                data, cols, rows = coo_rows(a, lo, hi)
                sel = (rows % step) == 0
                n_s = -(-(hi - lo) // step)
                blk = np.zeros((n_s, a.shape[1]), self.dtype)
                np.add.at(blk, (rows[sel] // step, cols[sel]),
                          data[sel])
            self._profile_fold(blk, strided=True)
        except Exception:
            self._profile_enabled = False

    def profile_snapshot(self):
        """The training profile as a JSON-safe dict (None when profiling
        is off / nothing folded) — what fits attach as
        ``estimator.training_profile_``."""
        prof = self.profile
        return prof.to_dict() if prof is not None and prof.rows else None

    @staticmethod
    def _disable_reader(readers, i):
        """A reader whose read failed mid-stream has an untrustworthy
        cursor (the failed ``next()`` may or may not have consumed its
        block) — drop it for the rest of the pass; reads fall back to
        POSITIONAL slices of the source, which are idempotent."""
        try:
            readers[i].close()
        except Exception:
            pass
        readers[i] = None

    def _retry_io(self, fn, what):
        """Run ``fn`` (an IDEMPOTENT staging step) with bounded
        exponential-backoff IO retry: OSError — a real disk/reader
        hiccup or an injected ``io`` fault — retries up to
        ``stream_io_retries`` times before raising the typed
        :class:`~dask_ml_tpu.reliability.StreamIORetriesExhausted`;
        :class:`InjectedCrash` (a modeled death, not a flaky read)
        propagates immediately."""
        import time as _time

        from ..observability import record_stream_retry
        from ..reliability import faults as _flt

        attempt = 0
        while True:
            try:
                return fn()
            except _flt.InjectedCrash:
                raise
            except OSError as exc:
                if attempt >= self._io_retries:
                    err = _flt.StreamIORetriesExhausted(
                        f"{what} still failing after {attempt + 1} "
                        f"attempt(s): {exc}"
                    )
                    try:
                        # opt-in incident hook (typed error, one
                        # module-global check when disarmed)
                        from ..observability import alerts as _obs_alerts

                        _obs_alerts.note_error(err, "stream_io")
                    except Exception:
                        pass
                    raise err from exc
                record_stream_retry()
                _time.sleep(min(0.02 * (2 ** attempt), 1.0))
                attempt += 1

    def _read_block_host(self, i, a, lo, hi, readers, out=None):
        """One host block read — dtype-cast dense rows [lo, hi) of
        array ``i`` — through the ``staging_read`` fault site with
        bounded exponential-backoff IO retry (``stream_io_retries``).
        With ``out`` the rows are written into ``out[:hi-lo]`` (the
        super-block slab path's single copy); else the block is
        returned (a source VIEW when dtype already matches)."""
        from ..observability import record_stream_retry
        from ..reliability import faults as _flt

        if readers is not None and readers[i] is not None:
            try:
                raw = _flt.fire_plan(self._fault_spec, "staging_read",
                                     readers[i].next())
                if out is not None:
                    out[: hi - lo] = raw
                    return None
                # copy out: the reader's ring buffer is reused, and
                # device_put reads the host buffer asynchronously
                return raw.astype(self.dtype, copy=True)
            except OSError:
                record_stream_retry()
                self._disable_reader(readers, i)

        def read():
            blk = _flt.fire_plan(
                self._fault_spec, "staging_read",
                _slice_dense(a, lo, hi, self.dtype)
            )
            if out is not None:
                out[: hi - lo] = blk
                return None
            return blk

        return self._retry_io(read,
                              f"staging read of rows [{lo}, {hi})")

    def _guard_block_host(self, outs, m):
        """Apply ``stream_nonfinite`` to one per-block staging result:
        returns (outs, m) unchanged, raises typed, or quarantines —
        data zeroed AND the valid-row count folded to 0, so the
        existing mask/prefix-count machinery drops the block with no
        shape change and no recompile."""
        if self._nonfinite == "off" or m == 0:
            return outs, m
        if all(bool(np.isfinite(np.asarray(o)[:m]).all()) for o in outs):
            return outs, m
        from ..reliability.faults import NonFiniteBlock

        if self._nonfinite == "raise":
            raise NonFiniteBlock(
                f"non-finite values in a streamed host block of {m} "
                "rows (config.stream_nonfinite='raise')"
            )
        from ..observability import record_stream_quarantine

        record_stream_quarantine()
        return [np.zeros_like(np.asarray(o)) for o in outs], 0

    def _block_host(self, b, readers=None):
        lo = b * self.block_rows
        hi = min(lo + self.block_rows, self.n_rows)
        m = hi - lo
        outs = []
        for i, a in enumerate(self.arrays):
            blk = self._read_block_host(i, a, lo, hi, readers)
            if i == 0:
                self._profile_fold(blk[:m])
            if m < self.block_rows:  # fixed shape: pad the tail block
                pad = [(0, self.block_rows - m)] + [(0, 0)] * (blk.ndim - 1)
                blk = np.pad(blk, pad)
            outs.append(blk)
        outs, m = self._guard_block_host(outs, m)
        mask = np.zeros(self.block_rows, self.dtype)
        mask[:m] = 1.0
        return outs, m, mask

    def _put(self, host_block):
        """Per-block device staging through the ``stream_put`` fault
        site, IO failures retried with the same bounded backoff as the
        reads (an injected transient fault must heal, not kill the
        pass)."""
        from ..reliability import faults as _flt

        def put():
            _flt.fire_plan(self._fault_spec, "stream_put")
            return self._put_impl(host_block)

        return self._retry_io(put, "device staging put")

    def _put_impl(self, host_block):
        outs, m, mask = host_block
        from ..observability import record_transfer

        record_transfer(sum(a.nbytes for a in outs) + mask.nbytes)
        dev = tuple(jax.device_put(a, s)
                    for a, s in zip(outs, self._shardings))
        return Block(dev, m, jax.device_put(mask, self._mask_sharding))

    def __iter__(self):
        import time as _time

        order = np.arange(self.n_blocks)
        if self.shuffle:
            self.rng.shuffle(order)
        readers, why = self._native_readers(sequential=not self.shuffle)
        # per-pass overlap accounting (SURVEY §7 B0: the double buffer is
        # the heart of the system — measure it, don't assume it):
        #   host_s   — disk/densify/pad time building host blocks
        #   put_s    — host-side device_put issue time
        #   wait_s   — time the CONSUMER would stall: popped block's
        #              transfer not yet complete (overlap shortfall)
        #   consume_s— time the consumer held each block (its compute)
        stats = {"host_s": 0.0, "put_s": 0.0, "wait_s": 0.0,
                 "consume_s": 0.0, "n_blocks": int(self.n_blocks),
                 "block_rows": int(self.block_rows),
                 "native_reader": readers is not None,
                 "native_reader_reason": why}
        t_pass = _time.perf_counter()
        # k-deep prefetch: device_put is async, so issuing the next k
        # transfers before consuming the current block overlaps DMA with
        # compute (k=1 is the classic double buffer)
        from collections import deque

        pending = deque()
        from ..observability import span

        def pop():
            blk = pending.popleft()
            if measure_wait:
                t0 = _time.perf_counter()
                jax.block_until_ready(blk.arrays)
                stats["wait_s"] += _time.perf_counter() - t0
            return blk

        def emit(blk):
            # consume = wall time the generator is SUSPENDED at this
            # yield — exactly the consumer's per-block work
            t_y = _time.perf_counter()
            yield blk
            stats["consume_s"] += _time.perf_counter() - t_y

        # one span per pass: nests under the enclosing fit span and
        # carries the overlap stats + transfer-counter deltas at close
        with span("stream.pass") as sp:
            # the readiness sync serializes the host loop behind each
            # block's transfer, trading a little overlap for the wait_s
            # signal — only pay it when someone consumes the signal: a
            # recording sink (the span resolved one — bound fit logger
            # or configured trace/metrics path, where an unmeasured 0.0
            # would read as "perfectly overlapped") or an autotune pass
            # recording spans only: a span tracked solely for the
            # watchdog (sinkless, armed timeout) must not switch on the
            # readiness syncs — that would perturb the very timed runs
            # the watchdog observes
            measure_wait = sp.recording or getattr(
                self, "_autotune_pass", False
            )
            try:
                for b in order:
                    t0 = _time.perf_counter()
                    hb = self._block_host(b, readers)
                    t1 = _time.perf_counter()
                    stats["host_s"] += t1 - t0
                    pending.append(self._put(hb))
                    stats["put_s"] += _time.perf_counter() - t1
                    if len(pending) > self.prefetch:
                        yield from emit(pop())
                while pending:
                    yield from emit(pop())
            finally:
                stats["pass_s"] = _time.perf_counter() - t_pass
                self.stats = stats
                self._passes = getattr(self, "_passes", 0) + 1
                # the span record IS the per-pass JSONL record (via the
                # thread-bound fit logger or the configured trace sink);
                # `stream_pass` keys it for consumers and the report CLI.
                # n_rows: the pass's valid rows — the report derives
                # samples/s (and, with program tracking on, measured MFU
                # from the ctr_program_flops delta this span carries —
                # the consumer's compute runs while the generator is
                # suspended INSIDE this span)
                # passes_total (known inside epochs()) lets the live
                # plane derive an ETA from the pass clock — host ints
                tot = getattr(self, "_epochs_total", None)
                if tot:
                    sp.add(passes_total=int(tot))
                sp.add(stream_pass=self._passes, n_rows=int(self.n_rows),
                       **{k: (round(v, 6) if isinstance(v, float) else v)
                          for k, v in stats.items()})
                if readers:
                    for r in readers:
                        if r is not None:
                            r.close()

    def _maybe_grow_blocks(self):
        """Epoch-boundary block autotune: when a pass spends more HOST
        time preparing blocks (slice/densify/pad + put issue) than the
        consumer holds them, the per-block fixed costs dominate — double
        the block so fewer, larger transfers amortize them. wait_s is
        deliberately NOT part of the signal: under async dispatch the
        device's compute backlog surfaces as transfer wait, and growing
        blocks doesn't reduce bytes moved — it would misfire on
        compute-bound fits. Only between ``epochs()`` passes (per-block
        solver state like ADMM's never sees a resize), at most twice,
        and only when there are enough blocks that halving their count
        still keeps the mesh busy."""
        st = getattr(self, "stats", None)
        if st is None or self._passes > 2 or self.n_blocks < 16:
            return
        if self.sparse_plan is not None:
            # the sparse staging plan (capacities, per-shard nnz) is
            # keyed to the block partition — a mid-fit resize would
            # invalidate it
            return
        if not self._pass_data_bound(st):
            return
        shards = data_shards(self.mesh)
        # never grow past the byte budget that bounds device footprint
        # (a block already AT the budget stays there)
        budget_rows = max(_AUTO_BLOCK_BYTES // max(self._row_bytes, 1), 1)
        cap = min(int(np.ceil(self.n_rows / shards)) * shards,
                  max(budget_rows, self.block_rows))
        new_rows = min(self.block_rows * 2, cap)
        # a grown block must stay a SHARD MULTIPLE: the byte-budget cap
        # is not rounded, and the sharded superblock flavor's per-shard
        # staging/counts (block_rows / D exactly) require even division
        new_rows = max(new_rows // shards * shards, shards)
        if new_rows <= self.block_rows:
            return
        self.block_rows = new_rows
        self.n_blocks = int(np.ceil(self.n_rows / self.block_rows))

    def __len__(self):
        return self.n_blocks

    def epochs(self, n_epochs, autotune=None):
        if autotune is None:
            from ..config import get_config

            autotune = get_config().stream_autotune
        self._autotune_pass = bool(autotune)  # enables wait_s measuring
        self._epochs_total = int(n_epochs)    # pass spans carry it (ETA)
        try:
            for e in range(n_epochs):
                yield from self
                if autotune and e < n_epochs - 1:
                    self._maybe_grow_blocks()
        finally:
            self._autotune_pass = False
            self._epochs_total = None

    # -- super-block execution (ISSUE 3 tentpole) -------------------------
    # K fixed-shape blocks stack into one [K, block_rows, d] device
    # buffer; a consumer runs ONE jitted lax.scan per super-block with a
    # donated carry — one XLA dispatch per K blocks instead of K, no
    # host round-trip inside the scan.

    def resolve_superblock_k(self) -> int:
        """Blocks per super-block for this stream: the K autotuner's
        override, else ``config.superblock_k``, else the auto policy
        (8, capped by the pass length and the super-block byte budget).
        1 — the per-block path — when the source is sparse and densifies
        (ragged CSR densify slices stage per-block; the fixed staging
        ring would re-densify whole slabs)."""
        from ..config import get_config

        cfg = get_config()
        if any(_is_sparse_source(a) for a in self.arrays):
            if self.sparse_plan is None:
                return 1
            # device-resident sparse blocks stack like dense slabs; the
            # K byte budget reasons about the bucketed-nnz triples plus
            # the dense side arrays, not the n x d densification
            k = self._superblock_k_override or int(cfg.superblock_k)
            if k <= 0:
                k = _AUTO_SUPERBLOCK_K
            dense_bytes = sum(
                4 * int(np.prod(a.shape[1:], dtype=np.int64) or 1)
                for a in self.arrays[1:]
            ) * self.block_rows
            block_bytes = max(
                self.sparse_plan.block_bytes() + dense_bytes, 1
            )
            budget_k = max(_SUPERBLOCK_BYTES // block_bytes, 1)
            return int(max(min(k, self.n_blocks, budget_k), 1))
        k = self._superblock_k_override or int(cfg.superblock_k)
        if k <= 0:
            k = _AUTO_SUPERBLOCK_K
        block_bytes = max(self.block_rows * self._x_row_bytes, 1)
        budget_k = max(_SUPERBLOCK_BYTES // block_bytes, 1)
        return int(max(min(k, self.n_blocks, budget_k), 1))

    def use_superblocks(self) -> bool:
        """True when a fused-scan consumer should take the super-block
        path (K > 1); False falls back to the per-block loop."""
        return self.resolve_superblock_k() > 1

    def sb_data_shards(self) -> int:
        """Data-axis shards of this stream's mesh — the D the sharded
        superblock flavor (shard_map + psum scan programs) runs over.
        1 means the single-device programs run untouched (their jaxprs
        stay byte-identical to the pre-mesh feature)."""
        return max(data_shards(self.mesh), 1)

    def sb_model_shards(self) -> int:
        """Model-axis shards the X super-blocks actually TILE over —
        M of the 2-D flavor. 1 on 1-D meshes AND whenever the X
        position couldn't tile (sparse / non-2-D / d not divisible:
        see ``model_tile_reason``), so consumers can branch on this
        one number."""
        return model_shards(self.mesh) if self.model_tiled else 1

    def sb_sharded(self) -> bool:
        """True when super-blocks stage device-sharded (over "data",
        "model", or both) and consumers should run their
        shard_map/psum scan flavor."""
        return self.sb_data_shards() > 1 or self.sb_model_shards() > 1

    def sb_sparse(self) -> bool:
        """True when super-blocks stage as device-resident bucketed-nnz
        sparse slabs (``SuperBlock.arrays[0]`` is a SparseSlab) and
        consumers should run their ``superblock.sparse.*`` flavor."""
        return self.sparse_plan is not None and self.use_superblocks()

    def _shard_counts_of(self, counts):
        """(D, K) per-shard valid-row counts: shard s owns rows
        [s*Sd, (s+1)*Sd) of every block (Sd = block_rows / D — the
        stream rounds block_rows to a shard multiple), so a ragged
        tail block fills shard 0..j and pads the rest with ZERO
        counts, exactly like the ragged final super-block pads its
        missing block slots."""
        D = self.sb_data_shards()
        sd = self.block_rows // D
        return np.clip(
            counts[None, :].astype(np.int64)
            - np.arange(D, dtype=np.int64)[:, None] * sd,
            0, sd,
        ).astype(np.int32)

    def _check_device_budget(self, k):
        """Enforce ``config.stream_device_byte_budget`` (0 = off): the
        bytes ONE device holds for a staged super-block — K blocks x
        its (block_rows/D) row slab x its (d/M when the X position
        tiles, else d) feature tile, per array, at the 4-byte staging
        dtype — must fit the simulated budget, else the fit refuses
        typed (``StreamBudgetExceeded``) instead of letting a wide-d
        1-D fit blow past per-chip HBM on real hardware."""
        from ..config import get_config

        budget = int(get_config().stream_device_byte_budget)
        if budget <= 0:
            return
        D = self.sb_data_shards()
        M = self.sb_model_shards()
        per_dev = 0
        for i, a in enumerate(self.arrays):
            feat = int(np.prod(
                getattr(a, "shape", (0,))[1:], dtype=np.int64) or 1)
            if i == 0 and self.model_tiled:
                feat = -(-feat // M)
            per_dev += int(k) * (self.block_rows // D) * feat * 4
        if per_dev > budget:
            raise StreamBudgetExceeded(
                f"staged super-block needs {per_dev} bytes per device "
                f"(K={k}, block_rows={self.block_rows}, mesh "
                f"{mesh_str(self.mesh)}), over the simulated "
                f"stream_device_byte_budget={budget}. Shard the "
                "over-budget axis: set config.mesh_shape to a 2-D "
                "'DxM' so X stages as (rows/D, d/M) per-device tiles "
                "(per-device bytes flat in d), or lower superblock_k / "
                "stream_block_rows."
            )

    def _put_sharded(self, a, sharding):
        """One batch-sharded ``jax.Array`` from PER-SHARD host slabs,
        each placed onto its own device (the overlapped staging worker
        issues the D per-device transfers together — one slab, one
        device, no runtime-side splitting of a monolithic host
        buffer). Slabs of a C-contiguous source whose shard boundary
        falls on a row boundary are zero-copy VIEWS until the transfer
        reads them."""
        from ..observability import record_shard_staging
        from ..reliability.faults import fire_plan

        fire_plan(self._fault_spec, "stream_put_sharded")
        imap = sharding.devices_indices_map(a.shape)
        devs = list(imap)
        slabs = [np.ascontiguousarray(a[imap[dv]]) for dv in devs]
        parts = jax.device_put(slabs, devs)
        record_shard_staging(len(devs))
        return jax.make_array_from_single_device_arrays(
            a.shape, sharding, parts
        )

    def _sb_ring(self, k):
        """Fixed ring of host staging slabs, one slab set per in-flight
        transfer: super-block i+1 is assembled and its device_put issued
        while the consumer still scans super-block i (the double-buffer
        pattern lifted one level). A slot is refilled only after its
        previous transfer is confirmed complete — device_put reads the
        host buffer asynchronously, and overwriting a buffer mid-read
        would corrupt the transfer."""
        shape_key = (k, self.block_rows)
        ring = getattr(self, "_ring", None)
        if ring is not None and self._ring_key == shape_key:
            return ring
        n_slots = self.prefetch + 2
        ring = [self._sb_slot(k) for _ in range(n_slots)]
        self._ring = ring
        self._ring_key = shape_key
        return ring

    def _guard_sb_block(self, slot, j, m, counts):
        """Apply ``stream_nonfinite`` to one staged super-block slot:
        a non-finite block either raises typed or quarantines — data
        zeroed and ``counts[j]`` folded to 0, exactly the shape the
        ragged-final-super-block padding already compiles for (no new
        program, no recompile; the scan's masked prefix-count drops
        it). No-op at the default policy."""
        if self._nonfinite == "off" or m == 0:
            return
        if all(bool(np.isfinite(buf[j, :m]).all())
               for buf in slot["bufs"]):
            return
        from ..reliability.faults import NonFiniteBlock

        if self._nonfinite == "raise":
            raise NonFiniteBlock(
                f"non-finite values in streamed super-block slot {j} "
                f"({m} rows; config.stream_nonfinite='raise')"
            )
        from ..observability import record_stream_quarantine

        counts[j] = 0
        for buf in slot["bufs"]:
            buf[j] = 0
        record_stream_quarantine()

    def _sb_slot(self, k):
        return {
            "bufs": [
                np.zeros((k, self.block_rows) + a.shape[1:], self.dtype)
                for a in self.arrays
            ],
            "counts": np.zeros(k, np.int32),
            "dev": None,
        }

    def superblocks(self, order=None):
        """One prefetched pass over K-stacked super-blocks.

        ``order`` (default: all blocks once, shuffled when the stream
        shuffles) is the sequence of block indices the consumer's scan
        steps through — block j of super-block i is ``order[i*K + j]``.
        An explicit ``order`` may be any length and revisit blocks (the
        adaptive-search cohort plane streams each round's block-step
        TIMELINE through here, ISSUE 14). The final super-block pads
        missing slots with zero counts so every dispatch has the
        identical [K, block_rows, d] shape."""
        if self.sparse_plan is not None:
            # device-resident sparse staging (ISSUE 13): bucketed-nnz
            # COO triples instead of densified slabs, same dispatch /
            # counts / sharding contract
            yield from self._superblocks_sparse(order)
            return
        import time as _time

        from ..observability import (record_superblock, record_transfer,
                                     span)

        k = self.resolve_superblock_k()
        if order is None:
            order = np.arange(self.n_blocks)
            if self.shuffle:
                self.rng.shuffle(order)
        order = np.asarray(order, np.int64)
        n_sb = max(int(np.ceil(len(order) / k)), 1)
        sequential = bool(
            len(order) == self.n_blocks
            and np.array_equal(order, np.arange(self.n_blocks))
        )
        readers, why = self._native_readers(sequential=sequential)
        # aliasing backends (device_put zero-copies host memory, see
        # _device_put_aliases) can never see a REUSED staging buffer — a
        # queued consumer computation would read the refill: no ring
        # there, a fresh slab set per super-block
        ring = None if _device_put_aliases() else self._sb_ring(k)
        D = self.sb_data_shards()
        sharded = self.sb_sharded()
        self._check_device_budget(k)
        stats = {"host_s": 0.0, "put_s": 0.0, "wait_s": 0.0,
                 "consume_s": 0.0, "n_blocks": int(len(order)),
                 "block_rows": int(self.block_rows),
                 "superblock_k": int(k),
                 "sb_shards": int(D),
                 "sb_model_shards": int(self.sb_model_shards()),
                 # pass-span mesh tag: the 2-D shape the report CLI /
                 # /status render as "DxM"
                 "mesh": mesh_str(self.mesh),
                 "dispatches_per_pass": int(n_sb),
                 # whether the C++ readahead reader fed the pass, else
                 # why not
                 "native_reader": readers is not None,
                 "native_reader_reason": why}
        t_pass = _time.perf_counter()
        from collections import deque

        pending = deque()

        def fill(slot, blocks):
            """Assemble ``blocks`` (block indices) into the slot's
            stacked host slabs and valid-row counts."""
            if slot["dev"] is not None:
                # the slot's previous transfer must have committed
                # before its host buffer is rewritten
                jax.block_until_ready(slot["dev"])
                slot["dev"] = None
            counts = slot["counts"]
            counts[:] = 0
            for j, b in enumerate(blocks):
                lo = int(b) * self.block_rows
                hi = min(lo + self.block_rows, self.n_rows)
                m = hi - lo
                counts[j] = m
                for i, a in enumerate(self.arrays):
                    buf = slot["bufs"][i]
                    self._read_block_host(i, a, lo, hi, readers,
                                          out=buf[j])
                    if i == 0:
                        self._profile_fold(buf[j, :m])
                    if m < self.block_rows:
                        buf[j, m:] = 0
                self._guard_sb_block(slot, j, m, counts)
            for buf in slot["bufs"]:
                buf[len(blocks):] = 0

        shard_counts_of = self._shard_counts_of

        def put(slot, n_real):
            parts, counts = slot["bufs"], slot["counts"]
            record_transfer(sum(b.nbytes for b in parts) + counts.nbytes)
            counts_d = jax.device_put(counts, self._counts_sharding)
            if sharded:
                # data-parallel staging (ISSUE 9): each array becomes a
                # batch-sharded jax.Array assembled from per-shard host
                # slabs placed onto their own device — the consumer's
                # shard_map scan then reads purely local rows and pays
                # ONE psum per super-block for its reducers
                dev = tuple(
                    self._put_sharded(b, s)
                    for b, s in zip(parts, self._sb_shardings)
                )
                shard_d = self._put_sharded(
                    shard_counts_of(counts), self._shard_counts_sharding
                )
                slot["dev"] = dev + (counts_d, shard_d)
                return SuperBlock(dev, counts_d, n_real,
                                  int(counts[:n_real].sum()),
                                  shard_counts=shard_d)
            dev = tuple(
                jax.device_put(b, s)
                for b, s in zip(parts, self._sb_shardings)
            )
            slot["dev"] = dev + (counts_d,)
            return SuperBlock(dev, counts_d, n_real,
                              int(counts[:n_real].sum()))

        def produce(i):
            """Stage + transfer super-block i (runs on the ONE staging
            worker thread, so slot and reader order stay sequential):
            assembly and device_put of super-block i+1 proceed while
            the consumer's scan over super-block i runs — on backends
            whose device_put is a synchronous host copy (CPU) the
            thread is what makes the overlap real."""
            blocks = order[i * k:(i + 1) * k]
            slot = self._sb_slot(k) if ring is None \
                else ring[i % len(ring)]
            t0 = _time.perf_counter()
            fill(slot, blocks)
            t1 = _time.perf_counter()
            stats["host_s"] += t1 - t0
            sb = put(slot, len(blocks))
            stats["put_s"] += _time.perf_counter() - t1
            return sb

        def pop():
            fut = pending.popleft()
            # the consumer's true stall: staging/transfer not done yet
            t0 = _time.perf_counter()
            sb = fut.result()
            if measure_wait:
                jax.block_until_ready(sb.arrays)
            stats["wait_s"] += _time.perf_counter() - t0
            return sb

        def emit(sb):
            # the superblock dispatch boundary fault site: a "crash"
            # arm here aborts the consumer MID-PASS — the in-process
            # stand-in for a killed fit that the pass-granular
            # checkpoint/resume machinery recovers from
            from ..reliability.faults import fire_plan

            fire_plan(self._fault_spec, "superblock_dispatch")
            record_superblock(sb.n_blocks)
            t_y = _time.perf_counter()
            yield sb
            stats["consume_s"] += _time.perf_counter() - t_y

        from concurrent.futures import ThreadPoolExecutor

        staging = ThreadPoolExecutor(max_workers=1)
        with span("streaming.superblock") as sp:
            # recording spans only: a span tracked solely for the
            # watchdog (sinkless, armed timeout) must not switch on the
            # readiness syncs — that would perturb the very timed runs
            # the watchdog observes
            measure_wait = sp.recording or getattr(
                self, "_autotune_pass", False
            )
            try:
                for i in range(n_sb):
                    pending.append(staging.submit(produce, i))
                    if len(pending) > self.prefetch:
                        yield from emit(pop())
                while pending:
                    yield from emit(pop())
            finally:
                staging.shutdown(wait=True)
                stats["pass_s"] = _time.perf_counter() - t_pass
                self.stats = stats
                self._passes = getattr(self, "_passes", 0) + 1
                # n_rows: valid rows this pass's `order` actually covered
                # (a partial-order pass must not claim the whole dataset)
                pass_rows = int(sum(
                    min((int(b) + 1) * self.block_rows, self.n_rows)
                    - int(b) * self.block_rows
                    for b in order
                ))
                tot = getattr(self, "_epochs_total", None)
                if tot:
                    sp.add(passes_total=int(tot))
                sp.add(stream_pass=self._passes,
                       dispatches=int(n_sb), n_rows=pass_rows,
                       **{key: (round(v, 6) if isinstance(v, float) else v)
                          for key, v in stats.items()})
                if readers:
                    for r in readers:
                        if r is not None:
                            r.close()
                # process-spanning pass barrier (multi-host streaming):
                # every process streams the same pass sequence, so the
                # sync matches up; behind the runtime capability probe —
                # a backend that cannot span processes makes this a
                # no-op instead of a crash
                from . import distributed as dist

                if dist.process_count() > 1:
                    dist.sync_stream_pass("superblock_pass")

    def superblock_epochs(self, n_epochs, autotune=None):
        """Epoch iterator over super-blocks (the superblocks() analog of
        :meth:`epochs`): shuffle redraws per pass, and opt-in autotune
        may grow the blocks AND the K between passes (each resize
        recompiles the consumer's scan once)."""
        if autotune is None:
            from ..config import get_config

            autotune = get_config().stream_autotune
        self._autotune_pass = bool(autotune)
        self._epochs_total = int(n_epochs)
        try:
            for e in range(n_epochs):
                yield from self.superblocks()
                if autotune and e < n_epochs - 1:
                    self._maybe_grow_blocks()
                    self._maybe_grow_superblock()
        finally:
            self._autotune_pass = False
            self._epochs_total = None

    def _pass_data_bound(self, st):
        """Was the last pass limited by data movement? Per-block passes
        compare the generator's staging time against the consumer's
        hold time (the original signal). Super-block passes stage on a
        BACKGROUND worker — host_s/put_s there are overlapped busy
        time, not consumer cost, and consume_s is mostly async dispatch
        issue — so the signal is the consumer's measured STALL: wait_s
        above 10% of the pass."""
        if "superblock_k" in st:
            return st.get("wait_s", 0.0) > 0.1 * max(
                st.get("pass_s", 0.0), 1e-9
            )
        return st["host_s"] + st["put_s"] > st["consume_s"]

    def _maybe_grow_superblock(self):
        """Epoch-boundary K autotune, alongside ``_maybe_grow_blocks``:
        when the consumer still stalls on staged data at the current K,
        double K so one scan amortizes more blocks and staging batches
        further ahead. Unlike block growth this never changes the
        minibatch partition (results are identical at any K); it is
        still opt-in-only because a resize recompiles the scan, and
        steady-state passes must stay at zero recompiles. Capped by the
        super-block byte budget and the pass length."""
        st = getattr(self, "stats", None)
        if st is None or "superblock_k" not in st:
            return
        if not self._pass_data_bound(st):
            return
        k = int(st["superblock_k"])
        block_bytes = max(self.block_rows * self._x_row_bytes, 1)
        cap = int(max(min(self.n_blocks,
                          _SUPERBLOCK_BYTES // block_bytes), 1))
        new_k = min(k * 2, cap)
        if new_k > k:
            self._superblock_k_override = new_k

    # -- device-resident sparse staging (ISSUE 13 tentpole) ---------------
    # A sparse X stages as fixed-shape bucketed-nnz COO triples
    # (data/cols/rows padded to the plan's capacity) stacked K-deep —
    # the sparse twin of the dense super-block path: same fixed host
    # ring, same overlapped staging worker, same counts/shard_counts
    # and dispatch contract, O(nnz) staged bytes instead of O(S * d).

    def _sp_ring(self, k):
        plan = self.sparse_plan
        D = self.sb_data_shards()
        width = plan.cap * D
        shape_key = ("sparse", k, self.block_rows, width)
        ring = getattr(self, "_sparse_ring", None)
        if ring is not None and self._sparse_ring_key == shape_key:
            return ring
        n_slots = self.prefetch + 2

        def slot():
            return {
                "data": np.zeros((k, width), np.float32),
                "cols": np.zeros((k, width), np.int32),
                "rows": np.zeros((k, width), np.int32),
                "bufs": [
                    np.zeros((k, self.block_rows) + a.shape[1:],
                             self.dtype)
                    for a in self.arrays[1:]
                ],
                "counts": np.zeros(k, np.int32),
                "dev": None,
            }

        ring = [slot() for _ in range(n_slots)]
        self._sparse_ring = ring
        self._sparse_ring_key = shape_key
        self._sparse_slot_fn = slot
        return ring

    def _guard_sparse_slot(self, slot, j, m, counts):
        """``stream_nonfinite`` for one sparse-staged slot: non-finite
        VALUES (the dense side arrays are checked too) raise typed or
        quarantine — data zeroed, count folded to 0, no shape change."""
        if self._nonfinite == "off" or m == 0:
            return
        finite = bool(np.isfinite(slot["data"][j]).all()) and all(
            bool(np.isfinite(buf[j, :m]).all()) for buf in slot["bufs"]
        )
        if finite:
            return
        from ..reliability.faults import NonFiniteBlock

        if self._nonfinite == "raise":
            raise NonFiniteBlock(
                f"non-finite values in streamed sparse super-block slot "
                f"{j} ({m} rows; config.stream_nonfinite='raise')"
            )
        from ..observability import record_stream_quarantine

        counts[j] = 0
        slot["data"][j] = 0
        slot["cols"][j] = 0
        slot["rows"][j] = 0
        for buf in slot["bufs"]:
            buf[j] = 0
        record_stream_quarantine()

    def _superblocks_sparse(self, order=None):
        """The sparse flavor of :meth:`superblocks`: one prefetched pass
        of K-stacked bucketed-nnz slabs. Identical stats keys, span
        record, fault sites, counts semantics and (on a >1-shard mesh)
        per-shard staging + ``shard_counts`` — consumers see
        ``SuperBlock.arrays[0]`` as a :class:`SparseSlab` and select
        their ``superblock.sparse.*`` scan programs."""
        import time as _time
        from collections import deque

        from ..observability import (record_sparse_staging,
                                     record_superblock, record_transfer,
                                     span)
        from ..reliability import faults as _flt
        from .sparse_stream import SparseSlab, pack_block

        plan = self.sparse_plan
        k = self.resolve_superblock_k()
        if order is None:
            order = np.arange(self.n_blocks)
            if self.shuffle:
                self.rng.shuffle(order)
        order = np.asarray(order, np.int64)
        n_sb = max(int(np.ceil(len(order) / k)), 1)
        ring = self._sp_ring(k)
        D = self.sb_data_shards()
        sharded = D > 1
        sd = self.block_rows // D
        cap = plan.cap
        sp_sharding = NamedSharding(
            self.mesh, P(None, DATA_AXIS) if sharded else P()
        )
        stats = {"host_s": 0.0, "put_s": 0.0, "wait_s": 0.0,
                 "consume_s": 0.0, "n_blocks": int(len(order)),
                 "block_rows": int(self.block_rows),
                 "superblock_k": int(k),
                 "sb_shards": int(D),
                 "sb_model_shards": 1,
                 "mesh": mesh_str(self.mesh),
                 "dispatches_per_pass": int(n_sb),
                 "sparse_cap": int(cap)}
        t_pass = _time.perf_counter()
        pending = deque()

        def fill(slot, blocks):
            if slot["dev"] is not None:
                jax.block_until_ready(slot["dev"])
                slot["dev"] = None
            counts = slot["counts"]
            counts[:] = 0
            nnz = 0
            X = self.arrays[0]
            for j, b in enumerate(blocks):
                lo = int(b) * self.block_rows
                hi = min(lo + self.block_rows, self.n_rows)
                m = hi - lo
                counts[j] = m

                def pack():
                    _flt.fire_plan(self._fault_spec, "staging_read")
                    return pack_block(
                        X, lo, hi, D, sd, cap, slot["data"][j],
                        slot["cols"][j], slot["rows"][j],
                    )

                nnz += self._retry_io(
                    pack, f"sparse staging read of rows [{lo}, {hi})"
                )
                self._profile_fold_sparse(X, lo, hi)
                for i, a in enumerate(self.arrays[1:], start=1):
                    buf = slot["bufs"][i - 1]
                    self._read_block_host(i, a, lo, hi, None,
                                          out=buf[j])
                    if m < self.block_rows:
                        buf[j, m:] = 0
                self._guard_sparse_slot(slot, j, m, counts)
            for j in range(len(blocks), k):
                slot["data"][j] = 0
                slot["cols"][j] = 0
                slot["rows"][j] = 0
                for buf in slot["bufs"]:
                    buf[j] = 0
            return nnz

        def put(slot, counts, n_real, nnz):
            nbytes = (slot["data"].nbytes + slot["cols"].nbytes
                      + slot["rows"].nbytes
                      + sum(b.nbytes for b in slot["bufs"])
                      + counts.nbytes)
            record_transfer(nbytes)
            record_sparse_staging(n_real, nnz)
            if sharded:
                triple = tuple(
                    self._put_sharded(slot[name], sp_sharding)
                    for name in ("data", "cols", "rows")
                )
                dense_d = tuple(
                    self._put_sharded(buf, self._sb_shardings[i + 1])
                    for i, buf in enumerate(slot["bufs"])
                )
                counts_d = jax.device_put(counts, self._counts_sharding)
                shard_d = self._put_sharded(
                    self._shard_counts_of(counts),
                    self._shard_counts_sharding,
                )
            else:
                def putp():
                    _flt.fire_plan(self._fault_spec, "stream_put")
                    t = tuple(
                        jax.device_put(slot[name], sp_sharding)
                        for name in ("data", "cols", "rows")
                    )
                    dd = tuple(
                        jax.device_put(buf, self._sb_shardings[i + 1])
                        for i, buf in enumerate(slot["bufs"])
                    )
                    return t, dd, jax.device_put(
                        counts, self._counts_sharding
                    )

                triple, dense_d, counts_d = self._retry_io(
                    putp, "sparse device staging put"
                )
                shard_d = None
            slab = SparseSlab(*triple, n_rows=sd,
                              n_features=plan.n_features, shards=D,
                              cap=cap)
            slot["dev"] = triple + dense_d + (counts_d,)
            return SuperBlock((slab,) + dense_d, counts_d, n_real,
                              int(counts[:n_real].sum()),
                              shard_counts=shard_d)

        def produce(i):
            blocks = order[i * k:(i + 1) * k]
            slot = self._sparse_slot_fn() if _device_put_aliases() \
                else ring[i % len(ring)]
            t0 = _time.perf_counter()
            nnz = fill(slot, blocks)
            t1 = _time.perf_counter()
            stats["host_s"] += t1 - t0
            sb = put(slot, slot["counts"], len(blocks), nnz)
            stats["put_s"] += _time.perf_counter() - t1
            return sb

        def pop():
            fut = pending.popleft()
            t0 = _time.perf_counter()
            sb = fut.result()
            if measure_wait:
                jax.block_until_ready(
                    (sb.arrays[0].data,) + sb.arrays[1:]
                )
            stats["wait_s"] += _time.perf_counter() - t0
            return sb

        def emit(sb):
            _flt.fire_plan(self._fault_spec, "superblock_dispatch")
            record_superblock(sb.n_blocks)
            t_y = _time.perf_counter()
            yield sb
            stats["consume_s"] += _time.perf_counter() - t_y

        from concurrent.futures import ThreadPoolExecutor

        staging = ThreadPoolExecutor(max_workers=1)
        with span("streaming.superblock") as sp_:
            measure_wait = sp_.recording or getattr(
                self, "_autotune_pass", False
            )
            try:
                for i in range(n_sb):
                    pending.append(staging.submit(produce, i))
                    if len(pending) > self.prefetch:
                        yield from emit(pop())
                while pending:
                    yield from emit(pop())
            finally:
                staging.shutdown(wait=True)
                stats["pass_s"] = _time.perf_counter() - t_pass
                self.stats = stats
                self._passes = getattr(self, "_passes", 0) + 1
                pass_rows = int(sum(
                    min((int(b) + 1) * self.block_rows, self.n_rows)
                    - int(b) * self.block_rows
                    for b in order
                ))
                tot = getattr(self, "_epochs_total", None)
                if tot:
                    sp_.add(passes_total=int(tot))
                sp_.add(stream_pass=self._passes,
                        dispatches=int(n_sb), n_rows=pass_rows,
                        **{key: (round(v, 6) if isinstance(v, float)
                                 else v)
                           for key, v in stats.items()})
                from . import distributed as dist

                if dist.process_count() > 1:
                    dist.sync_stream_pass("superblock_pass")

    def sparse_block_put(self, b):
        """Stage ONE block as a single-slab sparse triple plus the
        dense side arrays — the grad-accum micro path's per-block
        staging (single-device placement; the grad-accum flavor merges
        on host). Returns (SparseSlab, dense device arrays, mask, m)."""
        from .sparse_stream import SparseSlab, pack_block

        plan = self.sparse_plan
        cap = plan.cap1
        lo = int(b) * self.block_rows
        hi = min(lo + self.block_rows, self.n_rows)
        m = hi - lo
        data = np.zeros(cap, np.float32)
        cols = np.zeros(cap, np.int32)
        rows = np.zeros(cap, np.int32)
        pack_block(self.arrays[0], lo, hi, 1, self.block_rows, cap,
                   data, cols, rows)
        dense = []
        for i, a in enumerate(self.arrays[1:], start=1):
            blk = self._read_block_host(i, a, lo, hi, None)
            if m < self.block_rows:
                pad = [(0, self.block_rows - m)] \
                    + [(0, 0)] * (blk.ndim - 1)
                blk = np.pad(blk, pad)
            dense.append(blk)
        mask = np.zeros(self.block_rows, self.dtype)
        mask[:m] = 1.0
        devs = jax.device_put([data, cols, rows] + dense + [mask],
                              NamedSharding(self.mesh, P()))
        slab = SparseSlab(*devs[:3], n_rows=self.block_rows,
                          n_features=plan.n_features, shards=1, cap=cap)
        return slab, tuple(devs[3:-1]), devs[-1], m


def streamed_map(X, block_rows, fn):
    """Map ``fn(block) -> host array (block_valid_rows, ...)`` over X's
    blocks and concatenate — the one stream→compute→host pattern shared by
    every streamed inference path (GLM decision values, KMeans labels /
    distances, PCA scores). ``fn`` receives the padded device block; its
    output is sliced to the block's logical rows here."""
    from ..config import get_config

    # inference streams must keep row alignment: quarantining (dropping)
    # a block would silently misalign the concatenated output against
    # the input rows, so the quarantine policy hardens to "raise" here
    nf = get_config().stream_nonfinite
    outs = []
    for blk in BlockStream((X,), block_rows=block_rows, profile=False,
                           nonfinite="raise" if nf != "off" else "off"):
        outs.append(np.asarray(fn(blk))[: blk.n_rows])
    return np.concatenate(outs, axis=0)
