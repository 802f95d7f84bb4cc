"""KMeans with k-means‖ (scalable k-means++) initialization.

Reference: ``dask_ml/cluster/k_means.py`` (SURVEY.md §2a KMeans row, §3.1
call stack): Lloyd's iterations over row-chunked arrays with a global
barrier per iteration, k-means‖ init (Bahmani 2012) with
``oversampling_factor``, plus ``init='k-means++'`` (on a sample) and
``'random'``.

TPU design (SURVEY.md §3.1 "boundary pattern" + §7 hard parts):

- The ENTIRE Lloyd loop is one jitted program (``lax.while_loop``):
  distance+argmin fuses into the MXU matmul, centroid sums/counts are
  ``segment_sum`` (memory-light — no (n, k) one-hot materialized), centers
  stay replicated, the tol test runs on device. The reference pays a
  cluster round-trip per iteration; here the host is only touched once.
- k-means‖ sampling draws a FIXED ``l = oversampling_factor * k`` points
  per round via Gumbel top-l with weights ∝ d² (weighted sampling without
  replacement), writing into a static-shape candidate buffer — XLA-friendly
  static shapes instead of the reference's variable-size Bernoulli draws
  (expected size l), same distribution in spirit.
- The final "cluster the candidates" step runs sklearn's k-means++ on the
  ≤(1 + l·rounds) weighted candidates on host, exactly the reference's
  pattern of running a local solver on the tiny candidate set.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..base import BaseEstimator, ClusterMixin, TransformerMixin, to_host
from ..ops.pairwise import euclidean_distances, euclidean_distances_sq
from ..ops.reductions import (small_segment_count, top_l_indices,
                               top_l_path)
from ..parallel.sharded import ShardedArray
from ..utils.validation import check_array, check_is_fitted


# -- jitted kernels ---------------------------------------------------------

from ..observability import current_span, emit_jit_step, span, track_program
from ..plans import tracked as plan_tracked


class _AmbientPhase:
    """Stands in for a phase's span where a resident fit runs INSIDE another
    estimator's fit (``KMeans._fit_inner``): it opens nothing and keeps no
    attribute; a wait is charged to the span the caller has open."""

    def __init__(self, name=None, **attrs):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add(self, **attrs):
        return self

    def sync(self, value):
        return current_span().sync(value)


@track_program("kmeans.lloyd")
@partial(jax.jit, static_argnames=("log", "mxu_dtype"))
def _lloyd_run(X, mask, centers0, max_iter, tol2, log=False,
               mxu_dtype=None):
    """Full Lloyd loop on device. Returns (centers, n_iter, final_shift2).

    ``mxu_dtype=jnp.bfloat16`` (config.dtype="bfloat16"): the distance
    cross-term matmul — the loop's FLOPs — runs at bf16 with f32
    accumulation; centroid sums/counts and the shift stay f32 (input
    data is untouched). Center parity vs f32 is ~1e-2 relative (bf16
    input rounding on distances can flip assignments of near-equidistant
    points)."""
    k = centers0.shape[0]

    def assign(centers):
        d2 = euclidean_distances_sq(X, centers, mxu_dtype=mxu_dtype)
        return jnp.argmin(d2, axis=1), jnp.min(d2, axis=1)

    def cond(carry):
        centers, it, shift2 = carry
        return (it < max_iter) & (shift2 > tol2)

    def body(carry):
        centers, it, _ = carry
        labels, _ = assign(centers)
        sums = jax.ops.segment_sum(X * mask[:, None], labels, num_segments=k)
        counts = jax.ops.segment_sum(mask, labels, num_segments=k)
        new = jnp.where(counts[:, None] > 0, sums / counts[:, None], centers)
        shift2 = jnp.sum((new - centers) ** 2)
        if log:
            emit_jit_step(it, center_shift2=shift2)
        return new, it + 1, shift2

    inf = jnp.asarray(jnp.inf, X.dtype)
    centers, it, shift2 = jax.lax.while_loop(cond, body, (centers0, 0, inf))
    return centers, it, shift2


@track_program("kmeans.lloyd_pallas")
@partial(jax.jit, static_argnames=("mesh", "interpret", "log"))
def _lloyd_run_pallas(X, mask, centers0, max_iter, tol2, mesh,
                      interpret=False, log=False):
    """Lloyd loop where each iteration's data pass is the fused Pallas
    kernel (ops/pallas_fused.py): X streams through VMEM once per
    iteration; sums/counts psum over ICI."""
    from jax.sharding import PartitionSpec as P

    from ..ops.pallas_fused import fused_lloyd_stats
    from ..parallel.mesh import DATA_AXIS

    k = centers0.shape[0]

    def shard_step(xs, ms, c):
        # per-shard valid-row count (valid rows are a prefix of each
        # shard's padded rows by construction) — the stats-only kernel
        # takes this scalar instead of an (n, 1) mask operand whose TPU
        # layout would pad 128x in HBM. Integer sum: an f32 accumulator
        # saturates at 2^24 rows, silently dropping rows past 16.7M
        nv = jnp.sum(ms.astype(jnp.int32))
        sums, counts, _ = fused_lloyd_stats(
            xs, nv, c, interpret=interpret
        )
        return (jax.lax.psum(sums, DATA_AXIS),
                jax.lax.psum(counts, DATA_AXIS))

    step = jax.shard_map(
        shard_step, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )

    def cond(carry):
        centers, it, shift2 = carry
        return (it < max_iter) & (shift2 > tol2)

    def body(carry):
        centers, it, _ = carry
        sums, counts = step(X, mask, centers)
        new = jnp.where(counts[:, None] > 0, sums / counts[:, None], centers)
        shift2 = jnp.sum((new - centers) ** 2)
        if log:
            emit_jit_step(it, center_shift2=shift2)
        return new, it + 1, shift2

    inf = jnp.asarray(jnp.inf, X.dtype)
    centers, it, shift2 = jax.lax.while_loop(cond, body, (centers0, 0, inf))
    return centers, it, shift2


@track_program("kmeans.labels_inertia")
@jax.jit
def _labels_inertia(X, mask, centers):
    d2 = euclidean_distances_sq(X, centers)
    labels = jnp.argmin(d2, axis=1)
    inertia = jnp.sum(jnp.min(d2, axis=1) * mask)
    return labels, inertia


@track_program("kmeans.tol_scale")
@jax.jit
def _tol_scale(x, mask, n_rows, tol):
    """``tol`` times the mean per-feature population variance of x, in two
    fused passes over x: plain f32 sums on the vector units (a
    ``tensordot`` with the mask would round x to bf16 on a TPU's MXU), no
    X-sized temporary. Two passes, not ``E[x^2] - E[x]^2``: in f32 that
    loses the variance of a feature whose mean is large beside its
    spread."""
    m = mask[:, None]
    mean = jnp.sum(x * m, axis=0) / n_rows
    xc = (x - mean) * m
    return tol * jnp.mean(jnp.sum(xc * xc, axis=0) / n_rows)


def _lloyd_tol2(X: ShardedArray, mask, tol):
    """(the Lloyd loop's stopping threshold on the squared centre shift,
    the passes over X it took). scikit-learn's rule
    (``sklearn.cluster._kmeans._tolerance``): ``tol`` scaled by the mean
    per-feature variance, and at ``tol == 0`` an exact zero for which X is
    not read."""
    if tol == 0:
        return jnp.asarray(0.0, X.dtype), 0
    return _tol_scale(X.data, mask, np.float32(X.n_rows),
                      np.float32(tol)), 2


@jax.jit
def _cost_to_candidates(X, mask, cands, cand_valid):
    d2 = euclidean_distances_sq(X, cands)
    d2 = jnp.where(cand_valid[None, :] > 0, d2, jnp.inf)
    dmin = jnp.min(d2, axis=1) * mask
    return dmin, jnp.sum(dmin)


def _gumbel_keys(weights, key):
    """Gumbel-perturbed log-weights: top-l of these keys IS a weighted
    sample of l items without replacement (P ∝ weights)."""
    g = jax.random.gumbel(key, weights.shape, dtype=jnp.float32)
    return jnp.where(weights > 0, jnp.log(weights) + g, -jnp.inf)


@partial(jax.jit, static_argnames=("l",))
def _gumbel_top_l(weights, key, l):
    """Indices of l draws without replacement with prob ∝ weights: exactly
    ``lax.top_k``'s, without its full sort (``ops/reductions.py``)."""
    return top_l_indices(_gumbel_keys(weights, key), l)


def _draw(weights, key, l, draws):
    """``_gumbel_top_l``, with the path it takes appended to ``draws`` (a
    list, or None): counted at the host call site, static from the shapes."""
    if draws is not None:
        draws.append(top_l_path(weights.shape[0], l))
    return _gumbel_top_l(weights, key, l)


def _draw_summary(draws):
    """{"draws": how many draws an init dispatched, "draw": the path they
    took — ``"tiled"`` / ``"sort"``, ``"mixed"`` where they differ, ``"none"``
    where there were none}."""
    paths = set(draws)
    path = paths.pop() if len(paths) == 1 else ("mixed" if paths else "none")
    return {"draws": len(draws), "draw": path}


@jax.jit
def _candidate_weights(X, mask, cands, cand_valid):
    """The rows nearest each candidate: a one-hot count fused after the
    argmin, not a scatter-add (``ops/reductions.py``)."""
    d2 = euclidean_distances_sq(X, cands)
    d2 = jnp.where(cand_valid[None, :] > 0, d2, jnp.inf)
    labels = jnp.argmin(d2, axis=1)
    return small_segment_count(labels, mask, cands.shape[0])


def _weigh(X, mask, cands, cand_valid, passes):
    """``_candidate_weights``, with the path of its count appended to
    ``passes`` (a list, or None), as ``_draw`` counts the draws."""
    if passes is not None:
        passes.append("onehot")
    return _candidate_weights(X, mask, cands, cand_valid)


def _weight_summary(passes):
    """{"weight_passes": how many candidate-weight passes an init
    dispatched, "weights": the path of their count — ``"onehot"``, or
    ``"none"`` where there were none}."""
    return {"weight_passes": len(passes),
            "weights": passes[0] if passes else "none"}


# -- streamed (out-of-core) kernels ----------------------------------------
# Host X (np.memmap / big ndarray) streams through BlockStream; each
# kernel returns the per-block partial sums the in-memory while_loop
# computes on the resident array, accumulated across blocks on device.
# The reference's analog IS its normal mode: per-chunk tasks +
# tree-reduce (SURVEY.md §3.1). One Lloyd iteration = one pass.

@track_program("kmeans.stream.block_assign")
@partial(jax.jit, static_argnames=("mxu_dtype",))
def _block_assign_stats(X, mask, centers, mxu_dtype=None):
    """(Σ_block x per label, count per label, Σ_block min-dist²).
    ``mxu_dtype``: same bf16 distance-matmul policy as ``_lloyd_run``;
    stats stay f32."""
    k = centers.shape[0]
    d2 = euclidean_distances_sq(X, centers, mxu_dtype=mxu_dtype)
    labels = jnp.argmin(d2, axis=1)
    sums = jax.ops.segment_sum(X * mask[:, None], labels, num_segments=k)
    counts = jax.ops.segment_sum(mask, labels, num_segments=k)
    inertia = jnp.sum(jnp.min(d2, axis=1) * mask)
    return sums, counts, inertia


@jax.jit
def _block_moments(X, mask):
    return jnp.tensordot(mask, X, axes=(0, 0)), \
        jnp.tensordot(mask, X * X, axes=(0, 0))


@plan_tracked("superblock.kmeans_assign")
@partial(jax.jit, static_argnames=("mxu_dtype",), donate_argnums=(0,))
def _sb_assign_stats(acc, Xs, counts, centers, mxu_dtype=None):
    """Super-block Lloyd pass (ISSUE 3): scan the (K, S, d) stack
    through the per-block assign+update kernel, accumulating
    (sums, counts, inertia) in a DONATED carry — one dispatch per K
    blocks; all-padding slots (counts == 0) contribute zero."""
    r = jnp.arange(Xs.shape[1])

    def step(acc, X, c):
        mask = (r < c).astype(X.dtype)
        s, cnt, i = _block_assign_stats.__wrapped__(
            X, mask, centers, mxu_dtype=mxu_dtype
        )
        return (acc[0] + s, acc[1] + cnt, acc[2] + i)

    def scan_step(acc, inp):
        return step(acc, *inp), jnp.float32(0.0)

    acc, _ = jax.lax.scan(scan_step, acc, (Xs, counts))
    return acc


import functools as _ft


@_ft.lru_cache(maxsize=16)
def _sb_assign_stats_sharded(mesh, mxu_dtype=None, fused=False,
                             interpret=False):
    """Data-parallel flavor of :func:`_sb_assign_stats` (ISSUE 9): the
    K-step assign+accumulate scan runs under ``shard_map`` over the
    stream mesh's "data" axis — each device scans only its own row slab
    of every block (local masks from the per-shard valid-row counts),
    the (sums, counts, inertia) carry stays REPLICATED, and the whole
    super-block pays exactly ONE ``lax.psum`` over "data" to fold the
    local delta into the running carry. Donated at the jit level like
    the single-device flavor.

    ``fused=True`` (ISSUE 12): each shard's block stats come from the
    fused Pallas assign-and-accumulate kernel running INSIDE the
    shard_map on its own (S/D, d) slab — one VMEM pass per block where
    the XLA body reads X twice — with the identical single psum per
    super-block; tracked as ``pallas.kmeans_stream.psum``. Both
    flavors run with ``check_vma=False`` (see ops/linalg.py::tsqr): the
    body psums its own local sums."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, data_shard_spec as spec_of

    if fused:
        from ..ops.pallas_fused import fused_kmeans_block_stats

    def body(acc, Xs, counts, centers):
        r = jnp.arange(Xs.shape[1])
        cts = counts[0]
        local = jax.tree.map(jnp.zeros_like, acc)

        def step(lacc, X, c):
            if fused:
                s, cnt, i = fused_kmeans_block_stats(
                    X, c, centers, mxu=mxu_dtype, interpret=interpret
                )
            else:
                mask = (r < c).astype(X.dtype)
                s, cnt, i = _block_assign_stats.__wrapped__(
                    X, mask, centers, mxu_dtype=mxu_dtype
                )
            return (lacc[0] + s, lacc[1] + cnt, lacc[2] + i)

        def scan_step(lacc, inp):
            return step(lacc, *inp), jnp.float32(0.0)

        local, _ = jax.lax.scan(scan_step, local, (Xs, cts))
        local = jax.lax.psum(local, DATA_AXIS)
        return tuple(a + l for a, l in zip(acc, local))

    @partial(jax.jit, donate_argnums=(0,))
    def run(acc, Xs, counts, centers):
        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), spec_of(Xs, 1), P(DATA_AXIS, None), P()),
            out_specs=P(),
            check_vma=False,
        )
        return f(acc, Xs, counts, centers)

    name = ("pallas.kmeans_stream.psum" if fused
            else "superblock.kmeans_assign.psum")
    return plan_tracked(name, run)


def _sparse_block_assign_stats(db, cb, rb, c, centers, S):
    """(Σ x per label, count per label, Σ min-dist²) of one bucketed-nnz
    sparse block (ISSUE 13): distances via the expanded form with the
    x·c matmul and ||x||² computed from the nnz alone
    (ops/sparse_kernels), label-bucketed feature sums as one flat
    segment_sum — nnz·k cost, no (S, d) densification."""
    from ..ops.sparse_kernels import (sparse_center_dots,
                                      sparse_label_sums, sparse_sq_norms)

    k = centers.shape[0]
    mask = (jnp.arange(S) < c).astype(jnp.float32)
    xx = sparse_sq_norms(db, rb, S)
    cc = jnp.sum(centers * centers, axis=1)[None, :]
    d2 = jnp.maximum(
        xx[:, None] + cc - 2.0 * sparse_center_dots(db, cb, rb, centers,
                                                    S),
        0.0,
    )
    labels = jnp.argmin(d2, axis=1)
    sums = sparse_label_sums(db, cb, rb, labels, k, centers.shape[1])
    counts = jax.ops.segment_sum(mask, labels, num_segments=k)
    inertia = jnp.sum(jnp.min(d2, axis=1) * mask)
    return sums, counts, inertia


@_ft.lru_cache(maxsize=16)
def _sb_assign_stats_sparse(S, mesh=None):
    """Sparse flavor of :func:`_sb_assign_stats`: the K-step
    assign+accumulate scan over bucketed-nnz COO stacks with the same
    donated (sums, counts, inertia) carry — one dispatch per
    super-block, zero compiles after pass 1. ``mesh`` selects the
    shard_map flavor (each device scans its own nnz segments/local row
    ids; ONE psum per super-block, the dense sharded flavor's exact
    collective shape)."""
    S = int(S)

    if mesh is None:
        @partial(jax.jit, donate_argnums=(0,))
        def run(acc, data, cols, rows, counts, centers):
            def scan_step(acc, inp):
                db, cb, rb, c = inp
                s, cnt, i = _sparse_block_assign_stats(db, cb, rb, c,
                                                       centers, S)
                return (acc[0] + s, acc[1] + cnt, acc[2] + i), \
                    jnp.float32(0.0)

            acc, _ = jax.lax.scan(scan_step, acc,
                                  (data, cols, rows, counts))
            return acc

        return plan_tracked("superblock.sparse.kmeans_assign", run)

    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS

    def body(acc, data, cols, rows, counts, centers):
        cts = counts[0]
        local = jax.tree.map(jnp.zeros_like, acc)

        def scan_step(lacc, inp):
            db, cb, rb, c = inp
            s, cnt, i = _sparse_block_assign_stats(db, cb, rb, c,
                                                   centers, S)
            return (lacc[0] + s, lacc[1] + cnt, lacc[2] + i), \
                jnp.float32(0.0)

        local, _ = jax.lax.scan(scan_step, local,
                                (data, cols, rows, cts))
        local = jax.lax.psum(local, DATA_AXIS)
        return tuple(a + l for a, l in zip(acc, local))

    @partial(jax.jit, donate_argnums=(0,))
    def run(acc, data, cols, rows, counts, centers):
        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(None, DATA_AXIS), P(None, DATA_AXIS),
                      P(None, DATA_AXIS), P(DATA_AXIS, None), P()),
            out_specs=P(),
            check_vma=False,
        )
        return f(acc, data, cols, rows, counts, centers)

    return plan_tracked("superblock.sparse.kmeans_assign.psum", run)


@plan_tracked("pallas.kmeans_stream")
@partial(jax.jit, static_argnames=("mxu_dtype", "interpret"),
        donate_argnums=(0,))
def _sb_assign_stats_pallas(acc, Xs, counts, centers, mxu_dtype=None,
                            interpret=False):
    """Pallas flavor of :func:`_sb_assign_stats` (ISSUE 8): each scan
    step is the fused assign-and-accumulate kernel — X streams through
    VMEM ONCE per block (the XLA flavor reads it twice: distance matmul
    + segment_sum) and only (tile, k) distances ever materialize.
    Selected by ``_streamed_lloyd`` on real TPU when the block shape
    fits ``kmeans_stream_tile``; parity within float tolerance
    (tests/test_precision.py)."""
    from ..ops.pallas_fused import fused_kmeans_block_stats

    def step(acc, X, c):
        s, cnt, i = fused_kmeans_block_stats(
            X, c, centers, mxu=mxu_dtype, interpret=interpret
        )
        return (acc[0] + s, acc[1] + cnt, acc[2] + i)

    def scan_step(acc, inp):
        return step(acc, *inp), jnp.float32(0.0)

    acc, _ = jax.lax.scan(scan_step, acc, (Xs, counts))
    return acc


@partial(jax.jit, static_argnames=("l",))
def _block_weighted_topl(X, weights, key, l):
    """Per-block Gumbel top-l: (keys, rows). Global weighted sampling
    without replacement = top-l of the per-block top-l keys (the Gumbel
    keys are independent across blocks), so blocks merge exactly."""
    keys = _gumbel_keys(weights, key)
    idx = top_l_indices(keys, l)
    return keys[idx], jnp.take(X, idx, axis=0)


def _proc_key(key, b):
    """Per-block Gumbel key, decorrelated ACROSS processes — identical
    key sequences on every process would correlate the sampling noise of
    different shards' rows. Nested fold_in (not an offset, which would
    collide past the offset's stride)."""
    from ..parallel import distributed as dist

    pid = dist.process_index()
    pkey = key if pid == 0 else jax.random.fold_in(key, 1_000_000 + pid)
    return jax.random.fold_in(pkey, b)


def _global_topl(kvs, rows, l):
    """Top-l rows by Gumbel key across ALL processes: local top-l pads
    to fixed l (−inf keys), one allgather, re-top — the exact global
    weighted sample, identical on every process (the Gumbel top-l merge
    is associative)."""
    from ..parallel import distributed as dist

    top = np.argsort(-kvs)[:l]
    top = top[np.isfinite(kvs[top])]
    if dist.process_count() == 1:
        return rows[top]
    d = rows.shape[1]
    kv_p = np.full(l, -np.inf, np.float32)
    kv_p[: top.size] = kvs[top]
    rw_p = np.zeros((l, d), np.float32)
    rw_p[: top.size] = rows[top]
    kv_all = dist.allgather_host(kv_p).ravel()
    rw_all = dist.allgather_host(rw_p).reshape(-1, d)
    t = np.argsort(-kv_all)[:l]
    t = t[np.isfinite(kv_all[t])]
    return rw_all[t]


def _streamed_sample(stream, weights_fn, key, l):
    """Draw l rows without replacement, P ∝ weights_fn(block), across a
    BlockStream — across every process's stream under a live multi-host
    runtime. Returns (≤l, d) host-merged rows, identical everywhere."""
    kvs, rows = [], []
    for b, blk in enumerate(stream):
        Xb = blk.arrays[0]
        w = weights_fn(blk)
        lb = min(l, Xb.shape[0])
        kv, r = _block_weighted_topl(Xb, w, _proc_key(key, b), lb)
        kvs.append(np.asarray(kv))
        rows.append(np.asarray(r))
    kvs = np.concatenate(kvs)
    rows = np.concatenate(rows, axis=0)
    return _global_topl(kvs, rows, l)


class _LloydCheckpoint:
    """Mid-run Lloyd checkpointing (SURVEY.md §5 checkpoint row): saves
    (centers, it) every k iterations under an IDENTITY TOKEN — a stale
    checkpoint from a different fit (other data, init, budget, shapes)
    is ignored rather than silently resumed, the same contract as the
    adaptive-search checkpoints (_incremental.py). Cleared on
    completion."""

    def __init__(self, path, every, token, k, d):
        self.path = path
        self.every = int(every)
        self.token = np.frombuffer(token.encode()[:40].ljust(40), np.uint8)
        self.k, self.d = k, d

    def restore(self):
        """(centers, it) if a matching checkpoint exists, else None."""
        from ..utils import checkpoint as ckpt

        # checkpoint_exists covers the atomic writer's crash window
        # (state parked at <path>.old after a kill mid-publish)
        if not ckpt.checkpoint_exists(self.path):
            return None
        like = {"token": np.zeros(40, np.uint8),
                "centers": jnp.zeros((self.k, self.d), jnp.float32),
                "it": 0}
        try:
            state = ckpt.restore_pytree(self.path, like=like)
        except Exception:
            return None  # different shapes = different fit: start fresh
        if not np.array_equal(np.asarray(state["token"]), self.token):
            return None
        return jnp.asarray(np.asarray(state["centers"])), int(state["it"])

    def save(self, centers, it):
        from ..utils import checkpoint as ckpt

        ckpt.save_pytree(self.path, {
            "token": self.token, "centers": centers, "it": it,
        })

    def clear(self):
        import os
        import shutil

        for suffix in ("", ".old", ".tmp"):
            shutil.rmtree(os.path.abspath(self.path) + suffix,
                          ignore_errors=True)


def _streamed_lloyd(stream, centers0, max_iter, tol2, logger=None,
                    ckpt=None, start_it=0, fit_dtype=None):
    """Host-loop Lloyd over streamed blocks; ``ckpt`` (a
    _LloydCheckpoint) persists every k passes so a killed multi-hour fit
    resumes mid-run, and clears on completion."""
    from ..config import mxu_dtype
    from ..parallel import distributed as dist

    mxu = mxu_dtype(fit_dtype)
    multi = dist.process_count() > 1
    centers = jnp.asarray(centers0)
    n_iter = start_it
    use_sb = hasattr(stream, "use_superblocks") and stream.use_superblocks()
    from ..observability import record_superblock_donation

    # fused Pallas scan flavor (one VMEM pass per block) when opted in
    # (real TPU, or interpret mode via pallas_stream_interpret) and the
    # PER-SHARD slab shape fits its grid — composed with the sharded
    # flavor by running inside its shard_map (ISSUE 12) — else the XLA
    # flavor, which with mxu=None traces byte-identically to the
    # pre-feature program
    from ..ops.pallas_fused import kmeans_stream_tile, stream_kernel_mode

    k0, d0 = jnp.asarray(centers0).shape
    sharded = bool(
        use_sb and getattr(stream, "sb_sharded", lambda: False)()
    )
    # bucketed-nnz sparse staging (ISSUE 13): assign-stats at nnz*k
    # cost through the superblock.sparse.kmeans_assign programs; the
    # fused Pallas flavor is a dense-slab feature and stays off
    sb_sparse = bool(
        use_sb and getattr(stream, "sb_sparse", lambda: False)()
    )
    use_k, interp = stream_kernel_mode()
    slab_rows = int(stream.block_rows) // (
        int(stream.sb_data_shards()) if sharded else 1
    )
    fused = bool(
        use_sb and use_k and not sb_sparse
        and kmeans_stream_tile(slab_rows, int(d0), int(k0)) is not None
    )
    sb_run = _sb_assign_stats_pallas if fused else _sb_assign_stats
    sparse_run = None
    if sb_sparse:
        sparse_run = _sb_assign_stats_sparse(
            slab_rows, mesh=stream.mesh if sharded else None
        )
    rep = None
    if sharded:
        # data-parallel flavor (ISSUE 9): one psum over "data" per
        # super-block; carry AND centers committed replicated so every
        # dispatch of the fit reuses one executable
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..config import resolve_dtype

        _, src = resolve_dtype(fit_dtype)
        if src.startswith("auto"):
            # mirror the resident auto-gate: under dtype="auto" the
            # single-device streamed flavor this displaces is the f32
            # Pallas kernel, so the sharded body stays f32 too —
            # bf16 distance assignments would put sharded-vs-single
            # parity at the mercy of argmin ties, not reassociation.
            # An EXPLICIT bfloat16 request is still honored
            mxu = None
        rep = NamedSharding(stream.mesh, P())
        centers = jax.device_put(centers, rep)
        sharded_run = _sb_assign_stats_sharded(stream.mesh, mxu,
                                               fused=fused,
                                               interpret=interp)

    for it in range(start_it, int(max_iter)):
        if use_sb:
            # the streamed hot loop as donated-carry super-block scans:
            # one dispatch per K blocks instead of K
            k_clusters, d = centers.shape
            acc = (jnp.zeros((k_clusters, d), jnp.float32),
                   jnp.zeros((k_clusters,), jnp.float32),
                   jnp.zeros((), jnp.float32))
            acc_bytes = 4 * (k_clusters * d + k_clusters + 1)
            if sb_sparse:
                if sharded:
                    acc = jax.device_put(acc, rep)
                for sb in stream.superblocks():
                    slab = sb.arrays[0]
                    cts = sb.shard_counts if sharded else sb.counts
                    acc = sparse_run(acc, slab.data, slab.cols,
                                     slab.rows, cts, centers)
                    record_superblock_donation(acc_bytes)
            elif sharded:
                acc = jax.device_put(acc, rep)
                for sb in stream.superblocks():
                    acc = sharded_run(acc, sb.arrays[0],
                                      sb.shard_counts, centers)
                    record_superblock_donation(acc_bytes)
            elif fused:
                for sb in stream.superblocks():
                    acc = sb_run(acc, sb.arrays[0], sb.counts,
                                 centers, mxu_dtype=mxu,
                                 interpret=interp)
                    record_superblock_donation(acc_bytes)
            else:
                for sb in stream.superblocks():
                    acc = sb_run(acc, sb.arrays[0], sb.counts,
                                 centers, mxu_dtype=mxu)
                    record_superblock_donation(acc_bytes)
            sums, counts, inertia = acc
        else:
            sums = counts = inertia = None
            for blk in stream:
                s, c, i = _block_assign_stats(blk.arrays[0], blk.mask,
                                              centers, mxu_dtype=mxu)
                sums = s if sums is None else sums + s
                counts = c if counts is None else counts + c
                inertia = i if inertia is None else inertia + i
        if multi:
            # per-process block stats → global (bit-identical on every
            # process, so centers never diverge across hosts)
            sums, counts, inertia = (
                jnp.asarray(np.asarray(a, np.float32)) for a in
                dist.psum_host(np.asarray(sums, np.float64),
                               np.asarray(counts, np.float64),
                               np.asarray(inertia, np.float64))
            )
        new = jnp.where(counts[:, None] > 0, sums / counts[:, None], centers)
        shift2 = float(jnp.sum((new - centers) ** 2))
        centers = new
        n_iter = it + 1
        if logger is not None:
            logger.log(step=it, inertia=float(inertia), center_shift2=shift2)
        if ckpt is not None and n_iter % ckpt.every == 0:
            # (multi-host passes ckpt=None — see _fit_streamed)
            ckpt.save(centers, n_iter)
        if shift2 <= tol2:
            break
    if ckpt is not None:
        ckpt.clear()
    return centers, n_iter


def _reduce_candidates(points, weights, n_clusters, random_state):
    """k-means‖'s last step on the host: the ≤ (1 + l·rounds) weighted
    candidates to ``n_clusters`` centres — scikit-learn's weighted k-means++
    seeding, then weighted Lloyd iterations in numpy until no candidate
    changes its centre (a centre that loses all its candidates stays).
    Plain numpy on purpose: on a hundred points ``sklearn.cluster.KMeans``'s
    OpenMP Lloyd loop and ``threadpoolctl``'s scan of the loaded libraries
    cost the host 15 ms a call with the chip idle, and ten times that where
    the host's cores are shared (PERF.md section 6); this is ~2 ms."""
    from sklearn.cluster import kmeans_plusplus

    points = np.asarray(points, np.float64)
    weights = np.asarray(weights, np.float64)
    centers, _ = kmeans_plusplus(points, n_clusters, sample_weight=weights,
                                 random_state=random_state)
    centers = centers.astype(np.float64)
    labels = None
    for _ in range(300):
        d2 = ((points[:, None, :] - centers[None, :, :]) ** 2).sum(axis=-1)
        new = d2.argmin(axis=1)
        if labels is not None and np.array_equal(new, labels):
            break
        labels = new
        for j in range(n_clusters):
            mine = labels == j
            if mine.any():
                centers[j] = np.average(points[mine], axis=0,
                                        weights=weights[mine])
    return centers


def init_scalable_streamed(stream, n_clusters, random_state, max_iter=None,
                           oversampling_factor=2):
    """k-means‖ over streamed blocks: the same fixed-budget Gumbel top-l
    rounds as ``init_scalable``, with each round's cost/sampling pass
    running block-by-block and merging exactly (see _block_weighted_topl)."""
    l = max(int(oversampling_factor * n_clusters), 1)
    key = jax.random.PRNGKey(0 if random_state is None else int(random_state))
    key, k0 = jax.random.split(key)
    first = _streamed_sample(stream, lambda blk: blk.mask, k0, 1)
    cands_list = [first]
    rounds = 5 if max_iter is None else max(int(max_iter), 1)
    for r in range(rounds):
        cands = jnp.asarray(np.concatenate(cands_list, axis=0))
        valid = jnp.ones((cands.shape[0],), jnp.float32)
        key, kr = jax.random.split(key)
        phi = 0.0
        kvs, rows = [], []
        for b, blk in enumerate(stream):
            Xb = blk.arrays[0]
            dmin, phi_b = _cost_to_candidates(Xb, blk.mask, cands, valid)
            phi += float(phi_b)
            lb = min(l, Xb.shape[0])
            kv, rw = _block_weighted_topl(Xb, dmin, _proc_key(kr, b), lb)
            kvs.append(np.asarray(kv))
            rows.append(np.asarray(rw))
        from ..parallel import distributed as dist

        phi = float(dist.psum_host(np.asarray(phi)))  # global cost
        if phi <= 0.0:
            break
        kvs = np.concatenate(kvs)
        rows = np.concatenate(rows, axis=0)
        picked = _global_topl(kvs, rows, l)
        if len(picked):
            cands_list.append(picked)
    cands_h = np.concatenate(cands_list, axis=0)
    cands = jnp.asarray(cands_h)
    valid = jnp.ones((cands.shape[0],), jnp.float32)
    weights = None
    for blk in stream:
        w = _candidate_weights(blk.arrays[0], blk.mask, cands, valid)
        weights = w if weights is None else weights + w
    from ..parallel import distributed as dist

    w_h = np.asarray(dist.psum_host(np.asarray(weights, np.float64)))
    w_h = np.where(w_h > 0, w_h, 1e-6)
    # DETERMINISTIC seed even when random_state is None: the candidate
    # sampling above already pins PRNGKey(0) in that case, and under
    # multi-host every process must reduce the (identical) candidate set
    # to the IDENTICAL centers — an unseeded draw would diverge them
    return jnp.asarray(_reduce_candidates(
        cands_h, w_h, n_clusters,
        0 if random_state is None else int(random_state)), cands.dtype)


def init_scalable(X: ShardedArray, n_clusters, random_state, max_iter=None,
                  oversampling_factor=2, draws=None, weight_passes=None):
    """k-means‖ candidate harvesting; ref
    dask_ml/cluster/k_means.py::init_scalable. ``draws``: a list that gets
    the path of every weighted draw dispatched (see ``_draw``);
    ``weight_passes`` the same of the candidate-weight pass (``_weigh``)."""
    data, mask = X.data, X.row_mask(X.dtype)
    n, d = X.shape
    n_pad = data.shape[0]
    # top_k needs l <= array length; tiny datasets clamp the oversample
    l = min(max(int(oversampling_factor * n_clusters), 1), n_pad)
    key = jax.random.PRNGKey(0 if random_state is None else int(random_state))

    # step 1: one uniform-random valid row
    key, k0 = jax.random.split(key)
    first = data[_draw(mask, k0, 1, draws)[0]]

    # candidate buffer with static shape (SURVEY.md §7 hard parts)
    if max_iter is None:
        # rounds ≈ log(phi); phi ≤ n * max_dist² — 5 is the practical
        # regime for sane data, matching the reference's few-round behavior
        rounds = 5
    else:
        rounds = max(int(max_iter), 1)
    c_max = 1 + rounds * l
    cands = jnp.zeros((c_max, d), data.dtype).at[0].set(first)
    cand_valid = jnp.zeros((c_max,), jnp.float32).at[0].set(1.0)

    for r in range(rounds):
        dmin, phi = _cost_to_candidates(data, mask, cands, cand_valid)
        # the draw is queued before the host asks for phi, so the chip draws
        # while the host waits (a round that ends the loop drops its draw)
        key, kr = jax.random.split(key)
        idx = _draw(dmin, kr, l, draws)
        if float(to_host(phi)) <= 0.0:
            break
        rows = jnp.take(data, idx, axis=0)
        start = 1 + r * l
        cands = jax.lax.dynamic_update_slice(cands, rows, (start, 0))
        cand_valid = jax.lax.dynamic_update_slice(
            cand_valid, jnp.ones((l,), jnp.float32), (start,)
        )

    weights = _weigh(data, mask, cands, cand_valid, weight_passes)
    cands_h = to_host(cands)
    valid_h = to_host(cand_valid) > 0
    w_h = to_host(weights)[valid_h]
    pts = cands_h[valid_h]
    w_h = np.where(w_h > 0, w_h, 1e-6)
    return jnp.asarray(_reduce_candidates(
        pts, w_h, n_clusters,
        None if random_state is None else int(random_state)), data.dtype)


def init_pp(X: ShardedArray, n_clusters, random_state, draws=None):
    """k-means++ on a device-drawn uniform sample (ref ::init_pp)."""
    from sklearn.cluster import kmeans_plusplus

    data, mask = X.data, X.row_mask(X.dtype)
    m = min(X.n_rows, max(10 * n_clusters, 500), data.shape[0])
    key = jax.random.PRNGKey(1 if random_state is None else int(random_state))
    idx = _draw(mask, key, m, draws)
    sample = to_host(jnp.take(data, idx, axis=0))
    centers, _ = kmeans_plusplus(
        sample, n_clusters,
        random_state=None if random_state is None else int(random_state),
    )
    return jnp.asarray(centers, data.dtype)


def init_random(X: ShardedArray, n_clusters, random_state, draws=None):
    data, mask = X.data, X.row_mask(X.dtype)
    key = jax.random.PRNGKey(2 if random_state is None else int(random_state))
    idx = _draw(mask, key, n_clusters, draws)
    return jnp.take(data, idx, axis=0)


def k_means(X, n_clusters, init="k-means||", max_iter=300, tol=1e-4,
            random_state=None, oversampling_factor=2, init_max_iter=None,
            return_n_iter=False):
    """Functional API (ref: dask_ml/cluster/k_means.py::k_means):
    returns (centroids, labels, inertia[, n_iter])."""
    est = KMeans(
        n_clusters=n_clusters, init=init, max_iter=max_iter, tol=tol,
        random_state=random_state, oversampling_factor=oversampling_factor,
        init_max_iter=init_max_iter,
    ).fit(X)
    if return_n_iter:
        return est.cluster_centers_, est.labels_, est.inertia_, est.n_iter_
    return est.cluster_centers_, est.labels_, est.inertia_


class KMeans(TransformerMixin, ClusterMixin, BaseEstimator):
    """Ref: dask_ml/cluster/k_means.py::KMeans.

    ``tol``: the Lloyd loop ends once the squared shift of the centres
    falls to ``tol`` times the mean per-feature variance of X, as
    scikit-learn scales it (a resident fit takes that variance in one
    program of two passes over X, ``solver_info_["tol_scale_passes"]``).
    ``tol=0`` computes no scale and reads X for none: the loop runs to
    ``max_iter`` or to an exact fixed point."""

    def __init__(self, n_clusters=8, init="k-means||", oversampling_factor=2,
                 max_iter=300, tol=1e-4, precompute_distances="auto",
                 random_state=None, copy_x=True, n_jobs=1, algorithm="full",
                 init_max_iter=None, use_pallas=None, checkpoint_path=None,
                 checkpoint_every=0, fit_dtype=None):
        self.n_clusters = n_clusters
        self.init = init
        self.oversampling_factor = oversampling_factor
        self.max_iter = max_iter
        self.tol = tol
        self.precompute_distances = precompute_distances
        self.random_state = random_state
        self.copy_x = copy_x
        self.n_jobs = n_jobs
        self.algorithm = algorithm
        self.init_max_iter = init_max_iter
        self.use_pallas = use_pallas
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = checkpoint_every
        # per-estimator precision override (None = config.dtype policy;
        # "float32" opts out of the TPU bf16 default, "bfloat16" forces
        # it); resolved choice lands on fit_dtype_
        self.fit_dtype = fit_dtype

    def _init_centers(self, X: ShardedArray, draws=None, weight_passes=None):
        """The initial centres; ``draws`` gets the path of every weighted
        draw dispatched, ``weight_passes`` that of every candidate-weight
        pass."""
        if isinstance(self.init, np.ndarray) or isinstance(
            self.init, jnp.ndarray
        ):
            centers = jnp.asarray(self.init, X.dtype)
            if centers.shape != (self.n_clusters, X.shape[1]):
                raise ValueError(
                    f"init array has shape {centers.shape}, expected "
                    f"{(self.n_clusters, X.shape[1])}"
                )
            return centers
        if self.init == "k-means||":
            return init_scalable(X, self.n_clusters, self.random_state,
                                 self.init_max_iter, self.oversampling_factor,
                                 draws, weight_passes)
        if self.init == "k-means++":
            return init_pp(X, self.n_clusters, self.random_state, draws)
        if self.init == "random":
            return init_random(X, self.n_clusters, self.random_state, draws)
        raise ValueError(f"Unknown init {self.init!r}")

    def _make_ckpt(self, X, n, d):
        """A _LloydCheckpoint when the knobs are set, else None. The
        identity token covers the init CONFIG (not the computed centers —
        resume must be able to skip init), the budget, and a data-content
        fingerprint."""
        if not (self.checkpoint_path and self.checkpoint_every):
            return None
        import hashlib

        from ..utils.validation import data_fingerprint

        if isinstance(self.init, (np.ndarray, jnp.ndarray)):
            init_piece = hashlib.sha1(np.ascontiguousarray(
                np.asarray(self.init, np.float32)).tobytes()).hexdigest()
        else:
            init_piece = f"{self.init}|{self.random_state}|"                          f"{self.oversampling_factor}|{self.init_max_iter}"
        token = hashlib.sha1("|".join([
            init_piece, str(self.n_clusters), str(n), str(d),
            str(self.max_iter), str(self.tol), data_fingerprint(X),
        ]).encode()).hexdigest()
        return _LloydCheckpoint(self.checkpoint_path, self.checkpoint_every,
                                token, self.n_clusters, d)

    def _init_centers_streamed(self, stream, n_features):
        if isinstance(self.init, (np.ndarray, jnp.ndarray)):
            centers = jnp.asarray(self.init, jnp.float32)
            if centers.shape != (self.n_clusters, n_features):
                raise ValueError(
                    f"init array has shape {centers.shape}, expected "
                    f"{(self.n_clusters, n_features)}"
                )
            return centers
        if self.init == "k-means||":
            return init_scalable_streamed(
                stream, self.n_clusters, self.random_state,
                self.init_max_iter, self.oversampling_factor,
            )
        seed_base = {"k-means++": 1, "random": 2}
        if self.init in seed_base:
            key = jax.random.PRNGKey(
                seed_base[self.init] if self.random_state is None
                else int(self.random_state)
            )
            if self.init == "random":
                return jnp.asarray(_streamed_sample(
                    stream, lambda blk: blk.mask, key, self.n_clusters
                ))
            from sklearn.cluster import kmeans_plusplus

            from ..parallel import distributed as dist

            # GLOBAL row count sizes the sample so every process's
            # _global_topl allgather payload has the same shape; the
            # deterministic seed keeps centers0 identical everywhere
            # (same rule as init_scalable_streamed)
            n_glob = int(dist.psum_host(np.asarray(float(stream.n_rows))))
            m = min(n_glob, max(10 * self.n_clusters, 500))
            sample = _streamed_sample(stream, lambda blk: blk.mask, key, m)
            centers, _ = kmeans_plusplus(
                sample, self.n_clusters,
                random_state=0 if self.random_state is None
                else int(self.random_state),
            )
            return jnp.asarray(centers, jnp.float32)
        raise ValueError(f"Unknown init {self.init!r}")

    def _fit_streamed(self, X, block_rows):
        """Out-of-core Lloyd: X stays host-resident (np.memmap / large
        ndarray); every pass streams fixed-shape blocks through the
        per-block assign+update kernel and accumulates (sums, counts) on
        device — the reference's per-chunk tasks + tree-reduce shape
        (SURVEY.md §3.1) without materializing X in HBM. ``labels_`` is a
        host int32 array (X's own size /(4·d) — small next to X)."""
        from ..parallel.streaming import BlockStream
        from ..observability import fit_logger

        n_local, d = X.shape
        from ..config import fit_dtype_info
        from ..parallel import distributed as dist

        # resolved precision on record (auto falls back to f32 off-TPU)
        self.fit_dtype_ = fit_dtype_info(self.fit_dtype)["fit_dtype"]
        multi = dist.process_count() > 1
        # multi-host: X is the process-local memmap shard; every global
        # statistic (n, variance, Lloyd stats, inertia, the k-means||
        # sampling) merges over the psum/allgather plane
        n = int(dist.psum_host(np.asarray(float(n_local)))) if multi \
            else n_local
        if self.n_clusters > n:
            raise ValueError(
                f"n_clusters={self.n_clusters} > n_samples={n}"
            )
        stream = BlockStream((X,), block_rows=block_rows)
        # sklearn-style tol scaling needs the global per-feature variance:
        # one moments pass
        s = ss = None
        for blk in stream:
            bs, bss = _block_moments(blk.arrays[0], blk.mask)
            s = bs if s is None else s + bs
            ss = bss if ss is None else ss + bss
        if multi:
            s, ss = (np.asarray(a) for a in dist.psum_host(
                np.asarray(s, np.float64), np.asarray(ss, np.float64)
            ))
        mean = s / n
        var = ss / n - mean * mean
        tol2 = float(self.tol * jnp.mean(jnp.asarray(var)))
        # multi-host checkpointing is OFF: resume must be a COLLECTIVE
        # decision (a coordinator-only resume would desync every
        # process's collective schedule); needs shared-FS coordination
        ckpt = None if multi else self._make_ckpt(X, n, d)
        resume = ckpt.restore() if ckpt is not None else None
        if resume is not None:
            # resume SKIPS init entirely — k-means|| costs ~10 full
            # passes over an out-of-core dataset
            centers0, start_it = resume
        else:
            with span("kmeans.init", streamed=True, init=str(self.init)):
                centers0, start_it = (
                    self._init_centers_streamed(stream, d), 0
                )
        with span("fit", component="KMeans", streamed=True, n_rows=n,
                  n_clusters=self.n_clusters) as sp, \
                fit_logger("KMeans", streamed=True, n_rows=n,
                           n_clusters=self.n_clusters) as logger:
            centers, n_iter = _streamed_lloyd(
                stream, centers0, self.max_iter, tol2, logger=logger,
                ckpt=ckpt, start_it=start_it, fit_dtype=self.fit_dtype,
            )
            sp.add(n_iter=int(n_iter))
        labels = np.empty(n_local, np.int32)  # labels stay process-local
        inertia = 0.0
        cursor = 0
        for blk in stream:
            lb, ib = _labels_inertia(blk.arrays[0], blk.mask, centers)
            m = blk.n_rows
            labels[cursor:cursor + m] = np.asarray(lb)[:m]
            inertia += float(ib)
            cursor += m
        if multi:
            inertia = float(dist.psum_host(np.asarray(inertia)))
        if not np.isfinite(inertia) or \
                not bool(jnp.isfinite(centers).all()):
            raise FloatingPointError(
                "KMeans produced non-finite centers/inertia: the input "
                "contains NaN/Inf"
            )
        self.cluster_centers_ = np.asarray(centers)
        self.labels_ = labels
        self.inertia_ = inertia
        self.n_iter_ = int(n_iter)
        # no "fused" here: the streamed loop picks its kernel per stream
        # and does not report it (a resident fit's record must not stay)
        self.solver_info_ = {"n_iter": self.n_iter_, "streamed": True,
                             "fit_dtype": self.fit_dtype_}
        self.n_features_in_ = d
        # per-feature training profile for train-vs-serve drift scoring
        self.training_profile_ = stream.profile_snapshot()
        return self

    def fit(self, X, y=None):
        from ..parallel.streaming import stream_plan

        block_rows = stream_plan(X)
        if block_rows is not None:
            return self._fit_streamed(X, block_rows)
        # the root span covers the whole resident call; its children
        # (fit.validate, fit.init, fit.tol_scale, fit.solve, fit.finish)
        # are the phases, each ending where its host code ends
        with span("fit", component="KMeans",
                  n_clusters=self.n_clusters) as root:
            return self._fit_resident(X, root, span)

    def _fit_inner(self, X):
        """The resident fit of a row-sharded ``X`` for an estimator that
        runs KMeans inside its OWN fit (``SpectralClustering``'s restarts):
        the same phases as :meth:`fit` with no span opened, so the caller's
        fit stays one root with its own flat children; every wait and fetch
        lands on the span the caller has open."""
        return self._fit_resident(X, _AmbientPhase(), _AmbientPhase)

    def _fit_resident(self, X, root, span):
        """``span``: what opens a phase — the tracer's ``span`` under
        :meth:`fit`'s root, ``_AmbientPhase`` under :meth:`_fit_inner`."""
        from ..config import fit_dtype_info, mxu_dtype as _mxu_dtype

        with span("fit.validate"):
            X = check_array(X, dtype=np.float32)
            if self.n_clusters > X.n_rows:
                raise ValueError(
                    f"n_clusters={self.n_clusters} > n_samples={X.n_rows}"
                )
            mask = X.row_mask(X.dtype)
            dt_info = fit_dtype_info(self.fit_dtype)
            auto_pol = dt_info["fit_dtype_source"].startswith("auto")
            mxu = _mxu_dtype(self.fit_dtype)
            use_pallas = self.use_pallas
            if use_pallas is None:
                # auto: fused kernel on real TPU only — an EXPLICIT bf16
                # request routes to the XLA distance path instead (the
                # resident Pallas kernel's VMEM tiling is f32); under the
                # default "auto" policy the f32 Pallas kernel keeps
                # priority — one X pass per Lloyd iteration beats a bf16
                # cross-term at this arithmetic intensity
                use_pallas = jax.default_backend() == "tpu" \
                    and (mxu is None or auto_pol)
            elif use_pallas and mxu is not None and not auto_pol:
                import warnings

                warnings.warn(
                    "KMeans(use_pallas=True) runs the f32 Pallas kernel; "
                    "config.dtype='bfloat16' is ignored on this path",
                    RuntimeWarning,
                )
            if use_pallas and mxu is not None:
                mxu = None
                dt_info = {"fit_dtype": "float32",
                           "fit_dtype_source": "pallas-resident"}
            self.fit_dtype_ = dt_info["fit_dtype"]
        root.add(n_rows=X.n_rows)
        with span("fit.init") as sp:
            draws, weight_passes = [], []
            centers0 = self._init_centers(X, draws, weight_passes)
            init_draw = _draw_summary(draws)
            init_weights = _weight_summary(weight_passes)
            sp.add(**init_draw, **init_weights)
        with span("fit.tol_scale") as sp:
            # dispatch only (none at tol == 0): the device works on into
            # fit.solve
            tol2, tol_passes = _lloyd_tol2(X, mask, self.tol)
            sp.add(passes=tol_passes)
        from ..observability import active_logger, fit_logger

        with span("fit.solve", fused=bool(use_pallas)) as sp, \
                fit_logger("KMeans", n_rows=X.n_rows,
                           n_clusters=self.n_clusters) as logger, \
                active_logger(logger):
            log_steps = logger is not None

            # bf16 distance matmuls (XLA path only, see use_pallas
            # resolution above)
            mxu_dtype = None if use_pallas else mxu

            def run_lloyd(c0, iters):
                if use_pallas:
                    return _lloyd_run_pallas(
                        X.data, mask, c0, jnp.asarray(iters), tol2, X.mesh,
                        interpret=jax.default_backend() != "tpu",
                        log=log_steps,
                    )
                return _lloyd_run(
                    X.data, mask, c0, jnp.asarray(iters), tol2,
                    log=log_steps, mxu_dtype=mxu_dtype,
                )

            ckpt = self._make_ckpt(X, X.n_rows, X.shape[1])
            if ckpt is None:
                centers, n_iter, shift2 = run_lloyd(centers0, self.max_iter)
            else:
                # chunked while_loops: every k iterations the (centers,
                # it) state hits stable storage — the resident analog of
                # the streamed path's per-pass checkpointing
                resume = ckpt.restore()
                centers, n_iter = (resume if resume is not None
                                   else (centers0, 0))
                shift2 = jnp.asarray(jnp.inf, X.dtype)
                while n_iter < self.max_iter:
                    chunk = min(int(self.checkpoint_every),
                                self.max_iter - n_iter)
                    centers, it_c, shift2 = run_lloyd(centers, chunk)
                    n_iter += int(it_c)
                    ckpt.save(centers, n_iter)
                    if int(it_c) < chunk:
                        break  # converged inside the chunk
                ckpt.clear()
            # the one scalar fetch: where the host waits for the loop
            n_iter = int(to_host(sp.sync(n_iter)))
            sp.add(n_iter=n_iter)
            if logger is not None and not log_steps:
                logger.log(step=n_iter, center_shift2=float(shift2),
                           summary=True)
            # active_logger's exit runs jax.effects_barrier(), draining
            # the per-iteration callbacks before the sink unbinds
        root.add(n_iter=n_iter)
        with span("fit.finish") as sp:
            labels, inertia = _labels_inertia(X.data, mask, centers)
            # NaN sanitizer (SURVEY.md §5): a NaN makes the tol while_loop
            # exit as "converged" (NaN comparisons are False) — check the
            # final inertia/centers instead of trusting convergence.
            # The first check is where the host waits for the labels
            # pass: sync on what it reads, never earlier (a sync on the
            # pass itself would hold back the check's own dispatch)
            if not bool(to_host(sp.sync(jnp.isfinite(inertia)))) or \
                    not bool(to_host(jnp.isfinite(centers).all())):
                raise FloatingPointError(
                    "KMeans produced non-finite centers/inertia: the input "
                    "contains NaN/Inf"
                )
            self.cluster_centers_ = to_host(centers)
            self.labels_ = ShardedArray(labels, X.n_rows, X.mesh)
            self.inertia_ = float(to_host(inertia))
            self.n_iter_ = n_iter
            # what carried the fit: the resident twin of the GLMs'
            # solver_info_ ("fused": the Pallas Lloyd kernel ran)
            self.solver_info_ = {"n_iter": n_iter, "fused": bool(use_pallas),
                                 "fit_dtype": self.fit_dtype_,
                                 "tol_scale_passes": tol_passes,
                                 "init_draw": init_draw,
                                 "init_weights": init_weights}
            self.n_features_in_ = X.shape[1]
            return self

    def predict(self, X):
        check_is_fitted(self, "cluster_centers_")
        from ..parallel.streaming import stream_plan, streamed_map

        block_rows = stream_plan(X)
        if block_rows is not None:
            c = jnp.asarray(self.cluster_centers_, jnp.float32)
            return streamed_map(
                X, block_rows,
                lambda blk: _labels_inertia(blk.arrays[0], blk.mask, c)[0],
            )
        # dispatch only: the labels stay on the device, nothing here waits
        with span("predict", component="KMeans") as root:
            X = check_array(X, dtype=np.float32)
            root.add(n_rows=X.n_rows)
            centers = jnp.asarray(self.cluster_centers_, X.dtype)
            labels, _ = _labels_inertia(X.data, X.row_mask(X.dtype), centers)
            return ShardedArray(labels, X.n_rows, X.mesh)

    def fit_predict(self, X, y=None):
        return self.fit(X).labels_

    def transform(self, X):
        check_is_fitted(self, "cluster_centers_")
        from ..parallel.streaming import stream_plan, streamed_map

        block_rows = stream_plan(X)
        if block_rows is not None:
            c = jnp.asarray(self.cluster_centers_, jnp.float32)
            return streamed_map(
                X, block_rows,
                lambda blk: euclidean_distances(blk.arrays[0], c),
            )
        X = check_array(X, dtype=np.float32)
        centers = jnp.asarray(self.cluster_centers_, X.dtype)
        d = euclidean_distances(X.data, centers)
        return ShardedArray(d, X.n_rows, X.mesh)

    def score(self, X, y=None):
        check_is_fitted(self, "cluster_centers_")
        from ..parallel.streaming import stream_plan, streamed_map

        block_rows = stream_plan(X)
        if block_rows is not None:
            c = jnp.asarray(self.cluster_centers_, jnp.float32)
            per_block = streamed_map(
                X, block_rows,
                lambda blk: _labels_inertia(blk.arrays[0], blk.mask, c)[1][None],
            )
            return -float(per_block.sum())
        X = check_array(X, dtype=np.float32)
        centers = jnp.asarray(self.cluster_centers_, X.dtype)
        _, inertia = _labels_inertia(X.data, X.row_mask(X.dtype), centers)
        return -float(inertia)
