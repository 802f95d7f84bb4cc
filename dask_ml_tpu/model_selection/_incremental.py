"""Adaptive incremental hyperparameter search.

Reference: ``dask_ml/model_selection/_incremental.py`` (SURVEY.md §2a
adaptive row, §3.5 call stack): an async controller over distributed
futures submits ``partial_fit``/``score`` block-by-block and adaptively
stops/keeps models via an ``additional_calls`` hook.

TPU mapping (SURVEY.md §3.5): the controller is a synchronous host loop
(trials are pinned work, not stolen futures); models train one data block
per call and are scored on a held-out split. The ``additional_calls``
protocol is preserved exactly: it receives ``{model_id: [history
records]}`` and returns ``{model_id: n_more_partial_fit_calls}`` — an
empty dict (or all-zero dict) stops the search. SuccessiveHalving and
Hyperband reuse this engine, as in the reference.
"""

from __future__ import annotations

import copy as _copy
import functools
import os
import threading
import time

import numpy as np
from sklearn.model_selection import ParameterSampler

from ..base import BaseEstimator, clone, to_host
from ..metrics.scorer import check_scoring
from ..parallel.sharded import ShardedArray
from ..utils.validation import data_fingerprint as _data_fingerprint
from ._split import train_test_split


def _to_host(a):
    from ..parallel.streaming import _is_sparse_source

    if _is_sparse_source(a):
        return a  # sparse stays sparse (np.asarray would mangle it)
    return a.to_numpy() if isinstance(a, ShardedArray) else np.asarray(a)


def _blocks_of(X, y, n_blocks, block_rows=None):
    """Row blocks = the unit of one partial_fit call.

    Device-resident data plane (VERDICT r1 #5): when X is a ShardedArray
    the blocks are extracted ON DEVICE via ``take_rows`` (a sharded
    gather) and stay there — no full-dataset device→host→device
    round-trip before training, which at BASELINE scale would be a
    TB-size copy. Host inputs keep host blocks (streamed to device per
    step, as the reference streams blocks to workers). ``block_rows``
    pins the exact block height (the streamed cohort plane passes its
    stream partition so solo fallbacks train the SAME minibatches the
    superblock scans do)."""
    if isinstance(X, ShardedArray):
        from ..parallel.sharded import take_rows

        ys = y if isinstance(y, ShardedArray) else None
        n = X.n_rows
        bs = int(block_rows) if block_rows \
            else max(int(np.ceil(n / n_blocks)), 1)
        out = []
        for i in range(0, n, bs):
            idx = np.arange(i, min(i + bs, n))
            if not idx.size:
                continue
            yb = take_rows(ys, idx) if ys is not None \
                else np.asarray(y)[idx]
            out.append((take_rows(X, idx), yb))
        return out
    from ..parallel.streaming import as_row_sliceable

    Xh, yh = _to_host(X), _to_host(y)
    Xh = as_row_sliceable(Xh)  # sparse: CSR slices, no densify
    n = int(Xh.shape[0])
    bs = int(block_rows) if block_rows \
        else max(int(np.ceil(n / n_blocks)), 1)
    return [(Xh[i:i + bs], yh[i:i + bs]) for i in range(0, n, bs)
            if int(Xh[i:i + bs].shape[0])]


def top_scores(scores, k):
    """The ``k`` model ids of ``{model id: score}`` a cut keeps: the highest
    scores, and among equal scores the LOWER model id — stated, because the
    order the candidates arrive in (a set's) is nobody's rule."""
    return sorted(scores, key=lambda mid: (-scores[mid], mid))[:k]


def _supports_batch(model) -> bool:
    return hasattr(type(model), "_batched_partial_fit") and \
        hasattr(model, "_batch_key")


def host_view_estimator(est):
    """Replace any device-array attributes with host numpy so the model
    pickles across the process-gather channel (and stays usable — every
    consumer re-coerces with jnp.asarray)."""
    import jax

    if est is None:
        return est
    for k, v in list(vars(est).items()):
        if isinstance(v, jax.Array):
            setattr(est, k, to_host(v))
    return est


# Hyperband distributes whole brackets across processes; the SHA fits it
# runs per bracket must NOT additionally distribute their candidates (the
# peers are busy with other brackets — a nested allgather would deadlock).
# Thread-local, not a module global: virtual process ranks are threads of
# ONE process, and rank A leaving its bracket must not re-enable
# distribution under rank B's still-running SHA.
import contextlib
import threading

_dist_state = threading.local()


def _dist_is_disabled():
    return getattr(_dist_state, "disabled", False)


@contextlib.contextmanager
def disable_process_distribution():
    prev = getattr(_dist_state, "disabled", False)
    _dist_state.disabled = True
    try:
        yield
    finally:
        _dist_state.disabled = prev


class _StreamCohortPlane:
    """The streamed superblock data plane for adaptive-search cohort
    rounds (ISSUE 14 tentpole): instead of keeping train blocks
    device-resident and dispatching the search's own cohort scans
    (HBM-capped, blind to the stream mesh, densifying sparse corpora),
    a round advances ALL surviving batchable candidates through ONE
    ``BlockStream`` superblock pass — the same staging ring, mesh
    sharding, bucketed-nnz sparse format and fused Pallas flavors every
    streamed fit already rides. The plane owns:

    - the block PARTITION (``fit_block_rows`` — the same formula the
      streamed SGD/Incremental fits use, so a search trains the same
      minibatches a plain streamed fit of the winner would);
    - one lazily-built ``BlockStream`` per cohort batch key (the stream
      needs the cohort's y encoding), reused across every round so the
      staging ring and compiled scans stay warm;
    - one staged validation HOLDOUT per key (dense device slab or
      packed sparse COO triple), scored in one batched dispatch per
      round;
    - ``n_slots`` — the search's total candidate count, the FIXED pad
      of the stacked cohort carry: bracket halving reuses the one
      compiled scan via the slot mask instead of recompiling at each
      surviving N.

    ``config.search_stream=False`` restores the device-resident cohort
    path on the SAME partition."""

    def __init__(self, X_train, y_train, X_test, y_test, n_slots):
        from ..parallel.streaming import BlockStream, fit_block_rows

        self.X, self.y = X_train, y_train
        self.X_test, self.y_test = X_test, y_test
        self.n_slots = int(n_slots)
        n = int(X_train.shape[0])
        self.block_rows = int(fit_block_rows(X_train))
        self.n_blocks = max(int(np.ceil(n / self.block_rows)), 1)
        self._streams = {}
        self._holdouts = {}
        self.stats = {"rounds": 0, "dispatches": 0, "shards": 1,
                      "sparse": False, "fused": False,
                      "fused_reason": None}
        # probe: the hot loop must actually superblock this source at
        # this partition (a sparse corpus that fell back to per-block
        # densify, or superblock_k=1, keeps the device plane)
        probe = BlockStream((X_train,), block_rows=self.block_rows,
                            profile=False)
        self.engaged = bool(
            probe.block_rows == self.block_rows
            and probe.use_superblocks()
        )
        self.reason = None if self.engaged else (
            probe.sparse_reason or "per-block-path"
        )

    @staticmethod
    def eligible(estimator, X_train):
        """The stream PARTITION (and, with ``config.search_stream`` on,
        the streamed execution plane) serves single-process searches
        over HOST-resident X with a streamed-cohort-capable estimator;
        the device-resident plane keeps everything else. A bracket SHA
        running under ``disable_process_distribution`` (multi-process
        Hyperband stripes whole brackets across processes) counts as
        single-process: it fits on its local mesh, and its stream
        resolves to exactly that mesh — BASELINE config 5's
        trials-parallel-across-hosts shape with every bracket riding
        the streamed plane. The knob is deliberately NOT part of this
        check — with it off the search keeps the stream partition but
        executes rounds through the device-resident cohort machinery,
        so the two paths train identical minibatches and their scores
        are comparable."""
        from ..parallel import distributed as _dist

        return (hasattr(type(estimator), "_streamed_cohort_round")
                and not isinstance(X_train, ShardedArray)
                and (_dist.process_count() == 1 or _dist_is_disabled()))

    def stream_for(self, key, model):
        """The (cached) training BlockStream for cohort batch key
        ``key`` — built on first use because the stream stages the
        ENCODED targets (the key pins the class set)."""
        stream = self._streams.get(key)
        if stream is None:
            from ..parallel.streaming import BlockStream

            y_enc = np.asarray(
                model._encode_y(np.asarray(self.y)), np.float32
            )
            stream = BlockStream((self.X, y_enc),
                                 block_rows=self.block_rows,
                                 shuffle=False, profile=False)
            # finer dispatch granularity than a plain streamed fit
            # (~4 super-blocks per full pass): a Hyperband round's
            # timeline mixes wide early steps with narrow survivor
            # tails, and each dispatch picks its slot RUNG from the
            # union of active candidates — coarse super-blocks would
            # drag the whole round onto the widest rung. The byte
            # budget in resolve_superblock_k still caps K
            stream._superblock_k_override = max(
                2, -(-self.n_blocks // 4)
            )
            self._streams[key] = stream
        return stream

    def holdout_for(self, key, cls, model):
        holdout = self._holdouts.get(key)
        if holdout is None:
            holdout = cls._cohort_holdout(self.X_test, self.y_test,
                                          model)
            self._holdouts[key] = holdout
        return holdout

    def note_round(self, info):
        """Fold one cohort round's engagement record into the plane's
        stats (surfaced on ``search.metadata_["stream"]`` so smoke
        suites assert engagement instead of trusting the gates)."""
        self.stats["rounds"] += 1
        self.stats["dispatches"] += int(info.get("dispatches", 0))
        self.stats["shards"] = int(info.get("shards", 1))
        self.stats["sparse"] = bool(info.get("sparse", False))
        self.stats["fused"] = bool(info.get("fused", False))
        self.stats["fused_reason"] = info.get("fused_reason")

    def snapshot(self):
        return {"streamed": True, "n_blocks": int(self.n_blocks),
                "block_rows": int(self.block_rows),
                "n_slots": int(self.n_slots), **self.stats}


@functools.cache
def _stack_programs():
    """(gather, scatter) of rows of the stacked weights, jitted once: an
    eager ``W[idx]`` / ``W.at[idx].set`` spends milliseconds of host time in
    jax's indexing machinery a call, twice a group."""
    import jax

    return (jax.jit(lambda W, idx: W[idx]),
            jax.jit(lambda W, idx, rows: W.at[idx].set(rows),
                    donate_argnums=0))


def _candidate_factory(estimator):
    """``params -> a new unfitted estimator with them``: what
    ``clone(estimator).set_params(**params)`` gives, without reading the
    constructor's signature twice a candidate (a search makes 143 of
    them). One clone is the prototype; a candidate is a shallow copy of it
    with its own parameters set. Nested estimators and unknown names take
    the slow way, which validates and deep-copies."""
    proto = clone(estimator)
    own = proto.get_params(deep=False)
    flat = not any(hasattr(v, "get_params") for v in own.values())

    def factory(params):
        if not (flat and set(params) <= set(own)):
            return clone(estimator).set_params(**params)
        model = _copy.copy(proto)
        for k, v in params.items():
            setattr(model, k, v)
        return model

    return factory


class _GridBlocks:
    """The blocks of a resident search's grid as the ``(X, y)`` pairs a solo
    trial's ``partial_fit`` takes — views of the grid made when first asked
    for (a cohort never asks)."""

    def __init__(self, plane):
        self._plane = plane
        self._pairs = {}

    def __len__(self):
        return self._plane.n_blocks

    def __getitem__(self, i):
        pair = self._pairs.get(i)
        if pair is None:
            p = self._plane
            nv = int(p.block_valid[i])
            pair = self._pairs[i] = (
                ShardedArray(p.Xr[i], nv, p.mesh),
                ShardedArray(p.raw_labels()[0][i], nv, p.mesh))
        return pair


class _ResidentCohortPlane:
    """The data plane of an adaptive search over a RESIDENT, row-sharded
    table and a batched-trial estimator: everything a fit keeps on the
    device, built once.

    - the PARTITION is ``grid_partition``'s — the minibatches
      ``Incremental`` and ``SGDClassifier.fit`` train on the same rows —
      and call ``i`` of a model trains block ``i mod B``;
    - the train/held-out split and the blocking are ONE gather a fit
      (``search.split_x`` / ``search.split_y``), into the fit dtype: the
      ``(B, S, d)`` block grid and the held-out block, with no float32 copy
      of either split beside the caller's X. The split itself is
      ``train_test_split``'s (shuffled within each shard, from
      ``random_state``);
    - the candidates' weights live STACKED on the device, ``W (n_slots,
      d+1)``, model ``mid`` in row ``mid``: a group of a round gathers its
      rows, runs one cohort scan over the grid and scatters them back; the
      whole stack is scored on the held-out block by one program a round;
      the host sees the weights once, when the fit finishes (``adopt``).
      A model that leaves the stack for a solo trial is ``release``d first
      and reloaded if it comes back.

    ``refused`` (a dict, the headroom gate's reading) instead of a plane
    when the grid does not fit beside what is allocated: the search then
    keeps the partition and gathers block by block."""

    def __init__(self, cls, probe, X, y, train_idx, test_idx, grid_dtype,
                 n_slots):
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import data_shards
        from ..parallel.sharded import _padded_rows, _scatter
        from ..parallel.streaming import grid_partition

        self.cls, self.mesh, self.n_slots = cls, X.mesh, int(n_slots)
        D = max(data_shards(X.mesh), 1)
        n_train, self.n_test = len(train_idx), len(test_idx)
        self.n_blocks, self.block_rows = grid_partition(
            _padded_rows(n_train, D), D)
        B, S = self.n_blocks, self.block_rows
        T = _padded_rows(self.n_test, D)
        tr = np.zeros(B * S, np.int32)
        tr[:n_train] = train_idx
        te = np.zeros(T, np.int32)
        te[:self.n_test] = test_idx
        self.block_valid = np.clip(n_train - S * np.arange(B), 0, S)
        self.NV = _scatter(self.block_valid.astype(np.int32), X.mesh, P())
        split_x, split_y = cls._cohort_split_programs(X.mesh, B, S, T,
                                                      grid_dtype)
        tr = _scatter(tr, X.mesh, P())
        te = _scatter(te, X.mesh, P())
        self.Xr, self.Xt = split_x(X.data, tr, te)
        self.yr = self.yt = None
        if probe is not None:
            # the cohort's targets: encoded once over all of y (the class
            # check's one scalar fetch), then split as X was
            y_enc = probe._encode_y(y)
            self.yr, self.yt = split_y(
                y_enc.data.astype(np.float32), tr, te)
        # the RAW labels are split only for a trial that leaves the cohort
        # (a solo partial_fit encodes for itself): a gather of scalars is
        # slow on the chip, and a cohort never asks
        self._raw = (split_y, y, tr, te)
        self.blocks = _GridBlocks(self)
        self.X_test = ShardedArray(self.Xt, self.n_test, X.mesh)
        self.grid_bytes = int(self.Xr.nbytes + self.Xt.nbytes
                              + (self.yr.nbytes + self.yt.nbytes
                                 if self.yr is not None else 0))
        self.W = None            # (n_slots, d + 1) on the device
        self.in_stack = set()    # model ids whose weights W holds

    def raw_labels(self):
        """(the grid's, the held-out block's) labels as the caller gave
        them, split on first use."""
        if callable(self._raw[0]):
            split_y, y, tr, te = self._raw
            self._raw = split_y(y.data, tr, te)
        return self._raw

    @property
    def y_test(self):
        return ShardedArray(self.raw_labels()[1], self.n_test, self.mesh)

    def close(self):
        """Drop everything the plane holds on the device, now: the fit is
        over (or failed), and the controller's closures, which refer to the
        plane in cycles, may outlive it until a garbage collection — by
        which time the next fit's gate has read 2 GiB less free."""
        if self.Xr is None:
            return
        self.Xr = self.Xt = self.yr = self.yt = self.W = self.NV = None
        self._raw = self.X_test = None
        self.blocks._pairs.clear()

    @classmethod
    def build(cls, estimator, parameters, X, y, split, fit_params,
              n_slots):
        """(plane or None, the gate's reading or None). None, None: the
        estimator has no resident-cohort protocol, y is missing, or
        several processes share the search."""
        from ..parallel import distributed as _dist
        from ..wrappers import _device_headroom

        ecls = type(estimator)
        if not (isinstance(X, ShardedArray) and y is not None
                and hasattr(ecls, "_cohort_split_programs")
                and (_dist.process_count() == 1 or _dist_is_disabled())):
            return None, None
        if not isinstance(y, ShardedArray):
            from ..parallel.sharded import as_sharded

            y = as_sharded(np.asarray(y), mesh=X.mesh)
        probe = clone(estimator)
        probe._batch_prepare(fit_params)
        if probe._batch_key() is None:
            probe = None         # every trial runs solo, on the raw labels
        names = set().union(*(
            [parameters] if isinstance(parameters, dict) else parameters))
        grid_dtype = ecls._cohort_grid_dtype(estimator,
                                             searched="fit_dtype" in names)
        train_idx, test_idx = split
        d = int(X.data.shape[1])
        item = np.dtype(grid_dtype or X.data.dtype).itemsize
        needed = (len(train_idx) + len(test_idx)) * d * item
        gate = _device_headroom(needed, X)
        if not gate["fits"]:
            return None, gate
        return cls(ecls, probe, X, y, train_idx, test_idx, grid_dtype,
                   n_slots), gate

    # -- the stacked weights ------------------------------------------------
    def take(self, mids, models):
        """The ``(len(mids), d+1)`` rows of ``mids`` (device), loading from
        the models those the stack does not hold yet."""
        import jax.numpy as jnp

        d1 = int(self.Xr.shape[2]) + 1
        if self.W is None:
            self.W = jnp.zeros((self.n_slots, d1), jnp.float32)
        missing = [m for m in mids if m not in self.in_stack]
        if missing:
            for m in missing:
                if getattr(models[m], "_w", None) is None:
                    # zeros, as _ensure_state starts a model
                    models[m]._w = np.zeros(d1, np.float32)
                    models[m]._t = 0
            rows = np.stack([np.asarray(models[m]._w, np.float32)
                             for m in missing])
            self.put(missing, rows)
        self.in_stack.update(missing)
        return _stack_programs()[0](self.W, np.asarray(mids, np.int32))

    def put(self, mids, Wg):
        self.W = _stack_programs()[1](self.W, np.asarray(mids, np.int32), Wg)

    def adopt(self, models, mids=None, release=False):
        """The stacked weights to the host in ONE fetch, handed to the
        models ``mids`` (default: all the stack holds) as their own
        ``_w`` and fitted attributes; ``release``: they leave the stack
        (a solo trial follows, or the fit is over)."""
        held = sorted(self.in_stack if mids is None
                      else self.in_stack.intersection(mids))
        if not held:
            return
        Wh = np.asarray(to_host(self.W), np.float32)
        d = Wh.shape[1] - 1
        for m in held:
            models[m]._w = Wh[m]
            models[m]._publish(d)
        if release:
            self.in_stack.difference_update(held)

    def scores(self):
        """The default score of every row of the stack on the held-out
        block: (device array (n_slots,), nothing fetched yet)."""
        import jax.numpy as jnp

        return self.cls._cohort_score(self.W, self.Xt, self.yt,
                                      jnp.int32(self.n_test))


def _program_calls():
    """Tracked-program calls so far; None unless ``config.obs_programs``."""
    from ..observability import programs_enabled, programs_snapshot

    if not programs_enabled():
        return None
    return sum(int(r["calls"]) for r in programs_snapshot())


def fit(model_factory, params_list, train_blocks, X_test, y_test, scorer,
        additional_calls, fit_params=None, patience=False, tol=1e-3,
        max_iter=None, prefix="", verbose=False, checkpoint=None,
        ckpt_token=None, hook_state=None, scoring_is_default=False,
        trial_tags=None, stream_plane=None, resident_plane=None,
        stats=None):
    """Core controller entry: opens the per-fit JSONL sink (closed even on
    error) around the actual controller loop in :func:`_fit`. It opens no
    span: the search's ``fit`` root and its ``fit.solve`` child are the
    caller's (:meth:`BaseIncrementalSearchCV.fit`). ``stats`` (a dict) is
    filled with the record of what ran: see ``search_info_``."""
    from ..observability import fit_logger

    with fit_logger("adaptive_search", prefix=prefix) as logger:
        return _fit(model_factory, params_list, train_blocks, X_test,
                    y_test, scorer, additional_calls, fit_params=fit_params,
                    patience=patience, tol=tol, max_iter=max_iter,
                    prefix=prefix, verbose=verbose, checkpoint=checkpoint,
                    ckpt_token=ckpt_token, hook_state=hook_state,
                    scoring_is_default=scoring_is_default, logger=logger,
                    trial_tags=trial_tags, stream_plane=stream_plane,
                    resident_plane=resident_plane, stats=stats)


def _fit(model_factory, params_list, train_blocks, X_test, y_test, scorer,
         additional_calls, fit_params=None, patience=False, tol=1e-3,
         max_iter=None, prefix="", verbose=False, checkpoint=None,
         ckpt_token=None, hook_state=None, scoring_is_default=False,
         logger=None, trial_tags=None, stream_plane=None,
         resident_plane=None, stats=None):
    """Core controller (ref: _incremental.py::_fit). Returns
    (info, models, meta, history).

    ``checkpoint`` (utils.checkpoint.SearchCheckpoint, optional) persists
    (history, meta, models, active set, hook state) after every adaptive
    round; an INTERRUPTED search whose saved identity token matches
    ``ckpt_token`` resumes at round granularity instead of restarting
    (SURVEY.md §5 — capability the reference lacks: its killed searches
    lose all model futures). A checkpoint is cleared on successful
    completion, so finished searches never leak state into new ones.
    ``hook_state`` is a (get, set) pair persisting the adaptive hook's
    schedule position (e.g. SHA's rung) alongside the controller state.
    """
    fit_params = fit_params or {}
    models = {}
    meta = {}
    history = []
    info = {}
    start = time.time()
    n_blocks = len(train_blocks)
    # the record of what ran (``search_info_``): one entry a round, one a
    # group of it, and the seconds of the solve by what they went to
    stats = {} if stats is None else stats
    stats.update(rounds=[], train_s=0.0, score_s=0.0, publish_s=0.0,
                 sync_s=0.0)
    groups_now = []          # the group records of the round being run

    def held_out():
        """(X, y) a solo trial is scored on; the resident plane splits its
        raw labels only when first asked."""
        if resident_plane is not None:
            return resident_plane.X_test, resident_plane.y_test
        return X_test, y_test

    def note_group(path, steps, n_models, program=None, dispatches=None,
                   model_steps=None):
        # ``steps`` on the group's timeline; ``model_steps``: partial_fit
        # calls it made (steps x models unless activity masks thin them)
        groups_now.append({"path": path, "steps": int(steps),
                           "n_models": int(n_models), "program": program,
                           "dispatches": dispatches,
                           "model_steps": int(steps * n_models
                                              if model_steps is None
                                              else model_steps)})

    def wait(value):
        """Block on ``value``; the stall goes to ``stats["sync_s"]`` and to
        the open span's (``fit.solve``'s) ``sync_s``."""
        import jax

        from ..observability import current_span

        t0 = time.perf_counter()
        current_span().sync(value)
        jax.block_until_ready(value)
        stats["sync_s"] += time.perf_counter() - t0
    # Multi-process candidate distribution (SURVEY.md §3.5 'trials pinned
    # to hosts'): model mid is OWNED by process (mid % n_proc); each
    # round every process trains/scores only its models, then one
    # object-allgather merges the round's records so the adaptive
    # decisions (additional_calls, patience, budget caps) are computed
    # identically everywhere from identical info.
    from ..parallel import distributed as _dist

    n_proc = 1 if _dist_is_disabled() else _dist.process_count()
    pid = _dist.process_index() if n_proc > 1 else 0
    placement_mesh = None
    if n_proc > 1:
        # per-process partial model state is not round-resumable
        checkpoint = None
        ckpt_token = None
        # owned candidates run on THIS process's local-device mesh: a
        # device estimator would otherwise dispatch global-mesh
        # collectives its peers (busy with their own candidates) never
        # enter — a silent deadlock (same placement rule as Hyperband's
        # bracket distribution)
        from ..parallel.distributed import local_mesh

        placement_mesh = local_mesh()

    def _owned(mid):
        return n_proc == 1 or mid % n_proc == pid

    pending = []  # this round's records, exchanged at the round barrier

    def sync_round(exc=None):
        if n_proc == 1:
            if exc is not None:
                raise exc
            return
        from ..parallel.distributed import allgather_object

        payload = {
            "records": list(pending),
            "meta": {mid: {k: meta[mid][k] for k in
                           ("partial_fit_calls", "block_cursor", "score")}
                     for mid in meta if _owned(mid)},
            "error": None if exc is None else repr(exc),
        }
        pending.clear()
        parts = allgather_object(payload)
        if exc is not None:
            raise exc
        bad = [p["error"] for p in parts if p["error"] is not None]
        if bad:
            raise RuntimeError(
                f"peer process failed during distributed adaptive "
                f"search: {bad}"
            )
        merged = [r for p in parts for r in p["records"]]
        merged.sort(key=lambda r: (r["partial_fit_calls"], r["model_id"]))
        for rec in merged:
            history.append(rec)
            info[rec["model_id"]].append(rec)
        for p in parts:
            for mid, m in p["meta"].items():
                meta[mid].update(m)

    def run_round(requests):
        """One adaptive round: local execution of the owned share (on the
        local mesh under multi-process), then the record exchange — a
        failure anywhere fails every process fast instead of hanging
        peers in the allgather."""
        import contextlib

        import jax

        from ..parallel.mesh import use_mesh

        placement = (use_mesh(placement_mesh) if placement_mesh is not None
                     else contextlib.nullcontext())
        # a round is a RECORD, not a span: the search's root keeps flat
        # children only. The bare annotation puts it on a profiler's
        # timeline, where idle gaps are put down to rounds
        rec = {"round": round_idx, "n_trials": len(requests),
               "n_calls": int(sum(requests.values()))}
        del groups_now[:]
        calls0, sync0 = _program_calls(), stats["sync_s"]
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("dmt.search.round"), placement:
                run_requests(requests)
        except Exception as e:
            sync_round(e)
            raise
        sync_round()
        rec.update(wall_s=time.perf_counter() - t0,
                   sync_s=stats["sync_s"] - sync0,
                   dispatches=None if calls0 is None
                   else _program_calls() - calls0,
                   groups=list(groups_now))
        stats["rounds"].append(rec)
        if logger is not None:
            logger.log(event="search.round", **{
                k: rec[k] for k in ("round", "n_trials", "n_calls",
                                    "wall_s", "sync_s")})
    round_idx = 0
    active = None

    restored = checkpoint.load() if checkpoint is not None else None
    if restored is not None and restored.get("token") == ckpt_token \
            and ckpt_token is not None:
        round_idx = restored["round"]
        history = restored["history"]
        meta = restored["meta"]
        models = restored["models"]
        active = set(restored["active"])
        # keep history timestamps monotonic across the restart
        start = time.time() - restored.get("elapsed", 0.0)
        if hook_state is not None and restored.get("hook") is not None:
            hook_state[1](restored["hook"])
        info = {mid: [r for r in history if r["model_id"] == mid]
                for mid in models}
    else:
        restored = None

    def save_round():
        if checkpoint is None:
            return
        if resident_plane is not None:
            # the models are pickled: their weights come to the host
            t0 = time.perf_counter()
            resident_plane.adopt(models)
            stats["publish_s"] += time.perf_counter() - t0
        checkpoint.save_round(round_idx, history, meta, models, extra={
            "token": ckpt_token,
            "active": sorted(active) if active is not None else sorted(models),
            "hook": hook_state[0]() if hook_state is not None else None,
            "elapsed": time.time() - start,
        })

    if restored is None:
        for mid, params in enumerate(params_list):
            models[mid] = model_factory(params)
            meta[mid] = {
                "model_id": mid, "params": params, "partial_fit_calls": 0,
                "score": None, "block_cursor": 0,
            }
            info[mid] = []

    def record_scores(mids, scores, fit_time, score_time,
                      executor="sequential"):
        for mid, score in zip(mids, scores):
            m = meta[mid]
            m["score"] = float(score)
            record = {
                "model_id": mid,
                "params": m["params"],
                "partial_fit_calls": m["partial_fit_calls"],
                "partial_fit_time": fit_time,
                "score": float(score),
                "score_time": score_time,
                "elapsed_wall_time": time.time() - start,
                "batch_size": len(mids),
                "executor": executor,
                "thread": threading.get_ident(),
                "owner": pid,
            }
            if n_proc > 1:
                pending.append(record)
            else:
                history.append(record)
                info[mid].append(record)
            if logger is not None:
                tags = trial_tags(mid) if trial_tags is not None else {}
                logger.log(step=m["partial_fit_calls"], model_id=mid,
                           partial_fit_calls=m["partial_fit_calls"],
                           score=float(score), batch_size=len(mids),
                           partial_fit_time=fit_time,
                           score_time=score_time, **tags)

    def train_one(mid, n_calls, executor="sequential", blocks=None,
                  test=None):
        """``blocks``/``test`` override the shared data plane when a
        trial runs on a submesh with pre-placed copies."""
        import scipy.sparse as sp

        m = meta[mid]
        model = models[mid]
        if resident_plane is not None:
            resident_plane.adopt(models, [mid], release=True)
        device_model = type(model).__module__.startswith("dask_ml_tpu")
        t0 = time.time()
        for i in range(n_calls):
            Xb, yb = (blocks[i] if blocks is not None
                      else train_blocks[m["block_cursor"] % n_blocks])
            if sp.issparse(Xb) and device_model:
                # device estimators' per-block partial_fit takes dense
                # operands; a solo trial that fell out of the streamed
                # cohort densifies ONE block at a time (host sklearn
                # estimators consume the CSR natively)
                Xb = Xb.toarray()
            model.partial_fit(Xb, yb, **fit_params)
            m["block_cursor"] += 1
            m["partial_fit_calls"] += 1
        fit_time = time.time() - t0
        t0 = time.time()
        Xt, yt = test if test is not None else held_out()
        score = scorer(model, Xt, yt)
        score_time = time.time() - t0
        stats["train_s"] += fit_time
        stats["score_s"] += score_time
        note_group("solo", n_calls, 1)
        record_scores([mid], [score], fit_time, score_time,
                      executor=executor)

    # per-submesh test-split copies, keyed by the submesh's device ids;
    # rebuilt only when the round's partition changes
    _submesh_test_cache = {}

    def run_dev_solo(dev_solo):
        """Device-native solo trials on DISJOINT submeshes (VERDICT r3
        weak #3): the same placement rule grid search uses
        (_search.py::_submeshes) applied to the incremental controller —
        k heterogeneous device candidates run concurrently, each
        entirely inside its own submesh, so their XLA collectives can
        never interleave on shared devices. Trained weights are pulled
        to host after each wave (host_view_estimator): model state must
        not stay pinned to a submesh, because the NEXT round may place
        the model on a different mesh."""
        from concurrent.futures import ThreadPoolExecutor

        from ..parallel.mesh import use_mesh

        if not dev_solo:
            return
        device_plane = isinstance(train_blocks[0][0], ShardedArray)
        if device_plane:
            parent = train_blocks[0][0].mesh
        elif placement_mesh is not None:
            parent = placement_mesh
        else:
            from ..parallel.mesh import resolve_mesh

            parent = resolve_mesh(None)
        if len(dev_solo) <= 1 or parent.devices.size < 2:
            for mid, n_calls in dev_solo:
                train_one(mid, n_calls)
                # the invariant below holds on EVERY path: weights go
                # back to host so a later round may re-place the model
                host_view_estimator(models[mid])
            return
        from ._search import _submeshes

        subs = _submeshes(parent, len(dev_solo))
        if not device_plane:
            # host blocks: each trial checks a submesh out; concurrent
            # host->device placement is safe (same rule as grid search's
            # pure-host-folds branch)
            import queue as _queue

            free = _queue.SimpleQueue()
            for s in subs:
                free.put(s)

            def on_submesh(mid, n_calls):
                sub = free.get()
                try:
                    with use_mesh(sub):
                        train_one(mid, n_calls, executor="submesh")
                    host_view_estimator(models[mid])
                finally:
                    free.put(sub)

            with ThreadPoolExecutor(max_workers=len(subs)) as pool:
                futures = [pool.submit(on_submesh, mid, n_calls)
                           for mid, n_calls in dev_solo]
                for f in futures:
                    f.result()
            return
        # device plane: reshard each trial's round blocks + one test copy
        # per submesh DEVICE-TO-DEVICE on the parent mesh BEFORE trials
        # launch (parent-mesh programs in flight during submesh trials
        # can deadlock on shared devices), then run the wave concurrently
        import jax as _jx

        from ..parallel.sharded import reshard

        def _reshard_pair(pair, sub):
            Xb, yb = pair
            return (
                reshard(Xb, sub) if isinstance(Xb, ShardedArray) else Xb,
                reshard(yb, sub) if isinstance(yb, ShardedArray) else yb,
            )

        keys = {tuple(d.id for d in s.devices.reshape(-1)) for s in subs}
        if set(_submesh_test_cache) != keys:
            _submesh_test_cache.clear()
        S = len(subs)
        for w0 in range(0, len(dev_solo), S):
            wave = dev_solo[w0:w0 + S]
            prepared = []
            for j, (mid, n_calls) in enumerate(wave):
                sub = subs[j]
                cur = meta[mid]["block_cursor"]
                blks = [
                    _reshard_pair(train_blocks[(cur + i) % n_blocks], sub)
                    for i in range(n_calls)
                ]
                key = tuple(d.id for d in sub.devices.reshape(-1))
                if key not in _submesh_test_cache:
                    _submesh_test_cache[key] = _reshard_pair(
                        held_out(), sub
                    )
                prepared.append((mid, n_calls, sub, blks,
                                 _submesh_test_cache[key]))
            _jx.block_until_ready([
                a.data for _, _, _, blks, test in prepared
                for pair in (list(blks) + [test]) for a in pair
                if isinstance(a, ShardedArray)
            ])

            def on_sub(mid, n_calls, sub, blks, test):
                with use_mesh(sub):
                    train_one(mid, n_calls, executor="submesh",
                              blocks=blks, test=test)
                host_view_estimator(models[mid])

            with ThreadPoolExecutor(max_workers=len(wave)) as pool:
                futures = [pool.submit(on_sub, *args) for args in prepared]
                for f in futures:
                    f.result()

    def train_cohort(mids, n_calls):
        """Advance a homogeneous cohort: each of the n_calls steps is ONE
        jitted vmapped program over the stacked weight pytree — the TPU
        replacement for the reference's N concurrent model futures
        (ref _incremental.py::_fit async controller, SURVEY.md §3.5)."""
        cohort = [models[mid] for mid in mids]
        cls = type(cohort[0])
        t0 = time.time()
        fused = n_calls > 1 and hasattr(cls, "_batched_fused_calls")
        if fused:
            # the round's n_calls block steps collapse into ONE scan
            # program (same updates and lr clocks as the call loop).
            # Blocks are deduplicated — a multi-epoch rung revisits them
            # through the order operand — and the stack must fit
            # alongside the dataset (one block at a time otherwise).
            cursor = meta[mids[0]]["block_cursor"]
            idxs = [(cursor + i) % n_blocks for i in range(n_calls)]
            uniq = sorted(set(idxs))
            pos = {j: k for k, j in enumerate(uniq)}
            stack_bytes = sum(
                train_blocks[j][0].data.nbytes for j in uniq
                if isinstance(train_blocks[j][0], ShardedArray)
            )
            from ..wrappers import _device_headroom

            fused = _device_headroom(
                stack_bytes, train_blocks[uniq[0]][0]
            )["fits"]
        if fused:
            cls._batched_fused_calls(
                cohort, [train_blocks[j] for j in uniq],
                order=[pos[j] for j in idxs],
            )
            for mid in mids:
                meta[mid]["block_cursor"] += n_calls
                meta[mid]["partial_fit_calls"] += n_calls
        else:
            for _ in range(n_calls):
                cursor = meta[mids[0]]["block_cursor"] % n_blocks
                Xb, yb = train_blocks[cursor]
                cls._batched_partial_fit(cohort, Xb, yb)
                for mid in mids:
                    meta[mid]["block_cursor"] += 1
                    meta[mid]["partial_fit_calls"] += 1
        cls._batch_publish(cohort, train_blocks[0][0].shape[1])
        fit_time = time.time() - t0
        t0 = time.time()
        if scoring_is_default and hasattr(cls, "_batched_score_default"):
            scores = cls._batched_score_default(cohort, X_test, y_test)
        else:
            scores = [scorer(m, X_test, y_test) for m in cohort]
        score_time = time.time() - t0
        stats["train_s"] += fit_time
        stats["score_s"] += score_time
        note_group("blocks_scan" if fused else "step_loop", n_calls,
                   len(mids))
        # per-model share of the cohort's wall time: summing history_
        # timings then matches actual wall clock whether models advanced
        # solo or batched (batch_size recovers the cohort total)
        record_scores(mids, scores, fit_time / len(mids),
                      score_time / len(mids), executor="vmapped")

    def train_groups_resident(groups):
        """A round over the resident plane: every group is ONE cohort scan
        over the fit's grid, dispatched one after another on the stacked
        weights; then the whole stack is scored on the held-out block by
        one program and the scores come to the host in one fetch. The
        operands of every group are built before the first dispatch, so
        the seconds split cleanly: building them is the controller's,
        first dispatch to the stack's sync is ``train_s``."""
        plane = resident_plane
        staged = []
        for (key, n_calls, cursor), mids in sorted(
            groups.items(), key=lambda kv: kv[1][0]
        ):
            cohort = [models[mid] for mid in mids]
            staged.append((
                mids, cohort, n_calls,
                [(cursor + i) % n_blocks for i in range(n_calls)],
                plane.take(mids, models),
                plane.cls._cohort_operands(cohort, n_calls)))
        t0 = time.perf_counter()
        for mids, cohort, n_calls, order, Wg, operands in staged:
            calls0 = _program_calls()
            Wg, _, name = plane.cls._cohort_scan(
                cohort, plane, order, Wg, operands)
            plane.put(mids, Wg)
            note_group("cohort_scan", n_calls, len(mids), program=name,
                       dispatches=None if calls0 is None
                       else _program_calls() - calls0)
            for mid in mids:
                meta[mid]["block_cursor"] += n_calls
                meta[mid]["partial_fit_calls"] += n_calls
        wait(plane.W)
        train_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        trained = [mid for mids, *_ in staged for mid in mids]
        if scoring_is_default:
            dev = plane.scores()
            wait(dev)
            score_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            scores = np.asarray(to_host(dev), np.float64)[trained]
            publish_s = time.perf_counter() - t0
        else:
            plane.adopt(models, trained)
            publish_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            scores = [scorer(models[mid], *held_out()) for mid in trained]
            score_s = time.perf_counter() - t0
        stats["train_s"] += train_s
        stats["score_s"] += score_s
        stats["publish_s"] += publish_s
        # a model's share of the round's wall: by its calls for the
        # training, evenly for the scoring
        total = float(sum(n * len(mids) for mids, _, n, *_ in staged))
        at = 0
        for mids, _, n_calls, *_ in staged:
            record_scores(mids, scores[at:at + len(mids)],
                          train_s * n_calls / total,
                          score_s / len(trained), executor="vmapped")
            at += len(mids)

    def train_cohort_streamed(key, ent):
        """Advance every batchable candidate sharing ``key`` through
        ONE streamed superblock pass (ISSUE 14 tentpole): the round's
        requests — heterogeneous ``n_calls`` included — compress onto
        one block-step timeline (two models at the same absolute call
        index share the step; per-model activity masks pick who
        advances), so the data is read from host once per round
        regardless of candidate count, and each model still trains on
        exactly the blocks its own ``partial_fit`` loop would have."""
        mids = [mid for mid, _ in ent]
        cohort = [models[mid] for mid in mids]
        cls = type(cohort[0])
        t0 = time.time()
        stream = stream_plane.stream_for(key, cohort[0])
        nb = stream_plane.n_blocks
        starts = {mid: meta[mid]["block_cursor"] for mid in mids}
        timeline = sorted({starts[mid] + j
                           for mid, nc in ent for j in range(nc)})
        step_of = {t: s for s, t in enumerate(timeline)}
        order = np.asarray([t % nb for t in timeline], np.int64)
        act = np.zeros((len(timeline), len(mids)), np.float32)
        for i, (mid, nc) in enumerate(ent):
            for j in range(nc):
                act[step_of[starts[mid] + j], i] = 1.0
        info_round = cls._streamed_cohort_round(
            cohort, stream, order, act, stream_plane.n_slots,
            # first streamed round of the search: warm the whole slot
            # rung ladder so later bracket shrinks stay at zero compiles
            warm=stream_plane.stats["rounds"] == 0,
        )
        for mid, nc in ent:
            meta[mid]["block_cursor"] += nc
            meta[mid]["partial_fit_calls"] += nc
        fit_time = time.time() - t0
        t0 = time.time()
        if scoring_is_default and hasattr(cls, "_cohort_holdout_scores"):
            holdout = stream_plane.holdout_for(key, cls, cohort[0])
            scores = cls._cohort_holdout_scores(
                cohort, holdout, stream_plane.n_slots
            )
        else:
            scores = [scorer(m, X_test, y_test) for m in cohort]
        score_time = time.time() - t0
        stats["train_s"] += fit_time
        stats["score_s"] += score_time
        note_group("streamed", len(timeline), len(mids),
                   dispatches=int(info_round.get("dispatches", 0)),
                   model_steps=sum(nc for _, nc in ent))
        stream_plane.note_round(info_round)
        record_scores(mids, scores, fit_time / len(mids),
                      score_time / len(mids), executor="streamed")

    def run_requests(requests):
        """Execute {mid: n_calls>0}: cohort-batch everything batchable,
        grouped by (batch key, n_calls, block cursor)."""
        solo, groups = [], {}
        for mid, n_calls in requests.items():
            if not _owned(mid):
                continue
            model = models[mid]
            key = None
            if _supports_batch(model):
                model._batch_prepare(fit_params)
                key = model._batch_key()
            if key is None:
                solo.append((mid, n_calls))
            else:
                gk = (key, n_calls, meta[mid]["block_cursor"] % n_blocks)
                groups.setdefault(gk, []).append(mid)
        # Solo trials: RAW HOST estimators (sklearn et al — nothing from
        # this package) run through a thread pool: their partial_fit/
        # score is host compute, so threads genuinely overlap. Device
        # estimators — batched-protocol models that fell out of a
        # cohort, IncrementalPCA, wrappers — run concurrently on
        # DISJOINT submeshes (run_dev_solo): concurrent XLA programs are
        # safe exactly when they share no devices.
        def _is_host_model(m):
            return not type(m).__module__.startswith("dask_ml_tpu")

        dev_solo = [(m, n) for m, n in solo if not _is_host_model(models[m])]
        host_solo = [(m, n) for m, n in solo if _is_host_model(models[m])]
        run_dev_solo(dev_solo)
        if len(host_solo) > 1:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(
                max_workers=min(8, len(host_solo))
            ) as pool:
                futures = [
                    pool.submit(train_one, mid, n_calls, "threads")
                    for mid, n_calls in host_solo
                ]
                for f in futures:
                    f.result()
        else:
            for mid, n_calls in host_solo:
                train_one(mid, n_calls)
        if stream_plane is not None and groups:
            # streamed cohort plane (ISSUE 14): merge every batchable
            # group with the same key — heterogeneous (n_calls, cursor)
            # combinations ride the SAME pass via per-model step masks,
            # so a Hyperband round's whole bracket union is one stream
            by_key = {}
            for (key, n_calls, _cursor), mids in groups.items():
                by_key.setdefault(key, []).extend(
                    (mid, n_calls) for mid in mids
                )
            for key, ent in sorted(
                by_key.items(), key=lambda kv: min(m for m, _ in kv[1])
            ):
                train_cohort_streamed(key, sorted(ent))
            return
        if resident_plane is not None and groups:
            train_groups_resident(groups)
            return
        for (key, n_calls, _cursor), mids in sorted(
            groups.items(), key=lambda kv: kv[1][0]
        ):
            if len(mids) == 1 and n_calls == 1:
                train_one(mids[0], n_calls)
            else:
                # a SINGLE batchable model asked for several calls still
                # takes the cohort path: its n_calls block steps fuse
                # into one scan program (super-block execution of the
                # partial_fit driver) instead of n_calls dispatches
                train_cohort(mids, n_calls)

    # first round: one call each (skipped when resuming a checkpoint)
    if restored is None:
        run_round({mid: 1 for mid in models})
        round_idx = 1
        active = set(models)
        save_round()

    while active:
        instructions = additional_calls(
            {mid: info[mid] for mid in active}
        )
        instructions = {
            mid: c for mid, c in instructions.items() if mid in active
        }
        active = set(instructions)
        if not instructions or all(c == 0 for c in instructions.values()):
            break
        requests = {}
        for mid, n_calls in instructions.items():
            if n_calls <= 0:
                continue
            if patience and len(info[mid]) > patience:
                recent = [r["score"] for r in info[mid][-patience:]]
                if max(recent) < info[mid][-patience - 1]["score"] + tol:
                    # plateaued: retire so the hook stops asking for it
                    active.discard(mid)
                    continue
            if max_iter is not None and (
                meta[mid]["partial_fit_calls"] + n_calls > max_iter
            ):
                n_calls = max_iter - meta[mid]["partial_fit_calls"]
                if n_calls <= 0:
                    active.discard(mid)
                    continue
            requests[mid] = n_calls
        if not requests:
            break  # every requested model was retired; nothing can advance
        run_round(requests)
        round_idx += 1
        save_round()

    if checkpoint is not None:
        checkpoint.clear()  # completed: never resume into a new search
    if n_proc > 1:
        # every process receives every trained model (small: weights +
        # params), so best_estimator_ and post-fit delegation work
        # identically everywhere
        from ..parallel.distributed import allgather_object

        parts = allgather_object({
            mid: host_view_estimator(models[mid])
            for mid in models if _owned(mid)
        })
        for part in parts:
            models.update(part)
    return info, models, meta, history


class BaseIncrementalSearchCV(BaseEstimator):
    """Shared plumbing of the futures-style searches."""

    def __init__(self, estimator, parameters, n_initial_parameters=10,
                 test_size=None, patience=False, tol=1e-3, max_iter=100,
                 random_state=None, scoring=None, verbose=False, prefix=""):
        self.estimator = estimator
        self.parameters = parameters
        self.n_initial_parameters = n_initial_parameters
        self.test_size = test_size
        self.patience = patience
        self.tol = tol
        self.max_iter = max_iter
        self.random_state = random_state
        self.scoring = scoring
        self.verbose = verbose
        self.prefix = prefix

    # -- hooks overridden by subclasses -----------------------------------
    def _n_initial(self):
        return self.n_initial_parameters

    def _additional_calls(self, info):
        raise NotImplementedError

    def _reset_hook(self):
        """Reset adaptive-schedule state at the start of each fit."""

    def _hook_state(self):
        """Schedule position persisted with checkpoints (e.g. SHA rung)."""
        return {}

    def _trial_tags(self, mid):
        """Extra JSONL fields attached to model ``mid``'s telemetry
        records (Hyperband tags the bracket)."""
        return {}

    def _set_hook_state(self, state):
        for k, v in state.items():
            setattr(self, k, v)

    def _sample_params(self, n):
        return list(ParameterSampler(
            self.parameters, n, random_state=self.random_state
        ))

    def fit(self, X, y=None, **fit_params):
        """One root span ``fit`` over the whole call, with four flat
        children: ``fit.validate`` (checks, the scorer, the candidates'
        draw), ``fit.prepare`` (the split, the blocks or the resident
        grid), ``fit.solve`` (every round; it carries the sums of
        ``search_info_``) and ``fit.finish`` (the weights' way to the
        host, ``cv_results_``, ``best_estimator_``). A round is a record
        of ``search_info_["rounds"]``, not a span."""
        from ..observability import span

        with span("fit", component=type(self).__name__) as root:
            with span("fit.validate"):
                test_size, scorer_raw, params_list = \
                    self._fit_validate(X, y)
            with span("fit.prepare") as sp:
                plane = self._fit_prepare(X, y, test_size, params_list,
                                          fit_params)
                sp.add(data_plane=plane["info"]["plane"],
                       grid_bytes=plane["info"]["grid_bytes"])
            try:
                with span("fit.solve") as sp:
                    stats = {}
                    t0 = time.perf_counter()
                    solved = self._fit_solve(
                        plane, params_list, scorer_raw, test_size,
                        fit_params, X, y, stats)
                    sums = self._search_sums(stats,
                                             time.perf_counter() - t0)
                    # ``dispatches`` on the span is its ledger's own count
                    sp.add(**{k: v for k, v in sums.items()
                              if k != "dispatches"})
                with span("fit.finish"):
                    self._fit_finish(plane, params_list, scorer_raw, solved,
                                     stats, sums)
            finally:
                if plane["resident"] is not None:
                    plane["resident"].close()   # a fit that raised
            root.add(n_models=self.metadata_["n_models"],
                     partial_fit_calls=self.metadata_["partial_fit_calls"],
                     n_iter=sums["rounds"])
        return self

    def _fit_validate(self, X, y):
        from ..parallel import distributed as _dist

        if _dist.process_count() > 1 and not _dist_is_disabled():
            if isinstance(X, ShardedArray) or isinstance(y, ShardedArray):
                raise ValueError(
                    "multi-process adaptive search requires host-resident "
                    "X/y (each process loads its copy and trains a "
                    "disjoint candidate subset)"
                )
            if self.random_state is None:
                raise ValueError(
                    "multi-process adaptive search requires a fixed "
                    "random_state: every process must derive the "
                    "IDENTICAL train/test split and candidate sample"
                )
            self._dist_stats = (_dist.process_index(), _dist.process_count())
        test_size = self.test_size
        if test_size is None:
            test_size = 0.15
        scorer_raw = check_scoring(self.estimator, self.scoring)
        return test_size, scorer_raw, self._sample_params(self._n_initial())

    def _fit_prepare(self, X, y, test_size, params_list, fit_params):
        """The fit's data plane: ``{"blocks", "X_test", "y_test",
        "stream", "resident", "info"}``. Three flavours —

        - a RESIDENT table and a batched-trial estimator: the resident
          cohort plane (:class:`_ResidentCohortPlane`: ``grid_partition``
          blocks, one grid and one held-out block a fit, stacked weights);
          where the headroom gate refuses the grid, the same partition
          with blocks gathered one by one (``plane == "blocks"``, the
          gate's reading recorded);
        - host X and a streamed-cohort-capable estimator: the stream
          partition and, by default, the streamed superblock plane;
        - everything else: host (or device) blocks, one a data shard."""
        from ..config import get_config
        from ..parallel.mesh import data_shards, resolve_mesh
        from ..parallel.streaming import _is_sparse_source

        # _split_random_state decouples the SPLIT seed from the SAMPLING
        # seed: Hyperband's multi-process bracket SHAs sample with
        # random_state + s but must split identically to the
        # single-process interleaved fit (one shared split), or results
        # would diverge by process count
        split_seed = getattr(self, "_split_random_state", self.random_state)
        est_device = _supports_batch(self.estimator)
        info = {"plane": "blocks", "gate": None, "grid_bytes": 0,
                "block_rows": None, "blocks": None}
        out = {"stream": None, "resident": None, "info": info}
        block_rows = None
        if est_device and isinstance(X, ShardedArray) and y is not None:
            from ._split import split_indices

            split = split_indices(
                X, X.n_rows, test_size=test_size,
                rng=np.random.RandomState(split_seed))
            resident, info["gate"] = _ResidentCohortPlane.build(
                self.estimator, self.parameters, X, y, split, fit_params,
                len(params_list))
            if resident is not None:
                info.update(plane="grid", grid_bytes=resident.grid_bytes,
                            block_rows=resident.block_rows,
                            blocks=resident.n_blocks)
                out.update(resident=resident, blocks=resident.blocks,
                           X_test=resident.X_test, y_test=None)
                return out
            if info["gate"] is not None:
                # refused: the same minibatches, gathered block by block
                from ..parallel.sharded import _padded_rows
                from ..parallel.streaming import grid_partition

                D = max(data_shards(X.mesh), 1)
                block_rows = grid_partition(
                    _padded_rows(len(split[0]), D), D)[1]
        X_train, X_test, y_train, y_test = train_test_split(
            X, y, test_size=test_size, random_state=split_seed
        )
        # Device-resident data plane for estimators whose partial_fit
        # consumes device blocks (the batched-trial protocol implies it):
        # blocks and test split stay as ShardedArrays — no full-dataset
        # host round-trip (VERDICT r1 #5). Everything else (raw sklearn,
        # host-only partial_fit like IncrementalPCA) keeps the host plane,
        # as the reference streams blocks to workers.
        if not est_device:
            X_train, y_train = _to_host(X_train), _to_host(y_train)
            X_test, y_test = _to_host(X_test), _to_host(y_test)
        out.update(X_test=X_test, y_test=y_test)
        # Streamed cohort plane (ISSUE 14): single-process searches over
        # host X with a streamed-cohort-capable estimator take the
        # STREAM partition (fit_block_rows — the same minibatches a
        # plain streamed fit trains), and by default execute each round
        # as one BlockStream superblock pass. config.search_stream=False
        # keeps the partition but runs the device-resident cohort
        # machinery over it — the honest A/B the bench records.
        stream_partition = _StreamCohortPlane.eligible(
            self.estimator, X_train
        )
        if stream_partition:
            plane = _StreamCohortPlane(X_train, y_train, X_test, y_test,
                                       n_slots=len(params_list))
            if plane.engaged and get_config().search_stream:
                out["stream"] = plane
                info["plane"] = "stream"
            n_blocks = plane.n_blocks
            blocks = _blocks_of(X_train, y_train, n_blocks,
                                block_rows=plane.block_rows)
            if _is_sparse_source(X_train) and out["stream"] is None:
                raise ValueError(
                    "adaptive search over a sparse X needs the streamed "
                    "cohort plane (the device-resident cohort path would "
                    "densify the corpus); it did not engage: "
                    f"{plane.reason if not plane.engaged else 'config.search_stream=False'}. "
                    "Enable config.stream_sparse/search_stream or "
                    "densify explicitly within the dense byte budget."
                )
        else:
            if est_device and _is_sparse_source(X_train):
                raise ValueError(
                    "adaptive search over a sparse X requires a "
                    "single-process, host-resident streamed cohort "
                    "plane (multi-process searches and device-resident "
                    "inputs keep the dense data plane)"
                )
            n_blocks = (
                data_shards(X.mesh) if isinstance(X, ShardedArray)
                else data_shards(resolve_mesh(None))
            )
            blocks = _blocks_of(X_train, y_train, n_blocks,
                                block_rows=block_rows)
        out["blocks"] = blocks
        info.update(blocks=len(blocks),
                    block_rows=int(blocks[0][0].shape[0]) if blocks else 0)
        return out

    def _fit_solve(self, plane, params_list, scorer_raw, test_size,
                   fit_params, X, y, stats):
        blocks = plane["blocks"]
        factory = _candidate_factory(self.estimator)
        self._reset_hook()
        from ..config import get_config

        ckpt_dir = get_config().checkpoint_dir
        checkpoint = None
        ckpt_token = None
        # random_state=None draws a fresh split every run, so resume is
        # impossible (the split cannot be reproduced) — no checkpoint is
        # created AT ALL: writing unresumable state every round is pure
        # overhead and a shared-directory hazard (ADVICE r1 #2).
        if ckpt_dir and self.random_state is not None:
            import hashlib

            from ..utils.checkpoint import SearchCheckpoint
            from ._normalize import _token_piece, estimator_token

            # identity token: a stale checkpoint from a different search
            # (estimator, candidate params, data CONTENT + shape, split,
            # budget) must NOT be resumed — it would relabel old models
            # with new params or leak a different split's training rows
            # into test scores. The content fingerprint (ADVICE r1 #1)
            # catches same-shape-different-data: a handful of sample rows
            # is hashed, so it costs one tiny device fetch at most.
            ckpt_token = hashlib.sha1("|".join([
                type(self).__name__, self.prefix,
                estimator_token(self.estimator),
                _token_piece(params_list),
                str(getattr(X, "shape", np.shape(X))),
                _data_fingerprint(X), _data_fingerprint(y),
                str(len(blocks)), str(self.max_iter),
                str(self.patience), str(self.tol),
                str(self.random_state), str(test_size),
            ]).encode()).hexdigest()
            # per-search directory: another search of the same class must
            # not overwrite or clear this search's resumable state
            sub = "-".join(
                p for p in (type(self).__name__, self.prefix,
                            ckpt_token[:12])
                if p
            )
            checkpoint = SearchCheckpoint(os.path.join(ckpt_dir, sub))

        return fit(
            factory, params_list, blocks, plane["X_test"], plane["y_test"],
            scorer_raw, self._additional_calls, fit_params=fit_params,
            patience=self.patience, tol=self.tol, max_iter=self.max_iter,
            prefix=self.prefix, verbose=self.verbose, checkpoint=checkpoint,
            ckpt_token=ckpt_token,
            hook_state=(self._hook_state, self._set_hook_state),
            scoring_is_default=self.scoring is None,
            trial_tags=self._trial_tags, stream_plane=plane["stream"],
            resident_plane=plane["resident"], stats=stats,
        )

    @staticmethod
    def _search_sums(stats, wall_s):
        """The sums of a solve over its rounds: what ``fit.solve`` carries
        and ``search_info_`` repeats. ``control_s`` is what is left of the
        solve's wall beside the cohort programs (``train_s``), the
        scoring (``score_s``) and the fetches (``publish_s``): the
        controller's own decisions, records and operands."""
        rounds = stats["rounds"]
        groups = [g for r in rounds for g in r["groups"]]
        known = [r["dispatches"] for r in rounds]
        timed = stats["train_s"] + stats["score_s"] + stats["publish_s"]
        return {
            "rounds": len(rounds), "groups": len(groups),
            "dispatches": None if None in known else int(sum(known)),
            "scan_steps": int(sum(g["steps"] for g in groups)),
            "model_steps": int(sum(g["model_steps"] for g in groups)),
            "train_s": stats["train_s"], "score_s": stats["score_s"],
            "publish_s": stats["publish_s"],
            "control_s": max(wall_s - timed, 0.0),
        }

    def _fit_finish(self, plane, params_list, scorer_raw, solved, stats,
                    sums):
        info, models, meta, history = solved
        resident = plane["resident"]
        if resident is not None:
            # the stacked weights come to the host once, here; then the
            # grid goes (inside this span: freeing 2 GiB is the fit's work)
            resident.adopt(models, release=True)
            resident.close()
        self.history_ = history
        self.model_history_ = info
        n_models = len(params_list)
        scores = np.array([
            info[mid][-1]["score"] if info[mid] else np.nan
            for mid in range(n_models)
        ])
        calls = np.array([meta[mid]["partial_fit_calls"]
                          for mid in range(n_models)])
        order = np.argsort(-scores, kind="stable")
        ranks = np.empty(n_models, np.int32)
        ranks[order] = np.arange(1, n_models + 1)
        results = {
            "params": params_list,
            "test_score": scores,
            "mean_test_score": scores,
            "rank_test_score": ranks,
            "model_id": np.arange(n_models),
            "partial_fit_calls": calls,
        }
        for key in sorted({k for p in params_list for k in p}):
            results[f"param_{key}"] = np.ma.masked_all(n_models, dtype=object)
            for ci, p in enumerate(params_list):
                if key in p:
                    results[f"param_{key}"][ci] = p[key]
        self.cv_results_ = results
        self.best_index_ = int(np.nanargmax(scores))
        self.best_score_ = float(scores[self.best_index_])
        self.best_params_ = params_list[self.best_index_]
        self.best_estimator_ = models[self.best_index_]
        self.n_splits_ = 1
        self.multimetric_ = False
        self.scorer_ = scorer_raw
        stream_plane = plane["stream"]
        self.metadata_ = {
            "n_models": n_models,
            "partial_fit_calls": int(calls.sum()),
            # the streamed-plane engagement record (ISSUE 14): which
            # execution plane the cohort rounds rode, how many
            # superblock dispatches the whole search paid, and the
            # mesh/sparse/fused composition — smoke suites assert on
            # this instead of trusting the gates
            "stream": (stream_plane.snapshot() if stream_plane is not None
                       else {"streamed": False}),
        }
        from ..config import fit_dtype_info

        # what happened, as Incremental.pass_info_ says it of a pass: the
        # data plane and its gate, every round with its groups (path,
        # steps, models, program, dispatches), and the solve's sums
        self.search_info_ = {
            **plane["info"], **sums,
            "fit_dtype": fit_dtype_info(
                getattr(self.estimator, "fit_dtype", None))["fit_dtype"]
            if _supports_batch(self.estimator) else None,
            "sync_s": stats["sync_s"],
            "rounds": stats["rounds"], "n_rounds": sums["rounds"],
        }
        self._annotate_results()

    def _annotate_results(self):
        """What a subclass adds to the controller's outputs (Hyperband:
        the bracket of every record)."""

    # -- post-fit delegation ----------------------------------------------
    # a device estimator takes a resident X as it is (its predict is one
    # program and one fetch); a host estimator needs the rows on the host
    def _for_best(self, a):
        return a if type(self.best_estimator_).__module__.startswith(
            "dask_ml_tpu") else _to_host(a)

    def predict(self, X):
        return self.best_estimator_.predict(self._for_best(X))

    def predict_proba(self, X):
        return self.best_estimator_.predict_proba(self._for_best(X))

    def decision_function(self, X):
        return self.best_estimator_.decision_function(self._for_best(X))

    def score(self, X, y=None):
        return self.scorer_(self.best_estimator_, self._for_best(X),
                            self._for_best(y))

    @property
    def classes_(self):
        return self.best_estimator_.classes_


class IncrementalSearchCV(BaseIncrementalSearchCV):
    """Ref: dask_ml/model_selection/_incremental.py::IncrementalSearchCV —
    inverse-decay model dropping: after scoring event k, keep the top
    ``n_initial / (1 + decay_rate * k)`` models and give each one more
    partial_fit call; ``decay_rate=None`` keeps all models to max_iter."""

    def __init__(self, estimator, parameters, n_initial_parameters=10,
                 decay_rate=1.0, test_size=None, patience=False, tol=1e-3,
                 fits_per_score=1, max_iter=100, random_state=None,
                 scoring=None, verbose=False, prefix=""):
        super().__init__(estimator, parameters,
                         n_initial_parameters=n_initial_parameters,
                         test_size=test_size, patience=patience, tol=tol,
                         max_iter=max_iter, random_state=random_state,
                         scoring=scoring, verbose=verbose, prefix=prefix)
        self.decay_rate = decay_rate
        self.fits_per_score = fits_per_score
        self._step = 0

    def _reset_hook(self):
        # re-fitting the same instance must restart the decay schedule
        self._step = 0

    def _hook_state(self):
        return {"_step": self._step}

    def _n_initial(self):
        if self.n_initial_parameters == "grid":
            from sklearn.model_selection import ParameterGrid

            return len(ParameterGrid(self.parameters))
        return self.n_initial_parameters

    def _sample_params(self, n):
        if self.n_initial_parameters == "grid":
            from sklearn.model_selection import ParameterGrid

            return list(ParameterGrid(self.parameters))
        return super()._sample_params(n)

    def _additional_calls(self, info):
        self._step += 1
        scores = {mid: recs[-1]["score"] for mid, recs in info.items()}
        calls = {mid: recs[-1]["partial_fit_calls"]
                 for mid, recs in info.items()}
        if self.decay_rate is None:
            keep = list(scores)
        else:
            n_keep = max(
                1, int(self._n_initial() / (1 + self.decay_rate * self._step))
            )
            keep = top_scores(scores, n_keep)
        out = {}
        for mid in keep:
            if calls[mid] >= self.max_iter:
                out[mid] = 0
            else:
                out[mid] = self.fits_per_score
        if all(v == 0 for v in out.values()):
            return {mid: 0 for mid in out}
        return out


class InverseDecaySearchCV(IncrementalSearchCV):
    """Explicit-name alias used in later dask-ml versions."""
