"""Perf smoke gate for the super-block streaming hot loop (ISSUE 3 +
the ISSUE 9 data-parallel flavor).

Runs a small streamed-SGD fit and fails (exit 1) when the
dispatch-collapse contract regresses:

- ``dispatches_per_pass`` must not exceed ceil(n_blocks / superblock_k)
  + 1 — the whole point of super-block execution is one XLA dispatch
  per K blocks, so a pass that dispatches per block again is a
  regression even if it still passes the numeric tests;
- after the first pass has warmed the compile caches, later passes must
  pay ZERO new XLA compiles — a shape wobble (ragged tail leaking into
  the compiled signature, ring buffers changing layout) shows up here
  long before it shows up as a throughput number;
- the SHARDED flavor (8 virtual devices, shard_map + psum scan
  programs) must keep exactly the same dispatch shape: ceil(n_blocks/K)
  dispatches per pass — one per super-block, NOT one per shard — and
  the same zero-compiles-after-pass-1 contract.

Kept small (~64k rows) so verify.sh stays fast. It asserts counts, which
a CPU can say; it yields no time worth writing down.
"""

import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# 8 virtual devices BEFORE jax initializes so the sharded section has a
# mesh to shard over; the single-device section pins stream_mesh=1.
# force_cpu_platform APPENDS/RAISES the device-count flag inside an
# already-set XLA_FLAGS instead of silently losing it (a setdefault
# would fail the gate on any box that exports XLA_FLAGS for tuning)
from dask_ml_tpu._platform import force_cpu_platform  # noqa: E402

force_cpu_platform(n_devices=8)

import numpy as np  # noqa: E402


def main():
    from dask_ml_tpu import config
    from dask_ml_tpu import observability as obs
    from dask_ml_tpu.models.sgd import SGDClassifier
    from dask_ml_tpu.parallel.streaming import BlockStream

    n, d = 64_000, 32
    rng = np.random.RandomState(0)
    X = rng.randn(n, d).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)

    failures = []
    # -- single-device section (stream_mesh=1: the pre-mesh hot loop) --
    with config.set(stream_block_rows=n // 32, stream_autotune=False,
                    stream_mesh=1):
        stream = BlockStream((X, y), block_rows=n // 32)
        k = stream.resolve_superblock_k()
        n_blocks = stream.n_blocks
        if k <= 1:
            failures.append(
                f"super-block execution is off (resolved K={k}); the "
                "streamed hot loop is dispatching per block"
            )
        # pass 1: warmup (compiles the scan at the steady-state shapes)
        SGDClassifier(max_iter=1, random_state=0, shuffle=False).fit(X, y)
        obs.counters_reset()
        clf = SGDClassifier(max_iter=2, random_state=0, shuffle=False)
        clf.fit(X, y)
        snap = obs.counters_snapshot()
        st = dict(getattr(clf, "_last_stream_stats", None) or {})

    # fused-kernel dispatch contract (ISSUE 8): enabling the fused
    # streamed kernels must NOT change the dispatch shape of a pass —
    # the Pallas flavor replaces the per-block BODY inside the same
    # scan, never the scan structure (and off-TPU it must be inert).
    with config.set(stream_block_rows=n // 32, stream_autotune=False,
                    stream_mesh=1, pallas_stream=False):
        off = SGDClassifier(max_iter=1, random_state=0, shuffle=False)
        off.fit(X, y)
    off_st = dict(getattr(off, "_last_stream_stats", None) or {})

    budget = math.ceil(n_blocks / max(k, 1)) + 1
    dpp = st.get("dispatches_per_pass")
    if off_st.get("dispatches_per_pass") != dpp:
        failures.append(
            f"fused SGD step changed dispatches_per_pass: "
            f"{dpp} (pallas_stream=on) vs "
            f"{off_st.get('dispatches_per_pass')} (off) — the fused "
            "path must not add dispatches"
        )
    if dpp is None:
        failures.append("no dispatches_per_pass in stream stats — the "
                        "fit did not take the super-block path")
    elif dpp > budget:
        failures.append(
            f"dispatches_per_pass={dpp} exceeds ceil({n_blocks}/{k})+1="
            f"{budget}"
        )
    recompiles = snap.get("recompiles", 0)
    if recompiles > 0:
        failures.append(
            f"{recompiles} new XLA compiles AFTER the first pass — "
            "steady-state streaming must hit only warm compile caches"
        )
    if snap.get("superblock_dispatches", 0) <= 0:
        failures.append("superblock_dispatches counter never moved")

    # -- sharded section (ISSUE 9): 8-way data-parallel streaming ------
    import jax

    sh_dpp = sh_recompiles = sh_shards = None
    if len(jax.devices()) < 8:
        failures.append(
            f"expected 8 virtual devices for the sharded section, got "
            f"{len(jax.devices())} (XLA_FLAGS not honored?)"
        )
    else:
        with config.set(stream_block_rows=n // 32,
                        stream_autotune=False, stream_mesh=0):
            sh_stream = BlockStream((X, y), block_rows=n // 32)
            sh_k = sh_stream.resolve_superblock_k()
            sh_blocks = sh_stream.n_blocks
            SGDClassifier(max_iter=1, random_state=0,
                          shuffle=False).fit(X, y)  # warmup pass
            obs.counters_reset()
            sh = SGDClassifier(max_iter=2, random_state=0,
                               shuffle=False)
            sh.fit(X, y)
            sh_snap = obs.counters_snapshot()
            sh_st = dict(getattr(sh, "_last_stream_stats", None) or {})
        sh_dpp = sh_st.get("dispatches_per_pass")
        sh_shards = sh_st.get("sb_shards")
        sh_recompiles = sh_snap.get("recompiles", 0)
        if sh_shards != 8:
            failures.append(
                f"sharded fit ran at sb_shards={sh_shards}, wanted 8 — "
                "the data-parallel flavor did not engage"
            )
        # ONE dispatch per super-block, never per shard: the sharded
        # budget is EXACT (no +1 slack — a per-shard dispatch leak
        # would multiply dispatches by D, and this is the gate that
        # catches it)
        if sh_dpp != math.ceil(sh_blocks / max(sh_k, 1)):
            failures.append(
                f"sharded dispatches_per_pass={sh_dpp} != "
                f"ceil({sh_blocks}/{sh_k})="
                f"{math.ceil(sh_blocks / max(sh_k, 1))} — one dispatch "
                "per super-block, NOT per shard"
            )
        if sh_recompiles > 0:
            failures.append(
                f"{sh_recompiles} new XLA compiles after pass 1 on the "
                "SHARDED path — sharding must not break the warm-cache "
                "contract"
            )
        if sh_snap.get("shard_slab_puts", 0) <= 0:
            failures.append(
                "shard_slab_puts counter never moved — super-blocks "
                "did not stage per-shard"
            )

    # -- fused x sharded section (ISSUE 12): the Pallas bodies inside
    # the shard_map scan programs (interpret mode on this CPU box) must
    # keep EXACTLY the unfused sharded flavor's dispatch shape and the
    # zero-compiles-after-pass-1 contract — the fusion swaps the
    # per-block BODY, never the scan/psum structure.
    fu_dpp = fu_recompiles = None
    if len(jax.devices()) >= 8:
        nf, df = 16_384, 16
        Xf = rng.randn(nf, df).astype(np.float32)
        yf = (Xf[:, 0] > 0).astype(np.float32)
        # 2048-row blocks -> 256-row per-shard slabs (128-multiple):
        # the fused flavor's tile gate passes at D=8
        def fused_run(interpret):
            with config.set(stream_block_rows=2048,
                            stream_autotune=False, stream_mesh=0,
                            pallas_stream_interpret=interpret):
                SGDClassifier(max_iter=1, random_state=0,
                              shuffle=False).fit(Xf, yf)  # warmup
                obs.counters_reset()
                clf = SGDClassifier(max_iter=2, random_state=0,
                                    shuffle=False)
                clf.fit(Xf, yf)
                return (dict(getattr(clf, "_last_stream_stats", None)
                             or {}),
                        obs.counters_snapshot(),
                        dict(getattr(clf, "solver_info_", None) or {}))
        fu_st, fu_snap, fu_info = fused_run(True)
        base_st, _, _ = fused_run(False)
        fu_dpp = fu_st.get("dispatches_per_pass")
        fu_recompiles = fu_snap.get("recompiles", 0)
        if not fu_info.get("fused_stream"):
            failures.append(
                "fused x sharded section did not engage the Pallas "
                f"bodies (reason={fu_info.get('fused_stream_reason')})"
            )
        if fu_dpp != base_st.get("dispatches_per_pass"):
            failures.append(
                f"fused x sharded changed dispatches_per_pass: "
                f"{fu_dpp} (fused) vs "
                f"{base_st.get('dispatches_per_pass')} (unfused)"
            )
        if fu_recompiles > 0:
            failures.append(
                f"{fu_recompiles} new XLA compiles after pass 1 on the "
                "FUSED sharded path — fusing the bodies must not break "
                "the warm-cache contract"
            )

    # -- sparse section (ISSUE 13): device-resident bucketed-nnz staging
    # must keep the EXACT dispatch shape (one per super-block — the
    # stream plan pads every super-block of a fit to one nnz capacity,
    # so this budget has no +1 slack), pay zero XLA compiles after
    # pass 1 even though pass 2 shuffles, and the nnz-bucket ladder
    # must stay small (<= 4 distinct per-block rungs).
    import scipy.sparse as sp_

    sp_dpp = sp_recompiles = sp_rungs = None
    rng2 = np.random.RandomState(1)
    Xsp = sp_.random(32_000, 64, density=0.05, format="csr",
                     random_state=rng2, dtype=np.float64)
    ssum = np.asarray(Xsp.sum(axis=1)).ravel()
    ysp = (ssum > np.median(ssum)).astype(np.float64)
    with config.set(stream_block_rows=2_000, stream_autotune=False,
                    stream_mesh=1, stream_sparse=True):
        sstream = BlockStream((Xsp, ysp.astype(np.float32)),
                              block_rows=2_000)
        sp_k = sstream.resolve_superblock_k()
        sp_blocks = sstream.n_blocks
        plan = sstream.sparse_plan
        if plan is None:
            failures.append(
                "sparse staging plan did not engage "
                f"(reason={sstream.sparse_reason})"
            )
        else:
            sp_rungs = len(set(plan.block_buckets))
            if sp_rungs > 4:
                failures.append(
                    f"nnz-bucket ladder used {sp_rungs} > 4 distinct "
                    "rungs in one pass"
                )
        SGDClassifier(max_iter=1, random_state=0, shuffle=True).fit(
            Xsp, ysp
        )   # pass 1: warm
        obs.counters_reset()
        spc = SGDClassifier(max_iter=2, random_state=0,
                            shuffle=True).fit(Xsp, ysp)
        sp_snap = obs.counters_snapshot()
        sp_st = dict(getattr(spc, "_last_stream_stats", None) or {})
    sp_dpp = sp_st.get("dispatches_per_pass")
    sp_recompiles = sp_snap.get("recompiles", 0)
    if not (spc.solver_info_ or {}).get("sparse_stream"):
        failures.append(
            "sparse fit did not engage the device-resident path "
            f"(reason={(spc.solver_info_ or {}).get('sparse_stream_reason')})"
        )
    if sp_dpp != math.ceil(sp_blocks / max(sp_k, 1)):
        failures.append(
            f"sparse dispatches_per_pass={sp_dpp} != "
            f"ceil({sp_blocks}/{sp_k})="
            f"{math.ceil(sp_blocks / max(sp_k, 1))} — one dispatch per "
            "super-block with sparse staging"
        )
    if sp_recompiles > 0:
        failures.append(
            f"{sp_recompiles} new XLA compiles after pass 1 on the "
            "SPARSE path — one capacity per fit means shuffled passes "
            "must hit only warm caches"
        )
    if sp_snap.get("sparse_blocks_staged", 0) <= 0:
        failures.append("sparse_blocks_staged counter never moved — "
                        "blocks did not stage as bucketed-nnz slabs")

    # -- search section (ISSUE 14): the adaptive-search cohort rides
    # the streamed superblock plane — every round must be exactly
    # ceil(steps / K) dispatches (one per super-block, the round-1
    # {mid: 1} round exactly one), and after round 1 (which warms the
    # slot RUNG ladder) the whole search — INCLUDING shrinking
    # candidate sets, 8 -> 4 -> 2 -> 1 under decay — must pay zero new
    # XLA compiles: bracket halving reuses compiled scans via padded
    # slot masks, never a recompile per surviving N.
    from dask_ml_tpu.model_selection import IncrementalSearchCV

    ns, ds = 16_384, 16
    Xq = rng.randn(ns, ds).astype(np.float32)
    yq = (Xq[:, 0] > 0).astype(np.float64)
    params_q = {"alpha": list(np.logspace(-4, -1, 8))}
    marks = []

    class _Probe(IncrementalSearchCV):
        def _additional_calls(self, info):
            marks.append(obs.counters_snapshot().get("recompiles", 0))
            return super()._additional_calls(info)

    with config.set(stream_block_rows=2048, stream_autotune=False,
                    stream_mesh=1):
        sq = _Probe(SGDClassifier(learning_rate="constant"), params_q,
                    n_initial_parameters=8, decay_rate=1.0,
                    max_iter=48, fits_per_score=8, random_state=0)
        obs.counters_reset()
        sq.fit(Xq, yq, classes=[0.0, 1.0])
    sm = sq.metadata_["stream"]
    if not sm.get("streamed"):
        failures.append("search section: streamed cohort plane did "
                        f"not engage ({sm})")
    else:
        n_rounds = sm["rounds"]
        k_search = max(2, math.ceil(sm["n_blocks"] / 4))
        expect = 1 + (n_rounds - 1) * math.ceil(8 / k_search)
        if sm["dispatches"] != expect:
            failures.append(
                f"search dispatches={sm['dispatches']} != {expect} "
                f"(1 for round 1 + ceil(8/{k_search}) per later "
                f"round x {n_rounds - 1}) — one dispatch per "
                "super-block per round"
            )
        if n_rounds < 4:
            failures.append(
                f"search ran only {n_rounds} rounds — the shrinking-"
                "bracket contract needs several"
            )
    if len(marks) >= 2 and marks[-1] != marks[0]:
        failures.append(
            f"{marks[-1] - marks[0]} new XLA compiles AFTER round 1 "
            f"across shrinking candidate sets (marks={marks}) — "
            "bracket halving must reuse the compiled scan via the "
            "padded-N slot mask, not recompile at each N"
        )
    # sharded search flavor: the cohort scans run under shard_map on
    # the 8-virtual-device mesh with the same zero-compile contract
    sh_search = None
    if len(jax.devices()) >= 8:
        marks.clear()
        with config.set(stream_block_rows=2048, stream_autotune=False,
                        stream_mesh=0):
            sq8 = _Probe(SGDClassifier(learning_rate="constant"),
                         params_q, n_initial_parameters=8,
                         decay_rate=1.0, max_iter=24, fits_per_score=8,
                         random_state=0)
            obs.counters_reset()
            sq8.fit(Xq, yq, classes=[0.0, 1.0])
        sh_search = sq8.metadata_["stream"]
        if sh_search.get("shards") != 8:
            failures.append(
                f"sharded search ran at shards={sh_search.get('shards')}"
                ", wanted 8 — the cohort psum flavor did not engage"
            )
        if len(marks) >= 2 and marks[-1] != marks[0]:
            failures.append(
                f"{marks[-1] - marks[0]} new XLA compiles after round "
                "1 on the SHARDED search path"
            )

    # -- plans section (ISSUE 15): the CROSS-CLIENT zero-recompile gate.
    # One process warms all three compiled-program machineries through
    # the plan layer — serving's (method, bucket) grid, the stacked
    # C-grid direct solves, and the streamed superblock scan — then
    # runs ragged serving traffic + a second C-grid search + a second
    # streamed fit and asserts ZERO new XLA compiles across ALL of
    # them. Before the plans subsystem each machinery was gated
    # separately; a client whose warmup missed a shape the others
    # relied on could only be caught by its own gate.
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.model_selection import GridSearchCV
    from dask_ml_tpu.serving import BucketLadder, ModelServer

    npl, dpl = 8_192, 16
    Xpl = rng.randn(npl, dpl).astype(np.float32)
    ypl = (Xpl[:, 0] > 0).astype(np.float64)
    grid_c = {"C": [0.1, 1.0, 10.0]}

    def run_search():
        GridSearchCV(
            LogisticRegression(solver="lbfgs", max_iter=5, tol=0.0),
            grid_c, cv=2, refit=False, scheduler="synchronous",
        ).fit(Xpl, ypl)

    pl_recompiles = None
    with config.set(stream_block_rows=1024, stream_autotune=False,
                    stream_mesh=1):
        # max_iter=2: at this scale a pass is ONE superblock dispatch,
        # so the carry-from-previous-output program variant only
        # appears at pass 2 — the warm fit must cover it
        clf_pl = SGDClassifier(max_iter=2, random_state=0,
                               shuffle=False)
        clf_pl.fit(Xpl, ypl)       # warms the streamed scan programs
        run_search()               # warms the stacked C-grid solves
        srv_pl = ModelServer(clf_pl, methods=("predict",),
                             ladder=BucketLadder(8, 128, 2.0),
                             batch_window_ms=1.0, timeout_ms=0)
        srv_pl.warmup()            # warms the serving grid (plan layer)
        obs.counters_reset()
        with srv_pl:
            SGDClassifier(max_iter=2, random_state=0,
                          shuffle=False).fit(Xpl, ypl)
            run_search()
            rngs = np.random.RandomState(7)
            for _ in range(20):
                nreq = rngs.randint(1, 128)
                i = rngs.randint(0, npl - nreq)
                srv_pl.predict(Xpl[i:i + nreq])
            pl_recompiles = obs.counters_snapshot().get("recompiles", 0)
    if pl_recompiles:
        failures.append(
            f"{pl_recompiles} new XLA compiles across the warmed "
            "serving + C-grid search + streamed fit trio — the plan "
            "layer's cross-client zero-recompile contract broke"
        )
    # the plans table must name what warmed: serving rungs + any
    # plan-built program attribution
    from dask_ml_tpu import plans as _plans

    pl_rows = {r["program"]: r for r in _plans.plans_snapshot()}
    srv_row = pl_rows.get("serving.SGDClassifier.predict")
    if not srv_row or srv_row["warmups"] < 1 \
            or "128" not in srv_row["rungs"]:
        failures.append(
            f"plans table missing the warmed serving grid: {srv_row}"
        )
    if "glm.lbfgs_lam_grid" not in pl_rows:
        failures.append(
            "plans table missing the stacked C-grid solve program"
        )

    # -- 2-D mesh section (ISSUE 18): feature-sharded streaming --------
    # mesh_shape="2x4" tiles the streamed X slabs as (rows/2, d/4)
    # per-device blocks; the dispatch-collapse contract must survive
    # unchanged — EXACTLY ceil(n_blocks/K) dispatches per pass (one per
    # super-block, never one per shard or per model tile) and zero XLA
    # compiles after the warming fit. mesh_shape="8x1" must COLLAPSE to
    # the cached 1-D data mesh so the 1-D reducer cache keys — and with
    # them the 1-D jaxprs — stay byte-identical.
    md_dispatches = md_recompiles = md_glm_recompiles = None
    if len(jax.devices()) >= 8:
        from dask_ml_tpu.linear_model import LogisticRegression
        from dask_ml_tpu.models.pca import PCA
        from dask_ml_tpu.models.solvers.streamed import _sb_reducer
        from dask_ml_tpu.parallel.mesh import (default_mesh,
                                               stream_data_mesh)

        with config.set(stream_mesh=0, mesh_shape="8x1"):
            m81 = stream_data_mesh()
        with config.set(stream_mesh=0, mesh_shape="auto"):
            m1d = stream_data_mesh()
        if not (m81 is m1d and m81 is default_mesh()):
            failures.append(
                "mesh_shape='8x1' did not collapse to the cached 1-D "
                "data mesh object — M=1 must route through the "
                "untouched 1-D programs"
            )
        r81 = _sb_reducer("vg", "logistic", True, 0, mesh=m81)
        r1d = _sb_reducer("vg", "logistic", True, 0, mesh=m1d)
        if r81 is not r1d:
            failures.append(
                "mesh_shape='8x1' minted a DISTINCT vg reducer — the "
                "M=1 cache key (and with it the 1-D jaxpr) must be "
                "byte-identical to the plain data-mesh program"
            )

        n3, d3 = 8_192, 64
        X3 = rng.randn(n3, d3).astype(np.float32)
        with config.set(stream_block_rows=512, stream_autotune=False,
                        stream_mesh=0, mesh_shape="2x4"):
            st3 = BlockStream((X3,), block_rows=512)
            k3 = st3.resolve_superblock_k()
            b3 = st3.n_blocks
            if st3.sb_model_shards() != 4 or st3.sb_data_shards() != 2:
                failures.append(
                    f"2x4 stream staged at "
                    f"{st3.sb_data_shards()}x{st3.sb_model_shards()} "
                    f"(model_tile_reason={st3.model_tile_reason}) — "
                    "the feature tiling did not engage"
                )
            PCA(n_components=8, svd_solver="randomized",
                random_state=0).fit(X3)             # pass 1: warm
            obs.counters_reset()
            PCA(n_components=8, svd_solver="randomized",
                random_state=0).fit(X3)
            md_snap = obs.counters_snapshot()
        md_dispatches = md_snap.get("superblock_dispatches", 0)
        md_recompiles = md_snap.get("recompiles", 0)
        # streamed randomized SVD is a FIXED pass plan: 1 moments pass
        # + (n_iter+1)=3 range passes, each exactly ceil(n_blocks/K)
        # super-block dispatches — the budget is EXACT
        exp3 = 4 * math.ceil(b3 / max(k3, 1))
        if md_dispatches != exp3:
            failures.append(
                f"2-D streamed PCA dispatched {md_dispatches} != "
                f"4*ceil({b3}/{k3})={exp3} — one dispatch per "
                "super-block per pass, NOT per shard/tile"
            )
        if md_recompiles > 0:
            failures.append(
                f"{md_recompiles} new XLA compiles after the warming "
                "fit on the 2-D streamed PCA path"
            )

        n4, d4 = 8_192, 64
        X4 = rng.randn(n4, d4).astype(np.float32)
        y4 = (X4[:, 0] > 0).astype(np.float64)
        with config.set(stream_block_rows=1024, stream_autotune=False,
                        stream_mesh=0, mesh_shape="2x4"):
            st4 = BlockStream((X4, y4.astype(np.float32)),
                              block_rows=1024)
            k4 = st4.resolve_superblock_k()
            b4 = st4.n_blocks
            LogisticRegression(solver="lbfgs", max_iter=5).fit(X4, y4)
            obs.counters_reset()
            LogisticRegression(solver="lbfgs", max_iter=5).fit(X4, y4)
            md_glm_snap = obs.counters_snapshot()
        md_glm_recompiles = md_glm_snap.get("recompiles", 0)
        glm_disp = md_glm_snap.get("superblock_dispatches", 0)
        per_pass = math.ceil(b4 / max(k4, 1))
        if glm_disp <= 0 or glm_disp % per_pass:
            failures.append(
                f"feature-sharded GLM dispatched {glm_disp} — not a "
                f"multiple of ceil({b4}/{k4})={per_pass} per pass"
            )
        if md_glm_recompiles:
            failures.append(
                f"{md_glm_recompiles} new XLA compiles after the "
                "warming fit on the feature-sharded GLM path"
            )
        pl2 = {r["program"] for r in _plans.plans_snapshot()}
        if not any(p.startswith("superblock.glm.")
                   and p.endswith(".model_psum") for p in pl2):
            failures.append(
                "plans table missing the feature-sharded GLM programs "
                "(superblock.glm.*.model_psum)"
            )
        if not any(p.startswith("superblock.pca.") for p in pl2):
            failures.append(
                "plans table missing the streamed PCA programs "
                "(superblock.pca.*)"
            )

    print(f"perf smoke: n_blocks={n_blocks} K={k} "
          f"dispatches_per_pass={dpp} (budget {budget}) "
          f"recompiles_after_pass1={recompiles} | sharded: "
          f"shards={sh_shards} dispatches_per_pass={sh_dpp} "
          f"recompiles_after_pass1={sh_recompiles} | fused-sharded: "
          f"dispatches_per_pass={fu_dpp} "
          f"recompiles_after_pass1={fu_recompiles} | sparse: "
          f"dispatches_per_pass={sp_dpp} "
          f"recompiles_after_pass1={sp_recompiles} "
          f"ladder_rungs={sp_rungs} | search: "
          f"rounds={sm.get('rounds')} dispatches={sm.get('dispatches')} "
          f"shards8={None if sh_search is None else sh_search.get('shards')}"
          f" | plans: cross-client recompiles={pl_recompiles}"
          f" | mesh2d: pca_dispatches={md_dispatches} "
          f"pca_recompiles={md_recompiles} "
          f"glm_recompiles={md_glm_recompiles}")
    if failures:
        for f in failures:
            print(f"PERF SMOKE FAIL: {f}", file=sys.stderr)
        return 1
    print("perf smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
