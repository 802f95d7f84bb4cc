"""ISSUE 12: the fused Pallas streamed kernels composed with the
data-parallel shard_map scan programs, plus the gradient-accumulation
cross-host streamed SGD flavor.

Contracts under test, per the tentpole:

- fused x sharded parity: with ``pallas_stream_interpret`` on (the CPU
  CI stand-in for a real TPU's compiled kernels), the shard_map scan
  programs trace the FUSED bodies — program-registry names
  ``pallas.*.psum`` — and GLM/SGD/KMeans streamed fits match the
  unfused sharded flavor to 1e-6 at mesh {1, 2, 8}, ragged per-shard
  tails included;
- tile selection reasons about the PER-SHARD slab height (S/D rows),
  not the global block: a block that divides into non-128-multiple
  slabs refuses with reason "non-128-mult shard rows" instead of
  mistracing;
- the shuffled SGD fit keeps its lr-clock identity (same ``_t``, same
  weights) across the fused/unfused flavors;
- ``fused_stream_reason`` lands in solver_info_ naming why fused was
  gated off — and is None exactly when the kernels engaged;
- ``stream_grad_accum``: exact (bit-level) parity with the sequential
  single-host fit at A=1, documented-tolerance convergence at A in
  {2, 4}, and the virtual-2-process flavor bit-matching the
  single-process A*P fit over the interleaved blocks;
- the sharded streamed-ADMM dispatch is tracked under its
  ``...admm_local.gspmd`` program name with the reduce-volume estimate
  on the ``gspmd_reduce_bytes`` counter.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dask_ml_tpu import config
from dask_ml_tpu import observability as obs
from dask_ml_tpu.parallel.streaming import BlockStream

MESHES = (1, 2, 8)


def _mk_xy(n=2300, d=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    y = (X @ rng.randn(d) > 0).astype(np.float32)
    return X, y


def _objective(stream, n, d, **kw):
    from dask_ml_tpu.models.solvers.streamed import StreamedObjective

    return StreamedObjective(
        stream, n, jnp.asarray(0.1, jnp.float32), jnp.ones(d + 1),
        0.5, "logistic", "l2", True, **kw,
    )


class TestFusedShardedGLM:
    @pytest.mark.parametrize("sm", MESHES)
    def test_objective_parity_vs_unfused_sharded(self, sm):
        """1024-row blocks divide into 128-multiple slabs at every mesh
        width; n=2300 leaves a ragged tail block whose trailing shards
        are all-padding."""
        n, d = 2300, 6
        X, y = _mk_xy(n, d)
        beta = np.random.RandomState(3).randn(d + 1)
        out = {}
        for interp in (False, True):
            with config.set(stream_block_rows=1024, stream_mesh=sm,
                            pallas_stream_interpret=interp):
                o = _objective(BlockStream((X, y), block_rows=1024), n, d)
                mxu, fused, _, reason = o._sb_flavor("vg")
                assert fused is interp, (fused, reason)
                assert (reason is None) is interp
                v, g = o.value_and_grad(beta)
                v2, g2, h = o.value_and_grad_and_hess(beta)
                out[interp] = (v, g, v2, g2, h, o.value(beta))
        for a, b in zip(out[True], out[False]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-6)

    def test_sharded_reducer_is_tracked_as_pallas_psum(self):
        from dask_ml_tpu.models.solvers.streamed import _sb_reducer
        from dask_ml_tpu.parallel.mesh import stream_data_mesh

        mesh = stream_data_mesh()
        assert mesh.devices.size == 8
        fused = _sb_reducer("vg", "logistic", True, 0, fused=True,
                            interpret=True, mesh=mesh)
        assert fused.program_name == "pallas.glm_vg.psum"
        plain = _sb_reducer("vg", "logistic", True, 0, mesh=mesh)
        assert plain.program_name == "superblock.glm.vg.psum"
        multi = _sb_reducer("vg", "logistic", True, 3, fused=True,
                            interpret=True, mesh=mesh)
        assert multi.program_name == "pallas.glm_vg_multi.psum"

    @pytest.mark.parametrize("sm", MESHES)
    def test_fused_fit_records_engagement_and_matches(self, sm):
        """A streamed fit with the kernels OFF equals the same fit with
        them ON at every stream mesh width — the D-times-gradient
        regression, stated once and directly. The XLA flavour autodiffs
        the replicated beta inside shard_map and then psums; were the
        body traced with check_vma=True, jax 0.9 would already have
        psummed that gradient and the XLA fit's gradient would be sm
        times the fused one's (right value, wrong line search, a coef_
        that drifts apart with sm)."""
        n, d = 2300, 6
        X, y = _mk_xy(n, d)
        from dask_ml_tpu.linear_model import LogisticRegression

        fits = {}
        for interp in (False, True):
            with config.set(stream_block_rows=1024, stream_mesh=sm,
                            pallas_stream_interpret=interp):
                fits[interp] = LogisticRegression(
                    solver="lbfgs", max_iter=15
                ).fit(X.astype(np.float64), y.astype(np.float64))
        info = fits[True].solver_info_
        assert info["fused_stream"] is True
        assert info["fused_stream_reason"] is None
        assert info["stream_shards"] == sm
        assert fits[False].solver_info_["fused_stream"] is False
        assert fits[False].solver_info_["fused_stream_reason"] == "off-TPU"
        # per-PASS parity is 1e-6 (the objective test above); a full
        # 15-iteration solve accumulates it — compare relatively
        np.testing.assert_allclose(fits[True].coef_, fits[False].coef_,
                                   rtol=1e-6, atol=1e-6)

    def test_multiclass_objective_parity(self):
        from dask_ml_tpu.models.solvers.streamed import (
            MulticlassStreamedObjective,
        )

        n, d, C = 2300, 5, 3
        X, _ = _mk_xy(n, d)
        y = np.random.RandomState(5).randint(0, C, n).astype(np.float32)
        beta = np.random.RandomState(6).randn(C * (d + 1))
        out = {}
        for interp in (False, True):
            with config.set(stream_block_rows=1024, stream_mesh=8,
                            pallas_stream_interpret=interp):
                o = MulticlassStreamedObjective(
                    BlockStream((X, y), block_rows=1024), n,
                    jnp.asarray(0.1, jnp.float32),
                    jnp.ones(C * (d + 1)), 0.5, "logistic", "l2", True,
                    n_classes=C,
                )
                _, fused, _, reason = o._sb_flavor("vg")
                assert fused is interp, reason
                # the per-class Hessian stack stays XLA, with a reason
                assert o._sb_flavor("vgh")[3] == "multiclass-hessian-xla"
                out[interp] = o.value_and_grad(beta)
        np.testing.assert_allclose(out[True][0], out[False][0], rtol=1e-6)
        np.testing.assert_allclose(out[True][1], out[False][1],
                                   atol=1e-6, rtol=1e-6)

    def test_non_128_multiple_shard_slab_refuses_with_reason(self):
        """A 96-row block divides into 12-row slabs at D=8 — the fused
        flavor must refuse on the PER-SHARD height with the documented
        reason, never mistrace."""
        n, d = 1100, 6
        X, y = _mk_xy(n, d)
        with config.set(stream_block_rows=96,
                        pallas_stream_interpret=True):
            o = _objective(BlockStream((X, y), block_rows=96), n, d)
            mxu, fused, _, reason = o._sb_flavor("vg")
        assert fused is False and reason == "non-128-mult shard rows"


class TestFusedShardedSGD:
    @pytest.mark.parametrize("sm", MESHES)
    def test_shuffled_fit_parity_and_lr_clock_identity(self, sm):
        from dask_ml_tpu.models.sgd import SGDClassifier

        n, d = 8192, 8
        X, y = _mk_xy(n, d, seed=1)
        res = {}
        for interp in (False, True):
            with config.set(stream_block_rows=1024, stream_mesh=sm,
                            pallas_stream_interpret=interp):
                m = SGDClassifier(max_iter=2, random_state=0,
                                  shuffle=True).fit(X, y)
                res[interp] = (m.coef_.copy(), m.intercept_.copy(),
                               m._t, m.solver_info_)
        assert res[True][2] == res[False][2]        # identical lr clock
        assert res[True][3]["fused_stream"] is True
        assert res[True][3]["fused_stream_reason"] is None
        assert res[False][3]["fused_stream"] is False
        np.testing.assert_allclose(res[True][0], res[False][0], atol=1e-6)
        np.testing.assert_allclose(res[True][1], res[False][1], atol=1e-6)

    def test_sharded_scan_tracked_as_pallas_psum(self):
        from dask_ml_tpu.models.sgd import _sgd_stream_program
        from dask_ml_tpu.parallel.mesh import stream_data_mesh

        mesh = stream_data_mesh()
        fused = _sgd_stream_program(mesh, "pallas", "log_loss", False,
                                    interpret=True)
        assert fused.program_name == "pallas.sgd_step.psum"
        plain = _sgd_stream_program(mesh, "xla", "log_loss", False)
        assert plain.program_name == "superblock.sgd_scan.psum"

    def test_multiclass_fused_parity(self):
        from dask_ml_tpu.models.sgd import SGDClassifier

        n = 8192
        X, _ = _mk_xy(n, 8, seed=2)
        y = np.random.RandomState(5).randint(0, 3, n).astype(float)
        res = {}
        for interp in (False, True):
            with config.set(stream_block_rows=1024, stream_mesh=8,
                            pallas_stream_interpret=interp):
                m = SGDClassifier(max_iter=2, random_state=0,
                                  shuffle=False, penalty="elasticnet",
                                  l1_ratio=0.4).fit(X, y)
                res[interp] = (m.coef_.copy(), m.solver_info_)
        assert res[True][1]["fused_stream"] is True
        np.testing.assert_allclose(res[True][0], res[False][0], atol=1e-6)

    def test_dispatch_shape_and_zero_recompiles_after_pass1(self):
        """The fused flavor must not change the dispatch shape — one
        scan dispatch per super-block, NOT per shard — nor mint XLA
        compiles after the first pass."""
        from dask_ml_tpu.models.sgd import SGDClassifier

        n = 8192
        X, y = _mk_xy(n, 8, seed=3)
        with config.set(stream_block_rows=1024,
                        pallas_stream_interpret=True):
            SGDClassifier(max_iter=1, random_state=0,
                          shuffle=False).fit(X, y)  # pass 1 compiles
            obs.counters_reset()
            m = SGDClassifier(max_iter=3, random_state=0,
                              shuffle=False).fit(X, y)
        st = dict(m._last_stream_stats or {})
        assert st["sb_shards"] == 8
        assert st["dispatches_per_pass"] == \
            -(-st["n_blocks"] // st["superblock_k"])
        snap = obs.counters_snapshot()
        assert snap.get("recompiles", 0) == 0, snap
        assert m.solver_info_["fused_stream"] is True

    def test_cohort_scan_fused_matches_xla(self):
        from dask_ml_tpu.models.sgd import (_sgd_cohort_scan,
                                            _sgd_cohort_scan_pallas)

        rng = np.random.RandomState(7)
        B, bs, d, N, S = 3, 256, 8, 4, 5
        Xr = jnp.asarray(rng.randn(B, bs, d).astype(np.float32))
        yr = jnp.asarray((rng.rand(B, bs) > 0.5).astype(np.float32))
        NV = jnp.asarray([bs, bs - 40, bs], jnp.int32)
        order = jnp.asarray(np.array([0, 1, 2, 0, 1], np.int32))
        W = jnp.asarray(rng.randn(N, d + 1).astype(np.float32) * 0.1)
        LRS = jnp.asarray(np.full((S, N), 0.05, np.float32))
        args = (jnp.full((N,), 1e-3), jnp.full((N,), 0.7),
                jnp.full((N,), 0.3),
                jnp.asarray([1.0, 0.0, 1.0, 1.0], jnp.float32))
        Wx, lx = _sgd_cohort_scan(Xr, yr, NV, order, jnp.array(W), LRS,
                                  *args, "log_loss")
        Wp, lp = _sgd_cohort_scan_pallas(Xr, yr, NV, order,
                                         jnp.array(W), LRS, *args,
                                         "log_loss", interpret=True)
        np.testing.assert_allclose(Wp, Wx, atol=1e-5)
        np.testing.assert_allclose(lp, lx, rtol=1e-5, atol=1e-5)

    def test_batched_fused_calls_pick_pallas_when_gated_in(self):
        """The adaptive-search cohort driver routes through the fused
        scan when the stacked block height fits the kernel grid, and
        the advanced models match the XLA route."""
        from dask_ml_tpu.models.sgd import SGDClassifier

        rng = np.random.RandomState(11)
        blocks = [(rng.randn(256, 6).astype(np.float32),
                   (rng.rand(256) > 0.5).astype(np.float32))
                  for _ in range(3)]

        def cohort():
            ms = [SGDClassifier(alpha=a, random_state=0)
                  for a in (1e-4, 1e-3)]
            for m in ms:
                m._set_classes(np.array([0.0, 1.0]))
            return ms

        with config.set(pallas_stream_interpret=True):
            fused = SGDClassifier._batched_fused_calls(cohort(), blocks)
        plain = SGDClassifier._batched_fused_calls(cohort(), blocks)
        for mf, mp in zip(fused, plain):
            np.testing.assert_allclose(np.asarray(mf._w),
                                       np.asarray(mp._w), atol=1e-5)


class TestFusedShardedKMeans:
    def test_streamed_lloyd_fused_parity(self):
        from dask_ml_tpu.models.kmeans import KMeans

        rng = np.random.RandomState(2)
        X = np.concatenate([
            rng.randn(1400, 5).astype(np.float32) + c for c in (0, 6, 12)
        ])
        res = {}
        for interp in (False, True):
            with config.set(stream_block_rows=1024,
                            pallas_stream_interpret=interp):
                km = KMeans(n_clusters=3, random_state=0,
                            max_iter=15).fit(X)
                res[interp] = (np.sort(km.cluster_centers_, axis=0),
                               km.inertia_)
        np.testing.assert_allclose(res[True][0], res[False][0],
                                   atol=1e-5)
        assert res[True][1] == pytest.approx(res[False][1], rel=1e-5)

    def test_sharded_assign_stats_tracked_as_pallas_psum(self):
        from dask_ml_tpu.models.kmeans import _sb_assign_stats_sharded
        from dask_ml_tpu.parallel.mesh import stream_data_mesh

        mesh = stream_data_mesh()
        fused = _sb_assign_stats_sharded(mesh, None, fused=True,
                                         interpret=True)
        assert fused.program_name == "pallas.kmeans_stream.psum"
        plain = _sb_assign_stats_sharded(mesh, None)
        assert plain.program_name == "superblock.kmeans_assign.psum"


class TestGradAccum:
    def _xy(self, n=5000, d=8):
        # 5000 rows / 512-row blocks: a ragged 392-row tail whose
        # valid-row count is NOT a power of two — the case where a
        # normalize-after-the-sum flavor would diverge in the last bit
        return _mk_xy(n, d, seed=9)

    def test_a1_exact_parity_with_sequential(self):
        """Parity vs the sequential SINGLE-DEVICE flavor
        (stream_mesh=1), whose step normalizes inside autodiff exactly
        like the micro kernel. The band is one f32 ulp (2.5e-7
        relative measured): the sequential step is one iteration of a
        ``lax.scan`` over the super-block, the micro step a dispatch of
        its own, and XLA may fuse the two differently. The sharded
        sequential scan normalizes its raw sums after the psum, so
        parity there is float-reassociation-level (last assert)."""
        from dask_ml_tpu.models.sgd import SGDClassifier

        X, y = self._xy()
        with config.set(stream_block_rows=512, stream_mesh=1):
            base = SGDClassifier(max_iter=3, random_state=0,
                                 shuffle=False).fit(X, y)
        with config.set(stream_block_rows=512, stream_mesh=1,
                        stream_grad_accum=1):
            a1 = SGDClassifier(max_iter=3, random_state=0,
                               shuffle=False).fit(X, y)
        assert a1.solver_info_["grad_accum"] == 1
        assert a1._t == base._t
        np.testing.assert_allclose(a1.coef_, base.coef_,
                                   rtol=1e-6, atol=1e-8)
        np.testing.assert_allclose(a1.intercept_, base.intercept_,
                                   rtol=1e-6, atol=1e-8)
        with config.set(stream_block_rows=512):
            sh = SGDClassifier(max_iter=3, random_state=0,
                               shuffle=False).fit(X, y)
        with config.set(stream_block_rows=512, stream_grad_accum=1):
            g8 = SGDClassifier(max_iter=3, random_state=0,
                               shuffle=False).fit(X, y)
        np.testing.assert_allclose(g8.coef_, sh.coef_, atol=1e-6)

    def test_a1_exact_parity_shuffled(self):
        """The same one-ulp band as the sequential case above, for the
        same reason, with the block order redrawn each pass."""
        from dask_ml_tpu.models.sgd import SGDClassifier

        X, y = self._xy()
        with config.set(stream_block_rows=512, stream_mesh=1):
            base = SGDClassifier(max_iter=2, random_state=0,
                                 shuffle=True).fit(X, y)
        with config.set(stream_block_rows=512, stream_mesh=1,
                        stream_grad_accum=1):
            a1 = SGDClassifier(max_iter=2, random_state=0,
                               shuffle=True).fit(X, y)
        np.testing.assert_allclose(a1.coef_, base.coef_,
                                   rtol=1e-6, atol=1e-8)

    def test_a1_exact_parity_multiclass(self):
        from dask_ml_tpu.models.sgd import SGDClassifier

        X, _ = self._xy()
        y = np.random.RandomState(5).randint(0, 3, len(X)).astype(float)
        with config.set(stream_block_rows=512, stream_mesh=1):
            base = SGDClassifier(max_iter=2, random_state=0,
                                 shuffle=False).fit(X, y)
        with config.set(stream_block_rows=512, stream_mesh=1,
                        stream_grad_accum=1):
            a1 = SGDClassifier(max_iter=2, random_state=0,
                               shuffle=False).fit(X, y)
        np.testing.assert_array_equal(a1.coef_, base.coef_)

    @pytest.mark.parametrize("A", [2, 4])
    def test_larger_a_converges_within_documented_tolerance(self, A):
        """A>1 trains on A-block effective batches — fewer, larger
        steps: the fit converges to a near-identical model (the
        documented tolerance: >=99% prediction agreement with the
        sequential fit and comparable accuracy)."""
        from dask_ml_tpu.models.sgd import SGDClassifier

        X, y = self._xy()
        with config.set(stream_block_rows=512):
            base = SGDClassifier(max_iter=3, random_state=0,
                                 shuffle=False).fit(X, y)
        with config.set(stream_block_rows=512, stream_grad_accum=A):
            m = SGDClassifier(max_iter=3, random_state=0,
                              shuffle=False).fit(X, y)
        assert m.solver_info_["grad_accum"] == A
        assert np.mean(m.predict(X) == base.predict(X)) >= 0.99
        assert m.score(X, y) >= base.score(X, y) - 0.01

    def test_two_virtual_processes_match_single_process_a2(self):
        """P processes at A over round-robin block shards ==
        single-process at A*P, bit-exact (both accumulate/merge the
        identical f64 additions in the identical order; stream_mesh=1
        pins the per-block kernels to one device so their partial sums
        cannot reassociate)."""
        from dask_ml_tpu.models.sgd import SGDClassifier
        from dask_ml_tpu.parallel import distributed as dist

        n, d, br = 4096, 8, 256
        X, y = self._xy(n, d)
        blocks = [X[i:i + br] for i in range(0, n, br)]
        yblocks = [y[i:i + br] for i in range(0, n, br)]

        def proc(rank):
            Xl = np.concatenate(blocks[rank::2])
            yl = np.concatenate(yblocks[rank::2])
            with config.set(stream_block_rows=br, stream_grad_accum=1,
                            stream_mesh=1):
                m = SGDClassifier(max_iter=2, random_state=0,
                                  shuffle=False).fit(Xl, yl)
            return np.asarray(m.coef_)

        res = dist.run_virtual_processes(proc, world=2)
        with config.set(stream_block_rows=br, stream_grad_accum=2,
                        stream_mesh=1):
            ref = SGDClassifier(max_iter=2, random_state=0,
                                shuffle=False).fit(X, y)
        np.testing.assert_array_equal(res[0], res[1])
        np.testing.assert_array_equal(res[0], ref.coef_)

    def test_quarantine_composition_refused(self):
        """Group counts are exchanged before blocks are read, so the
        quarantine policy (which folds counts to zero at read time)
        cannot compose — refuse loudly instead of normalizing wrong."""
        from dask_ml_tpu.models.sgd import SGDClassifier

        X, y = self._xy(1024)
        with config.set(stream_block_rows=256, stream_grad_accum=1,
                        stream_nonfinite="quarantine"):
            with pytest.raises(ValueError, match="quarantine"):
                SGDClassifier(max_iter=1, random_state=0,
                              shuffle=False).fit(X, y)

    def test_refusal_still_names_the_escape_hatch(self):
        from dask_ml_tpu.models.sgd import SGDClassifier
        from dask_ml_tpu.parallel import distributed as dist

        X, y = self._xy(1024)

        def proc(rank):
            SGDClassifier(max_iter=1).fit(X, y)

        with pytest.raises(NotImplementedError,
                           match="stream_grad_accum"):
            dist.run_virtual_processes(proc, world=2)


class TestAdmmGspmdTracking:
    def test_sharded_admm_records_program_and_reduce_bytes(self):
        from dask_ml_tpu.linear_model import LogisticRegression
        from dask_ml_tpu.models.solvers.streamed import _sb_admm_local

        assert _sb_admm_local(2, "logistic", True, 0,
                              gspmd=True).program_name \
            == "superblock.glm.admm_local.gspmd"
        assert _sb_admm_local(2, "logistic", True, 0).program_name \
            == "superblock.glm.admm_local"
        X, y = _mk_xy(2048, 5)
        obs.counters_reset()
        with config.set(stream_block_rows=512):
            clf = LogisticRegression(solver="admm", max_iter=4).fit(
                X.astype(np.float64), y.astype(np.float64)
            )
        snap = obs.counters_snapshot()
        assert clf.solver_info_["stream_shards"] == 8
        assert snap.get("gspmd_reduce_dispatches", 0) >= 1, snap
        assert snap.get("gspmd_reduce_bytes", 0) > 0
        # trivial mesh: no implicit GSPMD, no counter movement
        obs.counters_reset()
        with config.set(stream_block_rows=512, stream_mesh=1):
            LogisticRegression(solver="admm", max_iter=2).fit(
                X.astype(np.float64), y.astype(np.float64)
            )
        assert obs.counters_snapshot().get("gspmd_reduce_bytes", 0) == 0
