"""Data-parallel superblock streaming (ISSUE 9): the streamed hot loop
sharded over the mesh's "data" axis.

Contracts under test, per the tentpole:

- per-pass parity: streamed GLM/SGD/KMeans at mesh sizes {1, 2, 8}
  match the single-device path to 1e-6 — per-shard partial sums only
  reassociate float additions, they never change the math;
- staging: super-blocks arrive batch-sharded (every device owns a
  contiguous row slab of every block) with per-shard valid-row counts —
  a ragged tail block pads its trailing SHARDS with zero counts exactly
  like the ragged final super-block pads its missing block slots;
- carries replicate (out spec P()) and stay donated (the input buffer
  dies, the donation counters move), with ONE dispatch per super-block
  (never one per shard) and zero XLA compiles after pass 1;
- the trivial mesh (config.stream_mesh=1) routes through the original
  single-device programs whose jaxprs are BYTE-IDENTICAL with the mesh
  feature present — and contain no collective, while the sharded
  programs psum.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dask_ml_tpu import config
from dask_ml_tpu import observability as obs
from dask_ml_tpu.parallel.streaming import BlockStream


def _mk_xy(n=1100, d=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    y = (X @ rng.randn(d) > 0).astype(np.float32)
    return X, y


MESHES = (1, 2, 8)


def _objective(stream, n, d):
    from dask_ml_tpu.models.solvers.streamed import StreamedObjective

    return StreamedObjective(
        stream, n, jnp.asarray(0.1, jnp.float32), jnp.ones(d + 1),
        0.5, "logistic", "l2", True,
    )


class TestShardedStaging:
    def test_superblocks_stage_batch_sharded_with_shard_counts(self):
        X, y = _mk_xy(1100)
        with config.set(stream_block_rows=96, superblock_k=8):
            s = BlockStream((X, y), block_rows=96)
            assert s.sb_data_shards() == 8 and s.sb_sharded()
            sbs = list(s.superblocks())
        for sb in sbs:
            blk = sb.arrays[0]
            # every device owns its own contiguous row slab
            assert len(blk.sharding.device_set) == 8
            sc = np.asarray(sb.shard_counts)
            assert sc.shape == (8, np.asarray(sb.counts).shape[0])
            # per-shard counts repartition the global counts exactly
            np.testing.assert_array_equal(sc.sum(axis=0),
                                          np.asarray(sb.counts))
        assert s.stats["sb_shards"] == 8

    def test_ragged_tail_pads_per_shard_with_zero_counts(self):
        # 1100 rows / 96-row blocks: the tail block holds 44 rows; at
        # D=8 each shard owns 12 rows, so its per-shard counts are
        # [12, 12, 12, 8, 0, 0, 0, 0] — trailing shards all-padding
        X, y = _mk_xy(1100)
        with config.set(stream_block_rows=96, superblock_k=8):
            s = BlockStream((X, y), block_rows=96)
            last = list(s.superblocks())[-1]
        sc = np.asarray(last.shard_counts)
        tail_slot = last.n_blocks - 1
        np.testing.assert_array_equal(
            sc[:, tail_slot], [12, 12, 12, 8, 0, 0, 0, 0]
        )
        # padding block slots are zero on EVERY shard
        np.testing.assert_array_equal(sc[:, last.n_blocks:], 0)

    def test_trivial_mesh_stages_single_device_without_shard_counts(self):
        X, y = _mk_xy(600)
        with config.set(stream_block_rows=96, stream_mesh=1):
            s = BlockStream((X, y), block_rows=96)
            assert s.sb_data_shards() == 1 and not s.sb_sharded()
            sb = next(iter(s.superblocks()))
        assert sb.shard_counts is None
        blk = sb.arrays[0]
        assert len(blk.sharding.device_set) == 1

    def test_stream_mesh_n_limits_the_shard_count(self):
        X, y = _mk_xy(600)
        with config.set(stream_block_rows=96, stream_mesh=2):
            s = BlockStream((X, y), block_rows=96)
            assert s.sb_data_shards() == 2


class TestGLMParity:
    def test_objective_per_pass_parity_across_mesh_sizes(self):
        n, d = 1100, 6
        X, y = _mk_xy(n, d)
        beta = np.random.RandomState(3).randn(d + 1)
        out = {}
        for sm in MESHES:
            with config.set(stream_block_rows=96, stream_mesh=sm):
                o = _objective(BlockStream((X, y), block_rows=96), n, d)
                v, g = o.value_and_grad(beta)
                v2, g2, h = o.value_and_grad_and_hess(beta)
                out[sm] = (v, g, v2, g2, h, o.value(beta))
        for sm in MESHES[1:]:
            for a, b in zip(out[sm], out[1]):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-6
                )

    def test_multiclass_objective_parity(self):
        from dask_ml_tpu.models.solvers.streamed import (
            MulticlassStreamedObjective,
        )

        n, d, C = 900, 5, 3
        X, _ = _mk_xy(n, d)
        y = np.random.RandomState(5).randint(0, C, n).astype(np.float32)
        beta = np.random.RandomState(6).randn(C * (d + 1))
        out = {}
        for sm in (1, 8):
            with config.set(stream_block_rows=96, stream_mesh=sm):
                o = MulticlassStreamedObjective(
                    BlockStream((X, y), block_rows=96), n,
                    jnp.asarray(0.1, jnp.float32),
                    jnp.ones(C * (d + 1)), 0.5, "logistic", "l2", True,
                    n_classes=C,
                )
                out[sm] = o.value_and_grad(beta)
        np.testing.assert_allclose(out[8][0], out[1][0], rtol=1e-6)
        np.testing.assert_allclose(out[8][1], out[1][1],
                                   atol=1e-6, rtol=1e-6)

    def test_streamed_lbfgs_fit_records_stream_shards(self):
        from dask_ml_tpu.linear_model import LogisticRegression

        X, y = _mk_xy(1100)
        with config.set(stream_block_rows=96):
            clf = LogisticRegression(solver="lbfgs", max_iter=15).fit(
                X.astype(np.float64), y.astype(np.float64)
            )
        assert clf.solver_info_["streamed"] is True
        assert clf.solver_info_["stream_shards"] == 8
        assert clf.score(X, y) > 0.8


class TestSGDParity:
    def test_fit_weights_parity_across_mesh_sizes(self):
        from dask_ml_tpu.models.sgd import SGDClassifier

        X, y = _mk_xy(1100)
        res = {}
        for sm in MESHES:
            with config.set(stream_block_rows=96, stream_mesh=sm):
                m = SGDClassifier(max_iter=2, random_state=0,
                                  shuffle=True).fit(X, y)
                res[sm] = (m.coef_.copy(), m.intercept_.copy(), m._t)
        for sm in MESHES[1:]:
            assert res[sm][2] == res[1][2]      # identical lr clock
            np.testing.assert_allclose(res[sm][0], res[1][0], atol=1e-6)
            np.testing.assert_allclose(res[sm][1], res[1][1], atol=1e-6)

    def test_multiclass_elasticnet_parity(self):
        from dask_ml_tpu.models.sgd import SGDClassifier

        X, _ = _mk_xy(900)
        y = np.random.RandomState(5).randint(0, 3, len(X)).astype(float)
        res = {}
        for sm in (1, 8):
            with config.set(stream_block_rows=96, stream_mesh=sm):
                m = SGDClassifier(max_iter=2, random_state=0,
                                  shuffle=False, penalty="elasticnet",
                                  l1_ratio=0.4).fit(X, y)
                res[sm] = m.coef_.copy()
        np.testing.assert_allclose(res[8], res[1], atol=1e-6)

    def test_incremental_wrapper_threads_the_mesh(self):
        from dask_ml_tpu.models.sgd import SGDClassifier
        from dask_ml_tpu.wrappers import Incremental

        X, y = _mk_xy(1100)
        res = {}
        for sm in (1, 8):
            with config.set(stream_block_rows=96, stream_mesh=sm):
                inc = Incremental(
                    SGDClassifier(max_iter=1, random_state=0),
                    shuffle_blocks=True, random_state=7,
                ).fit(X, y)
                res[sm] = inc.estimator_.coef_.copy()
        np.testing.assert_allclose(res[8], res[1], atol=1e-6)


class TestKMeansParity:
    def test_streamed_lloyd_parity(self):
        from dask_ml_tpu.models.kmeans import KMeans

        rng = np.random.RandomState(2)
        X = np.concatenate([
            rng.randn(400, 5).astype(np.float32) + c for c in (0, 6, 12)
        ])
        res = {}
        for sm in (1, 8):
            with config.set(stream_block_rows=96, stream_mesh=sm):
                km = KMeans(n_clusters=3, random_state=0,
                            max_iter=20).fit(X)
                res[sm] = (np.sort(km.cluster_centers_, axis=0),
                           km.inertia_)
        np.testing.assert_allclose(res[8][0], res[1][0], atol=1e-5)
        assert res[8][1] == pytest.approx(res[1][1], rel=1e-5)


class TestCarriesAndDispatch:
    def test_carry_replicates_and_donates(self):
        from dask_ml_tpu.models.solvers.streamed import _sb_reducer
        from dask_ml_tpu.parallel.mesh import stream_data_mesh

        mesh = stream_data_mesh()
        assert mesh.devices.size == 8
        d = 4
        run = _sb_reducer("vg", "logistic", True, 0, mesh=mesh)
        X, y = _mk_xy(192, d)
        with config.set(stream_block_rows=96, superblock_k=2):
            s = BlockStream((X, y), block_rows=96)
            sb = next(iter(s.superblocks()))
        rep = NamedSharding(mesh, P())
        beta = jnp.zeros(d + 1, jnp.float32)
        acc = jax.device_put(
            (jnp.zeros((), jnp.float32), jnp.zeros(d + 1, jnp.float32)),
            rep,
        )
        out = run(acc, beta, sb.arrays[0], sb.arrays[1],
                  sb.shard_counts)  # compile once
        # the carry comes back REPLICATED on the stream mesh
        for o in out:
            assert o.sharding == rep, o.sharding
        acc = jax.device_put(
            (jnp.zeros((), jnp.float32), jnp.zeros(d + 1, jnp.float32)),
            rep,
        )
        out = run(acc, beta, sb.arrays[0], sb.arrays[1],
                  sb.shard_counts)
        # ... and the donated input buffer is dead
        with pytest.raises(Exception):
            np.asarray(acc[1])

    def test_sgd_weight_carry_is_replicated(self):
        from dask_ml_tpu.models.sgd import SGDClassifier
        from dask_ml_tpu.parallel.mesh import stream_data_mesh

        X, y = _mk_xy(1100)
        with config.set(stream_block_rows=96):
            m = SGDClassifier(max_iter=1, random_state=0,
                              shuffle=False).fit(X, y)
        rep = NamedSharding(stream_data_mesh(), P())
        assert m._w.sharding == rep, m._w.sharding

    def test_one_dispatch_per_superblock_and_zero_recompiles(self):
        """Sharding must not change the dispatch shape: one scan
        dispatch per super-block (NOT per shard), and pass 2+ pays zero
        new XLA compiles."""
        from dask_ml_tpu.models.sgd import SGDClassifier

        X, y = _mk_xy(1100)
        with config.set(stream_block_rows=96):
            SGDClassifier(max_iter=1, random_state=0,
                          shuffle=False).fit(X, y)  # pass 1 compiles
            obs.counters_reset()
            m = SGDClassifier(max_iter=3, random_state=0,
                              shuffle=False).fit(X, y)
        st = dict(m._last_stream_stats or {})
        k = st["superblock_k"]
        assert st["dispatches_per_pass"] == -(-st["n_blocks"] // k)
        assert st["sb_shards"] == 8
        snap = obs.counters_snapshot()
        assert snap.get("recompiles", 0) == 0, snap
        assert snap.get("superblock_donations", 0) >= 3
        assert snap.get("shard_slab_puts", 0) > 0
        assert snap.get("shard_staging_batches", 0) > 0


class TestTrivialMeshJaxpr:
    def test_trivial_mesh_jaxpr_byte_identical_and_collective_free(self):
        """With config.stream_mesh=1 the streamed SGD scan program is
        the ORIGINAL single-device one: its jaxpr is byte-identical
        whether the knob is set or left at default resolution semantics
        (the mesh feature adds nothing to the trace) and contains no
        psum; the sharded program's jaxpr does psum."""
        from dask_ml_tpu.models.sgd import _sgd_stream_program
        from dask_ml_tpu.parallel.mesh import stream_data_mesh

        K, S, d = 2, 96, 4

        def trace_xla():
            W = jnp.zeros(d + 1, jnp.float32)
            Xs = jnp.zeros((K, S, d), jnp.float32)
            ys = jnp.zeros((K, S), jnp.float32)
            counts = jnp.zeros((K,), jnp.int32)
            lrs = jnp.ones((K,), jnp.float32)
            z = jnp.float32(0.0)
            run = _sgd_stream_program(None, "xla", "log_loss", False)
            return str(jax.make_jaxpr(
                lambda W, Xs, *a: run.__wrapped__(W, (Xs,), *a)
            )(W, Xs, ys, counts, lrs, z, z, z, z))

        baseline = trace_xla()
        with config.set(stream_mesh=1):
            assert trace_xla() == baseline
        with config.set(stream_mesh=8):
            assert trace_xla() == baseline
        assert "psum" not in baseline

        mesh = stream_data_mesh()
        run = _sgd_stream_program(mesh, "xla", "log_loss", False)
        W = jnp.zeros(d + 1, jnp.float32)
        Xs = jnp.zeros((K, S, d), jnp.float32)
        ys = jnp.zeros((K, S), jnp.float32)
        sc = jnp.zeros((8, K), jnp.int32)
        counts = jnp.zeros((K,), jnp.int32)
        lrs = jnp.ones((K,), jnp.float32)
        z = jnp.float32(0.0)
        sharded = str(jax.make_jaxpr(
            lambda W, Xs, ys, sc, *a: run.__wrapped__(
                W, (Xs,), ys, *a, shard_counts=sc)
        )(W, Xs, ys, sc, counts, lrs, z, z, z, z))
        assert "psum" in sharded

    def test_trivial_mesh_fit_takes_original_program(self):
        from dask_ml_tpu.models.sgd import SGDClassifier

        X, y = _mk_xy(600)
        with config.set(stream_block_rows=96, stream_mesh=1):
            m = SGDClassifier(max_iter=1, random_state=0,
                              shuffle=False).fit(X, y)
        # single-device carry: no mesh sharding entered the fit
        assert len(m._w.sharding.device_set) == 1
