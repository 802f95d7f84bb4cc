"""Super-block scan execution (ISSUE 3): K streamed blocks consumed by
one donated-carry XLA dispatch.

Covers the tentpole's contracts: ragged final super-block (fewer than K
blocks AND a short last block) pads with zero counts and contributes
nothing; sparse sources fall back to the per-block path; the donated
carry actually reuses buffers (no reallocation per dispatch, zero new
compiles after the first pass); and the super-block path's numbers match
the per-block path's to 1e-6 per pass.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dask_ml_tpu import config
from dask_ml_tpu import observability as obs
from dask_ml_tpu.parallel.streaming import BlockStream, SparseBlocks


def _mk_xy(n=1100, d=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    y = (X @ rng.randn(d) > 0).astype(np.float32)
    return X, y


class TestSuperBlockIterator:
    def test_ragged_final_superblock_pads_with_zero_counts(self):
        # 1100 rows / 96-row blocks = 12 blocks; K=8 -> super-blocks of
        # 8 and 4 real slots, the last real block holding 44 rows
        X, y = _mk_xy(1100)
        with config.set(stream_block_rows=96, superblock_k=8):
            s = BlockStream((X, y), block_rows=96)
            sbs = list(s.superblocks())
        assert [sb.n_blocks for sb in sbs] == [8, 4]
        last = sbs[-1]
        counts = np.asarray(last.counts)
        assert counts.shape == (8,)                      # fixed K shape
        assert np.asarray(last.arrays[0]).shape == \
            np.asarray(sbs[0].arrays[0]).shape
        assert list(counts[4:]) == [0, 0, 0, 0]          # padding slots
        assert counts[3] == 1100 - 11 * s.block_rows     # ragged rows
        # padding slots are zeroed, so masked kernels can't read junk
        assert float(np.abs(np.asarray(last.arrays[0])[4:]).sum()) == 0.0
        # every row round-trips exactly once, in order
        rows = []
        for sb in sbs:
            yb = np.asarray(sb.arrays[1])
            for j in range(sb.n_blocks):
                rows.append(yb[j][: np.asarray(sb.counts)[j]])
        np.testing.assert_array_equal(np.concatenate(rows), y)

    def test_k_resolution_and_opt_out(self):
        X, y = _mk_xy()
        with config.set(stream_block_rows=96):
            s = BlockStream((X, y), block_rows=96)
            assert s.resolve_superblock_k() > 1
            assert s.use_superblocks()
        with config.set(stream_block_rows=96, superblock_k=1):
            s = BlockStream((X, y), block_rows=96)
            assert s.resolve_superblock_k() == 1
            assert not s.use_superblocks()
        with config.set(stream_block_rows=96, superblock_k=3):
            s = BlockStream((X, y), block_rows=96)
            assert s.resolve_superblock_k() == 3
        # K never exceeds the pass length
        with config.set(stream_block_rows=96, superblock_k=64):
            s = BlockStream((X, y), block_rows=96)
            assert s.resolve_superblock_k() == s.n_blocks

    def test_sparse_source_falls_back(self):
        import scipy.sparse as sp

        X, y = _mk_xy(400)
        Xs = SparseBlocks([sp.csr_matrix(X[:200]), sp.csr_matrix(X[200:])])
        with config.set(stream_block_rows=96):
            s = BlockStream((Xs,), block_rows=96)
            assert s.resolve_superblock_k() == 1
            assert not s.use_superblocks()

    def test_dispatch_stats_and_counters(self):
        X, y = _mk_xy(1100)
        obs.counters_reset()
        with config.set(stream_block_rows=96, superblock_k=4):
            s = BlockStream((X, y), block_rows=96)
            n = sum(1 for _ in s.superblocks())
        assert n == 3 == s.stats["dispatches_per_pass"]
        assert s.stats["superblock_k"] == 4
        assert s.stats["n_blocks"] == 12
        snap = obs.counters_snapshot()
        assert snap.get("superblock_dispatches") == 3
        assert snap.get("superblock_blocks") == 12

    def test_autotune_grows_k_when_consumer_stalls(self):
        X, y = _mk_xy(2000)
        with config.set(stream_block_rows=96, superblock_k=2):
            s = BlockStream((X, y), block_rows=96)
            list(s.superblocks())
            # synthesize a data-bound pass: the consumer stalled >10%
            # of the pass waiting on staged super-blocks
            s.stats["wait_s"] = 0.5
            s.stats["pass_s"] = 1.0
            s._maybe_grow_superblock()
            assert s.resolve_superblock_k() == 4
            # fully-overlapped passes leave K alone — worker busy time
            # (host_s/put_s) is NOT a growth signal for super-blocks
            s.stats["wait_s"] = 0.0
            s.stats["host_s"] = 1.0
            s.stats["put_s"] = 1.0
            s.stats["consume_s"] = 0.0
            s._maybe_grow_superblock()
            assert s.resolve_superblock_k() == 4


class TestObjectiveParity:
    def _objective(self, stream, n, d):
        from dask_ml_tpu.models.solvers.streamed import StreamedObjective

        return StreamedObjective(
            stream, n, jnp.asarray(0.1, jnp.float32), jnp.ones(d + 1),
            0.5, "logistic", "l2", True,
        )

    def test_per_pass_sums_match_per_block_to_1e6(self):
        n, d = 1100, 6
        X, y = _mk_xy(n, d)
        beta = np.random.RandomState(3).randn(d + 1)
        out = {}
        for sb in (True, False):
            with config.set(stream_block_rows=96,
                            superblock_k=(0 if sb else 1)):
                objective = self._objective(
                    BlockStream((X, y), block_rows=96), n, d
                )
                v, g = objective.value_and_grad(beta)
                v2, g2, h = objective.value_and_grad_and_hess(beta)
                out[sb] = (v, g, v2, g2, h, objective.value(beta))
        for a, b in zip(out[True], out[False]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-6)

    def test_glm_streamed_solvers_run_superblocked(self):
        from dask_ml_tpu.linear_model import LogisticRegression

        n, d = 1100, 6
        X, y = _mk_xy(n, d)
        for solver in ("lbfgs", "newton", "admm"):
            with config.set(stream_block_rows=96):
                clf = LogisticRegression(solver=solver, max_iter=20,
                                         tol=1e-5).fit(X.astype(np.float64),
                                                       y.astype(np.float64))
            assert clf.solver_info_["streamed"] is True
            assert clf.score(X, y) > 0.8


class TestSGDParity:
    def test_epoch_weights_match_per_block_to_1e6(self):
        from dask_ml_tpu.models.sgd import SGDClassifier

        X, y = _mk_xy(1100)
        res = {}
        for sb in (True, False):
            with config.set(stream_block_rows=96,
                            superblock_k=(0 if sb else 1)):
                m = SGDClassifier(max_iter=2, random_state=0,
                                  shuffle=True).fit(X, y)
                res[sb] = (m.coef_.copy(), m.intercept_.copy(), m._t)
        assert res[True][2] == res[False][2]  # identical lr clock
        np.testing.assert_allclose(res[True][0], res[False][0], atol=1e-6)
        np.testing.assert_allclose(res[True][1], res[False][1], atol=1e-6)

    def test_multiclass_and_l1_parity(self):
        from dask_ml_tpu.models.sgd import SGDClassifier

        X, _ = _mk_xy(900)
        y = np.random.RandomState(5).randint(0, 3, len(X)).astype(float)
        res = {}
        for sb in (True, False):
            with config.set(stream_block_rows=96,
                            superblock_k=(0 if sb else 1)):
                m = SGDClassifier(max_iter=2, random_state=0, shuffle=False,
                                  penalty="elasticnet", l1_ratio=0.4,
                                  ).fit(X, y)
                res[sb] = m.coef_.copy()
        np.testing.assert_allclose(res[True], res[False], atol=1e-6)

    def test_incremental_wrapper_host_data_parity(self):
        from dask_ml_tpu.models.sgd import SGDClassifier
        from dask_ml_tpu.wrappers import Incremental

        X, y = _mk_xy(1100)
        res = {}
        for sb in (True, False):
            with config.set(stream_block_rows=96,
                            superblock_k=(0 if sb else 1)):
                inc = Incremental(
                    SGDClassifier(max_iter=1, random_state=0),
                    shuffle_blocks=True, random_state=7,
                ).fit(X, y)
                res[sb] = inc.estimator_.coef_.copy()
        np.testing.assert_allclose(res[True], res[False], atol=1e-6)


class TestKMeansParity:
    def test_streamed_lloyd_matches_per_block(self):
        from dask_ml_tpu.models.kmeans import KMeans

        rng = np.random.RandomState(2)
        X = np.concatenate([
            rng.randn(400, 5).astype(np.float32) + c for c in (0, 6, 12)
        ])
        res = {}
        for sb in (True, False):
            with config.set(stream_block_rows=96,
                            superblock_k=(0 if sb else 1)):
                km = KMeans(n_clusters=3, random_state=0, max_iter=30).fit(X)
                res[sb] = (np.sort(km.cluster_centers_, axis=0),
                           km.inertia_)
        np.testing.assert_allclose(res[True][0], res[False][0], atol=1e-5)
        # an f32 summation-order band: the scan carries one accumulator
        # through K blocks where the per-block loop adds K partial sums
        assert res[True][1] == pytest.approx(res[False][1], rel=1e-5)


class TestDonationAndCompiles:
    def test_donated_carry_reuses_buffer_and_no_recompiles_after_pass1(self):
        """The scan carry is donated: across a pass the accumulator
        advances in place (on backends honoring donation the buffer
        pointer survives), and pass 2+ of identical shapes pays ZERO new
        XLA compiles — the steady-state contract the verify.sh perf gate
        enforces."""
        from dask_ml_tpu.models.sgd import SGDClassifier

        X, y = _mk_xy(1100)
        with config.set(stream_block_rows=96):
            warm = SGDClassifier(max_iter=1, random_state=0,
                                 shuffle=False).fit(X, y)  # pass 1 compiles
            obs.counters_reset()
            m = SGDClassifier(max_iter=3, random_state=0,
                              shuffle=False).fit(X, y)
        snap = obs.counters_snapshot()
        assert snap.get("recompiles", 0) == 0, snap
        assert snap.get("superblock_dispatches", 0) >= 3
        assert snap.get("superblock_donations", 0) >= 3
        assert warm.coef_.shape == m.coef_.shape

    def test_donation_reuses_buffer_pointer(self):
        """XLA:CPU honors donation: the carry handed to the scan is the
        same allocation the result comes back in."""
        from dask_ml_tpu.models.solvers.streamed import _sb_reducer

        d = 4
        run = _sb_reducer("vg", "logistic", True, 0)
        beta = jnp.zeros(d + 1, jnp.float32)
        Xs = jnp.ones((2, 8, d), jnp.float32)
        ys = jnp.zeros((2, 8), jnp.float32)
        counts = jnp.asarray([8, 8], jnp.int32)
        acc = (jnp.zeros((), jnp.float32), jnp.zeros(d + 1, jnp.float32))
        run(acc, beta, Xs, ys, counts)  # compile once
        acc = (jnp.zeros((), jnp.float32), jnp.zeros(d + 1, jnp.float32))
        ptr = acc[1].unsafe_buffer_pointer()
        out = run(acc, beta, Xs, ys, counts)
        assert out[1].unsafe_buffer_pointer() == ptr
        with pytest.raises(Exception):
            np.asarray(acc[1])  # the donated input buffer is dead


class TestSparseAndHostFallback:
    def test_sparse_sgd_fit_still_streams_per_block(self):
        import scipy.sparse as sp

        from dask_ml_tpu.models.sgd import SGDClassifier

        X, y = _mk_xy(600)
        Xs = sp.csr_matrix(X)
        with config.set(stream_block_rows=96):
            m = SGDClassifier(max_iter=1, random_state=0).fit(Xs, y)
            ref = SGDClassifier(max_iter=1, random_state=0).fit(X, y)
        # the sparse per-block path trains the same minibatches
        np.testing.assert_allclose(m.coef_, ref.coef_, atol=1e-5)

    def test_host_estimator_keeps_per_block_loop(self):
        from sklearn.linear_model import SGDClassifier as SkSGD

        from dask_ml_tpu.wrappers import Incremental

        X, y = _mk_xy(600)
        with config.set(stream_block_rows=96):
            inc = Incremental(SkSGD(max_iter=5, random_state=0),
                              shuffle_blocks=False).fit(X, y)
        assert inc.estimator_.coef_.shape == (1, X.shape[1])


class TestStackedLayout:
    """One stacked [K, S, d] buffer consumed by a lax.scan: the layout
    every backend runs."""

    def test_stacked_scan_parity(self):
        from dask_ml_tpu.models.sgd import SGDClassifier

        X, y = _mk_xy(1100)
        with config.set(stream_block_rows=96, superblock_k=1):
            ref = SGDClassifier(max_iter=2, random_state=0,
                                shuffle=False).fit(X, y)
        with config.set(stream_block_rows=96):
            s = BlockStream((X, y), block_rows=96)
            sb = next(iter(s.superblocks()))
            assert sb.arrays[0].shape == (8, s.block_rows, X.shape[1])
            m = SGDClassifier(max_iter=2, random_state=0,
                              shuffle=False).fit(X, y)
        np.testing.assert_allclose(m.coef_, ref.coef_, atol=1e-6)
        np.testing.assert_allclose(m.intercept_, ref.intercept_,
                                   atol=1e-6)

    def test_stacked_glm_objective_parity(self):
        """K changes the dispatch granularity and where the ragged final
        super-block pads, never the sums: K=5 (12 blocks -> 5+5+2, three
        padded slots) against the auto K=8 (8+4, four padded)."""
        from dask_ml_tpu.models.solvers.streamed import StreamedObjective

        n, d = 1100, 6
        X, y = _mk_xy(n, d)
        beta = np.random.RandomState(3).randn(d + 1)

        def run(k):
            with config.set(stream_block_rows=96, superblock_k=k):
                stream = BlockStream((X, y), block_rows=96)
                assert stream.resolve_superblock_k() == (k or 8)
                objective = StreamedObjective(
                    stream, n, jnp.asarray(0.1, jnp.float32),
                    jnp.ones(d + 1), 0.5, "logistic", "l2", True,
                )
                return objective.value_and_grad(beta)

        v_k5, g_k5 = run(5)
        v_k8, g_k8 = run(0)
        np.testing.assert_allclose(v_k5, v_k8, atol=1e-6)
        np.testing.assert_allclose(g_k5, g_k8, atol=1e-6)


def test_auto_sized_blocks_still_superblock():
    """At the AUTO block size (256 MiB of X, what ``stream_plan`` gives a
    memmap) the 512 MiB super-block budget must hold two blocks with y
    riding along — both budgets count X bytes. Counting y in one of them
    made K == 1 at exactly the sizes nobody sets ``stream_block_rows``
    for: the deployment path ran per block, unfused (PR 21, on the v5e).
    The arrays are stride-0 views: 1 GiB is never allocated."""
    from dask_ml_tpu.parallel.streaming import auto_block_rows

    n, d = 1 << 20, 256
    X = np.broadcast_to(np.zeros((1, d), np.float32), (n, d))
    y = np.broadcast_to(np.zeros((), np.float32), (n,))
    stream = BlockStream((X, y), block_rows=auto_block_rows(n, 4 * d))
    assert stream.block_rows == 262_144 and stream.n_blocks == 4
    assert stream.resolve_superblock_k() == 2
    assert stream.use_superblocks()
