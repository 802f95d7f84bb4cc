"""Chaos plane (ISSUE 11): deterministic fault injection, the hardening
each injected fault exercises, pass-granular streamed-fit resume, and
replica supervision.

Contracts under test, per the tentpole:

- fault plans parse strictly, fire by invocation INDEX (replayable),
  and cost one config read when unset — the streamed scan jaxpr is
  byte-identical with the whole plane armed (every site is host-side);
- transient staging IO faults are absorbed by bounded-backoff retry
  (``stream_io_retries``) with the fit's result bit-identical to a
  fault-free run; exhaustion raises typed;
- the non-finite block policy raises typed or quarantines via the
  existing masked prefix-count (counts folded to 0 — no recompile);
- streamed SGD/GLM fits killed after pass p and resumed match an
  uninterrupted fit to 1e-6 (shuffled lr-clock identity and the
  sharded dp>1 flavor included); a wrong-fingerprint checkpoint is
  ignored; completion clears the slot;
- ``utils.checkpoint`` writes are atomic: a kill mid-save leaves the
  previous checkpoint restorable;
- a dead fleet replica is rebuilt off the serving path (warmed before
  rejoining), its queued requests drained onto the replacement, under
  a bounded restart budget; its stale gauge series are dropped.
"""

import os
import tempfile
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dask_ml_tpu import config
from dask_ml_tpu.observability import counters_reset, counters_snapshot
from dask_ml_tpu.reliability import (
    FaultInjected,
    FaultPlan,
    InjectedCrash,
    InjectedIOError,
    NonFiniteBlock,
    StreamIORetriesExhausted,
    fault_point,
    reset_plans,
)


@pytest.fixture(autouse=True)
def _clean_plane():
    reset_plans()
    counters_reset()
    yield
    reset_plans()
    counters_reset()


def _xy(n=2000, d=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    return X, y


# ---------------------------------------------------------------------------
# fault plan
# ---------------------------------------------------------------------------

class TestFaultPlan:
    def test_index_schedules_fire_deterministically(self):
        p = FaultPlan.parse("staging_read:io@2;serving_execute:crash@1*2")
        kinds = [(a.kind if a else None)
                 for a in (p.fire("staging_read") for _ in range(5))]
        assert kinds == [None, None, "io", None, None]
        kinds = [(a.kind if a else None)
                 for a in (p.fire("serving_execute") for _ in range(4))]
        assert kinds == [None, "crash", "crash", None]

    def test_every_k_schedule(self):
        p = FaultPlan.parse("staging_read:io@1+3")
        fired = [i for i in range(10)
                 if p.fire("staging_read") is not None]
        assert fired == [1, 4, 7]

    def test_probabilistic_schedule_replays_exactly(self):
        seq1 = [FaultPlan.parse("staging_read:io~0.5@seed7")
                .fire("staging_read") is not None for _ in range(64)]
        p2 = FaultPlan.parse("staging_read:io~0.5@seed7")
        seq2 = []
        for _ in range(64):
            seq2.append(p2.fire("staging_read") is not None)
        # fresh plan, same seed, same invocation sequence -> same fires
        p3 = FaultPlan.parse("staging_read:io~0.5@seed7")
        assert seq2 == [p3.fire("staging_read") is not None
                        for _ in range(64)]
        assert any(seq2) and not all(seq2)

    def test_unknown_site_and_kind_raise_listing(self):
        with pytest.raises(ValueError, match="staging_read"):
            FaultPlan.parse("bogus_site:io@0")
        with pytest.raises(ValueError, match="crash"):
            FaultPlan.parse("staging_read:meteor@0")
        with pytest.raises(ValueError, match="site:kind"):
            FaultPlan.parse("just-nonsense")

    def test_snapshot_counts_invocations_and_fires(self):
        p = FaultPlan.parse("staging_read:io@1")
        for _ in range(3):
            p.fire("staging_read")
        snap = p.snapshot()
        assert snap["staging_read"] == {"invocations": 3, "fired": 1}

    def test_fault_point_default_is_identity(self):
        # zero-overhead contract: unset plan returns the payload as-is
        sentinel = object()
        assert fault_point("staging_read", sentinel) is sentinel

    def test_typed_errors(self):
        assert issubclass(InjectedIOError, OSError)
        assert issubclass(InjectedIOError, FaultInjected)
        assert not issubclass(InjectedCrash, OSError)
        with config.set(fault_plan="serving_execute:crash@0"):
            with pytest.raises(InjectedCrash):
                fault_point("serving_execute")

    def test_nan_kind_poisons_a_copy_never_the_source(self):
        src = np.ones((8, 3), np.float32)
        with config.set(fault_plan="staging_read:nan@0"):
            out = fault_point("staging_read", src)
        assert np.isnan(out).any()
        assert np.isfinite(src).all()          # source untouched
        assert out is not src


# ---------------------------------------------------------------------------
# staging retry + non-finite policy
# ---------------------------------------------------------------------------

class TestStagingHardening:
    def test_io_fault_retried_to_bitwise_parity(self):
        X, y = _xy()
        from dask_ml_tpu.models.sgd import SGDClassifier

        with config.set(stream_block_rows=256):
            clean = SGDClassifier(max_iter=2, random_state=0).fit(X, y)
        reset_plans()
        with config.set(stream_block_rows=256, stream_io_retries=2,
                        fault_plan="staging_read:io@3"):
            faulted = SGDClassifier(max_iter=2, random_state=0).fit(X, y)
        snap = counters_snapshot()
        assert snap.get("stream_retries", 0) >= 1
        assert snap.get("faults_injected", 0) >= 1
        assert snap.get("faults_injected_staging_read", 0) >= 1
        assert np.array_equal(clean.coef_, faulted.coef_)

    def test_retries_exhausted_raises_typed(self):
        X, y = _xy(600)
        from dask_ml_tpu.parallel.streaming import BlockStream

        with config.set(stream_block_rows=128, stream_io_retries=2,
                        fault_plan="staging_read:io@0*64"):
            with pytest.raises(StreamIORetriesExhausted):
                for _ in BlockStream((X, y), block_rows=128):
                    pass

    def test_put_fault_retried(self):
        X, y = _xy(600)
        from dask_ml_tpu.parallel.streaming import BlockStream

        with config.set(stream_block_rows=128, superblock_k=1,
                        stream_io_retries=2, fault_plan="stream_put:io@1"):
            blocks = list(BlockStream((X, y), block_rows=128))
        assert counters_snapshot().get("stream_retries", 0) >= 1
        assert sum(b.n_rows for b in blocks) == len(X)

    def test_nonfinite_raise_is_typed(self):
        X, y = _xy(1500)
        X[400:410, 1] = np.inf
        from dask_ml_tpu.models.sgd import SGDClassifier

        with config.set(stream_block_rows=256, stream_nonfinite="raise"):
            with pytest.raises(NonFiniteBlock):
                SGDClassifier(max_iter=1, shuffle=False).fit(X, y)

    def test_nonfinite_quarantine_folds_counts_to_zero(self):
        X, y = _xy(1500)
        X[300:310, 2] = np.nan     # inside block 1 at 256-row blocks
        from dask_ml_tpu.parallel.streaming import BlockStream

        with config.set(stream_block_rows=256, stream_mesh=1,
                        stream_nonfinite="quarantine"):
            s = BlockStream((X, y), block_rows=256)
            sbs = list(s.superblocks())
        counts = np.concatenate([np.asarray(sb.counts)[:sb.n_blocks]
                                 for sb in sbs])
        assert counts[1] == 0 and counts[0] == 256
        # quarantined slot's DATA is zeroed too (a masked NaN would
        # still poison sums: NaN * 0 == NaN)
        first = np.asarray(sbs[0].arrays[0])
        blk1 = first[1] if first.ndim == 3 else np.asarray(
            sbs[0].arrays[0][1])
        assert np.all(blk1 == 0)
        assert counters_snapshot().get(
            "stream_quarantined_blocks", 0) >= 1

    def test_nonfinite_quarantine_fit_survives(self):
        X, y = _xy(1500)
        X[300:310, 2] = np.nan
        from dask_ml_tpu.models.sgd import SGDClassifier

        with config.set(stream_block_rows=256,
                        stream_nonfinite="quarantine"):
            clf = SGDClassifier(max_iter=2, random_state=0,
                                shuffle=False).fit(X, y)
        assert np.isfinite(clf.coef_).all()

    def test_inference_stream_hardens_quarantine_to_raise(self):
        # silently dropping a predict block would misalign output rows
        X, y = _xy(1500)
        from dask_ml_tpu.models.sgd import SGDClassifier

        with config.set(stream_block_rows=256):
            clf = SGDClassifier(max_iter=1, random_state=0).fit(X, y)
        Xbad = X.copy()
        Xbad[700:705, 0] = np.nan
        with config.set(stream_block_rows=256,
                        stream_nonfinite="quarantine"):
            with pytest.raises(NonFiniteBlock):
                clf.predict(Xbad)

    def test_bad_policy_value_raises_listing(self):
        from dask_ml_tpu.parallel.streaming import BlockStream

        X, y = _xy(600)
        with config.set(stream_nonfinite="meteor"):
            with pytest.raises(ValueError, match="quarantine"):
                BlockStream((X, y), block_rows=128)

    def test_jaxpr_byte_identical_with_plane_armed(self):
        """The acceptance-criteria contract: the streamed-SGD superblock
        jaxpr with the chaos plane armed (fault plan + quarantine +
        retries) is byte-identical to the default-config one — every
        site and policy is host-side."""
        from dask_ml_tpu.models.sgd import _sgd_stream_program
        from dask_ml_tpu.observability._programs import unwrap

        def scan_jaxpr():
            body = unwrap(_sgd_stream_program(None, "xla", "hinge", False))
            K, S, d = 2, 8, 3
            return str(jax.make_jaxpr(
                lambda W, Xs, ys, c, lrs: body(
                    W, (Xs,), ys, c, lrs, 1e-4, 1.0, 0.0, 1.0
                )
            )(jnp.zeros(d + 1), jnp.zeros((K, S, d)), jnp.zeros((K, S)),
              jnp.zeros(K, jnp.int32), jnp.zeros(K)))

        baseline = scan_jaxpr()
        with config.set(fault_plan="staging_read:io@0",
                        stream_nonfinite="quarantine",
                        stream_io_retries=7,
                        stream_checkpoint_path="/tmp/never-used"):
            assert scan_jaxpr() == baseline


# ---------------------------------------------------------------------------
# pass-granular checkpoint / resume
# ---------------------------------------------------------------------------

pytest.importorskip("orbax.checkpoint")


class TestStreamResume:
    def _kill_and_resume(self, make, crash_at, tmp, **cfg):
        """Run ``make()`` fits: control (no ckpt), killed (crash arm),
        resumed — returns (control, resumed)."""
        with config.set(**cfg):
            control = make()
        reset_plans()
        with config.set(stream_checkpoint_path=tmp,
                        fault_plan=f"superblock_dispatch:crash@{crash_at}",
                        **cfg):
            with pytest.raises(FaultInjected):
                make()
        reset_plans()
        with config.set(stream_checkpoint_path=tmp, **cfg):
            resumed = make()
        return control, resumed

    def test_sgd_shuffled_resume_parity(self, tmp_path):
        X, y = _xy(3000)
        from dask_ml_tpu.models.sgd import SGDClassifier

        def fit():
            return SGDClassifier(max_iter=4, random_state=0,
                                 shuffle=True).fit(X, y)

        ctl, res = self._kill_and_resume(fit, 4, str(tmp_path),
                                         stream_block_rows=256)
        assert counters_snapshot().get("stream_resumes", 0) == 1
        assert np.allclose(res.coef_, ctl.coef_, atol=1e-6)
        # completion cleared the slot
        assert not os.path.exists(os.path.join(str(tmp_path), "sgd"))

    def test_sgd_sharded_dp2_resume_parity(self, tmp_path):
        X, y = _xy(3000)
        from dask_ml_tpu.models.sgd import SGDClassifier

        def fit():
            return SGDClassifier(max_iter=3, random_state=0,
                                 shuffle=True).fit(X, y)

        ctl, res = self._kill_and_resume(fit, 3, str(tmp_path),
                                         stream_block_rows=256,
                                         stream_mesh=2)
        assert np.allclose(res.coef_, ctl.coef_, atol=1e-6)

    def test_wrong_fingerprint_checkpoint_ignored(self, tmp_path):
        X, y = _xy(3000)
        from dask_ml_tpu.models.sgd import SGDClassifier

        with config.set(stream_block_rows=256,
                        stream_checkpoint_path=str(tmp_path),
                        fault_plan="superblock_dispatch:crash@4"):
            with pytest.raises(FaultInjected):
                SGDClassifier(max_iter=4, random_state=0).fit(X, y)
        assert os.path.exists(os.path.join(str(tmp_path), "sgd"))
        reset_plans()
        counters_reset()
        X2 = X + 1.0   # different data content -> different fingerprint
        with config.set(stream_block_rows=256,
                        stream_checkpoint_path=str(tmp_path)):
            SGDClassifier(max_iter=4, random_state=0).fit(X2, y)
        assert counters_snapshot().get("stream_resumes", 0) == 0

    def test_glm_lbfgs_resume_parity(self, tmp_path):
        X, y = _xy(2000)
        from dask_ml_tpu.linear_model import LogisticRegression

        def fit():
            return LogisticRegression(solver="lbfgs",
                                      max_iter=10).fit(X, y)

        ctl, res = self._kill_and_resume(fit, 10, str(tmp_path),
                                         stream_block_rows=256)
        assert counters_snapshot().get("stream_resumes", 0) == 1
        assert np.allclose(res.coef_, ctl.coef_, atol=1e-6)
        assert not os.path.exists(os.path.join(str(tmp_path), "glm"))

    def test_glm_admm_resume_parity(self, tmp_path):
        X, y = _xy(2000)
        from dask_ml_tpu.linear_model import LogisticRegression

        def fit():
            return LogisticRegression(solver="admm", penalty="l1",
                                      C=1.0, max_iter=8).fit(X, y)

        # one super-block dispatch per admm iteration at this shape:
        # crash@5 kills the fit mid-iteration 6 of 8
        ctl, res = self._kill_and_resume(fit, 5, str(tmp_path),
                                         stream_block_rows=256)
        assert np.allclose(res.coef_, ctl.coef_, atol=1e-6)

    def test_incremental_pass_resume_parity(self, tmp_path):
        X, y = _xy(2000)
        from dask_ml_tpu.models.sgd import SGDClassifier
        from dask_ml_tpu.wrappers import Incremental

        def make():
            return Incremental(SGDClassifier(random_state=0),
                               shuffle_blocks=True, random_state=0)

        ctl = make()
        for _ in range(5):
            ctl.partial_fit(X, y, classes=[0.0, 1.0])
        with config.set(stream_block_rows=256,
                        stream_checkpoint_path=str(tmp_path)):
            a = make()
            for _ in range(3):
                a.partial_fit(X, y, classes=[0.0, 1.0])
            assert a.completed_passes_ == 3
            # "kill": a fresh wrapper restores the killed run's state
            b = make()
            b.partial_fit(X, y, classes=[0.0, 1.0])
            assert b.completed_passes_ == 4
            assert counters_snapshot().get("stream_resumes", 0) == 1
            b.partial_fit(X, y, classes=[0.0, 1.0])
            b._clear_pass_checkpoint()
        assert np.allclose(b.estimator_.coef_, ctl.estimator_.coef_,
                           atol=1e-6)

    def test_serve_while_training_resume_skips_completed_passes(
            self, tmp_path):
        """A pass driver killed AFTER its final pass (but before the
        completion clear) must resume to ZERO remaining work — not
        train and publish one pass past the target; killed mid-sequence
        it runs exactly the remaining passes."""
        from dask_ml_tpu.models.sgd import SGDClassifier
        from dask_ml_tpu.serving.fleet import serve_while_training
        from dask_ml_tpu.wrappers import Incremental

        X, y = _xy(1500)

        class DummyFleet:
            def __init__(self):
                self.tags = []

            def publish(self, est, tag=None, quantize=None):
                self.tags.append(tag)
                return len(self.tags)

        def make():
            return Incremental(SGDClassifier(random_state=0),
                               shuffle_blocks=True, random_state=0)

        ctl = make()
        for _ in range(3):
            ctl.partial_fit(X, y, classes=[0.0, 1.0])
        with config.set(stream_block_rows=256,
                        stream_checkpoint_path=str(tmp_path)):
            # killed AFTER pass 3 of 3 (no clear ran)
            a = make()
            for _ in range(3):
                a.partial_fit(X, y, classes=[0.0, 1.0])
            b = make()
            fleet = DummyFleet()
            serve_while_training(fleet, b, X, y, passes=3,
                                 classes=[0.0, 1.0])
            assert b.completed_passes_ == 3
            assert fleet.tags == []        # nothing re-trained
            assert np.allclose(b.estimator_.coef_, ctl.estimator_.coef_,
                               atol=1e-6)
            # killed after pass 2 of 3: exactly ONE more pass runs
            c = make()
            for _ in range(2):
                c.partial_fit(X, y, classes=[0.0, 1.0])
            d = make()
            fleet2 = DummyFleet()
            serve_while_training(fleet2, d, X, y, passes=3,
                                 classes=[0.0, 1.0])
            assert fleet2.tags == ["pass3"]
            assert d.completed_passes_ == 3
            assert np.allclose(d.estimator_.coef_, ctl.estimator_.coef_,
                               atol=1e-6)

    def test_multihost_refusal(self):
        from dask_ml_tpu.parallel.distributed import run_virtual_processes
        from dask_ml_tpu.reliability.stream_ckpt import stream_checkpoint

        def body(rank):
            with config.set(stream_checkpoint_path="/tmp/x"):
                return stream_checkpoint("sgd", ("a",))

        assert run_virtual_processes(body, world=2) == [None, None]


# ---------------------------------------------------------------------------
# atomic checkpoint writes
# ---------------------------------------------------------------------------

class TestAtomicCheckpoint:
    def test_kill_mid_save_keeps_previous_state(self):
        from dask_ml_tpu.utils import checkpoint as ckpt

        d = tempfile.mkdtemp()
        p = os.path.join(d, "state")
        ckpt.save_pytree(p, {"x": np.arange(4.0)})

        # a killed save leaves a partial temp sibling; the live slot is
        # untouched (orbax's own force=True used to DELETE it first)
        os.makedirs(p + ".tmp", exist_ok=True)
        with open(os.path.join(p + ".tmp", "junk"), "w") as f:
            f.write("partial garbage")
        st = ckpt.restore_pytree(p)
        assert np.array_equal(np.asarray(st["x"]), np.arange(4.0))
        # the next save bulldozes the junk and publishes atomically
        ckpt.save_pytree(p, {"x": np.arange(5.0)})
        assert np.asarray(ckpt.restore_pytree(p)["x"]).size == 5

    def test_crash_window_between_renames_restores_old(self):
        from dask_ml_tpu.utils import checkpoint as ckpt

        d = tempfile.mkdtemp()
        p = os.path.join(d, "state")
        ckpt.save_pytree(p, {"x": np.arange(3.0)})
        # simulate a kill between "retire old" and "publish new"
        os.rename(p, p + ".old")
        assert ckpt.checkpoint_exists(p)
        st = ckpt.restore_pytree(p)
        assert np.array_equal(np.asarray(st["x"]), np.arange(3.0))

    def test_repeated_crash_keeps_old_until_publish(self, monkeypatch):
        """After crash #1 left the only good state at ``.old``, a kill
        during the NEXT save's publish must still leave it restorable —
        the .old fallback may only be deleted once the new checkpoint
        has published."""
        from dask_ml_tpu.utils import checkpoint as ckpt

        d = tempfile.mkdtemp()
        p = os.path.join(d, "state")
        ckpt.save_pytree(p, {"x": np.arange(2.0)})
        os.rename(p, p + ".old")   # crash #1: retired, never published
        real_rename = os.rename

        def killed_publish(src, dst):
            if dst == p:
                raise RuntimeError("kill mid-publish")
            return real_rename(src, dst)

        monkeypatch.setattr(os, "rename", killed_publish)
        with pytest.raises(RuntimeError, match="kill mid-publish"):
            ckpt.save_pytree(p, {"x": np.arange(9.0)})
        monkeypatch.undo()
        assert ckpt.checkpoint_exists(p)
        st = ckpt.restore_pytree(p)
        assert np.array_equal(np.asarray(st["x"]), np.arange(2.0))

    def test_save_host_atomic(self):
        from dask_ml_tpu.utils import checkpoint as ckpt

        d = tempfile.mkdtemp()
        p = os.path.join(d, "h.pkl")
        ckpt.save_host(p, {"v": 1})

        class Boom:
            def __reduce__(self):
                raise RuntimeError("kill mid-write")

        with pytest.raises(RuntimeError):
            ckpt.save_host(p, Boom())
        assert ckpt.restore_host(p) == {"v": 1}
        assert not any(f.startswith("h.pkl.tmp") for f in os.listdir(d))


# ---------------------------------------------------------------------------
# pass-barrier deadline
# ---------------------------------------------------------------------------

class TestSyncDeadline:
    def test_deadline_raises_typed(self):
        from dask_ml_tpu.parallel.distributed import (
            StreamSyncTimeout, run_with_deadline)

        with pytest.raises(StreamSyncTimeout, match="checkpoint"):
            run_with_deadline(lambda: time.sleep(5.0), 0.15, "t")

    def test_body_error_propagates(self):
        from dask_ml_tpu.parallel.distributed import run_with_deadline

        def boom():
            raise ValueError("collective failed")

        with pytest.raises(ValueError, match="collective failed"):
            run_with_deadline(boom, 5.0, "t")

    def test_single_process_sync_is_noop(self):
        from dask_ml_tpu.parallel.distributed import sync_stream_pass

        assert sync_stream_pass("test", timeout_s=0.1) is False


# ---------------------------------------------------------------------------
# replica supervision
# ---------------------------------------------------------------------------

def _fitted_model():
    X, y = _xy(400)
    from dask_ml_tpu.models.sgd import SGDClassifier

    with config.set(stream_block_rows=0):
        return SGDClassifier(max_iter=2, random_state=0).fit(X, y), X


_SMALL_FLEET = dict(serving_min_batch=8, serving_max_batch=32,
                    serving_supervise=True,
                    serving_supervise_interval_s=0.05)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
class TestReplicaSupervision:
    def test_dead_replica_rebuilt_and_rejoins(self):
        from dask_ml_tpu.serving.fleet import FleetServer

        clf, X = _fitted_model()
        with config.set(fault_plan="replica_worker:crash@25",
                        **_SMALL_FLEET):
            fleet = FleetServer(clf, replicas=2, timeout_ms=10000).warmup()
            with fleet:
                deadline = time.time() + 20
                restarted = False
                while time.time() < deadline:
                    try:
                        fleet.predict(X[:8])
                    except Exception:
                        pass
                    if (counters_snapshot().get(
                            "serving_replica_restarts", 0) >= 1
                            and sum(1 for r in fleet.replicas
                                    if r.healthy) == 2):
                        restarted = True
                        break
                    time.sleep(0.02)
                assert restarted, counters_snapshot()
                # the rebuilt fleet still answers correctly
                out = fleet.predict(X[:16])
                assert len(out) == 16
                assert fleet.stats()["healthy_replicas"] == 2

    def test_restart_budget_degrades_to_permanent_failover(self):
        from dask_ml_tpu.serving.fleet import FleetServer

        clf, X = _fitted_model()
        cfg = dict(_SMALL_FLEET)
        cfg["serving_restart_budget"] = 0
        # rate-less @0 arm: the FIRST worker loop iteration of whichever
        # replica hits the site dies; budget 0 -> permanent failover
        with config.set(fault_plan="replica_worker:crash@0", **cfg):
            fleet = FleetServer(clf, replicas=2, timeout_ms=10000).warmup()
            with fleet:
                deadline = time.time() + 20
                failed = False
                while time.time() < deadline:
                    snap = counters_snapshot()
                    if snap.get("serving_replica_failures", 0) >= 1:
                        failed = True
                        break
                    time.sleep(0.02)
                assert failed, counters_snapshot()
                assert counters_snapshot().get(
                    "serving_replica_restarts", 0) == 0
                # the survivor keeps serving
                out = fleet.predict(X[:8])
                assert len(out) == 8
                assert fleet.stats()["healthy_replicas"] == 1

    def test_dead_replica_gauges_dropped(self):
        from dask_ml_tpu.observability import live
        from dask_ml_tpu.serving import metrics as smetrics

        live.metrics_reset()
        labels = (("replica", "7"),)
        live.gauge_set("serving_replica_version", 3, labels)
        live.gauge_set("serving_replica_healthy", 1, labels)
        live.gauge_set("serving_queue_depth", 2, labels)
        assert any(k[0].startswith("serving_replica")
                   for k in live.gauges_snapshot())
        smetrics.drop_replica_gauges(7)
        snap = live.gauges_snapshot()
        assert not any(("replica", "7") in k[1] for k in snap)
        live.metrics_reset()


# ---------------------------------------------------------------------------
# observability surface
# ---------------------------------------------------------------------------

class TestReliabilityObservability:
    def test_status_block(self):
        from dask_ml_tpu.observability.live import status_data
        from dask_ml_tpu.reliability import status_block

        with config.set(fault_plan="staging_read:io@0"):
            fault_point("staging_read", None) if False else None
            try:
                fault_point("staging_read")
            except InjectedIOError:
                pass
            block = status_block()
            assert block["fault_plan"] == "staging_read:io@0"
            assert block["sites"]["staging_read"]["fired"] == 1
            assert block["counters"].get("faults_injected") == 1
            assert status_data()["reliability"]["fault_plan"] \
                == "staging_read:io@0"
        # unarmed: the block is quiet, not absent
        assert status_block()["fault_plan"] is None

    def test_report_reliability_table(self):
        from dask_ml_tpu.observability._counters import counter_add
        from dask_ml_tpu.observability.report import (build_report,
                                                      report_data)

        counter_add("stream_retries", 3)
        counter_add("serving_replica_restarts", 1)
        counter_add("faults_injected_staging_read", 2)
        records = [{"counters": True, **counters_snapshot()}]
        data = report_data(records)
        names = {r["counter"] for r in data["reliability"]}
        assert {"stream_retries", "serving_replica_restarts",
                "faults_injected_staging_read"} <= names
        text = build_report(records)
        assert "reliability" in text and "stream_retries" in text

    def test_metrics_page_renders_reliability_counters(self):
        from dask_ml_tpu.observability._counters import counter_add
        from dask_ml_tpu.observability.live import render_prometheus

        counter_add("stream_retries", 2)
        counter_add("serving_replica_restarts", 1)
        page = render_prometheus()
        assert "dask_ml_tpu_stream_retries_total 2" in page
        assert "dask_ml_tpu_serving_replica_restarts_total 1" in page
