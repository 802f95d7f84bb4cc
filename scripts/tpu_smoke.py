"""Whole-surface smoke on the REAL TPU backend.

The test suite runs on a virtual CPU mesh (tests/conftest.py); Mosaic/XLA
TPU lowering differs (tiling constraints, layout rules), so every
estimator gets exercised here on the actual chip, through the chip tool:

    chiprun --timeout 1800 -- python scripts/tpu_smoke.py

Off-chip it exits non-zero naming the backend it found. Every surface
runs whatever the earlier ones did; the last lines list each surface as
OK or FAIL with the exception's last line, full tracebacks go to
``chiprun_out/tpu_smoke_failures.txt``, and the exit code is non-zero if
any surface failed.
"""

import os
import sys
import time
import traceback

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import numpy as np


def run(name, fn):
    """(name, seconds, None | formatted traceback) for one surface."""
    t0 = time.perf_counter()
    try:
        fn()
    except Exception:
        print(f"  FAIL {name}", flush=True)
        return name, time.perf_counter() - t0, traceback.format_exc()
    print(f"  OK   {name} ({time.perf_counter() - t0:.1f}s)", flush=True)
    return name, time.perf_counter() - t0, None


def main():
    import jax

    if jax.default_backend() != "tpu":
        sys.exit(f"tpu_smoke: needs a TPU, found backend "
                 f"{jax.default_backend()!r} ({jax.devices()[0].device_kind})")
    print("backend:", jax.default_backend(), jax.devices())
    from dask_ml_tpu import datasets

    X, y = datasets.make_classification(
        n_samples=20_000, n_features=32, random_state=0
    )
    Xr, yr = datasets.make_regression(
        n_samples=20_000, n_features=32, random_state=0
    )
    Xc, yc = datasets.make_counts(
        n_samples=10_000, n_features=16, random_state=0
    )
    results = []

    def glms():
        from dask_ml_tpu.linear_model import (
            LinearRegression, LogisticRegression, PoissonRegression,
        )

        for solver in ("lbfgs", "newton", "admm", "gradient_descent",
                       "proximal_grad"):
            clf = LogisticRegression(solver=solver, max_iter=20).fit(X, y)
            assert 0.5 < clf.score(X, y) <= 1.0, (solver, clf.score(X, y))
        LinearRegression(solver="lbfgs", max_iter=30).fit(Xr, yr)
        PoissonRegression(solver="lbfgs", max_iter=30).fit(Xc, yc)

    def sgd():
        from dask_ml_tpu.linear_model import SGDClassifier, SGDRegressor

        SGDClassifier(max_iter=5).fit(X, y).score(X, y)
        SGDRegressor(max_iter=5).fit(Xr, yr).predict(Xr)

    def kmeans():
        from dask_ml_tpu.cluster import KMeans

        km = KMeans(n_clusters=8, random_state=0, max_iter=30).fit(X)
        assert km.inertia_ > 0
        km.predict(X); km.transform(X)

    def spectral():
        from dask_ml_tpu.cluster import SpectralClustering

        Xs, _ = datasets.make_blobs(n_samples=3000, n_features=5, centers=3,
                                    random_state=0)
        sc = SpectralClustering(n_clusters=3, n_components=100,
                                random_state=0).fit(Xs)
        assert len(sc.labels_.to_numpy()) == 3000

    def decomposition():
        from dask_ml_tpu.decomposition import (
            IncrementalPCA, PCA, TruncatedSVD,
        )

        for solver in ("tsqr", "randomized"):
            p = PCA(n_components=5, svd_solver=solver, random_state=0).fit(X)
            assert p.components_.shape == (5, 32)
            p.transform(X)
        TruncatedSVD(n_components=5, random_state=0).fit(X).transform(X)
        IncrementalPCA(n_components=5).fit(X).transform(X)

    def preprocessing():
        from dask_ml_tpu.preprocessing import (
            MinMaxScaler, PolynomialFeatures, QuantileTransformer,
            RobustScaler, StandardScaler,
        )

        for T in (StandardScaler, MinMaxScaler, RobustScaler):
            T().fit_transform(X)
        QuantileTransformer(n_quantiles=100).fit_transform(X)
        PolynomialFeatures(degree=2).fit_transform(
            datasets.make_classification(n_samples=2000, n_features=6,
                                         random_state=0)[0]
        )

    def naive_bayes_impute():
        from dask_ml_tpu.impute import SimpleImputer
        from dask_ml_tpu.naive_bayes import GaussianNB

        GaussianNB().fit(X, y).score(X, y)
        Xn = X.to_numpy().copy()
        Xn[::7, 0] = np.nan
        SimpleImputer().fit_transform(Xn)

    def metrics_pairwise():
        from dask_ml_tpu import metrics as m

        Yc = np.random.RandomState(0).randn(16, 32).astype(np.float32)
        m.pairwise_distances(X, Yc)
        m.pairwise_distances_argmin_min(X, Yc)
        m.euclidean_distances(X, Yc)
        m.rbf_kernel(X, Yc)

    def search():
        from sklearn.linear_model import SGDClassifier as SkSGD

        from dask_ml_tpu.model_selection import (
            GridSearchCV, HyperbandSearchCV, train_test_split,
        )
        from dask_ml_tpu.linear_model import LogisticRegression

        train_test_split(X, y, test_size=0.2, random_state=0)
        gs = GridSearchCV(
            LogisticRegression(solver="lbfgs", max_iter=10),
            {"C": [0.1, 1.0]}, cv=2,
        ).fit(X, y)
        # a pure-C grid must take the stacked-lam fast path (one
        # compiled solve for the whole grid per fold)
        assert getattr(gs, "_c_grid_vmapped_", None) == 2, \
            "C-grid fast path not taken"
        # and through a Pipeline (prefix once per fold + stacked solve)
        from sklearn.pipeline import Pipeline

        from dask_ml_tpu.preprocessing import StandardScaler

        gp = GridSearchCV(
            Pipeline([("scale", StandardScaler()),
                      ("clf", LogisticRegression(solver="lbfgs",
                                                 max_iter=10))]),
            {"clf__C": [0.1, 1.0]}, cv=2,
        ).fit(X, y)
        assert getattr(gp, "_c_grid_vmapped_", None) == 2, \
            "pipeline C-grid fast path not taken"
        HyperbandSearchCV(
            SkSGD(tol=1e-3), {"alpha": [1e-4, 1e-3, 1e-2]},
            max_iter=4, aggressiveness=2, random_state=0,
        ).fit(X, y, classes=[0, 1])

    def wrappers_ensemble():
        from sklearn.linear_model import SGDClassifier as SkSGD

        from dask_ml_tpu.ensemble import BlockwiseVotingClassifier
        from dask_ml_tpu.wrappers import Incremental, ParallelPostFit

        ParallelPostFit(SkSGD(tol=1e-3)).fit(X, y).predict(X)
        Incremental(SkSGD(tol=1e-3)).fit(X, y, classes=[0, 1]).predict(X)
        BlockwiseVotingClassifier(SkSGD(tol=1e-3), classes=[0, 1]).fit(
            X, y
        ).predict(X)

    def streaming():
        from dask_ml_tpu.parallel.streaming import BlockStream

        Xh, yh = X.to_numpy(), y.to_numpy()
        total = 0
        for blk in BlockStream((Xh, yh), block_rows=4096):
            total += blk.n_rows
        assert total == len(Xh), total

    def round5_surfaces():
        """Round-5 surfaces on the real chip: sparse CSR streaming
        bridge, device roc_auc/f1 scorers, bf16 matmul policy
        (KMeans distances + fused SGD epoch grid at bf16/f32-acc),
        streamed-SGD overlap stats."""
        import scipy.sparse as sp

        import dask_ml_tpu.config as config
        from dask_ml_tpu.cluster import KMeans
        from dask_ml_tpu.linear_model import LogisticRegression
        from dask_ml_tpu.metrics import f1_score, roc_auc_score
        from dask_ml_tpu.metrics.scorer import get_scorer
        from dask_ml_tpu.models.sgd import SGDClassifier
        from dask_ml_tpu.wrappers import Incremental

        rng = np.random.RandomState(11)
        Xcsr = sp.random(20_000, 512, density=0.05, format="csr",
                         random_state=rng)
        rowsum = np.asarray(Xcsr.sum(axis=1)).ravel()
        ycsr = (rowsum > np.median(rowsum)).astype(np.float32)
        with config.set(stream_block_rows=4096):
            spc = LogisticRegression(solver="lbfgs", max_iter=20).fit(
                Xcsr, ycsr
            )
        assert np.isfinite(spc.coef_).all()
        clf = LogisticRegression(solver="lbfgs", max_iter=20).fit(X, y)
        auc = get_scorer("roc_auc")(clf, X, y)
        assert 0.5 < auc <= 1.0, auc
        yh, ph = y.to_numpy(), clf.predict(X)
        import sklearn.metrics as skm

        assert abs(f1_score(yh, ph) - skm.f1_score(yh, ph)) < 1e-6
        df = clf.decision_function(X)
        assert abs(roc_auc_score(yh, df) - skm.roc_auc_score(yh, df)) \
            < 1e-5
        with config.set(dtype="bfloat16"):
            km16 = KMeans(n_clusters=4, random_state=0, max_iter=5,
                          use_pallas=False).fit(X)
            assert np.isfinite(km16.cluster_centers_).all()
            inc = Incremental(SGDClassifier(max_iter=1, random_state=0),
                              shuffle_blocks=False)
            inc.fit(X, y)
            assert np.isfinite(inc.estimator_.coef_).all()
        # streamed SGD with overlap stats on host blocks
        Xh2 = np.asarray(X.to_numpy(), np.float32)
        s2 = SGDClassifier(max_iter=2, random_state=0, shuffle=False)
        s2.fit(Xh2, y.to_numpy())
        st = s2._last_stream_stats
        assert st and st["pass_s"] > 0

    def multiclass_round4():
        """Round-4 surfaces: multiclass in-core AND streamed OvR GLM,
        multiclass SGD submesh trials, OneHotEncoder(drop), sketched
        QuantileTransformer subsample — all Mosaic-lowered here."""
        from dask_ml_tpu import config
        from dask_ml_tpu.linear_model import (
            LogisticRegression, SGDClassifier,
        )
        from dask_ml_tpu.model_selection import IncrementalSearchCV
        from dask_ml_tpu.preprocessing import (
            OneHotEncoder, QuantileTransformer,
        )

        Xm, ym = datasets.make_classification(
            n_samples=6000, n_features=16, n_classes=3, n_informative=8,
            random_state=3,
        )
        clf = LogisticRegression(solver="lbfgs", max_iter=40).fit(Xm, ym)
        assert clf.coef_.shape == (3, 16)
        if jax.default_backend() == "tpu":
            # auto-gate: the multi-target fused kernel (one X pass for
            # all classes) must have carried the compiled solve
            assert clf.solver_info_.get("fused_multi") is True, \
                clf.solver_info_
        lp = clf.predict_log_proba(Xm)
        assert lp.shape == (6000, 3) and (lp <= 0).all()
        Xh, yh = Xm.to_numpy(), ym.to_numpy()
        with config.set(stream_block_rows=1500):
            st = LogisticRegression(solver="lbfgs", max_iter=40).fit(Xh, yh)
        assert st.solver_info_.get("n_classes") == 3
        assert np.mean(st.predict(Xh) == clf.predict(Xh)) > 0.98
        s = IncrementalSearchCV(
            SGDClassifier(random_state=0), {"alpha": [1e-4, 1e-3]},
            n_initial_parameters="grid", decay_rate=None, max_iter=3,
            random_state=0,
        )
        s.fit(Xm, ym, classes=[0.0, 1.0, 2.0])
        assert s.best_estimator_.coef_.shape == (3, 16)
        Xcat = np.array([[0.0, 1.0], [1.0, 2.0], [0.0, 1.0]])
        o = OneHotEncoder(drop="first").fit(Xcat)
        assert o.transform(Xcat).shape == (3, 2)
        QuantileTransformer(n_quantiles=50, subsample=3000,
                            random_state=0).fit_transform(Xm)
        # fused GLM value+grad Pallas kernel: on TPU the auto-gate runs
        # it COMPILED in every smooth-solver fit above; assert parity
        # against the XLA loss explicitly
        interp = jax.default_backend() != "tpu"  # CPU dry-runs interpret
        # pinned f32: this 5e-3 parity band is the f32 kernels' — the
        # "auto" policy would run both fits bf16 on TPU and compare
        # bf16 rounding noise against it
        xla = LogisticRegression(solver="lbfgs", max_iter=30, tol=1e-8,
                                 fit_dtype="float32",
                                 solver_kwargs={"use_pallas": False})
        pal = LogisticRegression(solver="lbfgs", max_iter=30, tol=1e-8,
                                 fit_dtype="float32",
                                 solver_kwargs={"use_pallas": True,
                                                "pallas_interpret": interp})
        yb2 = (ym.to_numpy() > 1).astype(np.float32)
        xla.fit(Xm, yb2)
        pal.fit(Xm, yb2)
        assert np.allclose(pal.coef_, xla.coef_, atol=5e-3), (
            np.abs(pal.coef_ - xla.coef_).max()
        )

    def fused_stream_round8():
        """ISSUE 8 surfaces on the real chip: the stacked-lax.scan
        super-block flavor (ROADMAP item 1 flags it as never run on
        real hardware — on TPU it IS the streamed layout), the fused
        Pallas streamed kernels (pallas.sgd_step / pallas.glm_* /
        pallas.kmeans_stream engage via the auto-gate at 128-multiple
        block heights), the bf16 "auto" default fit path, and the int8
        serving flavor — all at tiny shapes so Mosaic lowering and
        parity are exercised in seconds."""
        import dask_ml_tpu.config as config
        from dask_ml_tpu.cluster import KMeans
        from dask_ml_tpu.linear_model import LogisticRegression
        from dask_ml_tpu.models.sgd import SGDClassifier
        from dask_ml_tpu.ops.pallas_fused import use_stream_kernels
        from dask_ml_tpu.wrappers import compiled_batch_fn

        on_tpu = jax.default_backend() == "tpu"
        rng = np.random.RandomState(8)
        Xh = rng.randn(16_384, 32).astype(np.float32)
        yh = (Xh[:, 0] > 0).astype(np.float32)
        # bf16 "auto" default: on TPU the policy must resolve to bf16
        if on_tpu:
            assert config.mxu_dtype() is not None, \
                "auto dtype policy did not resolve to bf16 on TPU"
        # 2048-row blocks: a 128-multiple, so the fused kernels' grid
        # gate passes and the stacked (K, S, d) scan flavor runs
        with config.set(stream_block_rows=2048):
            assert use_stream_kernels() == on_tpu
            sgd = SGDClassifier(max_iter=2, random_state=0,
                                shuffle=False).fit(Xh, yh)
            assert np.isfinite(sgd.coef_).all()
            assert sgd.score(Xh, yh) > 0.7
            st = dict(sgd._last_stream_stats or {})
            assert st.get("superblock_k", 0) > 1, st
            glm = LogisticRegression(solver="lbfgs",
                                     max_iter=20).fit(Xh, yh)
            assert np.isfinite(glm.coef_).all()
            if on_tpu:
                assert glm.solver_info_.get("fused_stream") is True, \
                    glm.solver_info_
            km = KMeans(n_clusters=4, random_state=0, max_iter=5,
                        init="random").fit(Xh)
            assert np.isfinite(km.cluster_centers_).all()
        # parity vs the per-block XLA path on the same partition
        with config.set(stream_block_rows=2048, superblock_k=1,
                        pallas_stream=False, dtype="float32"):
            ref = SGDClassifier(max_iter=2, random_state=0,
                                shuffle=False).fit(Xh, yh)
        assert np.mean(sgd.predict(Xh) == ref.predict(Xh)) > 0.99
        # int8 serving flavor compiles + agrees on the real chip
        q8 = compiled_batch_fn(glm, "predict", quantize="int8")
        f32 = compiled_batch_fn(glm, "predict")
        assert np.mean(q8(Xh[:4096]) == f32(Xh[:4096])) >= 0.995

    def sharded_stream_round9():
        """ISSUE 9 surfaces on a real multi-chip slice: the streamed
        superblock hot loop sharded over the mesh — per-shard staging,
        shard_map/psum scan programs, replicated carries. Parity vs
        the single-chip path to 1e-5 (bf16 stays off: f32 pin) and
        per-chip throughput within 0.8x of single-chip — the
        data-parallel plumbing must not eat the chip it runs on. On a
        1-chip attach (or the CPU dry-run) the sharded flavor must
        simply never engage."""
        import time as _time

        from dask_ml_tpu import config
        from dask_ml_tpu.cluster import KMeans
        from dask_ml_tpu.linear_model import LogisticRegression
        from dask_ml_tpu.models.sgd import SGDClassifier

        n_dev = len(jax.devices())
        rng = np.random.RandomState(9)
        n, d = 131_072, 64
        Xh = rng.randn(n, d).astype(np.float32)
        yh = (Xh[:, 0] > 0).astype(np.float32)
        # 2048-row blocks: a 128-multiple (single-chip fused kernels)
        # that also splits per shard on any power-of-two slice
        base = dict(stream_block_rows=2048, stream_autotune=False,
                    dtype="float32")

        def timed_fit(stream_mesh):
            with config.set(stream_mesh=stream_mesh, **base):
                SGDClassifier(max_iter=1, random_state=0,
                              shuffle=False).fit(Xh, yh)  # warm
                clf = SGDClassifier(max_iter=2, random_state=0,
                                    shuffle=False)
                t0 = _time.perf_counter()
                clf.fit(Xh, yh)
                return clf, _time.perf_counter() - t0

        single, t1 = timed_fit(1)
        st1 = dict(single._last_stream_stats or {})
        assert st1.get("sb_shards", 1) == 1, st1
        if n_dev == 1:
            return  # nothing to shard on a 1-chip attach
        sharded, tN = timed_fit(0)
        stN = dict(sharded._last_stream_stats or {})
        assert stN.get("sb_shards") == n_dev, stN
        # one dispatch per super-block, never per shard
        assert stN["dispatches_per_pass"] == \
            -(-stN["n_blocks"] // stN["superblock_k"]), stN
        # parity: same minibatches, psum-reassociated float sums only
        assert np.allclose(sharded.coef_, single.coef_, atol=1e-5), \
            np.abs(sharded.coef_ - single.coef_).max()
        # GLM reducer + KMeans assign-stats flavors run + agree
        with config.set(stream_mesh=0, **base):
            glm = LogisticRegression(solver="lbfgs",
                                     max_iter=15).fit(Xh, yh)
            assert glm.solver_info_.get("stream_shards") == n_dev, \
                glm.solver_info_
            km = KMeans(n_clusters=4, random_state=0, max_iter=5,
                        init="random").fit(Xh)
            assert np.isfinite(km.cluster_centers_).all()
        with config.set(stream_mesh=1, **base):
            glm1 = LogisticRegression(solver="lbfgs",
                                      max_iter=15).fit(Xh, yh)
        assert np.allclose(glm.coef_, glm1.coef_, atol=1e-4), \
            np.abs(glm.coef_ - glm1.coef_).max()
        if jax.default_backend() != "tpu":
            return  # forced virtual devices share silicon: parity and
            # dispatch shape hold above, but the per-chip throughput
            # criterion is a real-chip claim
        # scaling: per-chip throughput within 0.8x of single-chip
        per_chip = (n * 2 / tN) / n_dev
        single_chip = n * 2 / t1
        assert per_chip >= 0.8 * single_chip, (
            f"sharded per-chip throughput {per_chip:.0f} samples/s < "
            f"0.8x single-chip {single_chip:.0f}"
        )
        print(f"    round-9: {n_dev} chips, single {single_chip:.0f} "
              f"samples/s, sharded {per_chip:.0f} samples/s/chip")

    def chaos_round10():
        """ISSUE 11 surfaces on real hardware: one injected-fault
        streamed resume and one supervised replica restart. Auto-
        degrades like round-9 — every leg runs identically on a 1-chip
        attach (thread replicas; the sharded flavor simply never
        engages), so the round gates correctness, not scale."""
        import tempfile
        import time as _time

        from dask_ml_tpu import config
        from dask_ml_tpu.models.sgd import SGDClassifier
        from dask_ml_tpu.observability import (counters_reset,
                                               counters_snapshot)
        from dask_ml_tpu.reliability import FaultInjected, reset_plans
        from dask_ml_tpu.serving.fleet import FleetServer

        rng = np.random.RandomState(11)
        n, d = 65_536, 32
        Xh = rng.randn(n, d).astype(np.float32)
        yh = (Xh[:, 0] > 0).astype(np.float32)
        base = dict(stream_block_rows=2048, stream_autotune=False,
                    dtype="float32")
        # (a) injected staging IO fault absorbed by retry, bit-parity
        counters_reset()
        reset_plans()
        with config.set(**base):
            clean = SGDClassifier(max_iter=2, random_state=0,
                                  shuffle=True).fit(Xh, yh)
        with config.set(fault_plan="staging_read:io@5",
                        stream_io_retries=3, **base):
            faulted = SGDClassifier(max_iter=2, random_state=0,
                                    shuffle=True).fit(Xh, yh)
        assert counters_snapshot().get("stream_retries", 0) >= 1
        assert np.allclose(faulted.coef_, clean.coef_, atol=1e-6)
        # (b) kill-mid-pass resume parity (crash at the dispatch
        # boundary, then rerun with the same knobs auto-resumes)
        tmp = tempfile.mkdtemp(prefix="tpu_chaos_")
        reset_plans()
        n_sb = -(-((n + 2047) // 2048) // 8)   # dispatches per pass
        with config.set(stream_checkpoint_path=tmp,
                        fault_plan=f"superblock_dispatch:crash@{n_sb}",
                        **base):
            try:
                SGDClassifier(max_iter=2, random_state=0,
                              shuffle=True).fit(Xh, yh)
                raise AssertionError("injected crash never fired")
            except FaultInjected:
                pass
        reset_plans()
        with config.set(stream_checkpoint_path=tmp, **base):
            resumed = SGDClassifier(max_iter=2, random_state=0,
                                    shuffle=True).fit(Xh, yh)
        assert counters_snapshot().get("stream_resumes", 0) >= 1
        assert np.allclose(resumed.coef_, clean.coef_, atol=1e-6), \
            np.abs(resumed.coef_ - clean.coef_).max()
        # (c) supervised replica restart under live traffic
        counters_reset()
        reset_plans()
        with config.set(serving_min_batch=8, serving_max_batch=64,
                        serving_supervise=True, obs_drift=False,
                        serving_supervise_interval_s=0.1,
                        fault_plan="replica_worker:crash@60",
                        dtype="float32"):
            fleet = FleetServer(clean, replicas=2,
                                timeout_ms=20000).warmup()
            with fleet:
                served = 0
                deadline = _time.time() + 60
                while _time.time() < deadline:
                    p = fleet.predict(Xh[: int(rng.randint(1, 64))])
                    served += len(p)
                    snap = counters_snapshot()
                    if snap.get("serving_replica_restarts", 0) >= 1 \
                            and sum(1 for r in fleet.replicas
                                    if r.healthy) == 2:
                        break
                assert counters_snapshot().get(
                    "serving_replica_restarts", 0) >= 1, \
                    counters_snapshot()
                assert len(fleet.predict(Xh[:32])) == 32
        print(f"    round-10: resume parity "
              f"{np.abs(resumed.coef_ - clean.coef_).max():.1e}, "
              f"retries absorbed, replica restarted under load")

    def fused_sharded_round11():
        """ISSUE 12 surfaces: the fused Pallas kernels INSIDE the
        shard_map scan programs (real multi-chip: compiled Mosaic; the
        parity legs also run on a 1-chip attach, where the sharded
        flavor simply never engages and the fused single-device flavor
        carries them), plus the grad-accum streamed-SGD flavor.
        Criteria: fused x sharded parity vs the unfused sharded flavor,
        fused actually ENGAGED (solver_info_ reasons, not just absence
        of errors), per-chip throughput >= the unfused sharded flavor,
        and grad-accum A=1 exactly matching the sequential fit."""
        import time as _time

        from dask_ml_tpu import config
        from dask_ml_tpu.cluster import KMeans
        from dask_ml_tpu.linear_model import LogisticRegression
        from dask_ml_tpu.models.sgd import SGDClassifier

        on_tpu = jax.default_backend() == "tpu"
        n_dev = len(jax.devices())
        rng = np.random.RandomState(12)
        n, d = 131_072, 64
        Xh = rng.randn(n, d).astype(np.float32)
        yh = (Xh[:, 0] > 0).astype(np.float32)
        # 2048-row blocks divide into 128-multiple slabs on any
        # power-of-two slice up to 16 chips
        base = dict(stream_block_rows=2048, stream_autotune=False,
                    dtype="float32", stream_mesh=0)
        interp = {} if on_tpu else {"pallas_stream_interpret": True}

        def timed_sgd(**kw):
            with config.set(**base, **kw):
                SGDClassifier(max_iter=1, random_state=0,
                              shuffle=False).fit(Xh, yh)  # warm
                clf = SGDClassifier(max_iter=2, random_state=0,
                                    shuffle=False)
                t0 = _time.perf_counter()
                clf.fit(Xh, yh)
                return clf, _time.perf_counter() - t0

        fused, t_f = timed_sgd(**interp)
        plain, t_p = timed_sgd(pallas_stream=False)
        info = dict(fused.solver_info_)
        assert info.get("fused_stream") is True, info
        assert info.get("fused_stream_reason") is None, info
        st = dict(fused._last_stream_stats or {})
        assert st.get("sb_shards") == n_dev, st
        assert st["dispatches_per_pass"] == \
            -(-st["n_blocks"] // st["superblock_k"]), st
        assert np.allclose(fused.coef_, plain.coef_, atol=1e-5), \
            np.abs(fused.coef_ - plain.coef_).max()
        # GLM + KMeans fused x sharded flavors run + agree + engage
        with config.set(**base, **interp):
            glm = LogisticRegression(solver="lbfgs",
                                     max_iter=15).fit(Xh, yh)
            assert glm.solver_info_.get("fused_stream") is True, \
                glm.solver_info_
            km = KMeans(n_clusters=4, random_state=0, max_iter=5,
                        init="random").fit(Xh)
        with config.set(**base, pallas_stream=False):
            glm0 = LogisticRegression(solver="lbfgs",
                                      max_iter=15).fit(Xh, yh)
            km0 = KMeans(n_clusters=4, random_state=0, max_iter=5,
                         init="random").fit(Xh)
        assert np.allclose(glm.coef_, glm0.coef_, atol=1e-4), \
            np.abs(glm.coef_ - glm0.coef_).max()
        assert np.allclose(np.sort(km.cluster_centers_, axis=0),
                           np.sort(km0.cluster_centers_, axis=0),
                           atol=1e-4)
        # grad-accum flavor: A=1 exactly the sequential fit (bit-exact
        # vs the single-device sequential flavor — the sharded scan
        # normalizes after its psum, so exactness pins stream_mesh=1);
        # A=2 sane
        ga = dict(base, stream_mesh=1)
        with config.set(**ga):
            seq = SGDClassifier(max_iter=2, random_state=0,
                                shuffle=False).fit(Xh, yh)
        with config.set(**ga, stream_grad_accum=1):
            a1 = SGDClassifier(max_iter=2, random_state=0,
                               shuffle=False).fit(Xh, yh)
        assert a1.solver_info_.get("grad_accum") == 1
        assert np.array_equal(a1.coef_, seq.coef_), \
            np.abs(a1.coef_ - seq.coef_).max()
        with config.set(**ga, stream_grad_accum=2):
            a2 = SGDClassifier(max_iter=2, random_state=0,
                               shuffle=False).fit(Xh, yh)
        # documented tolerance: larger effective batch, same model to
        # ~10% relative (predict would re-stage on the full mesh
        # against the stream_mesh=1-committed weights, so compare coef)
        assert np.isfinite(a2.coef_).all()
        assert np.abs(a2.coef_ - seq.coef_).max() \
            <= 0.1 * max(np.abs(seq.coef_).max(), 1e-6)
        if not on_tpu:
            return  # interpreter-speed kernels: throughput claims are
            # real-chip claims only
        # the fused bodies must not be SLOWER than the XLA bodies they
        # replace (per-chip throughput >= the unfused sharded flavor)
        assert t_f <= t_p * 1.05, (
            f"fused sharded pass slower than unfused: {t_f:.3f}s vs "
            f"{t_p:.3f}s"
        )
        print(f"    round-11: {n_dev} chips, fused "
              f"{n * 2 / t_f:.0f} rows/s vs unfused "
              f"{n * 2 / t_p:.0f} rows/s, grad-accum A=1 exact")

    def sparse_stream_round12():
        """ISSUE 13 surfaces: device-resident bucketed-nnz sparse
        streaming on real chips — the superblock.sparse.* scan programs
        (single-chip AND sharded: a >1-chip attach stages per-shard nnz
        segments and psums once per super-block), the serving
        (rows, nnz) grid, and the >= 2x-vs-densify claim at the
        hashed-text shape. Degrades to a 1-chip attach like rounds
        9/10/11 (the sharded flavor simply never engages)."""
        import time as _time

        import scipy.sparse as sp_

        from dask_ml_tpu import config
        from dask_ml_tpu.linear_model import LogisticRegression
        from dask_ml_tpu.models.sgd import SGDClassifier
        from dask_ml_tpu.serving import ModelServer

        on_tpu = jax.default_backend() == "tpu"
        n_dev = len(jax.devices())
        rng = np.random.RandomState(13)
        n, d = 65_536, 2 ** 14
        npr = d // 100                        # density ~1%
        indices = rng.randint(0, d, size=n * npr).astype(np.int32)
        data = rng.rand(n * npr).astype(np.float32)
        indptr = np.arange(0, n * npr + 1, npr, dtype=np.int64)
        Xs = sp_.csr_matrix((data, indices, indptr), shape=(n, d))
        eta = Xs @ rng.randn(d).astype(np.float32)
        yh = (eta > np.median(eta)).astype(np.float64)
        base = dict(stream_block_rows=2048, stream_autotune=False,
                    dtype="float32", stream_mesh=0)

        def timed(sparse_on):
            with config.set(**base, stream_sparse=sparse_on):
                SGDClassifier(max_iter=1, random_state=0,
                              shuffle=False).fit(Xs, yh)  # warm
                clf = SGDClassifier(max_iter=2, random_state=0,
                                    shuffle=False)
                t0 = _time.perf_counter()
                clf.fit(Xs, yh)
                return clf, _time.perf_counter() - t0

        sp_clf, t_s = timed(True)
        info = dict(sp_clf.solver_info_)
        assert info.get("sparse_stream") is True, info
        assert info.get("sparse_stream_reason") is None, info
        st = dict(sp_clf._last_stream_stats or {})
        assert st.get("sb_shards") == n_dev, st
        assert st["dispatches_per_pass"] == \
            -(-st["n_blocks"] // st["superblock_k"]), st
        dn_clf, t_d = timed(False)
        assert np.allclose(sp_clf.coef_, dn_clf.coef_, atol=1e-5), \
            np.abs(sp_clf.coef_ - dn_clf.coef_).max()
        # GLM sparse reducers agree with the densify path
        with config.set(**base, stream_sparse=True):
            glm = LogisticRegression(solver="gradient_descent",
                                     max_iter=3).fit(Xs, yh)
            assert glm.solver_info_.get("sparse_stream") is True, \
                glm.solver_info_
        # serving (rows, nnz) grid: warmed sparse predictions agree
        with config.set(serving_min_batch=8, serving_max_batch=256,
                        serving_sparse_nnz_per_row=2 * npr):
            srv = ModelServer(sp_clf, methods=("predict",))
            srv.warmup()
            srv.warmup_sparse()
            with srv:
                q = Xs[:100].tocsr()
                got = srv.submit(q, method="predict").result(60)
            want = sp_clf.predict(q.toarray())
            assert np.array_equal(got, want)
        if on_tpu:
            assert t_s * 2 <= t_d, (
                f"sparse streamed SGD {t_s:.3f}s not >= 2x faster than "
                f"densify {t_d:.3f}s at density ~1%, d=2**14"
            )
        print(f"    round-12: {n_dev} chips, sparse "
              f"{n * 2 / t_s:.0f} rows/s vs densify "
              f"{n * 2 / t_d:.0f} rows/s "
              f"({t_d / t_s:.2f}x), serving grid OK")

    def search_round13():
        """ISSUE 14 surfaces: the adaptive-search cohort as a client
        of the streamed superblock plane on real chips — one
        BlockStream pass per round (slot-rung cohort scans, sharded
        psum twins on >1-chip attaches, fused Pallas cohort bodies
        engaged), score parity with the device-resident cohort path on
        the same partition, and the >= 2x wall-clock claim measured
        where it belongs (on-chip HBM copies vs zero re-staging).
        Degrades to a 1-chip attach like rounds 8-12."""
        import time as _time

        from dask_ml_tpu import config
        from dask_ml_tpu.model_selection import HyperbandSearchCV
        from dask_ml_tpu.models.sgd import SGDClassifier

        on_tpu = jax.default_backend() == "tpu"
        n_dev = len(jax.devices())
        rng = np.random.RandomState(14)
        n, d = 262_144, 128
        X = rng.randn(n, d).astype(np.float32)
        yh = (X[:, 0] + 0.5 * rng.randn(n) > 0).astype(np.float64)
        params = {"alpha": [1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 1e-2],
                  "eta0": [0.01, 0.03, 0.05, 0.1, 0.3, 0.5]}
        # 2048-row blocks -> 128-multiple per-shard slabs on
        # power-of-two slices: the fused cohort tile gate passes
        base = dict(stream_block_rows=2048, stream_autotune=False,
                    dtype="float32", stream_mesh=0)

        def timed(streamed):
            with config.set(**base, search_stream=streamed):
                def run():
                    h = HyperbandSearchCV(
                        SGDClassifier(tol=1e-3, random_state=0),
                        params, max_iter=27, aggressiveness=3,
                        random_state=0,
                    )
                    h.fit(X, yh, classes=[0.0, 1.0])
                    return h

                run()                      # warm
                t0 = _time.perf_counter()
                h = run()
                return h, _time.perf_counter() - t0

        hs, t_s = timed(True)
        meta = hs.metadata_["stream"]
        assert meta["streamed"] is True, meta
        assert meta["shards"] == n_dev, meta
        if on_tpu:
            # the fused Pallas cohort bodies (pallas.sgd_cohort[.psum])
            # must ENGAGE on chips at these block shapes
            assert meta["fused"] is True, meta
        hd, t_d = timed(False)
        key = lambda r: (r["model_id"], r["partial_fit_calls"])  # noqa: E731
        a = np.asarray([r["score"] for r in
                        sorted(hs.history_, key=key)])
        b = np.asarray([r["score"] for r in
                        sorted(hd.history_, key=key)])
        assert a.shape == b.shape and np.abs(a - b).max() <= 1e-6, \
            np.abs(a - b).max()
        assert hs.best_params_ == hd.best_params_
        if on_tpu:
            assert t_s * 2 <= t_d, (
                f"streamed-cohort Hyperband {t_s:.3f}s not >= 2x "
                f"faster than the device-resident cohort path "
                f"{t_d:.3f}s on {n_dev} chips"
            )
        # sparse cohort engagement: the search must ride the
        # bucketed-nnz scans without densify
        import scipy.sparse as sp_

        Xsp = sp_.random(65_536, 2 ** 12, density=0.01, format="csr",
                         random_state=rng, dtype=np.float64)
        ssum = np.asarray(Xsp.sum(axis=1)).ravel()
        ysp = (ssum > np.median(ssum)).astype(np.float64)
        with config.set(**base):
            hsp = HyperbandSearchCV(
                SGDClassifier(tol=1e-3, random_state=0), params,
                max_iter=9, aggressiveness=3, random_state=0,
            )
            hsp.fit(Xsp, ysp, classes=[0.0, 1.0])
        assert hsp.metadata_["stream"]["sparse"] is True, \
            hsp.metadata_["stream"]
        print(f"    round-13: {n_dev} chips, streamed bracket "
              f"{t_s:.3f}s vs device-resident {t_d:.3f}s "
              f"({t_d / t_s:.2f}x), fused={meta['fused']}, "
              f"sparse cohort OK")

    def plans_round14():
        """ISSUE 15 surfaces: the plans subsystem on real chips — a
        plan-built serving grid, a C-grid search and a streamed fit all
        warmed in ONE process pay zero XLA compiles afterward (the
        cross-client contract perf_smoke gates on CPU), donation is
        honored on the serving path (TPU donates the batch operand),
        and the plans table renders with ladder:rung attribution.
        Degrades to a 1-chip attach like rounds 8-13."""
        from dask_ml_tpu import config, plans
        from dask_ml_tpu import observability as obs
        from dask_ml_tpu.linear_model import LogisticRegression
        from dask_ml_tpu.model_selection import GridSearchCV
        from dask_ml_tpu.models.sgd import SGDClassifier
        from dask_ml_tpu.serving import BucketLadder, ModelServer

        on_tpu = jax.default_backend() == "tpu"
        n_dev = len(jax.devices())
        rng = np.random.RandomState(15)
        n, d = 65_536, 64
        X = rng.randn(n, d).astype(np.float32)
        yh = (X[:, 0] > 0).astype(np.float64)

        def run_search():
            GridSearchCV(
                LogisticRegression(solver="lbfgs", max_iter=5,
                                   tol=0.0),
                {"C": [0.1, 1.0, 10.0]}, cv=2, refit=False,
                scheduler="synchronous",
            ).fit(X, yh)

        with config.set(stream_block_rows=4096, stream_autotune=False,
                        dtype="float32", stream_mesh=0):
            clf = SGDClassifier(max_iter=2, random_state=0,
                                shuffle=False)
            clf.fit(X, yh)             # warms the streamed scans
            run_search()               # warms the stacked solves
            srv = ModelServer(clf, methods=("predict",),
                              ladder=BucketLadder(8, 256, 2.0),
                              batch_window_ms=1.0, timeout_ms=0)
            srv.warmup()               # warms the serving grid
            obs.counters_reset()
            with srv:
                SGDClassifier(max_iter=2, random_state=0,
                              shuffle=False).fit(X, yh)
                run_search()
                r2 = np.random.RandomState(7)
                for _ in range(30):
                    k = r2.randint(1, 256)
                    i = r2.randint(0, n - k)
                    srv.predict(X[i:i + k])
            snap = obs.counters_snapshot()
        assert snap.get("recompiles", 0) == 0, snap.get("recompiles")
        if on_tpu:
            # the plan layer wired batch donation (TPU/GPU only)
            assert snap.get("donated_buffers_reused", 0) > 0, snap
        rows = {r["program"]: r for r in plans.plans_snapshot()}
        srow = rows.get("serving.SGDClassifier.predict")
        assert srow and srow["warmups"] >= 1 \
            and srow["ladder"] == "serving-rows" \
            and "256" in srow["rungs"], srow
        assert "glm.lbfgs_lam_grid" in rows, sorted(rows)
        print(f"    round-14: {n_dev} chips, cross-client "
              f"recompiles=0, plans table rows={len(rows)}, "
              f"serving rungs {srow['rungs']}")

    def mesh2d_round15():
        """ISSUE 18 surfaces: 2-D ("data", "model") hybrid meshes on
        real chips — hybrid-mesh bring-up, feature-sharded GLM parity
        vs the 1-D path, streamed randomized PCA parity vs the
        resident solve, and the Dx1 auto-degrade that keeps
        single-slice attaches on the untouched 1-D programs. Degrades
        to a 1-chip (or odd) attach like rounds 8-14."""
        from dask_ml_tpu import config
        from dask_ml_tpu.linear_model import LogisticRegression
        from dask_ml_tpu.models.pca import PCA
        from dask_ml_tpu.parallel.mesh import (
            DATA_AXIS, MODEL_AXIS, data_shards, default_mesh,
            mesh_str, model_shards, stream_data_mesh,
        )

        n_dev = len(jax.devices())
        rng = np.random.RandomState(18)

        # Dx1 auto-degrade: a trivial model axis must resolve to the
        # SAME cached 1-D mesh object — single-slice attaches stay on
        # the byte-identical 1-D programs
        with config.set(stream_mesh=0, mesh_shape=f"{n_dev}x1"):
            m_deg = stream_data_mesh()
        assert m_deg is default_mesh(), (m_deg, default_mesh())
        assert model_shards(m_deg) == 1

        if n_dev < 2 or n_dev % 2:
            print(f"    round-15: {n_dev} chip(s) — 1-D auto-degrade "
                  "verified; 2-D bring-up needs an even multi-chip "
                  "attach")
            return

        # hybrid-mesh bring-up: ("data", "model") axes over the real
        # chips (multi-slice topologies route through
        # create_hybrid_device_mesh inside device_mesh's topology
        # arranging — DCN outer on the data axis, ICI inner)
        with config.set(stream_mesh=0, mesh_shape="-1x2"):
            m2 = stream_data_mesh()
        assert m2.axis_names == (DATA_AXIS, MODEL_AXIS), m2.axis_names
        assert model_shards(m2) == 2
        assert data_shards(m2) == n_dev // 2
        shape = mesh_str(m2)

        # feature-sharded GLM parity vs the 1-D path
        n, d = 32_768, 64
        Xg = rng.randn(n, d).astype(np.float32)
        yg = (Xg[:, 0] > 0).astype(np.float64)
        fits = {}
        for label, knobs in (
            ("1d", dict(stream_mesh=1)),
            ("2d", dict(stream_mesh=0, mesh_shape="-1x2")),
        ):
            with config.set(stream_block_rows=4096,
                            stream_autotune=False, dtype="float32",
                            **knobs):
                fits[label] = LogisticRegression(
                    solver="lbfgs", max_iter=15).fit(Xg, yg)
        drift = np.abs(np.asarray(fits["2d"].coef_, np.float64)
                       - np.asarray(fits["1d"].coef_, np.float64)).max()
        assert drift <= 5e-4, drift

        # streamed randomized PCA parity vs the resident solve
        # (decaying spectrum so the range capture is well-posed)
        u = np.linalg.qr(rng.standard_normal((4096, d)))[0]
        v = np.linalg.qr(rng.standard_normal((d, d)))[0]
        sv = 100.0 * (0.7 ** np.arange(d))
        Xs = ((u * sv) @ v.T
              + 0.01 * rng.standard_normal((4096, d))
              + 1.5).astype(np.float32)
        with config.set(stream_block_rows=512, stream_autotune=False,
                        dtype="float32", stream_mesh=0,
                        mesh_shape="-1x2"):
            stp = PCA(n_components=8, svd_solver="randomized",
                      random_state=0).fit(Xs)
        res = PCA(n_components=8, svd_solver="full").fit(Xs)
        np.testing.assert_allclose(
            np.asarray(stp.singular_values_),
            np.asarray(res.singular_values_), rtol=1e-3,
        )
        align = np.linalg.svd(
            np.asarray(stp.components_, np.float64)
            @ np.asarray(res.components_, np.float64).T,
            compute_uv=False,
        )
        assert align.min() > 1 - 1e-4, align
        print(f"    round-15: mesh {shape}, GLM 1-D/2-D coef drift "
              f"{drift:.2e}, streamed PCA parity vs resident OK")

    def fleet_obs_round16():
        """ISSUE 19 surfaces: fleet-scope observability on real chips
        — cross-process trace propagation over a federated fleet
        (every routed request is ONE trace: router leg + full-stage
        worker leg on the same id), the federated
        ``dask_ml_tpu_fleet_*`` /metrics families off the shared
        status scrape, and ZERO post-warmup recompiles with the whole
        plane on. Runs a 2-process (virtual transport) fleet; degrades
        to 1 process on a 1-chip attach."""
        from dask_ml_tpu import config, observability as obs
        from dask_ml_tpu.linear_model import LogisticRegression
        from dask_ml_tpu.observability import _requests as rtrace
        from dask_ml_tpu.observability.live import render_prometheus
        from dask_ml_tpu.serving import (
            BucketLadder, FederatedFleet, FleetServer, LocalEndpoint,
        )

        n_dev = len(jax.devices())
        n_proc = 2 if n_dev >= 2 else 1
        rng = np.random.RandomState(19)
        n, d = 8192, 32
        Xf = rng.randn(n, d).astype(np.float32)
        yf = (Xf[:, 0] > 0).astype(np.float64)
        clf = LogisticRegression(solver="lbfgs", max_iter=15).fit(Xf, yf)
        ladder = BucketLadder(8, 256, 2.0)
        rtrace.traces_reset()
        with config.set(obs_trace_sample=1.0, obs_fleet_federate=True):
            fleets = [
                FleetServer(clf, name="smoke16", replicas=1,
                            ladder=ladder, batch_window_ms=1.0,
                            timeout_ms=0).warmup().start()
                for _ in range(n_proc)
            ]
            try:
                eps = [LocalEndpoint(f, f"p{i}")
                       for i, f in enumerate(fleets)]
                with FederatedFleet(eps, name="smoke16", ladder=ladder,
                                    poll_s=0.2) as fed:
                    c0 = obs.counters_snapshot().get("recompiles", 0)
                    for _ in range(16):
                        k = rng.randint(1, 200)
                        j = rng.randint(0, n - k)
                        fed.predict(Xf[j:j + k])
                    recompiles = obs.counters_snapshot() \
                        .get("recompiles", 0) - c0
                    assert recompiles == 0, recompiles
                    recs = rtrace.traces_data()["traces"]
                    router = [r for r in recs
                              if r.get("federation") == "smoke16"]
                    assert len(router) == 16, len(router)
                    for rt in router:
                        legs = [r for r in recs
                                if r["trace_id"] == rt["trace_id"]
                                and r is not rt]
                        assert legs and {"queue_pop", "execute_done"} \
                            <= set(legs[0]["stages"]), (rt, legs)
                    fed._poll_once()
                    page = render_prometheus()
                    procs = [ln for ln in page.splitlines()
                             if ln.startswith(
                                 "dask_ml_tpu_fleet_processes ")]
                    assert procs \
                        and int(float(procs[0].split()[1])) == n_proc, \
                        procs
                    # LocalEndpoints federate no counters BY DESIGN
                    # (in-process endpoints share the router's own
                    # registry — shipping them would double-count;
                    # federation_smoke asserts the counter aggregate
                    # over real HTTP processes), so the built-in
                    # scrape gauge is the honest surface here
                    assert "dask_ml_tpu_fleet_scrape_seconds" in page
            finally:
                for f in fleets:
                    f.stop(drain=False)
        rtrace.traces_reset()
        print(f"    round-16: {n_proc}-process fleet, 16 routed "
              "traces all joined cross-process, federated /metrics "
              "OK, recompiles=0")

    def incidents_round17():
        """ISSUE 20 surfaces: the incident plane on real chips — a
        firing alert rule freezes one atomic bundle (open spans +
        registry snapshots + device memory of the actual TPUs), the
        engine's ticker pays ZERO XLA compiles, and
        ``incidents.deep_profile`` runs a REAL ``jax.profiler`` window
        into the incident dir on TPU (the no-op-with-reason contract
        is asserted off-TPU instead)."""
        import tempfile
        import time as _time

        from dask_ml_tpu import config, observability as obs
        from dask_ml_tpu.observability import alerts, incidents
        from dask_ml_tpu.observability.live import gauge_set

        workdir = tempfile.mkdtemp(prefix="tpu_smoke_incidents_")
        idir = os.path.join(workdir, "incidents")
        alerts.reset()
        incidents.reset()
        try:
            with config.set(
                obs_alert_rules="smoke17_depth:gauge>10",
                incident_dir=idir, obs_alert_interval_s=0.1,
                trace_dir=os.path.join(workdir, "trace"),
            ):
                assert alerts.ensure_engine() is not None
                c0 = obs.counters_snapshot().get("recompiles", 0)
                with obs.span("tpu_smoke.incident17"):
                    gauge_set("smoke17_depth", 99.0)
                    deadline = _time.time() + 15
                    while not (os.path.isdir(idir) and any(
                            f.startswith("incident_")
                            and f.endswith(".json")
                            for f in os.listdir(idir))):
                        assert _time.time() < deadline, "no bundle"
                        _time.sleep(0.05)
                assert "smoke17_depth:gauge>10.0" \
                    in alerts.alerts_data()["firing"]
                compiles = obs.counters_snapshot() \
                    .get("recompiles", 0) - c0
                assert compiles == 0, compiles
                bundle = incidents.load_bundles(idir)[0]
                assert bundle["reason"] == \
                    "alert:smoke17_depth:gauge>10.0", bundle["reason"]
                assert any(s["span"] == "tpu_smoke.incident17"
                           for s in bundle["open_spans"])
                assert bundle["config"]["fingerprint"]
                # device_memory froze the REAL per-chip gauges here
                devmem = bundle["device_memory"]
                assert isinstance(devmem, dict), devmem

                out = incidents.deep_profile(seconds=1)
                if jax.default_backend() == "tpu":
                    assert out["profiled"] is True, out
                    trace_files = [
                        os.path.join(dp, f)
                        for dp, _dn, fns in os.walk(out["log_dir"])
                        for f in fns
                    ]
                    assert trace_files, "profiler window wrote nothing"
                    profiled = (f"{out['seconds']}s window, "
                                f"{len(trace_files)} trace files")
                else:
                    assert out["profiled"] is False \
                        and "TPU" in out["reason"], out
                    profiled = "no-op off-TPU (reason documented)"
        finally:
            alerts.reset()
            incidents.reset()
        print(f"    round-17: alert fired -> 1 bundle "
              f"(open span + device memory frozen), recompiles=0, "
              f"deep profile: {profiled}")

    for name, fn in [
        ("glm solvers x3 families", glms),
        ("device sgd", sgd),
        ("kmeans (pallas)", kmeans),
        ("spectral clustering", spectral),
        ("pca/tsvd/ipca", decomposition),
        ("preprocessing scalers", preprocessing),
        ("naive bayes + imputer", naive_bayes_impute),
        ("pairwise metrics", metrics_pairwise),
        ("grid + hyperband search", search),
        ("wrappers + ensemble", wrappers_ensemble),
        ("block streaming", streaming),
        ("round-4 multiclass/drop/subsample", multiclass_round4),
        ("round-5 sparse/scorers/bf16/overlap", round5_surfaces),
        ("round-8 fused-stream/bf16-auto/int8", fused_stream_round8),
        ("round-9 sharded superblock streaming", sharded_stream_round9),
        ("round-10 chaos/resume/supervision", chaos_round10),
        ("round-11 fused-x-sharded + grad-accum", fused_sharded_round11),
        ("round-12 device-resident sparse streaming",
         sparse_stream_round12),
        ("round-13 streamed-cohort adaptive search", search_round13),
        ("round-14 execution plans (plans/)", plans_round14),
        ("round-15 2-D hybrid meshes", mesh2d_round15),
        ("round-16 fleet observability", fleet_obs_round16),
        ("round-17 incident plane", incidents_round17),
    ]:
        results.append(run(name, fn))

    failed = [(n, tb) for n, _, tb in results if tb is not None]
    print("-- surfaces --")
    for n, secs, tb in results:
        last = "" if tb is None else \
            " :: " + tb.strip().splitlines()[-1][:300]
        print(f"{'FAIL' if tb else 'OK  '} {n} ({secs:.1f}s){last}")
    print(f"{len(results) - len(failed)}/{len(results)} surfaces OK")
    if failed:
        out_dir = os.path.join(_ROOT, "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "tpu_smoke_failures.txt"),
                  "w") as f:
            for n, tb in failed:
                f.write(f"=== {n}\n{tb}\n")
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
