"""The plans subsystem (ISSUE 15): shape ladders, ProgramPlan cache
keying, the WarmupRegistry, jaxpr byte-identity for every migrated
client (serving dense/sparse/int8, the stacked C-grid/OvR solves, the
superblock scan builders), and the naive_bayes onboarding (streamed fit
+ warmed serving at zero steady-state compiles)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dask_ml_tpu import config, plans
from dask_ml_tpu import observability as obs
from dask_ml_tpu.plans import (GeometricLadder, NnzLadder, ProgramPlan,
                               SlotRungLadder, warmups)


# -- shape ladders -----------------------------------------------------------

def test_geometric_ladder_rungs_and_clamp():
    lad = GeometricLadder(8, 100, 2.0)
    assert lad.buckets == (8, 16, 32, 64, 100)   # top rung CLAMPS
    assert lad.rung_for(1) == 8
    assert lad.rung_for(17) == 32
    assert lad.rung_for(100) == 100
    assert lad.padding_for(17) == 15
    with pytest.raises(ValueError):
        lad.rung_for(101)                        # chunk, don't pad down
    with pytest.raises(ValueError):
        GeometricLadder(0, 10)
    with pytest.raises(ValueError):
        GeometricLadder(16, 8)
    with pytest.raises(ValueError):
        GeometricLadder(8, 64, growth=1.0)


def test_bucket_ladder_is_the_plans_geometric_ladder():
    from dask_ml_tpu.serving._buckets import BucketLadder

    lad = BucketLadder(8, 128, 2.0)
    assert isinstance(lad, GeometricLadder)
    assert lad.bucket_for(9) == lad.rung_for(9) == 16
    assert repr(lad).startswith("BucketLadder")


def test_nnz_ladder_never_clamps_to_observed_max():
    lad = NnzLadder(min_nnz=128, growth=2.0)
    # a corpus peaking at 5000 nnz stages at the PURE rung 8192 — never
    # the observed max (clamping would mint a shape per corpus)
    assert lad.rung_for(5000) == 8192
    assert lad.rung_for(1) == 128
    assert lad.rung_for(128) == 128
    # callers pass an already-rung top (the max rung any block needs);
    # the clip is to that value, not a fresh clamp policy
    assert lad.rung_for(5000, top=512) == 512
    assert lad.rungs_to(1000) == (128, 256, 512, 1024)
    # ... and matches the sparse staging ladder exactly
    from dask_ml_tpu.parallel.sparse_stream import _nnz_rung

    for nnz in (1, 100, 128, 129, 5000, 100_000):
        assert _nnz_rung(nnz, 0) == lad.rung_for(nnz)


def test_slot_rung_ladder_matches_cohort_policy():
    lad = SlotRungLadder()
    assert lad.rungs_for(8) == [1, 2, 4, 8]
    assert lad.rungs_for(12) == [1, 2, 4, 8, 12]
    # near-duplicate top power dropped: 4 is within 25% of 5
    assert lad.rungs_for(5) == [1, 2, 5]
    assert lad.rung_for(3, 8) == 4
    assert lad.rung_for(8, 8) == 8
    from dask_ml_tpu.models.sgd import _cohort_rung_of, _cohort_rungs

    for n in (1, 2, 5, 8, 12, 33):
        assert _cohort_rungs(n) == lad.rungs_for(n)
        assert _cohort_rung_of(max(n // 2, 1), n) == \
            lad.rung_for(max(n // 2, 1), n)


def test_pad_rows_and_mask_colocated():
    lad = GeometricLadder(4, 64, 2.0)
    X = np.arange(12, dtype=np.float32).reshape(6, 2)
    rung = lad.rung_for(6)
    Xp = lad.pad_rows(X, rung)
    m = lad.row_mask(6, rung)
    assert Xp.shape == (rung, 2) and m.shape == (rung,)
    assert np.all(Xp[:6] == X) and np.all(Xp[6:] == 0)
    assert m.sum() == 6 and np.all(m[:6] == 1)
    # exact fit passes through without a copy
    assert lad.pad_rows(X, 6) is X
    with pytest.raises(ValueError):
        lad.pad_rows(X, 4)


def test_nnz_pad_triple():
    d, c, r = NnzLadder.pad_triple(
        np.ones(3, np.float32), np.arange(3), np.arange(3), 8
    )
    assert d.shape == c.shape == r.shape == (8,)
    assert d[:3].sum() == 3 and d[3:].sum() == 0
    with pytest.raises(ValueError):
        NnzLadder.pad_triple(np.ones(9), np.arange(9), np.arange(9), 8)


# -- ProgramPlan cache keying ------------------------------------------------

def _body(a, b):
    return a + b


def test_plan_cache_identical_specs_hit():
    p1 = ProgramPlan(name="test.plan.hit", body=_body,
                     key=("k", 1)).build()
    p2 = ProgramPlan(name="test.plan.hit", body=_body,
                     key=("k", 1)).build()
    assert p1 is p2
    x = jnp.ones(3)
    np.testing.assert_allclose(np.asarray(p1(x, x)), 2.0)


def test_plan_cache_differing_specs_miss():
    base = dict(name="test.plan.miss", body=_body)
    p = ProgramPlan(key=("mesh1", "f32", (), 8), **base).build()
    # differing mesh / dtype-mxu / donation / ladder rung all MISS
    assert ProgramPlan(key=("mesh2", "f32", (), 8), **base).build() \
        is not p
    assert ProgramPlan(key=("mesh1", "bf16", (), 8), **base).build() \
        is not p
    assert ProgramPlan(key=("mesh1", "f32", (), 8), donate=(0,),
                       **base).build() is not p
    assert ProgramPlan(key=("mesh1", "f32", (), 16), **base).build() \
        is not p
    # and a differing program name misses even at an equal key
    assert ProgramPlan(name="test.plan.miss2", body=_body,
                       key=("mesh1", "f32", (), 8)).build() is not p


def test_plan_cache_off_builds_fresh():
    with config.set(plan_cache=False):
        p1 = ProgramPlan(name="test.plan.off", body=_body,
                         key=("k",)).build()
        p2 = ProgramPlan(name="test.plan.off", body=_body,
                         key=("k",)).build()
    assert p1 is not p2


def test_plan_build_counters_move():
    obs.counters_reset()
    ProgramPlan(name="test.plan.ctr", body=_body, key=("c", 1)).build()
    ProgramPlan(name="test.plan.ctr", body=_body, key=("c", 1)).build()
    snap = obs.counters_snapshot()
    assert snap.get("plan_builds", 0) >= 1
    assert snap.get("plan_cache_hits", 0) >= 1


# -- WarmupRegistry ----------------------------------------------------------

def test_warmup_registry_idempotent_and_attributable():
    calls = []
    key = ("test-warm", id(test_warmup_registry_idempotent_and_attributable))
    obs.counters_reset()
    ran = warmups.warm(key, lambda: calls.append(1),
                       program="test.warm.prog", ladder="test-rows",
                       rung=32)
    assert ran and calls == [1]
    ran2 = warmups.warm(key, lambda: calls.append(1),
                        program="test.warm.prog", ladder="test-rows",
                        rung=32)
    assert not ran2 and calls == [1]          # idempotent
    snap = obs.counters_snapshot()
    assert snap.get("plan_warmups", 0) >= 1
    assert snap.get("plan_cache_hits", 0) >= 1
    rows = [r for r in warmups.snapshot()
            if r["program"] == "test.warm.prog"]
    assert rows and rows[0]["rungs"] == "32" \
        and rows[0]["warmups"] == 1 and rows[0]["warm_hits"] == 1
    # plan_rewarm forces re-execution
    with config.set(plan_rewarm=True):
        assert warmups.warm(key, lambda: calls.append(1))
    assert calls == [1, 1]


# -- jaxpr byte-identity for the migrated clients ----------------------------

def _jaxprs_match(tracked_fn, jit_kwargs, args, static_kwargs=None):
    """The plan-built entry point's jaxpr vs a hand-assembled
    ``jax.jit(raw_body, <the pre-migration flags>)`` — byte equality
    proves the plan layer changed plumbing only, never the traced
    computation."""
    static_kwargs = static_kwargs or {}
    ref = jax.jit(tracked_fn.__wrapped__, **jit_kwargs)

    def call_plan(*xs):
        return tracked_fn.__wrapped_jit__(*xs, **static_kwargs)

    def call_ref(*xs):
        return ref(*xs, **static_kwargs)

    a = str(jax.make_jaxpr(call_plan)(*args))
    b = str(jax.make_jaxpr(call_ref)(*args))
    return a == b


def test_jaxpr_identity_serving_dense_and_int8():
    from dask_ml_tpu.models.sgd import SGDClassifier
    from dask_ml_tpu.wrappers import compiled_batch_fn, _donate_spec

    rng = np.random.RandomState(0)
    X = rng.randn(64, 6).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    clf = SGDClassifier(max_iter=2, random_state=0).fit(X, y)
    donate = _donate_spec()
    kw = {"donate_argnums": donate} if donate else {}
    for quant in (None, "int8"):
        fn = compiled_batch_fn(clf, "predict", quantize=quant)
        params, _post = fn._state
        assert _jaxprs_match(fn._fn, kw, (params, X[:8]))


def test_jaxpr_identity_serving_sparse():
    from dask_ml_tpu.models.sgd import SGDClassifier
    from dask_ml_tpu.wrappers import sparse_batch_fn

    rng = np.random.RandomState(1)
    X = rng.randn(64, 16).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    clf = SGDClassifier(max_iter=2, random_state=0).fit(X, y)
    fn = sparse_batch_fn(clf, "predict")
    assert fn is not None
    params, _post = fn._state
    nnz = 32
    args = (params, np.zeros(nnz, np.float32),
            np.zeros(nnz, np.int32), np.zeros(nnz, np.int32))
    # static n_rows: bind it on both sides
    tracked = fn._fn
    ref = jax.jit(tracked.__wrapped__, static_argnums=(4,))
    a = str(jax.make_jaxpr(
        lambda *xs: tracked.__wrapped_jit__(*xs, 8))(*args))
    b = str(jax.make_jaxpr(lambda *xs: ref(*xs, 8))(*args))
    assert a == b


def test_jaxpr_identity_stacked_c_grid_solves():
    import optax

    from dask_ml_tpu.models.solvers import solvers as S

    n, d, k, C = 32, 4, 2, 3
    rng = np.random.RandomState(2)
    X = jnp.asarray(rng.randn(n, d), jnp.float32)
    y = jnp.asarray((rng.randn(n) > 0), jnp.float32)
    Y = jnp.asarray(rng.rand(C, n) > 0.5, jnp.float32)
    mask = jnp.ones(n, jnp.float32)
    pmask = jnp.ones(d, jnp.float32)
    lams = jnp.asarray(np.logspace(-3, -1, k), jnp.float32)
    opt = optax.lbfgs(memory_size=10)

    def carry_of(width):
        b0 = jnp.zeros((width,), jnp.float32)
        return (b0, opt.init(b0), jnp.asarray(jnp.inf, b0.dtype), 0)

    stop_it = jnp.asarray(3)
    tol = jnp.asarray(1e-6, jnp.float32)
    cases = [
        (S._lam_grid_chunk,
         {"static_argnames": ("family", "reg", "k", "n_folds", "intercept",
                              "memory")},
         (X, y, mask, jnp.asarray(np.arange(n) % 2, jnp.int32),
          jnp.asarray([n / 2, n / 2], jnp.float32), carry_of(2 * k * d),
          jnp.tile(lams, 2), pmask, stop_it, tol),
         {"family": "logistic", "reg": "l2", "k": k, "n_folds": 2,
          "intercept": False}),
        (S._lam_grid_multi_chunk,
         {"static_argnames": ("family", "reg", "k", "C", "memory")},
         (X, Y, mask, n, carry_of(k * C * d), lams, pmask, stop_it,
          tol),
         {"family": "logistic", "reg": "l2", "k": k, "C": C}),
        (S._multi_stacked_chunk,
         {"static_argnames": ("family", "reg", "C", "memory")},
         (X, Y, mask, n, carry_of(C * d), jnp.asarray(0.1), pmask,
          jnp.asarray(0.0), stop_it, tol),
         {"family": "logistic", "reg": "l2", "C": C}),
    ]
    for tracked, kw, args, statics in cases:
        assert _jaxprs_match(tracked, kw, args, static_kwargs=statics), \
            tracked.program_name


def test_jaxpr_identity_superblock_scan():
    from dask_ml_tpu.models.solvers.streamed import _sb_reducer

    tracked = _sb_reducer("vg", "normal", True, None)
    K, S, d = 2, 16, 4
    rng = np.random.RandomState(3)
    Xs = jnp.asarray(rng.randn(K, S, d), jnp.float32)
    ys = jnp.asarray(rng.randn(K, S), jnp.float32)
    counts = jnp.full((K,), S, jnp.int32)
    beta = jnp.zeros(d + 1, jnp.float32)        # intercept slot
    acc = (jnp.zeros((), jnp.float32), jnp.zeros(d + 1, jnp.float32))
    assert _jaxprs_match(tracked, {"donate_argnums": (0,)},
                         (acc, beta, Xs, ys, counts))


# -- plans table / attribution ----------------------------------------------

def test_programs_snapshot_carries_plan_attribution():
    from dask_ml_tpu.models.sgd import SGDClassifier
    from dask_ml_tpu.serving import ModelServer
    from dask_ml_tpu.serving._buckets import BucketLadder

    rng = np.random.RandomState(4)
    X = rng.randn(128, 5).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    clf = SGDClassifier(max_iter=1, random_state=0).fit(X, y)
    obs.programs_reset()
    with config.set(obs_programs=True):
        srv = ModelServer(clf, methods=("predict",),
                          ladder=BucketLadder(8, 16, 2.0))
        srv.warmup()
    rows = {p["program"]: p for p in obs.programs_snapshot()}
    row = rows.get("serving.SGDClassifier.predict")
    assert row is not None
    assert row.get("plan") == "serving"
    assert str(row.get("ladder_rung", "")).startswith("serving-rows:")
    # the plans snapshot names the rungs that minted specializations
    prow = [r for r in plans.plans_snapshot()
            if r["program"] == "serving.SGDClassifier.predict"]
    assert prow and "8" in prow[0]["rungs"]


def test_report_renders_plan_column_and_plans_table(tmp_path):
    from dask_ml_tpu.observability.report import (build_report,
                                                  report_data)

    records = [
        {"programs": [
            {"program": "serving.SGDClassifier.predict", "compiles": 2,
             "compile_s": 0.1, "calls": 4, "flops_per_call": 1e6,
             "flops_total": 4e6, "exec_s": 0.01,
             "hbm_peak_bytes": 1 << 20, "plan": "serving",
             "ladder_rung": "serving-rows:8,16"}],
         "plans": [
            {"program": "serving.SGDClassifier.predict",
             "plan": "serving", "ladder": "serving-rows",
             "rungs": "8,16", "warmups": 2, "warm_hits": 1}]},
    ]
    out = build_report(records)
    assert "plan" in out and "serving-rows:8,16" in out
    assert "plans (execution plans: ladder rungs / warmups)" in out
    data = report_data(records)
    assert data["plans"][0]["rungs"] == "8,16"        # --json mirrors
    assert data["programs"][0]["ladder_rung"] == "serving-rows:8,16"


def test_report_without_plans_is_unchanged():
    from dask_ml_tpu.observability.report import build_report

    records = [{"programs": [
        {"program": "glm.lbfgs", "compiles": 1, "compile_s": 0.1,
         "calls": 1, "flops_per_call": 1e6, "flops_total": 1e6,
         "exec_s": 0.0, "hbm_peak_bytes": 1 << 20}]}]
    out = build_report(records)
    assert "programs (XLA cost/memory per compiled entry point)" in out
    # no plan attribution anywhere -> the legacy table shape (no plan
    # column header on the programs table)
    header = [ln for ln in out.splitlines()
              if ln.startswith("program ")][0]
    assert "plan" not in header


# -- the onboarded estimator: streamed fit + warmed serving ------------------

def test_naive_bayes_streamed_fit_and_served_predict_zero_compiles():
    from dask_ml_tpu.naive_bayes import GaussianNB
    from dask_ml_tpu.serving import ModelServer
    from dask_ml_tpu.serving._buckets import BucketLadder
    from dask_ml_tpu.wrappers import Incremental

    rng = np.random.RandomState(5)
    X = np.concatenate([rng.randn(2000, 6) + 2,
                        rng.randn(2000, 6) - 2]).astype(np.float32)
    y = np.concatenate([np.zeros(2000), np.ones(2000)])
    p = rng.permutation(len(y))
    X, y = X[p], y[p]

    ref = GaussianNB().fit(X, y)
    inc = Incremental(GaussianNB(), shuffle_blocks=True, random_state=0)
    inc.fit(X, y)                       # pass 1 mints the block rungs
    obs.counters_reset()
    inc.partial_fit(X, y)               # pass 2: zero new compiles
    assert obs.counters_snapshot().get("recompiles", 0) == 0
    est = inc.estimator_
    np.testing.assert_allclose(est.theta_, ref.theta_, atol=1e-3)
    np.testing.assert_allclose(est.class_prior_, ref.class_prior_,
                               atol=1e-6)
    assert est.score(X, y) > 0.95

    srv = ModelServer(est, methods=("predict", "predict_proba"),
                      ladder=BucketLadder(8, 64, 2.0))
    srv.warmup()
    # the reference outputs run BEFORE the counter reset: each direct
    # predict at a novel request shape pays its own (off-ladder) compile
    sizes = (3, 17, 60, 9, 64)
    expect = {n: est.predict(X[:n]) for n in sizes}
    expect_proba = est.predict_proba(X[:33])
    obs.counters_reset()
    with srv:
        for n in sizes:
            np.testing.assert_array_equal(srv.predict(X[:n]),
                                          expect[n])
        proba = srv.predict_proba(X[:33])
    assert obs.counters_snapshot().get("recompiles", 0) == 0
    np.testing.assert_allclose(proba, expect_proba, atol=1e-4)


def test_naive_bayes_partial_fit_contract():
    from dask_ml_tpu.naive_bayes import GaussianNB

    rng = np.random.RandomState(6)
    X = rng.randn(100, 3).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    nb = GaussianNB()
    with pytest.raises(ValueError):
        nb.partial_fit(X, y)            # first call needs classes=
    nb.partial_fit(X[:50], y[:50], classes=[0.0, 1.0])
    nb.partial_fit(X[50:], y[50:])
    ref = GaussianNB().fit(X, y)
    np.testing.assert_allclose(nb.theta_, ref.theta_, atol=1e-4)
    with pytest.raises(ValueError):
        nb.partial_fit(X[:4], np.full(4, 7.0))   # unseen label refuses
    with pytest.raises(ValueError):
        nb.partial_fit(X[:4, :2], y[:4])         # width change refuses


def test_naive_bayes_hot_swap_through_serving():
    from dask_ml_tpu.naive_bayes import GaussianNB
    from dask_ml_tpu.serving import ModelServer
    from dask_ml_tpu.serving._buckets import BucketLadder

    rng = np.random.RandomState(7)
    X = rng.randn(300, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    a = GaussianNB().fit(X, y)
    b = GaussianNB().fit(X + 0.5, y)
    srv = ModelServer(a, methods=("predict",),
                      ladder=BucketLadder(8, 32, 2.0))
    srv.warmup()
    obs.counters_reset()
    with srv:
        srv.swap_model(b)
        out = srv.predict(X[:16])
    assert obs.counters_snapshot().get("recompiles", 0) == 0
    np.testing.assert_array_equal(out, b.predict(X[:16]))
