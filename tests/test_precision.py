"""ISSUE 8 precision ladder: fused Pallas streamed kernels (interpret
parity), the bf16 "auto" fit policy with its recorded f32 fallback and
per-estimator opt-out, the int8 weight-quantized serving flavor, and
the dtype-alias config surface.

Tolerance notes: bf16 input rounding is ~0.4% relative, so bf16-vs-f32
fit parity is documented at ~1e-2 relative (matching
tests/test_bf16_policy.py); int8 weights add per-channel <=1/254
rounding, and the serving criterion is prediction agreement >= 99.5%
on a margin-bearing parity suite."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dask_ml_tpu.config as config
from dask_ml_tpu import observability as obs

rng = np.random.RandomState(0)


# ---------------------------------------------------------------------------
# config: dtype aliases, auto policy, fallback recording
# ---------------------------------------------------------------------------

def test_mxu_dtype_aliases_and_auto():
    assert config.get_config().dtype == "auto"
    # auto on the CPU CI backend resolves to f32 (the recorded fallback)
    assert config.mxu_dtype() is None
    info = config.fit_dtype_info()
    assert info["fit_dtype"] == "float32"
    assert info["fit_dtype_source"].startswith("auto:")
    for alias in ("bfloat16", "bf16", "BF16"):
        with config.set(dtype=alias):
            assert config.mxu_dtype() is jnp.bfloat16
    for alias in ("float32", "f32", "fp32", "FP32"):
        with config.set(dtype=alias):
            assert config.mxu_dtype() is None
    # estimator override beats config
    with config.set(dtype="f32"):
        assert config.mxu_dtype("bf16") is jnp.bfloat16
        assert config.fit_dtype_info("bf16")["fit_dtype_source"] \
            == "estimator"


def test_mxu_dtype_rejects_typos_listing_spellings():
    with pytest.raises(ValueError) as ei:
        with config.set(dtype="b16"):
            config.mxu_dtype()
    msg = str(ei.value)
    for spelling in ("auto", "float32", "f32", "fp32", "bfloat16",
                     "bf16"):
        assert spelling in msg


# ---------------------------------------------------------------------------
# fused Pallas streamed kernels: interpret-mode parity vs XLA flavors
# ---------------------------------------------------------------------------

def _sb_fixture(K=3, S=256, d=8):
    r = np.random.RandomState(7)
    Xs = jnp.asarray(r.randn(K, S, d).astype(np.float32))
    ys = jnp.asarray((r.rand(K, S) > 0.5).astype(np.float32))
    counts = jnp.asarray([S, S - 56, 0], jnp.int32)  # ragged + padding
    return Xs, ys, counts


@pytest.mark.parametrize("loss", ["log_loss", "hinge", "squared_error"])
def test_pallas_sgd_scan_matches_xla(loss):
    from dask_ml_tpu.models.sgd import _sgd_stream_program

    Xs, ys, counts = _sb_fixture()
    K, _, d = Xs.shape
    lrs = jnp.full((K,), 0.05, jnp.float32)
    w0 = jnp.asarray(np.random.RandomState(1)
                     .randn(d + 1).astype(np.float32) * 0.1)
    args = (counts, lrs, jnp.float32(1e-3), jnp.float32(0.7),
            jnp.float32(0.3), jnp.float32(1.0))
    xla = _sgd_stream_program(None, "xla", loss, False)
    pallas = _sgd_stream_program(None, "pallas", loss, False,
                                 interpret=True)
    Wx, lx = xla(jnp.array(w0), (Xs,), ys, *args)
    Wp, lp = pallas(jnp.array(w0), (Xs,), ys, *args)
    np.testing.assert_allclose(Wp, Wx, atol=1e-5)
    np.testing.assert_allclose(lp, lx, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind", ["val", "vg", "vgh"])
@pytest.mark.parametrize("intercept", [True, False])
def test_pallas_glm_reducer_matches_xla(kind, intercept):
    from dask_ml_tpu.models.solvers.streamed import _sb_reducer

    Xs, ys, counts = _sb_fixture()
    d = Xs.shape[2]
    p = d + (1 if intercept else 0)
    beta = jnp.asarray(np.random.RandomState(2)
                       .randn(p).astype(np.float32) * 0.1)
    init = [jnp.zeros((), jnp.float32)]
    if kind != "val":
        init.append(jnp.zeros(p, jnp.float32))
    if kind == "vgh":
        init.append(jnp.zeros((p, p), jnp.float32))
    xla = _sb_reducer(kind, "logistic", intercept, 0)
    pal = _sb_reducer(kind, "logistic", intercept, 0, fused=True,
                      interpret=True)
    ax = xla(tuple(jnp.array(a) for a in init), beta, Xs, ys, counts)
    ap = pal(tuple(jnp.array(a) for a in init), beta, Xs, ys, counts)
    for got, want in zip(ap, ax):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-5)


def test_pallas_kmeans_stream_matches_xla():
    from dask_ml_tpu.models.kmeans import (_sb_assign_stats,
                                           _sb_assign_stats_pallas)

    Xs, _, counts = _sb_fixture()
    d = Xs.shape[2]
    C = jnp.asarray(np.random.RandomState(3)
                    .randn(4, d).astype(np.float32))

    def acc0():
        return (jnp.zeros((4, d), jnp.float32),
                jnp.zeros((4,), jnp.float32),
                jnp.zeros((), jnp.float32))

    ax = _sb_assign_stats(acc0(), Xs, counts, C)
    ap = _sb_assign_stats_pallas(acc0(), Xs, counts, C, interpret=True)
    for got, want in zip(ap, ax):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_stream_tile_gate():
    """The fused kernels refuse non-128-multiple block heights (they
    cannot pad inside the scan) and overwide designs; the flavor
    selectors then keep the XLA programs."""
    from dask_ml_tpu.ops.pallas_fused import (
        glm_stream_tile, kmeans_stream_tile, sgd_stream_tile,
    )

    assert sgd_stream_tile(256, 8) == 256
    assert sgd_stream_tile(12500, 128) is None      # not a 128-multiple
    assert sgd_stream_tile(512 * 1024, 128) is not None
    assert glm_stream_tile(256, 8, "vgh") == 256
    assert glm_stream_tile(250, 8, "vg") is None
    assert kmeans_stream_tile(256, 8, 4) == 256
    # a design too wide for even a 128-row tile falls back
    assert sgd_stream_tile(128, 3_000_000) is None


def test_xla_flavor_selected_and_unchanged_on_cpu():
    """Zero-overhead contract (ISSUE 8): off-TPU (and with
    pallas_stream off anywhere) the streamed programs are the plain XLA
    flavors — no pallas call, no bf16 casts — so the jaxpr is
    byte-identical to the pre-feature one."""
    from dask_ml_tpu.models.sgd import _sgd_stream_program, _stream_flavor
    from dask_ml_tpu.observability._programs import unwrap
    from dask_ml_tpu.ops.pallas_fused import use_stream_kernels

    assert jax.default_backend() == "cpu"
    assert not use_stream_kernels()         # backend gate, knob on
    with config.set(pallas_stream=False):
        assert not use_stream_kernels()

    body = unwrap(_sgd_stream_program(None, "xla", "log_loss", False))
    K, S, d = 2, 8, 3
    jaxpr = str(jax.make_jaxpr(
        lambda W, Xs, ys, c, lrs: body(
            W, (Xs,), ys, c, lrs, 1e-4, 1.0, 0.0, 1.0
        )
    )(jnp.zeros(d + 1), jnp.zeros((K, S, d)), jnp.zeros((K, S)),
      jnp.zeros(K, jnp.int32), jnp.zeros(K)))
    assert "bf16" not in jaxpr and "pallas" not in jaxpr

    # the streamed scans' gate picks the XLA reader on this backend and
    # says why the fused flavor was gated off
    class _FakeSB:
        arrays = (jnp.zeros((2, 256, 8)), jnp.zeros((2, 256)))
        counts = jnp.zeros(2, jnp.int32)
        shard_counts = None

    source, mxu, interp, reason = _stream_flavor(_FakeSB(), None, None)
    assert source == "xla" and mxu is None and reason == "off-TPU"
    with config.set(pallas_stream=False):
        assert _stream_flavor(_FakeSB(), None, None)[3] \
            == "pallas-stream-off"


# ---------------------------------------------------------------------------
# bf16 fit parity + opt-out + recorded fallback
# ---------------------------------------------------------------------------

def _margin_data(n=6000, d=16, seed=5):
    r = np.random.RandomState(seed)
    X = r.randn(n, d).astype(np.float32)
    w = r.randn(d).astype(np.float32)
    y = (X @ w + 0.5 * r.randn(n) > 0).astype(np.float32)
    return X, y


def _clipped_log_loss(y, proba):
    p = np.clip(np.asarray(proba)[:, 1], 1e-7, 1 - 1e-7)
    return float(-np.mean(y * np.log(p) + (1 - y) * np.log(1 - p)))


def test_logreg_bf16_parity_loss_and_predictions():
    from dask_ml_tpu.linear_model import LogisticRegression

    X, y = _margin_data()
    f32 = LogisticRegression(solver="lbfgs", max_iter=40).fit(X, y)
    with config.set(dtype="bf16"):
        b16 = LogisticRegression(solver="lbfgs", max_iter=40).fit(X, y)
    assert f32.fit_dtype_ == "float32"
    assert b16.fit_dtype_ == "bfloat16"
    # prediction agreement + loss gap within the documented bf16 band
    assert np.mean(b16.predict(X) == f32.predict(X)) >= 0.995
    l32 = _clipped_log_loss(y, f32.predict_proba(X))
    l16 = _clipped_log_loss(y, b16.predict_proba(X))
    assert abs(l16 - l32) <= 2e-2 * max(l32, 1e-6)


def test_streamed_sgd_bf16_parity_and_optout():
    from dask_ml_tpu.models.sgd import SGDClassifier

    X, y = _margin_data(n=4096, d=8)
    with config.set(stream_block_rows=512):
        f32 = SGDClassifier(max_iter=3, random_state=0,
                            shuffle=False).fit(X, y)
        with config.set(dtype="bfloat16"):
            b16 = SGDClassifier(max_iter=3, random_state=0,
                                shuffle=False).fit(X, y)
            # per-estimator opt-out wins over the config policy
            opt = SGDClassifier(max_iter=3, random_state=0,
                                shuffle=False,
                                fit_dtype="fp32").fit(X, y)
    assert b16.fit_dtype_ == "bfloat16"
    assert opt.fit_dtype_ == "float32"
    np.testing.assert_array_equal(opt.coef_, f32.coef_)
    assert np.mean(b16.predict(X) == f32.predict(X)) >= 0.99
    np.testing.assert_allclose(b16.coef_, f32.coef_, rtol=3e-2,
                               atol=3e-2)
    assert abs(float(b16._last_loss) - float(f32._last_loss)) \
        <= 2e-2 * max(float(f32._last_loss), 1e-6)


def test_streamed_glm_records_f32_fallback_in_info():
    from dask_ml_tpu.linear_model import LogisticRegression

    X, y = _margin_data(n=4096, d=8)
    with config.set(stream_block_rows=512, dtype="bfloat16"):
        st = LogisticRegression(solver="lbfgs", max_iter=10).fit(X, y)
    # streamed XLA reducers are f32-only; the bf16 request must be
    # recorded as fallen back, not silently honored
    assert st.solver_info_["fit_dtype"] == "float32"
    assert st.solver_info_["fit_dtype_source"] == "streamed-xla"
    assert st.solver_info_["fused_stream"] is False
    assert st.fit_dtype_ == "float32"


# ---------------------------------------------------------------------------
# int8 serving flavor
# ---------------------------------------------------------------------------

def test_int8_prediction_agreement_across_ladder():
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.wrappers import compiled_batch_fn

    X, y = _margin_data(n=8000, d=24, seed=11)
    clf = LogisticRegression(solver="lbfgs", max_iter=40).fit(X, y)
    f32 = compiled_batch_fn(clf, "predict")
    q8 = compiled_batch_fn(clf, "predict", quantize="int8")
    assert q8.quantize == "int8" and f32.quantize is None
    agree = total = 0
    for bucket in (8, 16, 32, 64, 128, 256, 512):   # the ladder shapes
        blk = X[:bucket]
        agree += int(np.sum(f32(blk) == q8(blk)))
        total += bucket
    assert agree / total >= 0.995, agree / total
    # decision_function stays within the combined bf16+int8 band
    d32 = compiled_batch_fn(clf, "decision_function")(X)
    d8 = compiled_batch_fn(clf, "decision_function",
                           quantize="int8")(X)
    assert np.max(np.abs(d32 - d8)) <= 2e-2 * np.max(np.abs(d32))


def test_int8_multiclass_and_regression_and_proba_fallback():
    from dask_ml_tpu.linear_model import (LinearRegression,
                                          LogisticRegression)
    from dask_ml_tpu.wrappers import compiled_batch_fn

    r = np.random.RandomState(13)
    X = r.randn(6000, 12).astype(np.float32)
    ym = np.argmax(X[:, :3] + 0.2 * r.randn(6000, 3), axis=1)
    multi = LogisticRegression(solver="lbfgs", max_iter=40).fit(X, ym)
    q8 = compiled_batch_fn(multi, "predict", quantize="int8")
    assert np.mean(compiled_batch_fn(multi, "predict")(X) == q8(X)) \
        >= 0.995
    # predict_proba refuses the int8 flavor (stays higher precision)
    pp = compiled_batch_fn(multi, "predict_proba", quantize="int8")
    assert pp.quantize is None

    yr = (X @ r.randn(12).astype(np.float32)).astype(np.float32)
    reg = LinearRegression(solver="lbfgs", max_iter=40).fit(X, yr)
    p32 = compiled_batch_fn(reg, "predict")(X)
    p8 = compiled_batch_fn(reg, "predict", quantize="int8")(X)
    scale = np.max(np.abs(p32))
    assert np.max(np.abs(p32 - p8)) <= 2e-2 * scale

    # poisson predict passes eta through exp — it refuses the int8
    # flavor (error would amplify multiplicatively) and falls back
    from dask_ml_tpu.linear_model import PoissonRegression

    yc = np.round(np.exp(0.3 * X[:, 0] + 1.0)).astype(np.float32)
    poi = PoissonRegression(solver="lbfgs", max_iter=30).fit(X, yc)
    pq = compiled_batch_fn(poi, "predict", quantize="int8")
    assert pq.quantize is None


def test_int8_quantization_is_per_channel():
    from dask_ml_tpu.wrappers import _quantize_w

    W = np.array([[1.0, -2.0, 0.5], [100.0, 50.0, -200.0],
                  [0.0, 0.0, 0.0]], np.float32)
    Wq, scale = _quantize_w(W)
    assert Wq.dtype == np.int8
    np.testing.assert_allclose(scale,
                               [2.0 / 127, 200.0 / 127, 1.0])
    # dequantized weights land within half a quantization step of the
    # originals, PER CHANNEL (the step is scale[c])
    assert np.all(np.abs(Wq * scale[:, None] - W)
                  <= scale[:, None] / 2 + 1e-6)
    assert np.all(Wq[2] == 0)


def test_int8_hot_swap_round_trip_zero_compiles():
    """f32 -> int8 -> f32 through a warmed ModelServer with the int8
    flavor pre-built (config.serving_warm_flavors): every flip and
    every served batch after warmup mints ZERO XLA compiles."""
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.serving import ModelServer

    X, y = _margin_data(n=4000, d=16, seed=17)
    v1 = LogisticRegression(solver="lbfgs", max_iter=30).fit(X, y)
    v2 = LogisticRegression(solver="lbfgs", max_iter=30,
                            C=0.3).fit(X, y)
    with config.set(serving_warm_flavors="int8", serving_min_batch=8,
                    serving_max_batch=64):
        srv = ModelServer(
            v1, methods=("predict", "decision_function", "predict_proba")
        ).warmup()
        obs.counters_reset()
        with srv:
            base = srv.predict(X[:200])
            srv.swap_model(v2, quantize="int8")
            p_int8 = srv.predict(X[:200])
            assert srv._active_flavor == "int8"
            # proba still serves (higher-precision fallback flavor)
            pr = np.asarray(
                srv.submit(X[:40], method="predict_proba").result()
            )
            srv.swap_model(v1)                      # back to f32
            p_back = srv.predict(X[:200])
        snap = obs.counters_snapshot()
    assert snap.get("recompiles", 0) == 0, snap
    assert np.mean(p_int8 == v2.predict(X[:200])) >= 0.99
    np.testing.assert_array_equal(p_back, base)
    assert pr.shape == (40, 2)


def test_int8_unwarmed_flavor_refuses_swap():
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.serving import ModelServer
    from dask_ml_tpu.wrappers import ParamSwapError

    X, y = _margin_data(n=1000, d=8, seed=19)
    clf = LogisticRegression(solver="lbfgs", max_iter=20).fit(X, y)
    srv = ModelServer(clf)                  # no warm flavors configured
    with pytest.raises(ParamSwapError):
        srv.swap_model(clf, quantize="int8")
    # rebuild_model installs the new flavor on the paid path instead
    srv.rebuild_model(clf, quantize="int8")
    assert srv._active_flavor == "int8"
    assert srv._fns["predict"].quantize == "int8"


def test_registry_publish_quantize_reaches_server():
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.serving import ModelServer
    from dask_ml_tpu.serving.registry import ModelRegistry

    X, y = _margin_data(n=1000, d=8, seed=23)
    v1 = LogisticRegression(solver="lbfgs", max_iter=20).fit(X, y)
    v2 = LogisticRegression(solver="lbfgs", max_iter=20,
                            C=0.5).fit(X, y)
    with config.set(serving_warm_flavors="int8"):
        srv = ModelServer(v1).warmup()
        regy = ModelRegistry(keep=4)

        def on_publish(mv):
            srv.swap_model(mv.estimator, version=mv.version,
                           quantize=mv.quantize)

        regy.subscribe("m", on_publish)
        obs.counters_reset()
        regy.publish("m", v2, quantize="int8")
        assert srv._active_flavor == "int8"
        assert srv.model_version == regy.current_version("m")
        regy.publish("m", v1)                       # back to f32
        assert srv._active_flavor == ""
        assert obs.counters_snapshot().get("recompiles", 0) == 0
        assert regy.get("m", 1).quantize == "int8"
        snap = regy.status_snapshot()["m"]
        assert snap["quantize"] is None             # current is v2/f32

