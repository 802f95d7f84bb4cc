"""Fused GLM value+grad Pallas kernel (ops/pallas_fused.py): one X pass
per value_and_grad. Interpret-mode parity vs the XLA loss across
families, solvers, and dtypes (the kernel auto-engages compiled on real
TPU; scripts/tpu_smoke.py asserts the same parity there)."""

from functools import partial

import numpy as np
import pytest

from dask_ml_tpu import config
from dask_ml_tpu.datasets import (
    make_classification, make_counts, make_regression,
)
from dask_ml_tpu.linear_model import (
    LinearRegression, LogisticRegression, PoissonRegression,
)

PALLAS = {"use_pallas": True, "pallas_interpret": True}


@pytest.mark.parametrize("name,maker,Est", [
    ("logistic", make_classification, LogisticRegression),
    ("normal", make_regression, LinearRegression),
    ("poisson", make_counts, PoissonRegression),
])
def test_fused_glm_matches_xla(name, maker, Est):
    X, y = maker(n_samples=3000, n_features=24, random_state=0)
    base = Est(solver="lbfgs", max_iter=60, tol=1e-8).fit(X, y)
    pal = Est(solver="lbfgs", max_iter=60, tol=1e-8,
              solver_kwargs=PALLAS).fit(X, y)
    np.testing.assert_allclose(pal.coef_, base.coef_, atol=5e-4)
    np.testing.assert_allclose(np.ravel(pal.intercept_),
                               np.ravel(base.intercept_), atol=5e-4)


def test_fused_glm_gradient_descent_and_bf16():
    X, y = make_classification(n_samples=3000, n_features=16,
                               random_state=1)
    base = LogisticRegression(solver="gradient_descent", max_iter=40,
                              tol=1e-8).fit(X, y)
    pal = LogisticRegression(solver="gradient_descent", max_iter=40,
                             tol=1e-8, solver_kwargs=PALLAS).fit(X, y)
    assert np.mean(pal.predict(X) == base.predict(X)) > 0.999
    # bf16 design matrix: kernel matvec at bf16 with f32 accumulation
    with config.set(dtype="bfloat16"):
        b16 = LogisticRegression(solver="lbfgs", max_iter=40,
                                 solver_kwargs=PALLAS).fit(X, y)
    assert b16.score(X, y) > 0.8


def _direct_inputs(family, dtype, n, d=13, seed=2):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    X = jnp.asarray(rng.randn(n, d).astype(np.float32), dtype)
    if family == "logistic":
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    elif family == "poisson":   # counts beyond 256 are not bf16-exact
        y = rng.poisson(3.0, size=n).astype(np.float32) * 101.0
    else:
        y = rng.randn(n).astype(np.float32)
    beta = rng.randn(d).astype(np.float32) * 0.1
    return X, jnp.asarray(y), jnp.asarray(beta)


# (rows, n_valid): ragged with a masked tail (the original case); a tile
# multiple; one padded tile; several tiles with rows past n_valid
_DIRECT_ROWS = [(391, 350), (2048, 2048), (1000, 1000), (3000, 2500)]


@pytest.mark.parametrize("n,n_valid", _DIRECT_ROWS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("family", ["logistic", "normal", "poisson"])
def test_fused_glm_kernel_direct(family, dtype, n, n_valid):
    """Kernel-level check against the plain XLA sum, including the
    padded-tail masking. The reference multiplies what the kernel's
    contract says it multiplies (X and beta at X's dtype, f32 products
    and sums; the residual at X's dtype into the gradient), so BOTH
    dtypes are held to the f32 band: an f32 design whose eta went
    through bf16 anywhere misses it by 100x, and so would labels
    rounded to bf16."""
    import jax
    import jax.numpy as jnp

    from dask_ml_tpu.models.solvers.families import get_family
    from dask_ml_tpu.ops.pallas_fused import fused_glm_value_grad

    X, y, beta = _direct_inputs(family, dtype, n)
    fam = get_family(family)
    hi = jax.lax.Precision.HIGHEST
    xf = X.astype(jnp.float32)
    m = (jnp.arange(n) < n_valid).astype(jnp.float32)
    eta = jnp.dot(xf, beta.astype(X.dtype).astype(jnp.float32),
                  precision=hi)
    v_ref = jnp.sum(fam.pointwise(eta, y) * m)
    resid = ((fam.mean(eta) - y) * m).astype(X.dtype).astype(jnp.float32)
    g_ref = np.asarray(jnp.dot(resid, xf, precision=hi))

    v, g = fused_glm_value_grad(X, n_valid, y, beta, family=family,
                                interpret=True)
    assert v.dtype == jnp.float32 and g.dtype == jnp.float32
    np.testing.assert_allclose(float(v), float(v_ref), rtol=1e-5)
    # atol: the file's 1e-5 at the original case's gradient scale (~10)
    np.testing.assert_allclose(np.asarray(g), g_ref, rtol=1e-4,
                               atol=1e-6 * np.abs(g_ref).max())


def _intercept_reference(family, X, y, beta, n_valid):
    """Raw sums (value, (d + 1,) gradient) by autodiff of
    ``solvers._smooth_loss`` in its scalar-intercept form, at the
    kernel's contract: X and coef at X's dtype, f32 products and sums,
    the intercept an f32 scalar nobody rounds."""
    import jax
    import jax.numpy as jnp

    from dask_ml_tpu.models.solvers import solvers as S

    n = X.shape[0]
    m = (jnp.arange(n) < n_valid).astype(jnp.float32)
    b = jnp.concatenate([beta[:-1].astype(X.dtype).astype(jnp.float32),
                         beta[-1:]])
    loss = partial(S._smooth_loss, X=X.astype(jnp.float32), y=y, mask=m,
                   n_rows=1.0, lam=jnp.float32(0.0),
                   pmask=jnp.ones_like(b), l1_ratio=0.5, family=family,
                   reg="none", intercept=True)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(b)


@pytest.mark.parametrize("n,n_valid", _DIRECT_ROWS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("family", ["logistic", "normal", "poisson"])
def test_fused_glm_kernel_intercept(family, dtype, n, n_valid):
    """The scalar-intercept form against ``_smooth_loss``'s autodiff:
    value, (d,) gradient and the intercept's gradient. Rows past
    ``n_valid`` hold ordinary data (not zeros) and see ``eta = b0``;
    they must add nothing to any of the three sums. ``n`` runs over
    ragged, tile-multiple and padded-tile row counts."""
    import jax.numpy as jnp

    from dask_ml_tpu.ops.pallas_fused import fused_glm_value_grad

    X, y, coef = _direct_inputs(family, dtype, n)
    beta = jnp.concatenate([coef, jnp.asarray([0.37], jnp.float32)])
    v_ref, g_ref = _intercept_reference(family, X, y, beta, n_valid)
    g_ref = np.asarray(g_ref)

    v, g, gb = fused_glm_value_grad(X, n_valid, y, beta[:-1], family=family,
                                    interpret=True, intercept=beta[-1])
    assert v.dtype == g.dtype == gb.dtype == jnp.float32
    assert g.shape == (X.shape[1],) and gb.shape == ()
    np.testing.assert_allclose(float(v), float(v_ref), rtol=1e-5)
    # a bf16 design rounds the residual to bf16 into the MXU (as the
    # column form always did); autodiff's reference does not
    band = 1e-6 if dtype == "float32" else 4e-3
    np.testing.assert_allclose(np.asarray(g), g_ref[:-1], rtol=1e-4,
                               atol=band * np.abs(g_ref).max())
    # the intercept's gradient sums the f32 residual itself
    np.testing.assert_allclose(float(gb), g_ref[-1], rtol=1e-4,
                               atol=1e-6 * np.abs(g_ref).max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("family", ["logistic", "normal", "poisson"])
def test_fused_glm_kernel_without_intercept_is_unchanged(family, dtype):
    """``intercept=None`` is the kernel as it was: four operands, two
    outputs, and bit for bit the value and gradient of a zero intercept
    (``eta + 0.0`` is ``eta``). An f32 ones column agrees with the
    scalar to rounding, and its gradient entry is the scalar's."""
    import jax
    import jax.numpy as jnp

    from dask_ml_tpu.ops.pallas_fused import fused_glm_value_grad

    n, n_valid = 3000, 2500
    X, y, coef = _direct_inputs(family, dtype, n)
    call = partial(fused_glm_value_grad, family=family, interpret=True)
    v0, g0 = call(X, n_valid, y, coef)
    vz, gz, _ = call(X, n_valid, y, coef, intercept=0.0)
    assert np.asarray(v0) == np.asarray(vz)
    np.testing.assert_array_equal(np.asarray(g0), np.asarray(gz))

    kernels = [e for e in _walk_eqns(jax.make_jaxpr(
        lambda x, y, b: call(x, n_valid, y, b))(X, y, coef).jaxpr)
        if e.primitive.name == "pallas_call"]
    assert len(kernels) == 1
    assert (len(kernels[0].invars), len(kernels[0].outvars)) == (4, 2)

    if dtype == "float32":       # a bf16 column would round 0.37
        ones = (jnp.arange(n) < n_valid).astype(X.dtype)[:, None]
        vc, gc = call(jnp.concatenate([X, ones], axis=1), n_valid, y,
                      jnp.concatenate([coef, jnp.asarray([0.37])]))
        vs, gs, gbs = call(X, n_valid, y, coef, intercept=0.37)
        np.testing.assert_allclose(float(vs), float(vc), rtol=1e-5)
        np.testing.assert_allclose(np.r_[np.asarray(gs), float(gbs)],
                                   np.asarray(gc), rtol=1e-4,
                                   atol=1e-6 * np.abs(gc).max())


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_scalar_intercept_losses_agree_on_mesh(n_devices, dtype):
    """The two flavours of ``_select_loss`` in the scalar-intercept form
    — the kernel per shard under ``_shard_psum_call``, and the XLA
    objective — give one value and one (d + 1,) gradient on a mesh of
    one device and of four with ragged shards (3001 rows pad to 751 a
    shard: the padding rows of EVERY shard see ``eta = b0``)."""
    import jax
    import jax.numpy as jnp

    from dask_ml_tpu.models.solvers import solvers as S
    from dask_ml_tpu.parallel.mesh import device_mesh
    from dask_ml_tpu.parallel.sharded import as_sharded

    n, d = 3001, 12
    rng = np.random.RandomState(5)
    mesh = device_mesh(devices=jax.devices()[:n_devices])
    Xs = as_sharded(rng.randn(n, d).astype(np.float32), mesh=mesh)
    ys = as_sharded((rng.rand(n) < 0.4).astype(np.float32), mesh=mesh)
    mask = Xs.row_mask(dtype=jnp.float32)
    X = Xs.data.astype(dtype)
    assert X.shape[0] > n or n_devices == 1
    beta = jnp.asarray(np.r_[rng.randn(d) * 0.2, -0.8], jnp.float32)
    pmask = jnp.asarray(np.r_[np.ones(d), 0.0], jnp.float32)

    def vg(use_pallas):
        loss = S._select_loss(use_pallas, X, ys.data, mask, float(n),
                              jnp.float32(1e-3), pmask, 0.5, "logistic",
                              "l2", mesh, True, intercept=True)
        return jax.jit(jax.value_and_grad(loss))(beta)

    (v_k, g_k), (v_x, g_x) = vg(True), vg(False)
    assert g_k.shape == g_x.shape == (d + 1,)
    np.testing.assert_allclose(float(v_k), float(v_x), rtol=2e-5)
    band = 1e-5 if dtype == "float32" else 4e-3
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_x),
                               atol=band * np.abs(np.asarray(g_x)).max())


def _walk_eqns(jaxpr):
    """Every equation of ``jaxpr`` and of every jaxpr nested in its
    parameters (loop bodies, shard_map, custom_vjp, the Pallas kernel)."""
    from jax.extend import core as jcore

    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    yield from _walk_eqns(sub)


def _avals(eqn):
    return [v.aval for v in (*eqn.invars, *eqn.outvars)
            if hasattr(v.aval, "shape")]


@pytest.mark.parametrize("intercept", [False, True])
def test_lbfgs_program_has_no_column_labels(intercept):
    """No (n_local, 1) / (tile, 1) f32 array exists anywhere in the fused
    ``glm.lbfgs`` program — loop bodies, the shard_map body and the
    kernel itself included. On a TPU such an array is tiled T(8,128):
    512 B a row, built and re-read on every objective evaluation. The
    chip shows that as time; this counts it. With ``intercept`` beta is
    one longer than X is wide, and no ``concatenate`` / ``pad`` in the
    program touches anything X-sized."""
    import jax
    import jax.numpy as jnp
    import optax

    from dask_ml_tpu.models.solvers import solvers as S
    from dask_ml_tpu.ops.pallas_fused import glm_tile
    from dask_ml_tpu.parallel.mesh import device_mesh

    shards, n_local, d = 4, 2560, 9       # 2560 rows a shard: 5 tiles of 512
    n = shards * n_local
    mesh = device_mesh(devices=jax.devices()[:shards])
    tile = glm_tile(n_local, d, 2)
    assert n_local % tile == 0 and n_local // tile > 1
    X = jax.ShapeDtypeStruct((n, d), jnp.bfloat16)
    y = mask = jax.ShapeDtypeStruct((n,), jnp.float32)
    w = d + int(intercept)
    beta0 = jnp.zeros((w,), jnp.float32)
    carry = (beta0, optax.lbfgs(memory_size=10).init(beta0),
             jnp.asarray(jnp.inf, jnp.float32), 0, np.zeros((), np.int32))

    def program(X, y, mask, carry):
        return S._lbfgs_chunk.__wrapped_jit__(
            X, y, mask, float(n), carry, lam=jnp.float32(1.0),
            pmask=jnp.ones((w,), jnp.float32), l1_ratio=0.5,
            stop_it=jnp.asarray(5), tol=jnp.float32(1e-3),
            family="logistic", reg="l2", memory=10, log=False,
            use_pallas=True, mesh=mesh, interpret=True,
            intercept=intercept)

    eqns = list(_walk_eqns(jax.make_jaxpr(program)(X, y, mask, carry).jaxpr))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert kernels, "the fused kernel is not in the program"
    for e in kernels:     # whatever feeds the kernel is lane-dense
        for a in _avals(e):
            assert not (len(a.shape) == 2 and a.shape[1] == 1
                        and a.shape[0] > 1), (e.primitive.name, a)
    columns = {(n, 1), (n_local, 1), (tile, 1)}
    found = [(e.primitive.name, a) for e in eqns for a in _avals(e)
             if tuple(a.shape) in columns]
    assert not found, found[:5]
    for e in kernels:     # the kernel's X block is as wide as X
        assert e.invars[0].aval.shape == (n_local, d)
    wide = [(e.primitive.name, a) for e in eqns
            if e.primitive.name in ("concatenate", "pad")
            for a in _avals(e) if a.shape and a.shape[0] in (n, n_local)]
    assert not wide, wide[:5]


@pytest.mark.parametrize("dtype,expected", [
    ("float32", "HIGHEST"), ("bfloat16", None)])
def test_fused_glm_eta_precision(dtype, expected):
    """An f32 design's eta contraction asks the MXU for f32 products
    (Mosaic's default multiplies f32 operands in bf16: seen on the chip,
    not in the interpreter); a bf16 design needs no such request."""
    import jax
    import jax.numpy as jnp

    from dask_ml_tpu.ops.pallas_fused import fused_glm_value_grad

    n, d = 1024, 9
    jaxpr = jax.make_jaxpr(
        lambda x, y, b: fused_glm_value_grad(
            x, n, y, b, family="logistic", interpret=True)
    )(jnp.ones((n, d), dtype), jnp.zeros((n,), jnp.float32),
      jnp.zeros((d,), jnp.float32)).jaxpr
    dots = [e for e in _walk_eqns(jaxpr)
            if e.primitive.name == "dot_general"]
    assert len(dots) == 2                   # eta, then the gradient
    eta, grad = dots
    assert eta.outvars[0].aval.shape[1] == n    # rows along lanes
    prec = eta.params["precision"]
    if expected is None:
        assert prec is None
    else:
        assert {str(p) for p in prec} == {expected}
    assert eta.outvars[0].aval.dtype == jnp.float32
    assert grad.outvars[0].aval.dtype == jnp.float32


@pytest.mark.parametrize("n_devices", [1, 4])
def test_fused_glm_matches_xla_on_mesh(n_devices):
    """Fit-level parity of the lane-dense kernel with the XLA loss on a
    mesh of one device (one shard holds every tile) and of four (ragged
    shards: 3001 rows pad to 751 a shard; ``test_fused_glm_matches_xla``
    runs the default mesh of 8)."""
    import jax

    from dask_ml_tpu.parallel.mesh import device_mesh, use_mesh

    with use_mesh(device_mesh(devices=jax.devices()[:n_devices])):
        X, y = make_classification(n_samples=3001, n_features=12,
                                   random_state=3)
        kw = dict(solver="lbfgs", max_iter=60, tol=1e-8)
        base = LogisticRegression(**kw).fit(X, y)
        pal = LogisticRegression(**kw, solver_kwargs=PALLAS).fit(X, y)
        assert len(X.data.sharding.device_set) == n_devices
    assert pal.solver_info_["fused"] and not base.solver_info_["fused"]
    np.testing.assert_allclose(pal.coef_, base.coef_, atol=5e-4)
    np.testing.assert_allclose(np.ravel(pal.intercept_),
                               np.ravel(base.intercept_), atol=5e-4)


def test_auto_selected_kernel_failure_fails_the_fit(monkeypatch):
    """An auto-selected kernel that fails to compile fails the fit: no
    retry on the XLA loss, no warning — "selected" means "compiles"."""
    import warnings

    from dask_ml_tpu.models.solvers import solvers as S

    X, y = make_classification(n_samples=500, n_features=8, random_state=0)

    real_chunk = S._lbfgs_chunk
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if kw.get("use_pallas"):
            raise RuntimeError("Mosaic lowering failed (simulated)")
        return real_chunk(*a, **kw)

    monkeypatch.setattr(S, "_lbfgs_chunk", flaky)
    # force the auto gate open without a TPU: _resolve_pallas(None, ...)
    monkeypatch.setattr(S, "_resolve_pallas",
                        lambda up, mesh, fam, X=None: True if up is None
                        else bool(up))
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        with pytest.raises(RuntimeError, match="Mosaic"):
            LogisticRegression(solver="lbfgs", max_iter=20).fit(X, y)
    assert calls["n"] == 1


@pytest.mark.slow
def test_fused_multiclass_matches_vmapped():
    """The flat multi-target kernel solve (one X pass for ALL classes
    per iteration) converges to the vmapped per-class solution — the
    objective is separable, so the joint optimum is the same."""
    X, y = make_classification(n_samples=3000, n_features=16, n_classes=3,
                               n_informative=9, random_state=1)
    base = LogisticRegression(solver="lbfgs", max_iter=80,
                              tol=1e-8).fit(X, y)
    pal = LogisticRegression(solver="lbfgs", max_iter=80, tol=1e-8,
                             solver_kwargs=PALLAS).fit(X, y)
    assert pal.solver_info_.get("fused_multi") is True
    assert base.solver_info_.get("fused_multi") is None
    np.testing.assert_allclose(pal.coef_, base.coef_, atol=2e-3)
    assert np.mean(pal.predict(X) == base.predict(X)) > 0.999


@pytest.mark.parametrize("Est,maker,pen", [
    (LogisticRegression, make_classification, "l1"),
    (LinearRegression, make_regression, "elastic_net"),
])
def test_fused_proximal_grad_matches_xla(Est, maker, pen):
    """proximal_grad's smooth part through the fused kernel: relative
    coefficient parity with the XLA loss. Support membership can flip
    only for coefficients AT the prox threshold (near-zero on both
    sides) — accumulation-order noise, not divergence."""
    X, y = maker(n_samples=3000, n_features=18, random_state=0)
    kw = dict(solver="proximal_grad", penalty=pen, max_iter=120, tol=1e-9)
    base = Est(**kw).fit(X, y)
    pal = Est(**kw, solver_kwargs=PALLAS).fit(X, y)
    c0 = np.asarray(base.coef_, float)
    c1 = np.asarray(pal.coef_, float)
    scale = max(np.abs(c0).max(), 1e-12)
    assert np.abs(c1 - c0).max() / scale < 5e-3
    flipped = (np.abs(c0) > 1e-6) != (np.abs(c1) > 1e-6)
    assert (np.abs(c0)[flipped] < 1e-3 * scale).all()
    assert (np.abs(c1)[flipped] < 1e-3 * scale).all()


@pytest.mark.parametrize("name,maker,Est", [
    ("logistic", make_classification, LogisticRegression),
    ("normal", make_regression, LinearRegression),
    ("poisson", make_counts, PoissonRegression),
])
def test_fused_newton_matches_xla(name, maker, Est):
    """Newton through the fused value+grad+Hessian kernel (one X pass
    for its whole data touch) matches the XLA path."""
    X, y = maker(n_samples=3000, n_features=20, random_state=0)
    base = Est(solver="newton", max_iter=40, tol=1e-9).fit(X, y)
    pal = Est(solver="newton", max_iter=40, tol=1e-9,
              solver_kwargs=PALLAS).fit(X, y)
    np.testing.assert_allclose(pal.coef_, base.coef_, atol=5e-4)


def test_newton_tile_budget():
    from dask_ml_tpu.ops.pallas_fused import glm_newton_tile

    assert glm_newton_tile(100_000, 128, 4) is not None
    assert glm_newton_tile(100_000, 2000, 4) is None  # (d,d) too big
