"""Fused GLM value+grad Pallas kernel (ops/pallas_fused.py): one X pass
per value_and_grad. Interpret-mode parity vs the XLA loss across
families, solvers, and dtypes (the kernel auto-engages compiled on real
TPU; scripts/tpu_smoke.py asserts the same parity there)."""

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


def test_fused_glm_kernel_direct():
    """Kernel-level check against the autodiff reference, including the
    padded-tail masking."""
    import jax
    import jax.numpy as jnp

    from dask_ml_tpu.models.solvers.families import get_family
    from dask_ml_tpu.ops.pallas_fused import fused_glm_value_grad

    rng = np.random.RandomState(2)
    n, d = 391, 13   # ragged on purpose: tile padding + masked tail
    X = rng.randn(n, d).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    beta = rng.randn(d).astype(np.float32) * 0.1
    n_valid = 350    # rows past this are padding

    def ref(b):
        eta = X @ b
        m = (np.arange(n) < n_valid).astype(np.float32)
        return jnp.sum(get_family("logistic").pointwise(
            jnp.asarray(eta), jnp.asarray(y)) * m)

    v_ref = float(ref(jnp.asarray(beta)))
    g_ref = np.asarray(jax.grad(lambda b: ref(b))(jnp.asarray(beta)))
    v, g = fused_glm_value_grad(X, n_valid, y, beta, family="logistic",
                                interpret=True)
    np.testing.assert_allclose(float(v), v_ref, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g), g_ref, rtol=1e-4, atol=1e-5)


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
