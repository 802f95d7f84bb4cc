"""GLM parity tests vs scikit-learn (SURVEY.md §4: sklearn is the oracle).

Mirrors the reference's ``tests/linear_model/test_glm.py`` strategy: fit the
distributed estimator on sharded data, fit sklearn in memory, compare
coefficients / predictions.
"""

import numpy as np
import pytest
import sklearn.linear_model as sklm

from dask_ml_tpu.linear_model import (
    LinearRegression,
    LogisticRegression,
    PoissonRegression,
)

SOLVERS_SMOOTH = ["lbfgs", "newton", "gradient_descent", "admm", "proximal_grad"]


@pytest.mark.parametrize("solver", SOLVERS_SMOOTH)
def test_logistic_l2_parity(xy_classification, solver):
    X, y = xy_classification
    ours = LogisticRegression(solver=solver, C=1.0, max_iter=500, tol=1e-7)
    ours.fit(X, y)
    ref = sklm.LogisticRegression(C=1.0, solver="lbfgs", max_iter=2000, tol=1e-10)
    ref.fit(X, y)
    atol = 0.03 if solver in ("admm", "gradient_descent", "proximal_grad") else 0.01
    np.testing.assert_allclose(ours.coef_, ref.coef_, atol=atol)
    np.testing.assert_allclose(ours.intercept_, ref.intercept_, atol=atol)
    assert ours.score(X, y) == pytest.approx(ref.score(X, y), abs=0.02)


def test_logistic_predict_api(xy_classification):
    X, y = xy_classification
    clf = LogisticRegression(solver="lbfgs", max_iter=200).fit(X, y)
    proba = clf.predict_proba(X)
    assert proba.shape == (len(y), 2)
    np.testing.assert_allclose(proba.sum(1), 1.0, atol=1e-5)
    pred = clf.predict(X)
    assert set(np.unique(pred)) <= set(clf.classes_)
    assert clf.score(X, y) > 0.8


def test_logistic_l1_sparsity(xy_classification):
    X, y = xy_classification
    clf = LogisticRegression(
        solver="proximal_grad", penalty="l1", C=0.01, max_iter=2000, tol=1e-9
    ).fit(X, y)
    # penalty="l1" must be explicit: modern sklearn IGNORES l1_ratio
    # under the default penalty="l2" (with only a warning), silently
    # turning the oracle into a dense L2 fit
    ref = sklm.LogisticRegression(
        penalty="l1", C=0.01, solver="saga", max_iter=5000, tol=1e-10
    ).fit(X, y)
    np.testing.assert_allclose(ours_zero := (np.abs(clf.coef_) < 1e-6),
                               np.abs(ref.coef_) < 1e-6)
    np.testing.assert_allclose(clf.coef_, ref.coef_, atol=0.02)


def test_logistic_admm_l1(xy_classification):
    X, y = xy_classification
    clf = LogisticRegression(
        solver="admm", penalty="l1", C=0.01, max_iter=400, tol=1e-5
    ).fit(X, y)
    # explicit penalty="l1" — see test_logistic_l1_sparsity
    ref = sklm.LogisticRegression(
        penalty="l1", C=0.01, solver="saga", max_iter=5000, tol=1e-10
    ).fit(X, y)
    np.testing.assert_allclose(clf.coef_, ref.coef_, atol=0.03)


@pytest.mark.parametrize("solver", ["lbfgs", "newton"])
def test_linear_regression_parity(xy_regression, solver):
    X, y = xy_regression
    ours = LinearRegression(
        solver=solver, penalty="none", max_iter=500, tol=1e-8
    ).fit(X, y)
    ref = sklm.LinearRegression().fit(X, y)
    np.testing.assert_allclose(ours.coef_, ref.coef_, atol=0.05, rtol=1e-3)
    np.testing.assert_allclose(ours.intercept_, ref.intercept_, atol=0.05)
    assert ours.score(X, y) == pytest.approx(ref.score(X, y), abs=1e-3)


def test_poisson_parity():
    rng = np.random.RandomState(0)
    X = rng.randn(400, 5)
    beta = np.array([0.3, -0.2, 0.1, 0.0, 0.4])
    y = rng.poisson(np.exp(X @ beta + 0.5)).astype(np.float64)
    alpha = 1e-4
    ours = PoissonRegression(
        solver="lbfgs", C=1.0 / (alpha * len(y)), max_iter=500, tol=1e-8
    ).fit(X, y)
    ref = sklm.PoissonRegressor(alpha=alpha, max_iter=2000, tol=1e-10).fit(X, y)
    np.testing.assert_allclose(ours.coef_, ref.coef_, atol=0.01)
    np.testing.assert_allclose(ours.intercept_, ref.intercept_, atol=0.01)


def test_clone_and_get_params():
    from sklearn.base import clone

    clf = LogisticRegression(C=2.0, solver="lbfgs")
    p = clf.get_params()
    assert p["C"] == 2.0
    c2 = clone(clf)
    assert c2.get_params()["C"] == 2.0


def test_warm_start(xy_classification):
    X, y = xy_classification
    clf = LogisticRegression(solver="lbfgs", max_iter=300, warm_start=True)
    clf.fit(X, y)
    c1 = clf.coef_.copy()
    clf.fit(X, y)  # warm restart from optimum: should stay there
    np.testing.assert_allclose(clf.coef_, c1, atol=1e-3)


def test_bfloat16_config_parity(xy_classification):
    """config.dtype='bfloat16' (MXU fast path) must match the f32 fit to
    within bf16 rounding on a well-conditioned problem."""
    from dask_ml_tpu import config

    X, y = xy_classification
    f32 = LogisticRegression(solver="lbfgs", max_iter=100).fit(X, y)
    with config.set(dtype="bfloat16"):
        bf16 = LogisticRegression(solver="lbfgs", max_iter=100).fit(X, y)
    assert abs(f32.score(X, y) - bf16.score(X, y)) < 0.02
    denom = np.linalg.norm(f32.coef_) + 1e-12
    assert np.linalg.norm(f32.coef_ - bf16.coef_) / denom < 0.15


def test_class_weight_raises_not_silently_ignored():
    from dask_ml_tpu.datasets import make_classification
    from dask_ml_tpu.linear_model import LogisticRegression

    X, y = make_classification(n_samples=500, n_features=5, random_state=0)
    with pytest.raises(ValueError, match="class_weight"):
        LogisticRegression(solver="lbfgs",
                           class_weight="balanced").fit(X, y)
    # None stays allowed
    LogisticRegression(solver="lbfgs", max_iter=5).fit(X, y)


@pytest.mark.parametrize("flavor", ["xla", "fused"])
def test_lbfgs_counts_objective_evaluations(flavor):
    """``solver_info_["n_evals"]`` is the number of times the objective ran
    (the first ``value_and_grad`` plus every zoom line-search step): the
    loop's own counter equals a Python counter around the loss when the
    same loop runs un-jitted, and the jitted solver reports the same."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import optax

    from dask_ml_tpu.models.solvers import solvers as S

    rng = np.random.RandomState(0)
    n, d = 96, 4
    X = jnp.asarray(rng.randn(n, d), jnp.float32)
    y = jnp.asarray(rng.rand(n) < 1 / (1 + np.exp(-2 * np.asarray(X[:, 0]))),
                    jnp.float32)
    mask, pmask = jnp.ones(n, jnp.float32), jnp.ones(d, jnp.float32)
    lam, tol, max_iter = jnp.float32(1e-2), 1e-4, 30
    beta0 = jnp.zeros(d, jnp.float32)
    kwargs = {}
    if flavor == "fused":
        from dask_ml_tpu.parallel.mesh import device_mesh

        kwargs = dict(use_pallas=True, pallas_interpret=True,
                      mesh=device_mesh(devices=jax.devices()[:1]))
    beta, info = S.lbfgs(X, y, mask, n, beta0, "logistic", "l2", lam, pmask,
                         max_iter=max_iter, tol=tol, **kwargs)
    assert info["fused"] is (flavor == "fused")
    assert info["n_iter"] < max_iter
    assert info["n_evals"] >= info["n_iter"] + 1

    loss = partial(S._smooth_loss, X=X, y=y, mask=mask, n_rows=n, lam=lam,
                   pmask=pmask, l1_ratio=0.5, family="logistic", reg="l2")
    ran = []

    def counted(b):
        ran.append(1)
        return loss(b)

    opt = optax.lbfgs(memory_size=10)
    carry = (beta0, opt.init(beta0), jnp.asarray(jnp.inf, jnp.float32), 0,
             jnp.zeros((), jnp.int32))
    with jax.disable_jit():
        out = S._lbfgs_loop(counted, carry, jnp.asarray(max_iter),
                            jnp.asarray(tol, jnp.float32), 10, False)
    assert int(out[4]) == len(ran)
    assert (info["n_iter"], info["n_evals"]) == (int(out[3]), len(ran))
    np.testing.assert_allclose(np.asarray(beta), np.asarray(out[0]),
                               atol=1e-4)
