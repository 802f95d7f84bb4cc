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


# -- where the intercept lives (PR 28) ----------------------------------------
# lbfgs / gradient_descent / proximal_grad carry it as the last entry of
# beta over an X as wide as the features ("scalar"); Newton, ADMM, the
# one-vs-rest and C-grid programs as a ones column of X ("column").

_FAMILIES = {
    "logistic": (LogisticRegression, "make_classification"),
    "normal": (LinearRegression, "make_regression"),
    "poisson": (PoissonRegression, "make_counts"),
}


def _family_data(family, n=1200, d=8, seed=3):
    from dask_ml_tpu import datasets

    Est, maker = _FAMILIES[family]
    X, y = getattr(datasets, maker)(n_samples=n, n_features=d,
                                    random_state=seed)
    return Est, X, y


@pytest.mark.parametrize("family", sorted(_FAMILIES))
@pytest.mark.parametrize("solver", ["lbfgs", "gradient_descent",
                                    "proximal_grad"])
def test_scalar_intercept_matches_column(solver, family):
    """One problem, the scalar form against Newton's column form:
    coef_ and intercept_ agree, and each fit says which form it ran."""
    Est, X, y = _family_data(family)
    col = Est(solver="newton", max_iter=100, tol=1e-9).fit(X, y)
    sca = Est(solver=solver, max_iter=3000, tol=1e-8).fit(X, y)
    assert col.solver_info_["intercept"] == "column"
    assert sca.solver_info_["intercept"] == "scalar"
    assert abs(float(np.ravel(col.intercept_)[0])) > 1e-3  # a real offset
    atol = 2e-3 if solver == "lbfgs" else 2e-2
    np.testing.assert_allclose(np.ravel(sca.coef_), np.ravel(col.coef_),
                               atol=atol)
    np.testing.assert_allclose(np.ravel(sca.intercept_),
                               np.ravel(col.intercept_), atol=atol)


@pytest.mark.parametrize("solver,form", [
    ("lbfgs", "scalar"), ("gradient_descent", "scalar"),
    ("proximal_grad", "scalar"), ("newton", "column"), ("admm", "scalar")])
@pytest.mark.parametrize("fit_intercept", [True, False])
def test_solver_info_names_the_intercept_form(solver, form, fit_intercept):
    Est, X, y = _family_data("logistic", n=400, d=5)
    clf = Est(solver=solver, max_iter=30, fit_intercept=fit_intercept)
    clf.fit(X, y)
    assert clf.solver_info_["intercept"] == (form if fit_intercept
                                             else "none")
    assert clf.coef_.shape == (1, 5)
    if not fit_intercept:
        assert float(np.ravel(clf.intercept_)[0]) == 0.0


def test_fit_solve_span_carries_the_intercept_form():
    from dask_ml_tpu import config
    from dask_ml_tpu.observability import recent_spans

    Est, X, y = _family_data("logistic", n=400, d=5)
    with config.set(obs_programs=True):
        Est(solver="lbfgs", max_iter=20).fit(X, y)
        solves = [s for s in recent_spans() if s["span"] == "fit.solve"]
    assert solves and solves[-1]["intercept"] == "scalar"


def test_column_solvers_refuse_the_scalar_form():
    from dask_ml_tpu.models.solvers import solvers as S

    with pytest.raises(ValueError, match="ones column"):
        S.solve("newton", intercept=True)
    assert set(S.SCALAR_INTERCEPT_SOLVERS) < set(S.SOLVERS)


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_scalar_intercept_warm_start(family):
    """The second fit of a warm-started estimator starts from
    (coef_, intercept_) of the first: at the optimum it stops at once
    and keeps both."""
    Est, X, y = _family_data(family)
    # targets in the hundreds: f32 gradient noise sits above 1e-6 there
    tol = 1e-3 if family == "normal" else 1e-6
    est = Est(solver="lbfgs", max_iter=300, tol=tol, warm_start=True)
    est.fit(X, y)
    c1, b1 = np.ravel(est.coef_).copy(), float(np.ravel(est.intercept_)[0])
    n1 = est.n_iter_
    est.fit(X, y)
    assert est.n_iter_ <= 2 < n1
    np.testing.assert_allclose(np.ravel(est.coef_), c1, atol=1e-4)
    assert float(np.ravel(est.intercept_)[0]) == pytest.approx(b1, abs=1e-4)


def test_scalar_intercept_checkpointed_lbfgs_resumes(tmp_path, monkeypatch):
    """The chunked lbfgs carries the (d + 1,) beta through its
    checkpoints: killed after the second chunk, the fit resumes at
    iteration 8 and ends where the uninterrupted fit ends, intercept
    included; a finished solve leaves no checkpoint."""
    import os

    from dask_ml_tpu.utils import checkpoint as ckpt

    Est, X, y = _family_data("logistic", n=600, d=6)
    path = str(tmp_path / "ck")
    kw = dict(solver="lbfgs", max_iter=16, tol=0.0)
    ckw = dict(kw, solver_kwargs={"checkpoint_path": path,
                                  "checkpoint_every": 4})
    ref = Est(**kw).fit(X, y)

    real_save, saves = ckpt.save_pytree, {"n": 0}

    def dying_save(p, tree, force=True):
        real_save(p, tree, force=force)
        saves["n"] += 1
        if saves["n"] == 2:
            raise KeyboardInterrupt("injected kill")

    monkeypatch.setattr(ckpt, "save_pytree", dying_save)
    with pytest.raises(KeyboardInterrupt):
        Est(**ckw).fit(X, y)
    monkeypatch.setattr(ckpt, "save_pytree", real_save)
    assert os.path.exists(path)

    clf = Est(**ckw).fit(X, y)
    assert clf.solver_info_["resumed_from"] == 8
    assert clf.solver_info_["n_iter"] == 16
    assert clf.solver_info_["intercept"] == "scalar"
    np.testing.assert_allclose(clf.coef_, ref.coef_, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(clf.intercept_, ref.intercept_, rtol=1e-4,
                               atol=1e-6)
    assert not os.path.exists(path)


def test_multiclass_fit_appends_the_column_itself():
    """A 3-class lbfgs fit learns only from prep's label scan that it is
    one: the column prep left out is appended for the one-vs-rest
    program, and every class row equals that class's own binary fit."""
    from dask_ml_tpu import datasets

    X, y = datasets.make_classification(
        n_samples=1500, n_features=8, n_classes=3, n_informative=5,
        random_state=2)
    ovr = LogisticRegression(solver="lbfgs", max_iter=300, tol=1e-7)
    ovr.fit(X, y)
    assert ovr.solver_info_["intercept"] == "column"
    assert ovr.coef_.shape == (3, 8) and ovr.intercept_.shape == (3,)
    yh = y.to_numpy()
    for c in range(3):
        one = LogisticRegression(solver="newton", max_iter=100, tol=1e-9)
        one.fit(X, (yh == c).astype(np.float32))
        np.testing.assert_allclose(ovr.coef_[c], one.coef_[0], atol=3e-3)
        np.testing.assert_allclose(ovr.intercept_[c], one.intercept_[0],
                                   atol=3e-3)


@pytest.mark.parametrize("fit_intercept", [True, False])
def test_C_grid_column_form_equals_scalar_fits(fit_intercept):
    """The stacked C-grid program takes the intercept as each block's
    last beta entry, as the plain lbfgs fit does (no ones column); each
    of its clones equals the estimator's own fit at that C."""
    Est, X, y = _family_data("logistic", n=900, d=6)
    Cs = [0.1, 1.0, 10.0]
    base = Est(solver="lbfgs", max_iter=300, tol=1e-7,
               fit_intercept=fit_intercept)
    fitted = base._fit_C_grid(X, y, Cs)
    assert fitted is not None and len(fitted) == 3
    for C, est in zip(Cs, fitted):
        assert est.solver_info_["intercept"] == (
            "scalar" if fit_intercept else "none")
        solo = Est(solver="lbfgs", max_iter=300, tol=1e-7, C=C,
                   fit_intercept=fit_intercept).fit(X, y)
        np.testing.assert_allclose(est.coef_, solo.coef_, atol=3e-3)
        np.testing.assert_allclose(est.intercept_, solo.intercept_,
                                   atol=3e-3)


def _row_sized_ops(jaxpr, n):
    """Names of the equations (nested jaxprs included; the ``jit``
    wrappers themselves left out) that read or write a rank-2 array of
    ``n`` rows: what touches something X-sized."""
    from tests.test_pallas_glm import _avals, _walk_eqns

    return [e.primitive.name for e in _walk_eqns(jaxpr)
            if e.primitive.name not in ("jit", "pjit")
            and any(len(a.shape) == 2 and a.shape[0] == n
                    for a in _avals(e))]


@pytest.mark.parametrize("to_bf16", [True, False])
def test_prepare_fit_scalar_path_builds_no_column(to_bf16):
    """Pins PR 28's finding: on the scalar path prep is the cast (or
    nothing) — nothing X-sized in its jaxpr but that one
    ``convert_element_type``, no ``concatenate`` / ``pad``, X out as wide
    as X in; the column path still appends one."""
    import jax
    import jax.numpy as jnp

    from dask_ml_tpu.models.glm import _prepare_fit

    n, d = 4096, 256
    X = jax.ShapeDtypeStruct((n, d), jnp.float32)
    v = jax.ShapeDtypeStruct((n,), jnp.float32)

    def trace(fit_intercept):
        return jax.make_jaxpr(
            lambda X, y, m: _prepare_fit(
                X, y, m, fit_intercept=fit_intercept, to_bf16=to_bf16,
                encode=True))(X, v, v)

    scalar = trace(False)
    names = sorted(_row_sized_ops(scalar.jaxpr, n))
    assert names == (["convert_element_type"] if to_bf16 else []), names
    assert scalar.out_avals[0].shape == (n, d)
    assert scalar.out_avals[0].dtype == (jnp.bfloat16 if to_bf16
                                         else jnp.float32)
    column = trace(True)
    assert "concatenate" in set(_row_sized_ops(column.jaxpr, n))
    assert column.out_avals[0].shape == (n, d + 1)


@pytest.mark.parametrize("solver,width", [("lbfgs", 0), ("newton", 1)])
def test_solver_sees_X_as_wide_as_the_features(solver, width, monkeypatch):
    """What reaches the solver's program in a fit: an lbfgs fit hands
    ``_lbfgs_chunk`` an (n, d) X beside a (d + 1,) beta and
    ``intercept=True``; a Newton fit an (n, d + 1) X."""
    from dask_ml_tpu.models.solvers import solvers as S

    Est, X, y = _family_data("logistic", n=400, d=5)
    name = {"lbfgs": "_lbfgs_chunk", "newton": "_newton_run"}[solver]
    real, seen = getattr(S, name), {}

    def spy(*a, **kw):
        beta = kw["carry"][0] if solver == "lbfgs" else a[4]
        seen.update(X=a[0].shape, beta=beta.shape, data=a[0],
                    intercept=kw.get("intercept", False))
        return real(*a, **kw)

    monkeypatch.setattr(S, name, spy)
    est = Est(solver=solver, max_iter=5).fit(X, y)
    assert seen["X"][1] == 5 + width
    # an f32 fit in the scalar form leaves X alone: prep returns no X,
    # the solver reads the caller's own array
    assert est.fit_dtype_ == "float32"
    assert (seen["data"] is X.data) is (solver == "lbfgs")
    assert seen["beta"] == (6,)
    assert seen["intercept"] is (solver == "lbfgs")
