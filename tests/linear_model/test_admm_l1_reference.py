"""``LogisticRegression(solver="admm", penalty="l1")`` — upstream's default
solver with the penalty it exists for — against the plain float32 reference
``models/solvers/reference_l1.py`` on seeded data, small sizes, the suite's
virtual CPU devices: the optimum (KKT residual, distance to the reference's
own proximal-gradient optimum), the SHARE (a mesh of 1 and a mesh of 4 reach
the same optimum and support; on a mesh of 4 the program follows the
reference's plain 4-block consensus ADMM iterate by iterate, and does not
follow it run wrongly), exact zeros and an unpenalised intercept, the
counters on ``fit.solve`` and in ``solver_info_``, one dispatch and one
fetch a solve, and the blocked local step's sums. A CPU run gives counts and
correctness, never a time."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dask_ml_tpu import config, observability as obs
from dask_ml_tpu.linear_model import LogisticRegression
from dask_ml_tpu.models.solvers import reference_l1 as ref
from dask_ml_tpu.models.solvers import solvers as S
from dask_ml_tpu.parallel import as_sharded
from dask_ml_tpu.parallel.mesh import device_mesh

TOL = 1e-4
# the stop rule's ceiling for ANY sound ADMM stopped at tol ((1 + L) tol <
# 1.3 tol in the 2-norm: benchmark/tolerances_l1.py's derivation); the
# cell's own limit is set from its chip readings, tighter
KKT_BAND = 2.0 * TOL
LAM = 2.0 ** -6
SHAPES = {"8192x32": (8192, 32, 4), "4096x256": (4096, 256, 32)}


@pytest.fixture(scope="module")
def problems():
    """{shape name: (X, y, the sparse teacher's support)}: Gaussian rows, a
    unit-norm teacher with ``k`` entries of +-1/sqrt(k), logits 2 x.t + 0.3
    (a real intercept)."""
    out = {}
    for name, (n, d, k) in SHAPES.items():
        rng = np.random.default_rng([36, n, d])
        X = rng.standard_normal((n, d)).astype(np.float32)
        beta = np.zeros(d, np.float32)
        beta[rng.choice(d, k, replace=False)] = \
            rng.choice([-1.0, 1.0], k) / np.sqrt(k)
        y = (rng.random(n) < 1.0 / (1.0 + np.exp(-2.0 * (X @ beta) - 0.3))
             ).astype(np.float32)
        out[name] = (X, y, beta != 0)
    return out


def _fit(X, y, n_dev, **kw):
    mesh = device_mesh(devices=jax.devices()[:n_dev])
    kw = {"tol": TOL, "max_iter": 100, **kw}
    clf = LogisticRegression(solver="admm", penalty="l1",
                             C=1.0 / (LAM * X.shape[0]), **kw)
    clf.fit(as_sharded(X, mesh=mesh), as_sharded(y, mesh=mesh))
    return clf


def _point(clf):
    return (np.float32(np.ravel(clf.coef_)),
            np.float32(np.ravel(clf.intercept_)[0]))


@pytest.fixture(scope="module")
def fitted(problems):
    """{(shape, mesh size): fitted estimator}, each fitted once."""
    return {(name, m): _fit(X, y, m)
            for name, (X, y, _) in problems.items() for m in (1, 4)}


@pytest.fixture(scope="module")
def optima(problems, fitted):
    """{shape: the reference's own optimum (coef, b, info)}, by proximal
    gradient from the one-device fit."""
    return {name: ref.optimum(X, y, LAM, *_point(fitted[name, 1]))
            for name, (X, y, _) in problems.items()}


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_estimator_against_the_reference(problems, fitted, optima, shape,
                                         n_dev):
    X, y, _ = problems[shape]
    clf = fitted[shape, n_dev]
    info = clf.solver_info_
    assert clf.n_iter_ < 100
    assert info["primal_residual"] <= TOL and info["dual_residual"] <= TOL
    coef, b = _point(clf)
    assert float(ref.kkt(coef, b, X, y, LAM).max()) <= KKT_BAND
    c_opt, b_opt, opt = optima[shape]
    assert opt["kkt"] <= 1e-6              # far below the band
    excess = ref.objective(coef, b, X, y, LAM) \
        - ref.objective(c_opt, b_opt, X, y, LAM)
    assert -5e-7 <= excess <= KKT_BAND ** 2 / (2 * 0.02) + 5e-7
    # a KKT residual r puts the point within r / mu of the optimum
    assert float(np.max(np.abs(coef - c_opt))) <= KKT_BAND / 0.05
    assert np.array_equal(coef != 0, c_opt != 0)


@pytest.mark.parametrize("shape", list(SHAPES))
def test_meshes_of_one_and_four_reach_the_same_optimum(fitted, shape):
    (c1, b1), (c4, b4) = _point(fitted[shape, 1]), _point(fitted[shape, 4])
    assert np.array_equal(c1 != 0, c4 != 0)
    assert float(np.max(np.abs(c1 - c4))) <= 2 * KKT_BAND / 0.05
    assert abs(float(b1 - b4)) <= 2 * KKT_BAND / 0.05
    # one block is not four: the consensus over more blocks takes longer
    assert fitted[shape, 4].n_iter_ > fitted[shape, 1].n_iter_
    assert fitted[shape, 1].solver_info_["intercept"] == "scalar"


@pytest.fixture(scope="module")
def reference_paths(problems):
    """The reference's 4-block ADMM on the small problem: run rightly to its
    stop, and six outer iterations of each wrong way."""
    X, y, _ = problems["8192x32"]
    right = ref.admm(X, y, LAM, 4, tol=TOL)
    wrong = {f: ref.admm(X, y, LAM, 4, tol=TOL, max_iter=6, fault=f)
             for f in ref.FAULTS}
    return right, wrong


@pytest.mark.parametrize("k", [1, 2, 3, 5])
def test_iterates_follow_the_reference_admm(problems, reference_paths, k):
    """Iterate ``k`` of the program on a mesh of 4 (``max_iter=k``: the
    count is an operand, one program) is the reference's 4-block iterate."""
    X, y, _ = problems["8192x32"]
    right, _ = reference_paths
    coef, b = _point(_fit(X, y, 4, max_iter=k))
    assert float(np.max(np.abs(np.r_[coef, b] - right["z_path"][k - 1]))) \
        <= 2e-6


def test_the_whole_solve_is_the_reference_admm(fitted, reference_paths):
    right, _ = reference_paths
    clf = fitted["8192x32", 4]
    assert clf.n_iter_ == right["n_iter"]
    assert clf.solver_info_["rho"] == right["rho"]
    assert float(np.max(np.abs(_point(clf)[0] - right["coef"]))) <= 5e-6
    assert clf.solver_info_["dual_residual"] == pytest.approx(
        right["dual_residual"], rel=1e-2)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_iterates_do_not_follow_the_reference_run_wrongly(
        problems, reference_paths, fault):
    """Each wrong way is off the program's path by far more than the 2e-6
    the right run is held to: a wrong threshold or a penalised intercept by
    1e-3 and more; ONE Newton step a local solve by 7.9e-5 at the third
    iterate (a first step from zero lands near the local optimum, and
    inexact consensus ADMM heals itself: the fault of the four that changes
    the path, not the fixed point); ``bf16_design`` by 1.8e-5 (the design
    and the coefficients rounded to 8 bits inside every product, the
    roundings averaged over 8,192 rows)."""
    X, y, _ = problems["8192x32"]
    _, wrong = reference_paths
    coef, b = _point(_fit(X, y, 4, max_iter=3))
    off = float(np.max(np.abs(np.r_[coef, b] - wrong[fault]["z_path"][2])))
    assert off >= {"bf16_design": 1e-5, "one_local_step": 5e-5}.get(fault,
                                                                    1e-3)


@pytest.mark.parametrize("fault", ["penalised_intercept", "no_1_over_n"])
def test_wrong_fixed_points_fail_the_kkt_band(problems, fault):
    X, y, _ = problems["8192x32"]
    res = ref.admm(X, y, LAM, 4, tol=TOL, fault=fault)
    assert float(ref.kkt(res["coef"], res["intercept"], X, y, LAM).max()) \
        > 10 * KKT_BAND


@pytest.mark.parametrize("shape", list(SHAPES))
def test_exact_zeros_and_an_unpenalised_intercept(problems, fitted, shape):
    X, y, support = problems[shape]
    clf = fitted[shape, 1]
    coef, b = _point(clf)
    assert clf.solver_info_["nnz"] == np.count_nonzero(coef) < coef.size
    assert np.all(coef[support] != 0)        # the teacher's entries live
    assert np.count_nonzero(coef[~support] == 0.0) >= 0.8 * (~support).sum()
    assert abs(float(b)) > 0.05              # a real offset
    # its gradient entry vanishes; a penalised one would rest at +-lam
    _, gb = ref.gradient(coef, b, X, y)
    assert abs(gb) <= KKT_BAND < LAM / 10


@pytest.mark.parametrize("n_dev", [1, 4])
def test_counters_on_the_span_and_in_solver_info(problems, n_dev):
    X, y, _ = problems["8192x32"]
    obs.reset_recent_spans()
    with config.set(obs_programs=True):
        before = {r["program"]: int(r["calls"])
                  for r in obs.programs_snapshot()}
        clf = _fit(X, y, n_dev)
        after = {r["program"]: int(r["calls"])
                 for r in obs.programs_snapshot()}
        ring = obs.recent_spans()
    obs.reset_recent_spans()
    solve = [r for r in ring if r["span"] == "fit.solve"][-1]
    root = [r for r in ring if r["span"] == "fit"][-1]
    info = clf.solver_info_
    for k in ("n_iter", "local_steps", "primal_residual", "dual_residual",
              "rho", "nnz", "intercept", "local_step"):
        assert solve[k] == info[k], k
    assert root["n_iter"] == info["n_iter"] == clf.n_iter_
    assert info["n_iter"] <= info["local_steps"] <= 8 * info["n_iter"]
    assert info["local_step"] == "xla_blocked"
    assert clf.fit_dtype_ == "float32"
    # one dispatch of glm.admm and one fetch a solve
    assert after["glm.admm"] - before.get("glm.admm", 0) == 1
    assert solve["dispatches"] == 1 and solve["fetches"] == 1
    assert solve["fetch_bytes"] == 4 * (X.shape[1] + 1 + 5)
    # lam, pmask, beta0, rho, tol, ... ride in with the dispatch
    assert solve["host_operands"] >= 8


def test_local_steps_stop_early_once_warm(problems):
    """``local_iter`` is a ceiling: a solve ends after the step whose Newton
    decrement fell to ``tol**2``, two or three steps once warm — to the
    point the reference's ADMM reaches with local solves run to
    convergence, in the same outer count."""
    X, y, _ = problems["8192x32"]
    early = _fit(X, y, 1)
    exact = ref.admm(X, y, LAM, 1, tol=TOL)
    steps, n_iter = early.solver_info_["local_steps"], early.n_iter_
    assert n_iter < steps < 3 * n_iter
    assert n_iter == exact["n_iter"] < exact["local_steps"]
    assert float(np.max(np.abs(_point(early)[0] - exact["coef"]))) <= 5e-6
    # the ceiling holds: one step a solve is one step a solve
    one = _fit(X, y, 1, solver_kwargs={"local_iter": 1})
    assert one.solver_info_["local_steps"] == one.n_iter_


@pytest.mark.parametrize("intercept", [True, False])
@pytest.mark.parametrize("rows", [None, 1024, 3072])
def test_blocked_statistics_are_the_plain_sums(rows, intercept, monkeypatch):
    """The blocked loop (one block, eight whole blocks, two blocks and a
    tail of 2,048 rows) against the formulas written out; the mask drops the
    padding rows."""
    if rows:
        monkeypatch.setattr(S, "_NEWTON_BLOCK_BYTES", rows * 16 * 4)
        assert S._newton_block_rows(8192, 16) == rows
    rng = np.random.default_rng(5)
    n, d = 8192, 16
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (rng.random(n) < 0.4).astype(np.float32)
    mask = (np.arange(n) < n - 100).astype(np.float32)
    b = (0.3 * rng.standard_normal(d + intercept)).astype(np.float32)
    g, H = jax.jit(S._newton_stats, static_argnums=(4, 5))(
        X, y, mask, b, "logistic", intercept)
    X1 = np.c_[X, np.ones(n)] if intercept else X.astype(np.float64)
    p = 1.0 / (1.0 + np.exp(-(X1 @ b)))
    np.testing.assert_allclose(g, X1.T @ ((p - y) * mask), rtol=2e-4,
                               atol=2e-3)
    np.testing.assert_allclose(
        H, (X1 * (p * (1 - p) * mask)[:, None]).T @ X1, rtol=2e-4, atol=2e-3)
    assert np.array_equal(np.asarray(H), np.asarray(H).T)


@pytest.mark.parametrize("intercept", [True, False])
def test_kernel_statistics_are_the_blocked_ones(intercept):
    """The fused kernel (interpret mode) against XLA's blocked loop: the
    vector sums to float32's rounding of a sum over 4,096 rows, the Gram to
    the bfloat16 rounding the kernel states (the blocked loop is exact on
    the CPU)."""
    rng = np.random.default_rng(6)
    n, d = 4096, 128
    X = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    y = jnp.asarray(rng.random(n) < 0.4, jnp.float32)
    mask = jnp.asarray(np.arange(n) < n - 100, jnp.float32)
    b = jnp.asarray(0.3 * rng.standard_normal(d + intercept), jnp.float32)
    g0, H0 = S._newton_stats(X, y, mask, b, "logistic", intercept)
    g1, H1 = S._newton_stats_pallas(
        X, mask, b, S._label_sums(X, y, mask, intercept), "logistic",
        intercept, True)
    scale = float(jnp.max(jnp.abs(H0)))
    assert float(jnp.max(jnp.abs(g1 - g0))) <= 2e-5 * scale
    assert float(jnp.max(jnp.abs(H1 - H0))) <= 2e-3 * scale
    assert np.array_equal(np.asarray(H1), np.asarray(H1).T)


@pytest.mark.parametrize("n_dev", [1, 4])
def test_the_kernel_path_reaches_the_blocked_path_s_point(problems, fitted,
                                                          n_dev):
    X, y, _ = problems["4096x256"]
    clf = _fit(X, y, n_dev, solver_kwargs={"use_pallas": True,
                                           "pallas_interpret": True})
    plain = fitted["4096x256", n_dev]
    assert clf.solver_info_["local_step"] == "pallas_newton_stats"
    assert clf.solver_info_["fused"] is True
    assert plain.solver_info_["fused"] is False
    assert clf.n_iter_ == plain.n_iter_
    assert clf.solver_info_["local_steps"] == plain.solver_info_[
        "local_steps"]
    assert float(np.max(np.abs(_point(clf)[0] - _point(plain)[0]))) <= 1e-5


def test_the_kernel_refuses_rows_that_are_not_whole_tiles():
    from dask_ml_tpu.ops import pallas_fused as pf

    with pytest.raises(ValueError, match="whole row tiles"):
        pf.fused_glm_newton_stats(jnp.zeros((1100, 8)), 1100, jnp.zeros(8),
                                  0.0, "logistic", interpret=True)
    mesh = device_mesh(devices=jax.devices()[:1])
    # off the TPU the gate keeps XLA's loop; an explicit request is honoured
    assert S._resolve_admm_pallas(None, mesh, "logistic",
                                  jnp.zeros((2048, 8))) is False
    assert S._resolve_admm_pallas(True, mesh, "logistic", None) is True


def test_block_rows_never_make_an_x_sized_temporary():
    assert S._newton_block_rows(4194304, 256) == 32768      # 32 MiB of f32
    assert S._newton_block_rows(2048, 256) == 2048          # one block
    assert S._newton_block_rows(10 ** 7, 8) == 1024 ** 2


def test_the_benchmark_keeps_a_copy_of_the_reference():
    """``benchmark/references/logreg_l1.py`` is this package's file below
    its header (a later PR that edits one must see the other)."""
    here = os.path.dirname(os.path.abspath(ref.__file__))
    root = os.path.dirname(os.path.dirname(os.path.dirname(here)))
    with open(ref.__file__) as f:
        mine = f.read()
    with open(os.path.join(root, "benchmark", "references",
                           "logreg_l1.py")) as f:
        theirs = f.read()
    body = lambda s: s[s.index('"""', 3):]          # noqa: E731
    assert body(mine) == body(theirs)


def test_multiclass_admm_still_takes_the_column(problems):
    """The one-vs-rest path appends the ones column itself and calls the
    solver without ``intercept``: ADMM then treats every column alike."""
    rng = np.random.default_rng(9)
    X = rng.standard_normal((600, 6)).astype(np.float32)
    y = np.argmax(X[:, :3] + 0.3 * rng.standard_normal((600, 3)), axis=1)
    clf = LogisticRegression(solver="admm", penalty="l1", C=0.5,
                             max_iter=200).fit(X, y.astype(np.float32))
    assert clf.coef_.shape == (3, 6)
    assert (clf.predict(X) == y).mean() > 0.8


def test_eta_and_gradient_run_at_the_stated_precision():
    """The lowered program asks for HIGHEST on eta, the residual products
    and the border, and for the default on the Gram alone."""
    mesh = device_mesh(devices=jax.devices()[:1])
    A = jax.ShapeDtypeStruct
    f32 = jnp.float32
    text = S._admm_run.__wrapped_jit__.lower(
        A((2048, 16), f32), A((2048,), f32), A((2048,), f32), 2048,
        A((17,), f32), A((), f32), A((17,), f32), 0.5, A((), f32),
        A((), jnp.int32), A((), f32), family="logistic",
        reg="l1", local_iter=8, mesh=mesh, intercept=True,
        use_pallas=False).as_text()
    dots = [ln for ln in text.splitlines() if "dot_general" in ln]
    highest = [ln for ln in dots if "HIGHEST" in ln]
    assert len(highest) == 3                   # eta, X^T r, X^T w
    (gram,) = [ln for ln in dots if "HIGHEST" not in ln]
    assert "tensor<16x16xf32>" in gram
