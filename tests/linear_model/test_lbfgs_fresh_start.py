"""The L-BFGS carry is born inside its program (PR 33).

A fresh start is a SHAPE of the carry, ``(beta0,)``: the solver's traced
function builds ``(beta0, opt.init(beta0), inf, 0, 0)`` itself
(``solvers._fresh_carry``), where the host used to build it leaf by leaf
with eager launches, and the program hands back what the host reads so
that a solve is one program and one fetch. Held here: (a) a cold solve
compiles exactly one program at each of the five L-BFGS sites; (b) the
fresh-start program and the carry program fed the eagerly built carry
agree to the bit; (c) a checkpointed solve killed after its first chunk —
the fresh-start program — resumes through the carry program to the
uninterrupted result; (d) the ``fit.solve`` span says how often the host
fetched.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dask_ml_tpu import config
from dask_ml_tpu import observability as obs
from dask_ml_tpu.models.solvers import solvers as S

N, D, C, K = 192, 5, 3, 3


def _problem(family="logistic", dtype=jnp.float32, seed=0):
    """A small resident problem on one device: ``(X, y, mask)``."""
    rng = np.random.RandomState(seed)
    X = rng.randn(N, D).astype(np.float32)
    eta = X @ rng.randn(D).astype(np.float32) * 0.5 + 0.2
    if family == "logistic":
        y = (rng.rand(N) < 1 / (1 + np.exp(-eta))).astype(np.float32)
    elif family == "poisson":
        y = rng.poisson(np.exp(0.3 * eta)).astype(np.float32)
    else:
        y = (eta + 0.1 * rng.randn(N)).astype(np.float32)
    return (jnp.asarray(X, dtype), jnp.asarray(y),
            jnp.ones(N, jnp.float32))


def _one_device_mesh():
    from dask_ml_tpu.parallel.mesh import device_mesh

    return device_mesh(devices=jax.devices()[:1])


def _site(site):
    """``() -> (beta, info)`` through the solver's public function at one
    of the five places an L-BFGS solve starts, with every device operand
    built beforehand and every small operand a host value, as the
    estimators hand them over."""
    X, y, mask = _problem()
    pmask = np.ones(D, np.float32)
    lam, tol = np.float32(1e-2), 1e-5
    pallas = dict(use_pallas=True, pallas_interpret=True,
                  mesh=_one_device_mesh())
    if site in ("single_xla", "single_pallas"):
        kw = pallas if site == "single_pallas" else {}
        return lambda: S.solve(
            "lbfgs", X=X, y=y, mask=mask, n_rows=N,
            beta0=np.zeros(D, np.float32), family="logistic", reg="l2",
            lam=lam, pmask=pmask, max_iter=30, tol=tol, **kw)
    codes = np.random.RandomState(1).randint(0, C, N)
    Y = jnp.asarray(np.eye(C, dtype=np.float32)[codes].T)       # (C, n)
    if site in ("one_vs_rest", "multi_pallas"):
        kw = pallas if site == "multi_pallas" else {}
        return lambda: S.solve_multi(
            "lbfgs", X, Y, mask, N, np.zeros((C, D), np.float32),
            "logistic", "l2", lam, pmask, max_iter=30, tol=tol, **kw)
    lams = [1e-3, 1e-2, 1e-1]
    if site == "c_grid":
        return lambda: S.solve_lam_grid(X, y, mask, N, lams, pmask,
                                        "logistic", "l2", max_iter=30,
                                        tol=tol)
    assert site == "c_grid_multi"
    return lambda: S.solve_lam_grid_multi(X, Y, mask, N, lams, pmask,
                                          "logistic", "l2", max_iter=30,
                                          tol=tol)


@pytest.mark.parametrize("site", ["single_xla", "single_pallas",
                                  "one_vs_rest", "multi_pallas", "c_grid",
                                  "c_grid_multi"])
def test_a_cold_solve_compiles_one_program(site):
    """From cold caches one solve is ONE backend compile: no eager
    ``zeros`` / cast / ``tile`` / ``argmax`` in front of the solver's
    program, no packing program behind it."""
    from dask_ml_tpu.plans import plans_reset

    solve = _site(site)
    jax.clear_caches()
    plans_reset()
    with config.set(obs_counters=True):
        before = obs.counters_snapshot().get("recompiles", 0)
        beta, info = solve()
        compiled = obs.counters_snapshot().get("recompiles", 0) - before
    assert compiled == 1, (site, compiled)
    assert isinstance(beta, np.ndarray) and np.isfinite(beta).all()
    assert 0 < info["n_iter"] < 30


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("intercept", [False, True],
                         ids=["none", "scalar"])
@pytest.mark.parametrize("family", ["logistic", "normal", "poisson"])
def test_fresh_start_equals_the_eager_carry(family, intercept, dtype):
    """``carry=(beta0,)`` and the five-element carry built on the host
    (what ``lbfgs()`` built until PR 33) are two programs over one loop:
    every leaf of the carry they return, and the result vector
    ``[*beta, it, gnorm, n_evals]``, are bit-equal."""
    X, y, mask = _problem(family, jnp.dtype(dtype), seed=3)
    w = D + int(intercept)
    beta0 = np.zeros(w, np.float32)
    pmask = np.ones(w, np.float32)
    pmask[-1] = 0.0 if intercept else 1.0

    def run(carry):
        return S._lbfgs_chunk(
            X, y, mask, N, carry=carry, lam=np.float32(1e-2), pmask=pmask,
            l1_ratio=0.5, stop_it=np.int32(25), tol=np.float32(1e-5),
            family=family, reg="l2", intercept=intercept)

    b0 = jnp.asarray(beta0)
    eager = (b0, optax.lbfgs(memory_size=10).init(b0),
             jnp.asarray(jnp.inf, jnp.float32), 0, np.zeros((), np.int32))
    fresh_carry, fresh = jax.device_get(run((beta0,)))
    eager_carry, carried = jax.device_get(run(eager))
    assert jax.tree.structure(fresh_carry) == jax.tree.structure(eager_carry)
    for a, b in zip(jax.tree.leaves(fresh_carry),
                    jax.tree.leaves(eager_carry)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(fresh, carried)
    beta, (it, gnorm, n_evals) = fresh[:-3], fresh[-3:]
    np.testing.assert_array_equal(beta, fresh_carry[0])
    assert (it, gnorm, n_evals) == (fresh_carry[3], fresh_carry[2],
                                    fresh_carry[4])
    assert 0 < it and it + 1 <= n_evals


def test_killed_after_the_first_chunk_resumes_to_the_same_result(
        tmp_path, monkeypatch):
    """The checkpointed solve runs the fresh-start program for its first
    chunk and the carry program after it. Killed right after the first
    save, it resumes from iteration 4 — through a state read back from
    disk, its template from ``jax.eval_shape`` — to what the solve gives
    in one piece."""
    from dask_ml_tpu.utils import checkpoint as ckpt

    X, y, mask = _problem(seed=5)
    pmask = np.ones(D, np.float32)
    path = str(tmp_path / "lbfgs_ckpt")

    def solve(**kw):
        return S.lbfgs(X, y, mask, N, np.zeros(D, np.float32), "logistic",
                       "l2", np.float32(1e-3), pmask, max_iter=12, tol=0.0,
                       **kw)

    whole, whole_info = solve()
    real_save = ckpt.save_pytree

    def dying_save(p, tree, force=True):
        real_save(p, tree, force=force)
        raise KeyboardInterrupt("injected kill")

    monkeypatch.setattr(ckpt, "save_pytree", dying_save)
    with pytest.raises(KeyboardInterrupt):
        solve(checkpoint_path=path, checkpoint_every=4)
    monkeypatch.setattr(ckpt, "save_pytree", real_save)
    assert os.path.exists(path)

    beta, info = solve(checkpoint_path=path, checkpoint_every=4)
    assert info["resumed_from"] == 4
    assert (info["n_iter"], info["n_evals"]) == (12, whole_info["n_evals"])
    np.testing.assert_allclose(np.asarray(beta), whole, rtol=1e-6, atol=1e-8)
    assert not os.path.exists(path)        # a finished solve clears it

    # and never killed, the chunked solve is the same solve
    beta, info = solve(checkpoint_path=path, checkpoint_every=4)
    assert info["resumed_from"] == 0 and info["n_iter"] == 12
    np.testing.assert_allclose(np.asarray(beta), whole, rtol=1e-6, atol=1e-8)


@pytest.mark.parametrize("solver,fetches", [("lbfgs", 1),
                                            ("gradient_descent", 2)])
def test_fit_solve_span_counts_the_fetches(solver, fetches):
    """``fit.solve`` records what the host did around the program:
    ``fetches`` is 1 for an L-BFGS fit (beta and the three scalars leave
    as one vector) and 2 where a solver still packs its scalars in a
    program of its own and fetches beta after it."""
    from dask_ml_tpu import datasets
    from dask_ml_tpu.linear_model import LogisticRegression

    X, y = datasets.make_classification(n_samples=400, n_features=6,
                                        random_state=0)
    with config.set(obs_programs=True):
        clf = LogisticRegression(solver=solver, max_iter=40).fit(X, y)
        rec = [r for r in obs.recent_spans() if r["span"] == "fit.solve"][-1]
    assert rec["fetches"] == fetches
    assert rec["n_iter"] == clf.n_iter_
    if solver == "lbfgs":
        assert rec["n_evals"] == clf.solver_info_["n_evals"] > clf.n_iter_
        assert "fetches" not in clf.solver_info_


def test_multiclass_fit_solve_span_counts_one_fetch():
    from dask_ml_tpu import datasets
    from dask_ml_tpu.linear_model import LogisticRegression

    X, y = datasets.make_classification(
        n_samples=600, n_features=8, n_classes=3, n_informative=5,
        random_state=0)
    with config.set(obs_programs=True):
        clf = LogisticRegression(solver="lbfgs", max_iter=40).fit(X, y)
        rec = [r for r in obs.recent_spans() if r["span"] == "fit.solve"][-1]
    assert rec["fetches"] == 1 and rec["n_iter"] == clf.n_iter_
    assert clf.coef_.shape == (3, 8)


@pytest.mark.parametrize("chunked", [False, True], ids=["whole", "chunks"])
def test_a_converged_start_runs_no_line_search(chunked, tmp_path):
    """The loop stops on the first iterate whose gradient meets tol: a fit
    warm-started at its own answer evaluates the objective once, makes no
    update and reports 0 iterations — also chunked, where a chunk that
    moved nothing must still end the solve."""
    from dask_ml_tpu.datasets import make_classification
    from dask_ml_tpu.linear_model import LogisticRegression

    X, y = make_classification(n_samples=2000, n_features=8, random_state=0)
    done = LogisticRegression(solver="lbfgs", tol=1e-4, max_iter=100).fit(X, y)
    assert done.solver_info_["n_evals"] >= done.n_iter_ + 1
    kw = {"checkpoint_path": str(tmp_path / "ck"),
          "checkpoint_every": 3} if chunked else None
    again = LogisticRegression(solver="lbfgs", tol=1e-4, max_iter=100,
                               warm_start=True, solver_kwargs=kw)
    again.coef_, again.intercept_ = done.coef_, done.intercept_
    again.fit(X, y)
    assert again.n_iter_ == 0
    assert again.solver_info_["grad_norm"] <= 1e-4
    np.testing.assert_array_equal(again.coef_, done.coef_)
    if not chunked:
        assert again.solver_info_["n_evals"] == 1
