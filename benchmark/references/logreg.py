"""Plain float32 ``jax.numpy`` reference for L2-penalised logistic
regression: the yardstick every ``glm`` cell is checked against.

A COPY of ``dask_ml_tpu/models/solvers/reference.py`` (PR 21) kept with the
benchmark so that later PRs, which may edit the program, cannot move it;
plus a plain Newton solve for the reference's own optimum. It shares no code
with the program: softplus negative log-likelihood plus the sklearn-scaled
L2 penalty on the coefficients (never the intercept), written out, every
matmul under ``jax.default_matmul_precision("highest")`` so a TPU multiplies
in f32 instead of its default single bf16 pass. Nothing the timed path uses
is imported from here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def objective(coef, intercept, X, y, lam):
    """mean_i[softplus(eta_i) - y_i * eta_i] + lam/2 * ||coef||^2 with
    ``eta = X @ coef + intercept``; ``y`` in {0, 1}. ``lam`` is the fit's
    ``1 / (C * n_train)``: the penalty is scaled by the rows the model was
    TRAINED on, whatever rows the mean runs over."""
    X = jnp.asarray(X, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    coef = jnp.asarray(coef, jnp.float32)
    with jax.default_matmul_precision("highest"):
        eta = X @ coef + jnp.asarray(intercept, jnp.float32)
    nll = jnp.logaddexp(0.0, eta) - y * eta
    return jnp.mean(nll) + 0.5 * lam * jnp.sum(coef * coef)


@jax.jit
def value_and_grad(coef, intercept, X, y, lam):
    """(objective, d/dcoef, d/dintercept) by autodiff of the plain
    expression."""
    with jax.default_matmul_precision("highest"):
        val, (g_coef, g_b) = jax.value_and_grad(objective, argnums=(0, 1))(
            jnp.asarray(coef, jnp.float32),
            jnp.asarray(intercept, jnp.float32), X, y, lam)
    return val, g_coef, g_b


@jax.jit
def proba(coef, intercept, X):
    """P(y = 1 | x) = sigmoid(X @ coef + intercept), f32 throughout."""
    with jax.default_matmul_precision("highest"):
        eta = jnp.asarray(X, jnp.float32) @ jnp.asarray(coef, jnp.float32) \
            + jnp.asarray(intercept, jnp.float32)
    return jax.nn.sigmoid(eta)


@jax.jit
def _newton_step(beta, X1, y, lam_vec):
    with jax.default_matmul_precision("highest"):
        eta = X1 @ beta
        p = jax.nn.sigmoid(eta)
        n = X1.shape[0]
        g = X1.T @ (p - y) / n + lam_vec * beta
        H = (X1 * (p * (1.0 - p))[:, None]).T @ X1 / n + jnp.diag(lam_vec)
        return beta - jnp.linalg.solve(H, g)


def optimum(X, y, lam, coef0, intercept0, steps=8):
    """The reference's OWN optimum on rows ``X, y``: plain Newton from
    ``(coef0, intercept0)`` — quadratic convergence on a strongly convex
    objective, so 8 steps from a point already near the optimum are far more
    than float32 needs. Returns (coef, intercept)."""
    X = jnp.asarray(X, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    X1 = jnp.concatenate([X, jnp.ones((X.shape[0], 1), jnp.float32)], axis=1)
    d = X.shape[1]
    lam_vec = jnp.concatenate([jnp.full((d,), lam, jnp.float32),
                               jnp.zeros((1,), jnp.float32)])
    beta = jnp.concatenate([jnp.asarray(coef0, jnp.float32).ravel(),
                            jnp.asarray(intercept0, jnp.float32).reshape(1)])
    for _ in range(steps):
        beta = _newton_step(beta, X1, y, lam_vec)
    return beta[:-1], beta[-1]
