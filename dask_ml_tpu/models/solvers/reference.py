"""Plain float32 ``jax.numpy`` reference for the logistic-regression
objective — what every fit path (resident XLA, resident Pallas, streamed
XLA, streamed Pallas, any mesh) is checked against on a row sample.

Deliberately shares NO code with the solvers: no family table, no
regularizer table, no masks, no kernels. Softplus negative log-likelihood
plus the sklearn-scaled L2 penalty on the coefficients (never the
intercept), written out, with every matmul under
``jax.default_matmul_precision("highest")`` so a TPU multiplies in f32
instead of its default single bf16 pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def logreg_objective(coef, intercept, X, y, lam):
    """mean_i[softplus(eta_i) - y_i * eta_i] + lam/2 * ||coef||^2 with
    ``eta = X @ coef + intercept``; ``y`` in {0, 1}. ``lam`` is the
    fit's ``1 / (C * n_train)`` — the penalty is scaled by the rows the
    model was TRAINED on, whatever sample the mean runs over."""
    X = jnp.asarray(X, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    coef = jnp.asarray(coef, jnp.float32)
    with jax.default_matmul_precision("highest"):
        eta = X @ coef + jnp.asarray(intercept, jnp.float32)
    nll = jnp.logaddexp(0.0, eta) - y * eta
    return jnp.mean(nll) + 0.5 * lam * jnp.sum(coef * coef)


@jax.jit
def logreg_value_and_grad(coef, intercept, X, y, lam):
    """(objective, d/dcoef, d/dintercept) of :func:`logreg_objective`,
    by autodiff of the plain expression."""
    with jax.default_matmul_precision("highest"):
        val, (g_coef, g_b) = jax.value_and_grad(
            logreg_objective, argnums=(0, 1)
        )(jnp.asarray(coef, jnp.float32),
          jnp.asarray(intercept, jnp.float32), X, y, lam)
    return val, g_coef, g_b


@jax.jit
def logreg_proba(coef, intercept, X):
    """P(y = 1 | x) = sigmoid(X @ coef + intercept), f32 throughout."""
    with jax.default_matmul_precision("highest"):
        eta = jnp.asarray(X, jnp.float32) @ jnp.asarray(coef, jnp.float32) \
            + jnp.asarray(intercept, jnp.float32)
    return jax.nn.sigmoid(eta)
