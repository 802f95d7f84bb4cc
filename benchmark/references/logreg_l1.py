"""Plain float32 ``jax.numpy`` reference for L1-penalised logistic
regression: the yardstick the cell ``logreg_admm_l1`` is checked against.

A COPY of ``dask_ml_tpu/models/solvers/reference_l1.py`` (PR 36) kept with
the benchmark so that later PRs, which may edit the program, cannot move it
(``tests/linear_model/test_admm_l1_reference.py`` holds the two files equal
below their headers). Nothing the timed path uses is imported from here.

It shares no code with the program: the softplus negative log-likelihood,
its gradient and Hessian written out, every product under
``jax.default_matmul_precision("highest")`` (a TPU multiplies f32 operands
in ONE bf16 pass otherwise), every sum over the rows taken block by block
(``lax.map`` over a reshape of X) so that 4,194,304 x 256 rows fit beside
what the timed path keeps. The objective is

    F(coef, b) = mean_i[softplus(eta_i) - y_i eta_i] + lam ||coef||_1,
    eta = X coef + b,   y in {0, 1},

``lam`` the fit's ``1 / (C n_train)``; the intercept ``b`` is never
penalised.

- :func:`objective`, :func:`kkt` — the value and the optimality residual at
  a point.
- :func:`optimum` — the reference's OWN optimum by a method that is not
  ADMM: proximal gradient (ISTA) with the step ``4 / lambda_max([X, 1]^T
  [X, 1] / n)``, the inverse of the smooth part's Lipschitz constant.
- :func:`admm` — plain consensus ADMM over N blocks of rows, written from
  the description of ``dask_glm/algorithms.py::admm`` (Boyd et al., section
  8): a local solve a block, ``z = shrink(mean(b_i + u_i), lam / (rho N))``,
  ``u_i += b_i - z``. Where THE PROGRAM departs from upstream this follows
  the program, so that the two can be compared iterate by iterate: (1) the
  local solves are Newton iterations run to convergence (upstream: scipy's
  L-BFGS-B to its own tolerance; the program: at most ``local_iter`` Newton
  steps, ended once the Newton decrement is under ``tol**2``); (2) ``rho``
  is rebalanced after every outer iteration (Boyd 3.4.1: doubled where the
  primal residual exceeds ten times the dual one, halved in the opposite
  case, the scaled duals ``u_i`` divided by the same factor; upstream keeps
  ``rho`` fixed); (3) the stop is ``primal <= tol and dual <= tol`` with
  ``primal = sqrt(sum_i ||b_i - z||^2)`` and ``dual = rho sqrt(N) ||z -
  z_prev||`` (upstream adds relative terms, ``abstol`` + ``reltol``); (4)
  the loss is the MEAN over all rows and the intercept is not penalised
  (upstream: the sum, ``lamduh = 1 / C``, every coordinate penalised).
  ``fault`` runs it WRONGLY in one named way, for showing that a check fails
  what it must fail.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_BLOCK_ROWS = 65536

FAULTS = ("bf16_design", "penalised_intercept", "no_1_over_n",
          "one_local_step")


def _block_rows(n):
    """The largest divisor of ``n`` no larger than ``_BLOCK_ROWS``: X is
    reshaped to whole blocks, never padded or copied."""
    for rows in range(min(n, _BLOCK_ROWS), 0, -1):
        if n % rows == 0:
            return rows


def _over_blocks(fn, X, y):
    """Sum ``fn(X_block, y_block)`` (a tuple of arrays) over blocks of rows."""
    n, d = X.shape
    rows = _block_rows(n)
    if rows == n:
        return fn(X, y)
    parts = jax.lax.map(lambda xy: fn(*xy), (X.reshape(-1, rows, d),
                                             y.reshape(-1, rows)))
    return tuple(jnp.sum(p, axis=0) for p in parts)


def _eta(X, coef, b, bf16):
    if bf16:   # the fault: a design and coefficients rounded to bfloat16
        X = X.astype(jnp.bfloat16).astype(jnp.float32)
        coef = coef.astype(jnp.bfloat16).astype(jnp.float32)
    return X @ coef + b


@functools.partial(jax.jit, static_argnames=("bf16",))
def _sums(coef, b, X, y, bf16=False):
    """(sum of the negative log-likelihood, sum of d/dcoef, sum of d/db)."""
    X, y = jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32)
    coef = jnp.asarray(coef, jnp.float32)
    b = jnp.asarray(b, jnp.float32)

    def block(Xb, yb):
        eta = _eta(Xb, coef, b, bf16)
        r = jax.nn.sigmoid(eta) - yb
        if bf16:
            Xb = Xb.astype(jnp.bfloat16).astype(jnp.float32)
        return (jnp.sum(jnp.logaddexp(0.0, eta) - yb * eta), r @ Xb,
                jnp.sum(r))

    with jax.default_matmul_precision("highest"):
        return _over_blocks(block, X, y)


@functools.partial(jax.jit, static_argnames=("bf16",))
def _hessian_sums(coef, b, X, y, bf16=False):
    """(sum of X^T W X, sum of X^T w, sum of w), W = diag(p (1 - p))."""
    X, y = jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32)

    def block(Xb, yb):
        p = jax.nn.sigmoid(_eta(Xb, coef, b, bf16))
        w = p * (1.0 - p)
        if bf16:
            Xb = Xb.astype(jnp.bfloat16).astype(jnp.float32)
        return (Xb * w[:, None]).T @ Xb, w @ Xb, jnp.sum(w)

    with jax.default_matmul_precision("highest"):
        return _over_blocks(block, X, y)


def objective(coef, intercept, X, y, lam):
    """``mean[softplus(eta) - y eta] + lam ||coef||_1`` over the rows
    given; ``lam`` is the fit's, whatever rows the mean runs over."""
    nll, _, _ = _sums(coef, intercept, X, y)
    return float(nll) / X.shape[0] + float(lam) * float(
        np.sum(np.abs(np.asarray(coef, np.float64))))


def gradient(coef, intercept, X, y):
    """The smooth part's gradient, ``(d/dcoef (d,), d/dintercept)``."""
    _, g, gb = _sums(coef, intercept, X, y)
    n = X.shape[0]
    return np.asarray(g, np.float64) / n, float(gb) / n


def kkt_from_gradient(coef, g, gb, lam):
    """The optimality residual from the smooth gradient ``(g, gb)``: a
    ``(d + 1,)`` vector, ``|g_j + lam sign(coef_j)|`` where ``coef_j != 0``,
    ``max(|g_j| - lam, 0)`` where ``coef_j == 0``, ``|g_b|`` last. Zero at
    an optimum and nowhere else."""
    coef = np.asarray(coef, np.float64)
    on = np.abs(g + lam * np.sign(coef))
    off = np.maximum(np.abs(g) - lam, 0.0)
    return np.r_[np.where(coef != 0, on, off), abs(gb)]


def kkt(coef, intercept, X, y, lam):
    g, gb = gradient(coef, intercept, X, y)
    return kkt_from_gradient(coef, g, gb, float(lam))


@jax.jit
def proba(coef, intercept, X):
    """P(y = 1 | x) = sigmoid(X coef + b), f32 throughout."""
    with jax.default_matmul_precision("highest"):
        eta = jnp.asarray(X, jnp.float32) @ jnp.asarray(coef, jnp.float32) \
            + jnp.asarray(intercept, jnp.float32)
    return jax.nn.sigmoid(eta)


def _shrink(v, t):
    return np.sign(v) * np.maximum(np.abs(v) - t, 0.0)


@jax.jit
def _gram_apply(v, vb, X):
    """``[X, 1]^T [X, 1] [v; vb]``, block by block."""
    X = jnp.asarray(X, jnp.float32)

    def block(Xb, _):
        t = Xb @ v + vb
        return t @ Xb, jnp.sum(t)

    with jax.default_matmul_precision("highest"):
        return _over_blocks(block, X, X[:, 0])


def lipschitz(X, iters=40):
    """``lambda_max([X, 1]^T [X, 1] / n) / 4``, the Lipschitz constant of
    the smooth part's gradient (``p (1 - p) <= 1 / 4``), by power
    iteration from a fixed start, with 2 % of room."""
    n, d = X.shape
    rng = np.random.default_rng(0)
    v = rng.standard_normal(d + 1)
    top = 1.0
    for _ in range(iters):
        v /= np.linalg.norm(v)
        gv, gvb = _gram_apply(np.float32(v[:-1]), np.float32(v[-1]), X)
        v = np.r_[np.asarray(gv, np.float64), float(gvb)] / n
        top = float(np.linalg.norm(v))
    return 1.02 * top / 4.0


def optimum(X, y, lam, coef0, intercept0, kkt_tol=1e-6, max_iter=2000):
    """The reference's OWN optimum on rows ``X, y`` by proximal gradient
    from ``(coef0, intercept0)``: ``coef <- shrink(coef - g / L, lam / L)``,
    ``b <- b - g_b / L``, until the largest entry of :func:`kkt` is under
    ``kkt_tol`` (far below any band a check holds the program to; float32
    sums over millions of rows leave ~1e-7). Returns ``(coef, b, info)``,
    ``info`` the iterations run and the residual reached."""
    lam = float(lam)
    step = 1.0 / lipschitz(X)
    coef = np.asarray(coef0, np.float64).ravel().copy()
    b = float(intercept0)
    for it in range(max_iter + 1):
        g, gb = gradient(np.float32(coef), np.float32(b), X, y)
        res = float(np.max(kkt_from_gradient(coef, g, gb, lam)))
        if res <= kkt_tol or it == max_iter:
            break
        coef = _shrink(coef - step * g, step * lam)
        b = b - step * gb
    return np.float32(coef), np.float32(b), {"n_iter": it, "kkt": res}


def _local_newton(b, v, rho, X, y, n_rows, steps, bf16, gtol=1e-7):
    """argmin_b  sum_block(nll) / n_rows + rho / 2 ||b - v||^2  over the
    ``(d + 1,)`` vector ``[coef, intercept]`` by Newton iterations: ``steps``
    of them, or (None) until the gradient's largest entry is under
    ``gtol``. Returns the point and the steps taken."""
    d1 = b.shape[0]
    taken = 0
    for _ in range(steps if steps is not None else 50):
        c32, b32 = np.float32(b[:-1]), np.float32(b[-1])
        _, g, gb = _sums(c32, b32, X, y, bf16=bf16)
        grad = np.r_[np.asarray(g, np.float64), float(gb)] / n_rows \
            + rho * (b - v)
        if steps is None and np.max(np.abs(grad)) <= gtol:
            break
        H, hb, hbb = _hessian_sums(c32, b32, X, y, bf16=bf16)
        hess = np.empty((d1, d1))
        hess[:-1, :-1] = np.asarray(H, np.float64)
        hess[:-1, -1] = hess[-1, :-1] = np.asarray(hb, np.float64)
        hess[-1, -1] = float(hbb)
        hess = hess / n_rows + rho * np.eye(d1)
        b = b - np.linalg.solve(hess, grad)
        taken += 1
    return b, taken


def admm(X, y, lam, n_blocks, rho=1.0, tol=1e-4, max_iter=100,
         fault=None, callback=None):
    """Plain consensus ADMM (module docstring) over ``n_blocks`` equal blocks
    of the rows, from zero. Returns a dict: ``coef`` / ``intercept`` (the
    consensus ``z``), ``n_iter``, ``local_steps`` (Newton steps, the
    slowest block's of every outer iteration summed), ``primal_residual``,
    ``dual_residual``, ``rho``, and ``z_path`` (``z`` after every outer
    iteration).

    ``fault``, ONE of :data:`FAULTS`: ``bf16_design`` — the design and the
    coefficients rounded to bfloat16 wherever eta, the gradient and the
    Hessian are formed; ``penalised_intercept`` — the intercept soft-
    thresholded with the rest; ``no_1_over_n`` — the threshold ``lam / rho``
    without the ``1 / N`` of the consensus mean; ``one_local_step`` — one
    Newton step a local solve where the solve should converge."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    n, d = X.shape
    if n % n_blocks:
        raise ValueError(f"{n} rows are not {n_blocks} equal blocks")
    m = n // n_blocks
    blocks = [(X[i * m:(i + 1) * m], y[i * m:(i + 1) * m])
              for i in range(n_blocks)]
    lam, rho = float(lam), float(rho)
    B = np.zeros((n_blocks, d + 1))
    U = np.zeros((n_blocks, d + 1))
    z = np.zeros(d + 1)
    z_path = []
    primal = dual = np.inf
    it = local_steps = 0
    while it < max_iter and (primal > tol or dual > tol):
        taken = np.zeros(n_blocks, int)
        for i, (Xi, yi) in enumerate(blocks):
            B[i], taken[i] = _local_newton(
                B[i], z - U[i], rho, Xi, yi, n,
                1 if fault == "one_local_step" else None,
                fault == "bf16_design")
        local_steps += int(taken.max())
        mean = np.mean(B + U, axis=0)
        t = lam / rho if fault == "no_1_over_n" else lam / (rho * n_blocks)
        z_new = _shrink(mean, t)
        if fault != "penalised_intercept":
            z_new[-1] = mean[-1]
        U = U + B - z_new
        primal = float(np.sqrt(np.sum((B - z_new) ** 2)))
        dual = float(rho * np.sqrt(n_blocks) * np.linalg.norm(z_new - z))
        z = z_new
        z_path.append(z.copy())
        it += 1
        scale = 2.0 if primal > 10.0 * dual else \
            0.5 if dual > 10.0 * primal else 1.0
        U, rho = U / scale, rho * scale
        if callback is not None:
            callback(it, z, primal, dual, rho)
    return {"coef": np.float32(z[:-1]), "intercept": np.float32(z[-1]),
            "n_iter": it, "local_steps": local_steps,
            "primal_residual": primal, "dual_residual": dual,
            "rho": rho, "z_path": z_path}
