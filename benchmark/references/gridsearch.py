"""Plain float32 ``jax.numpy`` reference for a cross-validated C grid over
L2-penalised logistic regression: the yardstick the ``gridsearch`` cell is
checked against. Nothing the timed path uses is imported here; the loss,
its gradient and the sample optimum are ``references/logreg.py``'s written-
out expressions (every product under ``highest``, so a TPU multiplies in
f32), the folds and the winner's rule are written out below from their
definitions:

- folds: scikit-learn's / dask-ml's ``KFold(5)`` without shuffling, the
  default ``cv=None`` resolves to — five contiguous test folds, the first
  ``n % 5`` one row longer, each model trained on the other four;
- a model of candidate ``C`` on fold ``f`` minimises the MEAN loss over its
  ``n_train_f`` training rows plus ``lam / 2 ||coef||^2`` with
  ``lam = 1 / (C * n_train_f)``, the intercept unpenalised;
- its score is the accuracy on fold ``f``'s test rows, the label being
  ``eta > 0``;
- the winner is the EARLIEST candidate whose mean score is within
  ``tie_tol`` of the best mean.

Rows are walked in blocks (``BLOCK_ROWS``), so the reference holds nothing
X-sized beyond X itself.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references import logreg as ref

BLOCK_ROWS = 524_288
# how many of a test fold's smallest |eta| ``fold_stats`` keeps a model: the
# band a recorded score needs is read off them (``near_band_needed``)
SMALLEST = 1024


def folds(n, k=5):
    """(starts, stops) of KFold(k)'s contiguous test folds over n rows."""
    sizes = np.full(k, n // k, np.int64)
    sizes[: n % k] += 1
    stops = np.cumsum(sizes)
    return stops - sizes, stops


@jax.jit
def _block_sums(W, b, Xb, yb, lo, hi, row0, band):
    """Over one row block (global rows ``row0 ...``): for each of the k
    models (``W`` (k, d), ``b`` (k,)) the sum of the NLL over its training
    rows (outside ``[lo, hi)``), the gradient sums, the training-row count,
    its right answers on the test rows (``[lo, hi)``) and how many of those
    lie within ``band`` of the boundary."""
    with jax.default_matmul_precision("highest"):
        eta = Xb @ W.T + b[None, :]                              # (r, k)
        row = row0 + jnp.arange(Xb.shape[0])
        test = (row >= lo) & (row < hi)
        train = (~test).astype(jnp.float32)[:, None]
        nll = (jnp.logaddexp(0.0, eta) - yb[:, None] * eta) * train
        resid = (jax.nn.sigmoid(eta) - yb[:, None]) * train
        g = resid.T @ Xb                                         # (k, d)
    right = ((eta > 0) == (yb[:, None] > 0.5)) & test[:, None]
    near = (jnp.abs(eta) < band) & test[:, None]
    return (jnp.sum(nll, axis=0), g, jnp.sum(resid, axis=0),
            jnp.sum(train), jnp.sum(right, axis=0), jnp.sum(near, axis=0))


@jax.jit
def _block_small(W, b, Xb, lo, hi, row0):
    """The ``min(SMALLEST, rows)`` smallest |eta| of each model over the
    block's test rows (inf past them), ascending: (k, s)."""
    with jax.default_matmul_precision("highest"):
        eta = Xb @ W.T + b[None, :]                              # (r, k)
    row = row0 + jnp.arange(Xb.shape[0])
    test = ((row >= lo) & (row < hi))[:, None]
    a = jnp.where(test, jnp.abs(eta), jnp.inf).T
    return -jax.lax.top_k(-a, min(SMALLEST, Xb.shape[0]))[0]


def near_band_needed(smallest, off):
    """The least near-tie band at which ``off`` rows of difference are all
    explained by rows that close to the boundary: the ``off``-th smallest
    |eta| of the test rows (a row counts where |eta| < band, so a band
    must exceed it), 0 for none; past ``SMALLEST`` rows, the largest one
    kept (a lower bound)."""
    off = int(off)
    if off <= 0:
        return 0.0
    return float(smallest[min(off, len(smallest)) - 1])


def fold_stats(X, y, W, b, lo, hi, lams, near_band):
    """For the k models ``(W, b)`` of ONE fold (test rows ``[lo, hi)``):
    the objective on the training rows, its gradient (coef and intercept),
    the training-row count, the right answers on the test rows, how many
    test rows lie within ``near_band`` of the boundary, and the
    ``SMALLEST`` least |eta| of the test rows, ascending (``smallest``,
    (k, SMALLEST)). Host numpy."""
    n = int(X.shape[0])
    W = jnp.asarray(W, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    tot, small = None, []
    for r0 in range(0, n, BLOCK_ROWS):
        r1 = min(r0 + BLOCK_ROWS, n)
        part = _block_sums(W, b, X[r0:r1], y[r0:r1], int(lo), int(hi), r0,
                           np.float32(near_band))
        tot = part if tot is None else tuple(a + p for a, p in
                                              zip(tot, part))
        if r0 < hi and r1 > lo:
            small.append(np.asarray(_block_small(W, b, X[r0:r1], int(lo),
                                                 int(hi), r0)))
    small = np.sort(np.concatenate(small, axis=1), axis=1)[:, :SMALLEST]
    nll, g, gb, m, right, near = (np.asarray(v, np.float64) for v in tot)
    W64 = np.asarray(W, np.float64)
    lams = np.asarray(lams, np.float64)
    value = nll / m + 0.5 * lams * np.sum(W64 * W64, axis=1)
    grad = np.concatenate([g / m + lams[:, None] * W64, (gb / m)[:, None]],
                          axis=1)
    return {"value": value, "grad": grad, "n_train": int(m),
            "right": right.astype(np.int64), "near": near.astype(np.int64),
            "smallest": small.astype(np.float64)}


def grad_at_zero(X, y, lo, hi):
    """The training rows' gradient at beta = 0 (the problem's gradient
    scale), all entries, host numpy."""
    s = fold_stats(X, y, np.zeros((1, X.shape[1]), np.float32),
                   np.zeros(1, np.float32), lo, hi, [0.0], 0.0)
    return s["grad"][0]


def sample_excess(Xs, ys, lam, coef, b0):
    """The reference loss on the sample rows at ``(coef, b0)`` minus its
    own Newton optimum there (``references/logreg.py``)."""
    c_opt, b_opt = ref.optimum(Xs, ys, lam, coef, b0)
    at_fit = float(ref.objective(coef, b0, Xs, ys, lam))
    at_opt = float(ref.objective(c_opt, b_opt, Xs, ys, lam))
    return at_fit - at_opt, at_opt


def winner(means, tie_tol):
    """The earliest candidate whose mean is within ``tie_tol`` of the
    best."""
    means = np.asarray(means, np.float64)
    return int(np.flatnonzero(means >= means.max() - tie_tol)[0])
