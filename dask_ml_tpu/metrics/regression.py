"""Regression metrics. Reference: ``dask_ml/metrics/regression.py``
(SURVEY.md §2a Metrics row)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .classification import _canon


def mean_squared_error(y_true, y_pred, sample_weight=None, squared=True):
    t, p, w, n = _canon(y_true, y_pred, sample_weight)
    mse = jnp.sum(((t - p) ** 2) * w) / jnp.sum(w)
    return float(mse if squared else jnp.sqrt(mse))


def mean_absolute_error(y_true, y_pred, sample_weight=None):
    t, p, w, n = _canon(y_true, y_pred, sample_weight)
    return float(jnp.sum(jnp.abs(t - p) * w) / jnp.sum(w))


def r2_score(y_true, y_pred, sample_weight=None):
    t, p, w, n = _canon(y_true, y_pred, sample_weight)
    wsum = jnp.sum(w)
    mean = jnp.sum(t * w) / wsum
    ss_res = jnp.sum(((t - p) ** 2) * w)
    ss_tot = jnp.sum(((t - mean) ** 2) * w)
    return _force_finite_ratio(ss_res, ss_tot)


def _force_finite_ratio(num, den):
    """1 - num/den with sklearn's force_finite semantics: a constant
    target (den == 0) scores 1.0 when the residual term is also 0
    (perfect fit) and 0.0 otherwise, instead of nan/-inf that would
    poison a CV search."""
    num, den = float(num), float(den)
    if den == 0.0:
        return 1.0 if num == 0.0 else 0.0
    return 1.0 - num / den


def mean_squared_log_error(y_true, y_pred, sample_weight=None):
    t, p, w, n = _canon(y_true, y_pred, sample_weight)
    err = (jnp.log1p(t) - jnp.log1p(p)) ** 2
    return float(jnp.sum(err * w) / jnp.sum(w))


def explained_variance_score(y_true, y_pred, sample_weight=None):
    t, p, w, n = _canon(y_true, y_pred, sample_weight)
    wsum = jnp.sum(w)
    err = t - p
    err_mean = jnp.sum(err * w) / wsum
    var_err = jnp.sum(((err - err_mean) ** 2) * w) / wsum
    t_mean = jnp.sum(t * w) / wsum
    var_t = jnp.sum(((t - t_mean) ** 2) * w) / wsum
    return _force_finite_ratio(var_err, var_t)


def max_error(y_true, y_pred):
    """Largest absolute residual (sklearn takes no sample_weight here);
    padded rows are masked out via the validity weights."""
    t, p, w, n = _canon(y_true, y_pred)
    return float(jnp.max(jnp.abs(t - p) * (w > 0)))


def median_absolute_error(y_true, y_pred, sample_weight=None):
    """Median of |err|, matching sklearn's two conventions exactly: the
    unweighted path is ``np.median`` (middle-two average over valid
    rows), the weighted path is ``_weighted_percentile``'s
    averaged-inverted-cdf (scikit-learn >= 1.8) — the FIRST sorted error
    whose cumulative weight reaches half the total, averaged with the
    next error of positive weight when the cumulative weight lands ON
    the half (so unit weights give ``np.median``, and an explicit
    zero-weight row can never contribute its error value). One device
    sort + host f64 prefix sums: an f32 cumsum of unit weights saturates
    at 2**24 rows (the same hazard the curve metrics guard)."""
    t, p, w, n = _canon(y_true, y_pred, sample_weight)
    err = jnp.abs(t - p)
    order = jnp.argsort(err)
    es = np.asarray(jnp.take(err, order), np.float64)
    ws = np.asarray(jnp.take(w, order), np.float64)
    if sample_weight is None:
        # w holds only the padding-validity mask here
        return float(np.median(es[ws > 0]))
    cw = np.cumsum(ws)
    half = 0.5 * cw[-1]
    i = int(np.argmax(cw >= half))
    if cw[i] - half > np.finfo(np.float64).eps:
        return float(es[i])
    # the next error that carries weight (none: es[i] stands alone)
    j = min(int(np.searchsorted(cw, cw[i], side="right")), len(es) - 1)
    return float(0.5 * (es[i] + es[j]))
