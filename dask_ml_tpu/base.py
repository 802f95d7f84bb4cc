"""Estimator base: the scikit-learn contract.

The reference's estimators subclass sklearn bases so that ``get_params`` /
``set_params`` / ``clone`` compose with pipelines and search (SURVEY.md §5
config row: "estimator params stay sklearn-style (MUST)"). We do the same —
sklearn's ``BaseEstimator`` provides the param introspection contract; the
mixins add ``score`` defaults. Fitted state is stored as numpy on the host
(small: coefs, centers, components) with device-resident copies created on
demand, so estimators pickle/clone cleanly.
"""

from __future__ import annotations

import numpy as np
from sklearn.base import (  # re-exported contract, verified sklearn 1.9
    BaseEstimator,
    ClassifierMixin,
    ClusterMixin,
    RegressorMixin,
    TransformerMixin,
    clone,
)

from .observability._spans import current_span

__all__ = [
    "BaseEstimator",
    "ClassifierMixin",
    "RegressorMixin",
    "TransformerMixin",
    "ClusterMixin",
    "clone",
    "to_host",
]


def log_proba(p):
    """log of a probability matrix, sklearn ``predict_log_proba``
    semantics: zero probabilities map to -inf, silently (no runtime
    warning). THE one implementation — every classifier's
    predict_log_proba delegates here so they cannot diverge."""
    with np.errstate(divide="ignore"):
        return np.log(p)


def _read_device(x):
    if not x.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(x)


def to_host(x):
    """A device value as host numpy: the one door a blocking read of a
    device value goes through (fitted attributes, a predict's result, a
    scalar the host decides on), so that it is one FETCH of the open
    span's handoff ledger (``observability/_spans.py``: ``fetches``,
    ``fetch_bytes``, ``fetch_s``). A value already on the host counts
    nothing, and with tracing off this is ``np.asarray``.

    Under a multi-process runtime an array on the global mesh spans
    devices this process cannot address; it is gathered to every host
    with a collective (all processes reach this call in SPMD lockstep —
    the same contract as any other collective op on the global mesh)."""
    import jax

    if isinstance(x, jax.Array):
        return current_span().fetch(_read_device, x)
    return np.asarray(x)
