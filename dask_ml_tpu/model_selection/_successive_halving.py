"""SuccessiveHalvingSearchCV.

Reference: ``dask_ml/model_selection/_successive_halving.py`` (SURVEY.md
§2a, §3.5): rungs of training where after each rung only the top
``1/aggressiveness`` fraction of models survives, and survivors train
``aggressiveness`` times longer — built on the incremental controller's
``additional_calls`` protocol.
"""

from __future__ import annotations

import math

import numpy as np

from ._incremental import BaseIncrementalSearchCV, top_scores


class SuccessiveHalvingSearchCV(BaseIncrementalSearchCV):
    """Ref: _successive_halving.py::SuccessiveHalvingSearchCV."""

    def __init__(self, estimator, parameters, n_initial_parameters=10,
                 n_initial_iter=None, max_iter=None, aggressiveness=3,
                 test_size=None, patience=False, tol=1e-3,
                 random_state=None, scoring=None, verbose=False, prefix=""):
        super().__init__(estimator, parameters,
                         n_initial_parameters=n_initial_parameters,
                         test_size=test_size, patience=patience, tol=tol,
                         max_iter=max_iter, random_state=random_state,
                         scoring=scoring, verbose=verbose, prefix=prefix)
        self.n_initial_iter = n_initial_iter
        self.aggressiveness = aggressiveness

    def fit(self, X, y=None, **fit_params):
        if self.n_initial_iter is None:
            raise ValueError("n_initial_iter must be specified")
        return super().fit(X, y, **fit_params)

    def _reset_hook(self):
        self._rung = 0

    def _hook_state(self):
        return {"_rung": self._rung}

    def _additional_calls(self, info):
        eta = self.aggressiveness
        # models have all trained r_i = n_initial_iter * eta^rung calls when
        # this fires; promote top 1/eta and triple (eta) their budget
        scores = {mid: recs[-1]["score"] for mid, recs in info.items()}
        calls = {mid: recs[-1]["partial_fit_calls"]
                 for mid, recs in info.items()}
        target = self.n_initial_iter * (eta ** self._rung)
        # first bring everyone to the current rung's budget
        pending = {
            mid: target - calls[mid]
            for mid in scores if calls[mid] < target
        }
        if pending:
            return {mid: max(c, 0) for mid, c in pending.items()}
        # rung complete: cut to top 1/eta
        n_keep = max(1, math.floor(len(scores) / eta))
        keep = top_scores(scores, n_keep)
        self._rung += 1
        next_target = self.n_initial_iter * (eta ** self._rung)
        if self.max_iter is not None:
            next_target = min(next_target, self.max_iter)
        out = {mid: next_target - calls[mid] for mid in keep}
        out = {mid: c for mid, c in out.items() if c > 0}
        if len(keep) == 1 and not out:
            return {}
        if not out:
            return {}
        return out
