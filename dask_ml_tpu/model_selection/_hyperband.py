"""HyperbandSearchCV.

Reference: ``dask_ml/model_selection/_hyperband.py`` (SURVEY.md §2a, §3.5
call stack): computes Hyperband brackets from (max_iter, aggressiveness)
and runs a SuccessiveHalving schedule per bracket. Like the reference,
all brackets are INTERLEAVED through one shared controller fit (VERDICT
r3 missing #4): every adaptive round advances the union of live
candidates across brackets, so cohort batching and submesh placement mix
brackets and an early-stopped bracket frees budget for live ones instead
of serializing behind them. With the streamed cohort plane (ISSUE 14,
``config.search_stream``), an interleaved round over host X is ONE
``BlockStream`` superblock pass: the brackets' heterogeneous
``n_calls`` fold onto a single block-step timeline with per-model
activity masks, so one data pass trains the whole bracket union. Under
multi-process, whole brackets are striped across processes (each an
independent SHA sweep on its local mesh, itself riding the streamed
plane on that mesh) — the cross-host unit stays coarse while the
intra-process execution interleaves.
"""

from __future__ import annotations

import math

import numpy as np
from sklearn.model_selection import ParameterSampler

from ..base import clone
from ._incremental import (
    BaseIncrementalSearchCV, disable_process_distribution,
    host_view_estimator, top_scores,
)
from ._successive_halving import SuccessiveHalvingSearchCV


def _brackets(max_iter, eta):
    """Hyperband bracket table: [(bracket, n_models, n_initial_iter)]."""
    s_max = int(math.floor(math.log(max_iter, eta)))
    B = (s_max + 1) * max_iter
    out = []
    for s in range(s_max, -1, -1):
        n = int(math.ceil(B / max_iter * (eta ** s) / (s + 1)))
        r = max(1, int(max_iter * (eta ** -s)))
        out.append((s, n, r))
    return out


class HyperbandSearchCV(BaseIncrementalSearchCV):
    """Ref: _hyperband.py::HyperbandSearchCV."""

    def __init__(self, estimator, parameters, max_iter=81, aggressiveness=3,
                 patience=False, tol=1e-3, test_size=None, random_state=None,
                 scoring=None, verbose=False, prefix=""):
        super().__init__(estimator, parameters,
                         test_size=test_size, patience=patience, tol=tol,
                         max_iter=max_iter, random_state=random_state,
                         scoring=scoring, verbose=verbose, prefix=prefix)
        self.max_iter = max_iter
        self.aggressiveness = aggressiveness

    def metadata(self):
        """Expected work BEFORE fitting (ref: HyperbandSearchCV.metadata)."""
        brackets = _brackets(self.max_iter, self.aggressiveness)
        bracket_info = []
        total_models = 0
        total_calls = 0
        for s, n, r in brackets:
            calls = self._bracket_calls(n, r)
            bracket_info.append({
                "bracket": s, "n_models": n,
                "partial_fit_calls": calls,
            })
            total_models += n
            total_calls += calls
        return {
            "n_models": total_models,
            "partial_fit_calls": total_calls,
            "brackets": bracket_info,
        }

    def _bracket_calls(self, n, r):
        eta = self.aggressiveness
        calls = n * r
        while True:
            # successive rungs: top n/eta models train to min(r*eta,
            # max_iter) — the same cap the SHA controller applies
            # (_successive_halving.py next_target), so the estimate counts
            # the final partial rung and the survivor's run to max_iter
            nk = max(1, math.floor(n / eta))
            rk = min(r * eta, self.max_iter)
            if rk == r:
                break
            calls += nk * (rk - r)
            n, r = nk, rk
        return calls

    # -- interleaved single-process schedule (controller hooks) -----------
    def _n_initial(self):
        return sum(n for _, n, _ in _brackets(self.max_iter,
                                              self.aggressiveness))

    def _sample_params(self, n):
        # per-bracket draws with the SAME seeds the sequential-bracket
        # (and multi-process) path uses, so the candidate sets agree.
        # ParameterSampler TRUNCATES small discrete spaces, so the
        # realized per-bracket counts are recorded for _reset_hook's
        # model-id ranges (assuming the nominal bracket sizes would
        # misalign every bracket after a truncated one).
        out = []
        self._sampled_counts = []
        for s, nb, _r in _brackets(self.max_iter, self.aggressiveness):
            seed = (None if self.random_state is None
                    else self.random_state + s)
            drawn = list(ParameterSampler(self.parameters, nb,
                                          random_state=seed))
            self._sampled_counts.append(len(drawn))
            out.extend(drawn)
        return out

    def _reset_hook(self):
        # model-id ranges per bracket + each bracket's SHA rung position
        self._bounds = []
        self._rungs = {}
        off = 0
        counts = getattr(self, "_sampled_counts", None)
        for i, (s, nb, r) in enumerate(
            _brackets(self.max_iter, self.aggressiveness)
        ):
            size = counts[i] if counts is not None else nb
            self._bounds.append((s, off, off + size, r))
            self._rungs[s] = 0
            off += size

    def _hook_state(self):
        return {"_rungs": dict(self._rungs)}

    def _bracket_of(self, mid):
        for s, lo, hi, _r in self._bounds:
            if lo <= mid < hi:
                return s
        return None

    def _trial_tags(self, mid):
        """Per-trial telemetry tag: which Hyperband bracket this model
        belongs to (``_bounds`` exists once ``_reset_hook`` ran; the
        multi-process path runs per-bracket SHAs whose prefix already
        names the bracket)."""
        if getattr(self, "_bounds", None):
            return {"bracket": self._bracket_of(mid)}
        return {}

    def _additional_calls(self, info):
        """One SHA step PER BRACKET over that bracket's live candidates,
        merged into a single round request — the round-robin interleave
        (ref _hyperband.py: all brackets submitted to one scheduler)."""
        eta = self.aggressiveness
        out = {}
        for s, lo, hi, r in self._bounds:
            binfo = {mid: recs for mid, recs in info.items()
                     if lo <= mid < hi}
            if not binfo:
                continue
            scores = {mid: recs[-1]["score"] for mid, recs in binfo.items()}
            calls = {mid: recs[-1]["partial_fit_calls"]
                     for mid, recs in binfo.items()}
            target = min(r * (eta ** self._rungs[s]), self.max_iter)
            pending = {mid: target - calls[mid]
                       for mid in scores if calls[mid] < target}
            if pending:
                out.update(pending)
                continue
            n_keep = max(1, math.floor(len(scores) / eta))
            keep = top_scores(scores, n_keep)
            self._rungs[s] += 1
            next_target = min(r * (eta ** self._rungs[s]), self.max_iter)
            promote = {mid: next_target - calls[mid] for mid in keep}
            out.update({mid: c for mid, c in promote.items() if c > 0})
        return out

    def _annotate_results(self):
        # bracket annotations on the merged controller outputs (inside
        # the root span's ``fit.finish``)
        if not getattr(self, "_bounds", None):
            return
        for rec in self.history_:
            rec["bracket"] = self._bracket_of(rec["model_id"])
        res = self.cv_results_
        res["bracket"] = np.asarray([
            self._bracket_of(mid) for mid in res["model_id"]
        ])
        meta_brackets = []
        for s, lo, hi, _r in self._bounds:
            sel = (res["model_id"] >= lo) & (res["model_id"] < hi)
            meta_brackets.append({
                "bracket": s, "n_models": int(sel.sum()),
                "partial_fit_calls": int(
                    res["partial_fit_calls"][sel].sum()
                ),
            })
        self.metadata_["brackets"] = meta_brackets

    def fit(self, X, y=None, **fit_params):
        rng_seed = self.random_state
        brackets = _brackets(self.max_iter, self.aggressiveness)

        # Multi-process: brackets are independent SHA sweeps, so each
        # process runs a strided share on its local-device mesh and the
        # per-bracket payloads (history, results, best model) merge via
        # one object-allgather — BASELINE configs[4] 'trials parallel
        # across TPU hosts' (SURVEY.md §3.5). Single-process: one
        # interleaved controller fit over all brackets.
        from ..parallel import distributed as _dist

        n_proc = _dist.process_count()
        if n_proc == 1:
            return super().fit(X, y, **fit_params)
        from ..parallel.sharded import ShardedArray

        if isinstance(X, ShardedArray) or isinstance(y, ShardedArray):
            raise ValueError(
                "multi-process Hyperband requires host-resident X/y "
                "(each process loads its copy and runs a disjoint "
                "bracket subset)"
            )
        from ..parallel.distributed import local_mesh
        from ..parallel.mesh import use_mesh

        placement_mesh = local_mesh()
        self._dist_stats = (_dist.process_index(), n_proc)

        payloads = {}
        local_exc = None
        for bi, (s, n, r) in enumerate(brackets):
            if bi % n_proc != _dist.process_index():
                continue
            sha = SuccessiveHalvingSearchCV(
                clone(self.estimator), self.parameters,
                n_initial_parameters=n, n_initial_iter=r,
                max_iter=self.max_iter, aggressiveness=self.aggressiveness,
                test_size=self.test_size, patience=self.patience,
                tol=self.tol,
                random_state=None if rng_seed is None else rng_seed + s,
                scoring=self.scoring, verbose=self.verbose,
                prefix=f"{self.prefix}bracket={s}",
            )
            # SPLIT with the shared seed (sampling stays rng_seed + s):
            # the single-process interleaved fit scores every bracket on
            # one split, and a 1-host vs N-host run of the same search
            # must produce the same scores
            sha._split_random_state = rng_seed
            try:
                # bracket-level distribution: the inner SHA must not also
                # distribute its candidates (peers run OTHER brackets)
                with disable_process_distribution(), \
                        use_mesh(placement_mesh):
                    sha.fit(X, y, **fit_params)
            except Exception as e:
                # hold the failure: peers must learn about it through the
                # gather below instead of blocking in it forever
                local_exc = e
                break
            payloads[bi] = {
                "s": s,
                "history": sha.history_,
                "model_history": sha.model_history_,
                "results": dict(sha.cv_results_),
                "best_score": sha.best_score_,
                "best_params": sha.best_params_,
                "best_estimator": host_view_estimator(sha.best_estimator_),
            }

        from ..parallel.distributed import allgather_object

        parts = allgather_object({
            "payloads": {} if local_exc is not None else payloads,
            "error": None if local_exc is None else repr(local_exc),
        })
        if local_exc is not None:
            raise local_exc
        bad = [p["error"] for p in parts if p["error"] is not None]
        if bad:
            raise RuntimeError(
                f"peer process failed during distributed Hyperband: {bad}"
            )
        payloads = {}
        for part in parts:
            payloads.update(part["payloads"])

        self.history_ = []
        self.model_history_ = {}
        all_results = []
        best = (-np.inf, None, None, None)  # score, params, est, bracket
        meta_brackets = []
        offset = 0
        for bi in range(len(brackets)):
            p = payloads[bi]
            s = p["s"]
            for rec in p["history"]:
                rec = dict(rec)
                rec["bracket"] = s
                rec["model_id"] = rec["model_id"] + offset
                self.history_.append(rec)
            for mid, recs in p["model_history"].items():
                self.model_history_[mid + offset] = recs
            res = p["results"]
            n_models = len(res["params"])
            res["bracket"] = np.full(n_models, s)
            res["model_id"] = res["model_id"] + offset
            all_results.append(res)
            meta_brackets.append({
                "bracket": s, "n_models": n_models,
                "partial_fit_calls": int(res["partial_fit_calls"].sum()),
            })
            if p["best_score"] > best[0]:
                best = (p["best_score"], p["best_params"],
                        p["best_estimator"], s)
            offset += n_models

        # merge bracket cv_results_
        keys = set().union(*(r.keys() for r in all_results))
        merged = {}
        for k in keys:
            vals = [
                r.get(k, np.ma.masked_all(len(r["params"]), dtype=object))
                for r in all_results
            ]
            if k == "params":
                merged[k] = [p for r in all_results for p in r["params"]]
            elif isinstance(vals[0], np.ma.MaskedArray):
                merged[k] = np.ma.concatenate(vals)
            else:
                merged[k] = np.concatenate(vals)
        scores = merged["test_score"]
        order = np.argsort(-scores, kind="stable")
        ranks = np.empty(len(scores), np.int32)
        ranks[order] = np.arange(1, len(scores) + 1)
        merged["rank_test_score"] = ranks
        self.cv_results_ = merged

        self.best_score_ = float(best[0])
        self.best_params_ = best[1]
        self.best_estimator_ = best[2]
        self.best_index_ = int(np.argmax(scores))
        self.scorer_ = None
        from ..metrics.scorer import check_scoring

        self.scorer_ = check_scoring(self.estimator, self.scoring)
        self.multimetric_ = False
        self.metadata_ = {
            "n_models": sum(b["n_models"] for b in meta_brackets),
            "partial_fit_calls": sum(
                b["partial_fit_calls"] for b in meta_brackets
            ),
            "brackets": meta_brackets,
        }
        return self
