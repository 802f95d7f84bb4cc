"""Execution plans (ISSUE 15): declare -> warm -> fit -> serve ->
scrape the plans table.

The ``dask_ml_tpu/plans`` subsystem is the ONE layer every compiled
specialization goes through — shape ladders (serving rows / sparse nnz
/ cohort slots), ``ProgramPlan.build()`` (cache keying, track_program
registration, donation wiring) and the
process-wide ``WarmupRegistry``. This example walks the whole loop on
the newest plan client, GaussianNB:

1. DECLARE — the estimator's streamed fit is one ProgramPlan (a
   donated-carry per-block class-stats reducer) plus a GeometricLadder
   for block heights; that declaration lives in
   ``dask_ml_tpu/naive_bayes.py`` and is ~a page of code.
2. FIT (streamed) — ``Incremental(GaussianNB())`` streams host blocks
   through the plan-built program; pass 2 pays zero new XLA compiles.
3. SERVE (warmed) — ``ModelServer(fitted).warmup()`` walks the serving
   ladder through the WarmupRegistry; ragged traffic then mints zero
   compiles, and a second server over the same shapes warms for free
   (``plan_cache_hits``).
4. SCRAPE — the plans table (also on ``/status`` and in the report
   CLI) names which ladder rung minted each specialization.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from dask_ml_tpu import observability as obs
from dask_ml_tpu import plans
from dask_ml_tpu.naive_bayes import GaussianNB
from dask_ml_tpu.serving import BucketLadder, ModelServer
from dask_ml_tpu.wrappers import Incremental

n = int(os.environ.get("DASK_ML_TPU_EXAMPLE_N", 50_000))
d = 16
rng = np.random.RandomState(0)
half = n // 2
X = np.concatenate([rng.randn(half, d) + 1.5,
                    rng.randn(n - half, d) - 1.5]).astype(np.float32)
y = np.concatenate([np.zeros(half), np.ones(n - half)])
p = rng.permutation(n)
X, y = X[p], y[p]

# -- 2. streamed fit through the plan-built stats program -------------------
inc = Incremental(GaussianNB(), shuffle_blocks=True, random_state=0)
inc.fit(X, y)                                  # pass 1 mints the rungs
before = obs.counters_snapshot().get("recompiles", 0)
inc.partial_fit(X, y)                          # pass 2: warm caches only
after = obs.counters_snapshot().get("recompiles", 0)
nb = inc.estimator_
print(f"streamed GaussianNB: acc={nb.score(X, y):.3f}, "
      f"pass-2 recompiles={after - before} (contract: 0)")
assert after - before == 0

# -- 3. warmed serving through the WarmupRegistry ---------------------------
ladder = BucketLadder(8, 256, 2.0)
server = ModelServer(nb, methods=("predict", "predict_proba"),
                     ladder=ladder, batch_window_ms=1.0, timeout_ms=0)
server.warmup()
before = obs.counters_snapshot().get("recompiles", 0)
with server:
    r = np.random.RandomState(1)
    for _ in range(30):
        k = r.randint(1, 256)
        i = r.randint(0, n - k)
        server.predict(X[i:i + k])
after = obs.counters_snapshot().get("recompiles", 0)
print(f"served ragged traffic: recompiles={after - before} "
      "(contract: 0)")
assert after - before == 0

# a SECOND server over the same-shaped model: the plan build cache
# returns the same entry points, so its warmup is pure registry hits
before_hits = obs.counters_snapshot().get("plan_cache_hits", 0)
ModelServer(nb, methods=("predict", "predict_proba"),
            ladder=ladder).warmup()
hits = obs.counters_snapshot().get("plan_cache_hits", 0) - before_hits
print(f"second server warmup: {hits} plan cache hits, 0 fresh compiles")

# -- 4. the plans table -----------------------------------------------------
print("\nplans (program / plan / ladder / rungs / warmups / hits):")
for row in plans.plans_snapshot():
    if row["warmups"] or row["warm_hits"] or "nb" in row["program"]:
        print(f"  {row['program']:<38} {row['plan']:<12} "
              f"{row['ladder']:<14} {row['rungs']:<14} "
              f"{row['warmups']:>3} {row['warm_hits']:>3}")
