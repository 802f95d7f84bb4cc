"""dask_ml_tpu — a TPU-native distributed ML framework with the
capabilities of dask-ml (see SURVEY.md for the blueprint).

Infrastructure layers:
- ``parallel/`` — mesh/sharding substrate (ShardedArray, streaming,
  multi-host runtime)
- ``ops/``      — masked reductions, distributed linalg (TSQR /
  randomized SVD), pairwise kernels, Pallas fused kernels
- ``models/``   — estimator implementations + GLM solver library
- ``io/``       — native (C++) block loaders
- ``observability/`` — JSONL metrics, span tracing, runtime counters,
  run-report CLI (``python -m dask_ml_tpu.observability.report``)
- ``plans/``    — the one execution plane for compiled programs: shape
  ladders, ProgramPlan build path (cache/track/donate/compile-cache),
  process-wide warmup registry
- ``serving/``  — online inference: ModelServer micro-batching over a
  shape-bucket ladder with admission control and warmup
- ``utils/``    — validation, checkpointing, testing

sklearn/dask-ml-parity namespaces (import as ``dask_ml_tpu.<name>``):
``cluster``, ``compose``, ``datasets``, ``decomposition``, ``ensemble``,
``feature_extraction``, ``impute``, ``linear_model``, ``metrics``,
``model_selection``, ``naive_bayes``, ``preprocessing``, ``wrappers``,
``xgboost``.
"""

__version__ = "0.1.0"

from .config import ensure_compile_cache as _ensure_compile_cache

_ensure_compile_cache()

__all__ = [
    "cluster", "compose", "config", "datasets", "decomposition",
    "ensemble", "feature_extraction", "impute", "linear_model", "metrics",
    "model_selection", "naive_bayes", "observability", "ops", "parallel",
    "plans", "preprocessing", "serving", "utils", "wrappers", "xgboost",
    "__version__",
]
