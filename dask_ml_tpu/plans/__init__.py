"""One execution plane for every compiled program (ISSUE 15).

dask-ml's leverage came from ONE execution engine (the dask task graph)
under every estimator; this package is that layer for the rebuild's
compiled programs. Three machineries used to own their own shape
policy, warmup, cache keying and donation — superblock scan programs
(streaming + search cohorts), serving's compiled batch entry points and
bucket ladders, and the stacked C-grid/OvR direct solves. They now all
construct their compiled specializations through here:

- :mod:`~dask_ml_tpu.plans.ladders` — the shape policies
  (:class:`GeometricLadder` / :class:`NnzLadder` /
  :class:`SlotRungLadder`), with padding/mask construction co-located
  with the rung choice;
- :mod:`~dask_ml_tpu.plans.plan` — :class:`ProgramPlan`, the
  declarative spec whose :meth:`~ProgramPlan.build` is the one path to
  a tracked jitted entry point (cache keying, ``track_program``
  registration, donation wiring), plus :func:`tracked` for pre-jitted scan builders;
- :mod:`~dask_ml_tpu.plans.warmup` — the process-wide
  :data:`warmups` registry: idempotent, attributable
  (``plan_warmups``/``plan_cache_hits`` counters, the ``plans`` table
  on ``/status`` and in the report CLI) warming for every client.

Any new estimator that declares its programs as plans gets streaming +
serving + sharding + telemetry behavior for free — ``naive_bayes``'s
streamed fit / served predict is the worked example
(``examples/12_plans.py``).

Config knobs: ``plan_cache`` (reuse identical plan builds process-wide)
and ``plan_rewarm`` (force warm executions to re-run).
"""

from .ladders import (GeometricLadder, NnzLadder, ShapeLadder,
                      SlotRungLadder)
from .plan import (ProgramPlan, annotate_programs, note_rung,
                   plans_reset, plans_snapshot, register_attr, tracked)
from .warmup import WarmupRegistry, warmups

__all__ = [
    "ShapeLadder", "GeometricLadder", "NnzLadder", "SlotRungLadder",
    "ProgramPlan", "tracked", "register_attr", "note_rung",
    "annotate_programs", "plans_snapshot", "plans_reset",
    "WarmupRegistry", "warmups",
]
