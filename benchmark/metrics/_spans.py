"""What the span readers share. The program keeps its closed spans in an
in-memory ring (``dask_ml_tpu.observability.recent_spans()``, on whenever
``obs_programs`` is — the one program switch a traced run sets): every span
of one ``fit`` or one ``predict`` carries the ``root_id`` of the call's
outermost span. A reader takes the window's calls — the LAST roots of that
name, as many as the harness counted (the warm-up's precede them) — and
returns a mean over them; None when the ring has nothing to read, as with a
program that has no ring."""

import statistics


def _ring():
    try:
        from dask_ml_tpu.observability import recent_spans
    except ImportError:          # a program from before the ring
        return []
    return recent_spans()


def calls(name, n, ring=None):
    """The last ``n`` calls whose root span is ``name`` and did not raise:
    [(root record, {child span name: record})], oldest first. ``ring``:
    records taken earlier, in place of the program's ring as it is now."""
    if not n:
        return []
    ring = _ring() if ring is None else ring
    roots = [r for r in ring if r["span"] == name and r["parent_id"] is None
             and "error" not in r][-n:]
    by_root = {r["span_id"]: {} for r in roots}
    for r in ring:
        if r["root_id"] in by_root and r["parent_id"] is not None:
            by_root[r["root_id"]][r["span"]] = r
    return [(r, by_root[r["span_id"]]) for r in roots]


def fits(ctx):
    return calls("fit", len(ctx["fits"]))


def predicts(ctx):
    return calls("predict", sum(len(c["predict_s"]) for c in ctx["cycles"]))


def mean(values):
    values = [v for v in values if v is not None]
    return statistics.fmean(values) if values else None
