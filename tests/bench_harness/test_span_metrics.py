"""The four per-layer metrics that read the program's own spans
(``evals_per_fit``, ``eval_ms``, ``fit_prep_ms``, ``predict_host_ms``) and
the tool that puts idle gaps down to host spans, rehearsed on the CPU: a
CPU run shows that the spans are there, nest, add up and carry the counts;
it never yields a time worth writing down."""

import jax
import pytest

from benchmark import harness
from benchmark.metrics import _spans
from benchmark.tools import gap_spans
from dask_ml_tpu import observability as obs

from .test_rehearsal import CELLS, _tiny

NEW = {"evals_per_fit": {"logreg_resident", "logreg_resident_x4"},
       "eval_ms": {"logreg_resident", "logreg_resident_x4"},
       "fit_prep_ms": set(CELLS),
       "predict_host_ms": {"logreg_resident", "logreg_resident_x4"}}


def _run(name, trace, dump):
    cell, devices = _tiny(harness.load_cell(name))
    obs.reset_recent_spans()
    lines = []
    res = harness.run_cell(cell, seed=5, seconds=0.2, trace=trace,
                           devices=devices, interpret=True, dump=str(dump),
                           log=lines.append)
    assert res["correct"] is True, lines
    return res, harness.load_json(dump, f"{name}_trace{trace}_s5.json")


@pytest.mark.parametrize("name", CELLS)
def test_span_metrics_in_a_traced_rehearsal(name, tmp_path):
    res, dumped = _run(name, 1, tmp_path)
    for metric, cells in NEW.items():
        if name in cells:
            assert res["metrics"][metric]["value"] > 0
        else:
            assert metric not in res["metrics"]
    fits = [f for c in dumped["cycles"] for f in c["fits"]]
    calls = _spans.calls("fit", len(fits))
    assert len(calls) == len(fits) >= 1
    # the warm-up's fit precedes the window's in the ring
    ring = obs.recent_spans()
    assert sum(r["span"] == "fit" for r in ring) == len(fits) \
        + harness.load_cell(name).traffic["cycle"].count("fit")
    for (root, kids), fit in zip(calls, fits):
        assert root["n_iter"] == fit["facts"]["n_iter"]
        # (2 ms of slack: the suite's workers share the cores, and a
        # rehearsal fit is tens of milliseconds)
        walls = sum(r["wall_s"] for r in kids.values())
        assert walls <= root["wall_s"] + 1e-5
        assert root["wall_s"] - walls <= 0.02 * root["wall_s"] + 2e-3
        # the program's root span against the harness's own clock around
        # the same call
        assert root["wall_s"] <= fit["fit_s"]
        assert fit["fit_s"] - root["wall_s"] <= 0.02 * fit["fit_s"] + 2e-3
    if name in NEW["evals_per_fit"]:
        evals = [kids["fit.solve"]["n_evals"] for _, kids in calls]
        assert all(e >= root["n_iter"] + 1
                   for e, (root, _) in zip(evals, calls))
        assert res["metrics"]["evals_per_fit"]["value"] \
            == pytest.approx(sum(evals) / len(evals))
        predicts = _spans.calls(
            "predict", sum(len(c["predict_s"]) for c in dumped["cycles"]))
        assert predicts and all(
            set(kids) == {"predict.decision", "predict.host"}
            for _, kids in predicts)
    obs.reset_recent_spans()


def test_untraced_run_leaves_the_ring_empty(tmp_path):
    res, _ = _run("logreg_resident", 0, tmp_path)
    assert set(res["metrics"]) == {"fit_s", "predict_rate", "setup_s"}
    assert obs.recent_spans() == []


def test_readers_find_nothing_without_a_ring(monkeypatch):
    """An empty ring, or a program from before the ring (the parent of the
    PR that adds it): every reader returns None and raises nothing."""
    obs.reset_recent_spans()
    ctx = {"fits": [{"fit_s": 0.1}] * 3, "cycles": [{"predict_s": [0.1]}]}
    for metric in NEW:
        assert harness.load_module("metrics", metric).read(ctx) is None
    monkeypatch.delattr(obs, "recent_spans")
    assert _spans._ring() == []


def test_gaps_are_put_down_to_the_innermost_host_span():
    """Chip 0 idles 8-20 ms (the host is in ``dmt.fit.prepare``, fetching)
    and 50-60 ms (``dmt.fit.finish``); chip 1 is busier, so chip 0 is the
    worst. A predict call idles after its matvec, under
    ``dmt.predict.host``. What runs outside the program's root spans counts
    nowhere."""
    ms = 1e6
    host = [["bench.fit", 0.0, 62 * ms], ["dmt.fit", 1 * ms, 59 * ms],
            ["dmt.fit.validate", 1 * ms, 2 * ms],
            ["dmt.fit.prepare", 3 * ms, 18 * ms],
            ["dmt.fit.solve", 21 * ms, 29 * ms],
            ["dmt.fit.finish", 50 * ms, 10 * ms],
            ["dmt.predict", 70 * ms, 30 * ms],
            ["dmt.predict.decision", 70 * ms, 6 * ms],
            ["dmt.predict.host", 76 * ms, 24 * ms],
            ["other.lib", 0.0, 100 * ms]]
    chip0 = [["prep.1 = fusion f32[8]", 3 * ms, 5 * ms],
             ["while.2", 20 * ms, 30 * ms],
             ["outside.3", 62 * ms, 5 * ms],
             ["matvec.4", 71 * ms, 4 * ms]]
    chip1 = [["prep.1 = fusion f32[8]", 3 * ms, 15 * ms],
             ["while.2", 20 * ms, 40 * ms],
             ["matvec.4", 71 * ms, 20 * ms]]
    table = {"planes": [
        {"name": f"/device:TPU:{i}",
         "lines": [{"name": "XLA Ops", "events": evs}]}
        for i, evs in enumerate([chip0, chip1])
    ] + [{"name": "/host:CPU",
          "lines": [{"name": "python", "events": host}]}], "names": {}}
    got = gap_spans.attribute(table, top=3)
    assert got == [
        {"s": pytest.approx(0.025), "in": "dmt.predict", "after": "matvec.4",
         "span": "dmt.predict.host"},
        {"s": pytest.approx(0.012), "in": "dmt.fit", "after": "prep.1",
         "span": "dmt.fit.prepare"},
        {"s": pytest.approx(0.010), "in": "dmt.fit", "after": "while.2",
         "span": "dmt.fit.finish"}]
    # all of chip 0's idle time, cut at the spans' edges: 1-3 ms lies
    # under validate, 50-60 under finish, 8-20 under prepare (20-21 of it
    # belongs to no child: the root's own)
    by_span = gap_spans.idle_by_span(table)
    assert {n: (r["calls"], round(r["idle_ms_a_call"], 6))
            for n, r in by_span.items()} == {
        "dmt.predict.host": (1, 24.0), "dmt.fit.prepare": (1, 12.0),
        "dmt.fit.finish": (1, 10.0), "dmt.fit.validate": (1, 2.0),
        "dmt.predict.decision": (1, 2.0)}
    no_device = {"planes": table["planes"][2:], "names": {}}
    assert gap_spans.attribute(no_device) is None
    assert gap_spans.idle_by_span(no_device) is None


def test_gap_spans_tool_rehearsal():
    """The tool's own traced cycles on the CPU: the program's spans are on
    the profiler's timeline beside the benchmark's annotations, one
    ``dmt.fit`` per ``bench.fit``, and the per-fit rows add up."""
    cell, devices = _tiny(harness.load_cell("logreg_resident"))
    table, fits, ring = gap_spans.run(cell, seed=5, cycles=1,
                                      devices=devices, interpret=True)
    obs.reset_recent_spans()
    out = gap_spans.report(cell, table, fits, ring, top=10)
    gap_spans.show(out)
    assert out["gaps"] is None                 # no device plane on the CPU
    names = [n for n, _, _ in gap_spans.host_spans(table, prefix="")]
    assert names.count("dmt.fit") == names.count("bench.fit") == len(fits) == 3
    assert names.count("dmt.predict.host") == names.count("bench.predict") == 1
    bench = gap_spans.host_spans(table, "bench.fit")
    prog = [sp for sp in gap_spans.host_spans(table) if sp[0] == "dmt.fit"]
    for (_, b0, b1), (_, p0, p1) in zip(bench, prog):
        assert b0 <= p0 <= p1 <= b1            # one timeline
    assert len(out["fits"]) == 3
    for row in out["fits"]:
        assert set(row["phases_ms"]) == {"fit.validate", "fit.prepare",
                                         "fit.solve", "fit.finish"}
        assert 0.9 < row["phases_over_root"] <= 1.0
        assert row["n_evals"] >= 2
    assert out["n_evals_sum"] == sum(r["n_evals"] for r in out["fits"])
    # the two clocks tick alike: they drift apart by well under a
    # millisecond between the first fit and the last
    assert all(abs(d) < 1e6 for d in out["clock"]["drift_ns"])
