"""The five handoff readers (PR 34: ``fit_host_gap_ms``, ``fit_dispatch_ms``,
``host_operands_per_fit``, ``fetches_per_fit``, ``predict_fetch_ms``) on
recorded rings with known answers — one root a fit, several roots a fit
(``facts["passes"]``), nothing on a ring whose spans keep no ledger (the
parent of PR 34) — their entries in ``BENCHMARK.json`` found by name, and one
traced rehearsal a kind of fit, where the counts are the program's own."""

import pytest

from benchmark import harness
from benchmark.metrics import _handoffs, _spans
from dask_ml_tpu import observability as obs

from .test_rehearsal import _tiny

FIT_READERS = {"fit_host_gap_ms": ("host_gap_s", 1e3),
               "fit_dispatch_ms": ("dispatch_s", 1e3),
               "host_operands_per_fit": ("host_operands", 1.0),
               "fetches_per_fit": ("fetches", 1.0)}
ALL_SIX = ["logreg_resident", "logreg_resident_x4", "kmeans_lloyd",
           "pca_rsvd_x512", "sgd_incremental", "hyperband_sgd"]
ENTRIES = [
    ("fit_host_gap_ms", "ms", "program_span", "Device", "fit_s", ALL_SIX),
    ("fit_dispatch_ms", "ms", "program_span", "Device programs", "fit_s",
     ALL_SIX),
    ("host_operands_per_fit", "count", "program_counter", "Device programs",
     "fit_s", ALL_SIX),
    ("fetches_per_fit", "count", "program_counter", "Estimator entry",
     "fit_s", ALL_SIX),
    ("predict_fetch_ms", "ms", "program_span", "Estimator entry",
     "predict_rate", ["logreg_resident", "logreg_resident_x4",
                      "sgd_incremental", "hyperband_sgd"]),
]


def _read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


def _rec(span, span_id, root_id, parent_id, t=0, **attrs):
    return {"span": span, "span_id": span_id, "root_id": root_id,
            "parent_id": parent_id, "wall_s": 0.1, "sync_s": 0.0,
            "t_start_ns": t, "t_end_ns": t + 1, **attrs}


def _ledger(i):
    """Root attributes that differ from call to call in a known way."""
    return {"dispatches": 2 + i, "dispatch_s": 0.001 * (i + 1),
            "host_operands": 7 + i, "host_operand_bytes": 100,
            "fetches": 2 * i, "fetch_bytes": 1000, "fetch_s": 0.002 * (i + 1),
            "host_gap_s": 0.004 * (i + 1)}


def _glm_ring(n, ledger=True):
    """``n`` fits and ``n`` predicts, one root each, the child records
    carrying other numbers (only roots are read)."""
    ring = []
    for i in range(n):
        root = 10 * i + 1
        attrs = _ledger(i) if ledger else {}
        kid = {k: 99 for k in attrs}
        ring += [_rec("fit.solve", root + 1, root, root, **kid),
                 _rec("fit", root, root, None, **attrs),
                 _rec("predict.decision", root + 6, root + 5, root + 5, **kid),
                 _rec("predict", root + 5, root + 5, None, **attrs)]
    return ring


def _pass_ring(n_fits, passes, ledger=True):
    """``n_fits`` wrapper fits of ``passes`` roots each (``fit`` then
    ``partial_fit``), in time order; pass j of fit i carries ``_ledger(i)``."""
    ring, t = [], 0
    for i in range(n_fits):
        for j in range(passes):
            t += 10
            attrs = _ledger(i) if ledger else {}
            ring.append(_rec("fit" if j == 0 else "partial_fit", t, t, None,
                             t=t, path="fused_epoch", **attrs))
    return ring


GLM_CTX = {"fits": [{"fit_s": 0.1, "facts": {"n_iter": 8}}] * 2,
           "cycles": [{"predict_s": [0.01]}, {"predict_s": [0.01]}]}
SGD_CTX = {"fits": [{"fit_s": 0.1, "facts": {"passes": 3}}] * 2,
           "cycles": [{"predict_s": [0.01]}]}


@pytest.mark.parametrize("name", sorted(FIT_READERS))
def test_one_root_a_fit_mean_over_the_window(name, monkeypatch):
    """Three fits in the ring, two in the window: the warm-up's is not
    read; the mean of roots 1 and 2."""
    key, scale = FIT_READERS[name]
    monkeypatch.setattr(_spans, "_ring", lambda: _glm_ring(3))
    want = scale * (_ledger(1)[key] + _ledger(2)[key]) / 2
    assert _read(name, GLM_CTX) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(FIT_READERS))
def test_several_roots_a_fit_are_summed(name, monkeypatch):
    """Two window fits of three passes each after a warm-up fit: a fit's
    number is the sum over its passes."""
    key, scale = FIT_READERS[name]
    monkeypatch.setattr(_spans, "_ring", lambda: _pass_ring(3, 3))
    want = scale * 3 * (_ledger(1)[key] + _ledger(2)[key]) / 2
    assert _read(name, SGD_CTX) == pytest.approx(want)


def test_predict_fetch_ms_reads_the_predict_roots(monkeypatch):
    monkeypatch.setattr(_spans, "_ring", lambda: _glm_ring(3))
    want = 1e3 * (_ledger(1)["fetch_s"] + _ledger(2)["fetch_s"]) / 2
    assert _read("predict_fetch_ms", GLM_CTX) == pytest.approx(want)


@pytest.mark.parametrize("name", [*sorted(FIT_READERS), "predict_fetch_ms"])
@pytest.mark.parametrize("ring,ctx", [
    ([], GLM_CTX),                                   # no ring at all
    (_glm_ring(3, ledger=False), GLM_CTX),           # the parent of PR 34
    (_pass_ring(3, 3, ledger=False), SGD_CTX),
    ([], SGD_CTX),
], ids=["empty", "parent", "parent-passes", "empty-passes"])
def test_nothing_to_read_is_none_not_an_error(name, ring, ctx, monkeypatch):
    monkeypatch.setattr(_spans, "_ring", lambda: ring)
    assert _read(name, ctx) is None


def test_a_fit_whose_passes_are_not_all_there_is_left_out(monkeypatch):
    """Five pass roots for two fits of three: the ring lost the first; the
    fits are cut from the newest, so the older fit is one pass short."""
    ring = _pass_ring(2, 3)[1:]
    monkeypatch.setattr(_spans, "_ring", lambda: ring)
    roots = _handoffs._fit_roots(SGD_CTX)
    assert [[r["span"] for r in f] for f in roots] == [
        ["fit", "partial_fit", "partial_fit"]]
    assert _read("fetches_per_fit", SGD_CTX) == 3 * _ledger(1)["fetches"]


@pytest.mark.parametrize("name,unit,source,layer,moves,cells", ENTRIES)
def test_the_entries_by_name(name, unit, source, layer, moves, cells):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": cells}
    for w in bench["workloads"]:
        listed = name in [m["name"]
                          for m in harness.load_cell(w["name"]).per_layer]
        assert listed == (w["name"] in cells)


def test_the_five_close_per_layer_in_the_issue_s_order():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    assert [m["name"] for m in bench["per_layer"][-5:]] == \
        [e[0] for e in ENTRIES]


@pytest.mark.parametrize("cell_name,operands,fetches,predict", [
    ("logreg_resident", 7.0, 2.0, True),
    ("sgd_incremental", 40.0, 10.0, True),
    ("pca_rsvd_x512", 0.0, 5.0, False),
])
def test_the_readers_in_a_traced_rehearsal(cell_name, operands, fetches,
                                           predict, tmp_path):
    cell, devices = _tiny(harness.load_cell(cell_name))
    obs.reset_recent_spans()
    lines = []
    res = harness.run_cell(cell, seed=7, seconds=0.2, trace=1,
                           devices=devices, interpret=True,
                           dump=str(tmp_path), log=lines.append)
    assert res["correct"] is True, lines
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["host_operands_per_fit"] == operands
    assert m["fetches_per_fit"] == fetches
    assert 0 < m["fit_dispatch_ms"] < 1e3 * max(
        f["fit_s"] for f in _fits(tmp_path, cell_name))
    assert m["fit_host_gap_ms"] > 0
    assert ("predict_fetch_ms" in m) == predict
    # every tracked call of a fit is a dispatch of one of its roots
    fits = _fits(tmp_path, cell_name)
    ctx = {"fits": fits, "cycles": [{"predict_s": []}]}
    per_fit = [sum(r["dispatches"] for r in roots)
               for roots in _handoffs._fit_roots(ctx)]
    assert per_fit == [sum(f["programs"].values()) for f in fits]
    obs.reset_recent_spans()


def _fits(tmp_path, cell_name):
    dumped = harness.load_json(tmp_path, f"{cell_name}_trace1_s7.json")
    return [f for c in dumped["cycles"] for f in c["fits"]]
