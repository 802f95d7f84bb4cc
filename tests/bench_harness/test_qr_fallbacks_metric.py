"""The reader ``qr_fallbacks_per_fit`` (PR 26) on recorded rings with known
answers: the window's mean, nothing on a program whose ``fit.solve`` span
has no ``qr_fallbacks`` (the parent of PR 26, another family), and its
entry in ``BENCHMARK.json``."""

import pytest

from benchmark import harness
from benchmark.metrics import _spans

NAME = "qr_fallbacks_per_fit"
CTX = {"fits": [{"fit_s": 0.1}, {"fit_s": 0.1}],
       "cycles": [{"predict_s": [0.01]}, {"predict_s": [0.01]}]}


def _read(ctx):
    return harness.load_module("metrics", NAME).read(ctx)


def _rec(span, span_id, root_id, parent_id, **attrs):
    return {"span": span, "span_id": span_id, "root_id": root_id,
            "parent_id": parent_id, "wall_s": 0.1, "sync_s": 0.0,
            "t_start_ns": 0, "t_end_ns": 0, **attrs}


def _ring(fallbacks):
    """One PCA fit per entry (the first is the warm-up's); None records a
    ``fit.solve`` span without the attribute."""
    ring = []
    for i, n in enumerate(fallbacks):
        root = 10 * i + 1
        attrs = {} if n is None else {"qr_fallbacks": n}
        ring += [_rec("fit.center", root + 1, root, root, x_sweeps=2),
                 _rec("fit.solve", root + 2, root, root, solver="randomized",
                      size=74, n_iter=2, x_sweeps=6, **attrs),
                 _rec("fit", root, root, None, component="PCA", n_rows=2048,
                      n_iter=2)]
    return ring


@pytest.mark.parametrize("fallbacks,want", [
    ((3, 0, 0), 0.0),            # the warm-up's fit is not the window's
    ((0, 3, 0), 1.5),
    ((0, 1, 1), 1.0),
    ((0, None, 2), 2.0),         # a fit without the attribute is left out
])
def test_mean_over_the_window_s_fits(fallbacks, want, monkeypatch):
    monkeypatch.setattr(_spans, "_ring", lambda: _ring(fallbacks))
    assert _read(CTX) == pytest.approx(want)


@pytest.mark.parametrize("ring", [
    [],                                         # no ring at all
    _ring((None, None, None)),                  # the parent of PR 26
    [_rec("fit.solve", 3, 1, 1, n_evals=9),     # another family's fit
     _rec("fit", 1, 1, None, component="LogisticRegression")],
], ids=["empty", "parent", "glm"])
def test_nothing_to_read_is_none_not_an_error(ring, monkeypatch):
    monkeypatch.setattr(_spans, "_ring", lambda: ring)
    assert _read(CTX) is None


def test_entry_in_the_benchmark():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry = bench["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "count", "better": "lower",
                     "source": "program_span", "layer": "Factorisation",
                     "moves": "fit_s", "workloads": ["pca_rsvd_x512"]}
    assert NAME in [m["name"]
                    for m in harness.load_cell("pca_rsvd_x512").per_layer]
    assert NAME not in [m["name"]
                        for m in harness.load_cell("kmeans_lloyd").per_layer]
