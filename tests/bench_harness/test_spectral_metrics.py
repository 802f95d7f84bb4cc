"""The ``spectral`` family's own pieces, rehearsed on the CPU: the four
readers of the cell ``spectral_nystrom`` (``spectral_embed_ms``,
``spectral_assign_ms``, ``spectral_lloyd_iters_per_fit``,
``spectral_fit_roofline``), the fit's cost function, the benchmark's entries
found BY NAME, the configuration's stated defaults against the two classes'
signatures, the generator, the check failing a lower precision and a wrong
label, and readers that return None — and raise nothing — on a program
without the spans. A CPU run gives counts and correctness, never a time
worth writing down."""

import inspect

import jax
import numpy as np
import pytest

from benchmark import harness
from benchmark import tolerances_spectral as T
from benchmark.families import spectral as fam
from dask_ml_tpu import observability as obs
from dask_ml_tpu.cluster import KMeans, SpectralClustering
from dask_ml_tpu.parallel.mesh import device_mesh

from .test_rehearsal import _tiny

CELL = "spectral_nystrom"
READERS = ("spectral_embed_ms", "spectral_assign_ms",
           "spectral_lloyd_iters_per_fit", "spectral_fit_roofline")


def _read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One traced rehearsal of the cell: (result, dump, ring)."""
    tmp = tmp_path_factory.mktemp("spectral")
    cell, devices = _tiny(harness.load_cell(CELL))
    obs.reset_recent_spans()
    lines = []
    res = harness.run_cell(cell, seed=2**31 + 17, seconds=0.2, trace=1,
                           devices=devices, interpret=True, dump=str(tmp),
                           log=lines.append)
    assert res["correct"] is True, lines
    ring = obs.recent_spans()
    obs.reset_recent_spans()
    return res, harness.load_json(
        tmp, f"{CELL}_trace1_s{2**31 + 17}.json"), ring


def test_readers_in_a_traced_rehearsal(rehearsal):
    res, dumped, ring = rehearsal
    m = {k: v["value"] for k, v in res["metrics"].items()}
    fits = [f for c in dumped["cycles"] for f in c["fits"]]
    roots = [r for r in ring if r["span"] == "fit"][-len(fits):]
    assert len(roots) == len(fits) >= 1
    for root, fit in zip(roots, fits):
        kids = {r["span"]: r for r in ring
                if r["root_id"] == root["span_id"] and r is not root}
        assert list(kids) == ["fit.prep", "fit.solve", "fit.assign",
                              "fit.finish"]
        assert all(r["parent_id"] == root["span_id"] for r in kids.values())
        walls = sum(r["wall_s"] for r in kids.values())
        assert walls <= root["wall_s"] + 1e-5
        assert root["wall_s"] - walls <= 0.02 * root["wall_s"] + 2e-3
        # the counter is the harness's own fact
        assert kids["fit.assign"]["n_iter"] == fit["facts"]["n_iter"] \
            == root["n_iter"] == sum(kids["fit.assign"]["n_iters"])
        assert kids["fit.assign"]["restarts"] == fit["facts"]["restarts"] \
            == 10
        # the TPU's choice, requested in the rehearsal (interpret mode)
        assert fit["facts"]["assign_fused"] is True
        assert fit["programs"] == {
            "spectral.embed": 1, "kmeans.tol_scale": 10,
            "kmeans.lloyd_pallas": 10, "kmeans.labels_inertia": 10}
    n = len(fits)
    assert m["spectral_lloyd_iters_per_fit"] == pytest.approx(
        sum(f["facts"]["n_iter"] for f in fits) / n)
    assert m["spectral_lloyd_iters_per_fit"] >= 10       # one a restart
    assert m["spectral_embed_ms"] > 0 and m["spectral_assign_ms"] > 0
    assert m["spectral_embed_ms"] + m["spectral_assign_ms"] \
        <= 1e3 * sum(f["fit_s"] for f in fits) / n
    assert m["fit_prep_ms"] > 0 and m["iter_ms"] > 0
    assert m["compiles_in_window"] == 0.0
    assert "spectral_fit_roofline" not in m       # no device plane here
    facts = dumped["facts"]
    assert facts["control_fails"] and facts["label_mismatch"] == 0.0
    assert facts["singular_gap"] >= T.MIN_GAP
    for name in ("embedding_row", "subspace_sine", "singular_values"):
        assert facts[name] <= facts[name + "_limit"] < facts[
            "control_" + name]


def test_readers_find_nothing_without_the_spans(monkeypatch):
    """An empty ring, a program from before the ring, another estimator's
    ``fit.solve`` (no ``embed``), no device trace: None, and no raise."""
    obs.reset_recent_spans()
    cell = harness.load_cell(CELL)
    ctx = {"fits": [{"fit_s": 0.1, "facts": {"n_iter": 20}}] * 3,
           "cycles": [{"predict_s": [0.1]}], "trace": None, "cell": cell}
    for name in READERS:
        assert _read(name, ctx) is None
    root = {"span": "fit", "parent_id": None, "span_id": 1, "root_id": 1}
    solve = {"span": "fit.solve", "parent_id": 1, "span_id": 2, "root_id": 1,
             "wall_s": 0.5, "n_iter": 20}
    monkeypatch.setattr(obs, "recent_spans", lambda: [root, solve] * 3)
    ctx["trace"] = {"kinds": {"bench.fit": {"calls": 3, "seconds": 3.0,
                                            "idle_pct": 1.0}}}
    for name in READERS:
        assert _read(name, ctx) is None
    monkeypatch.delattr(obs, "recent_spans")
    for name in READERS:
        assert _read(name, ctx) is None


def test_the_readers_on_a_made_up_ring_and_trace(monkeypatch):
    """A fit of 1.0 s with the chip busy 40 % of it: 40 ms of embedding,
    0.9 s of restarts, 23 Lloyd iterations; the floor is one read of X."""
    cell = harness.load_cell(CELL)
    root = {"span": "fit", "parent_id": None, "span_id": 1, "root_id": 1}
    kids = [{"span": "fit.solve", "wall_s": 0.04, "embed": "tsqr"},
            {"span": "fit.assign", "wall_s": 0.9, "restarts": 10,
             "n_iter": 23}]
    kids = [dict(r, parent_id=1, span_id=i + 2, root_id=1)
            for i, r in enumerate(kids)]
    monkeypatch.setattr(obs, "recent_spans", lambda: [root] + kids)
    ctx = {"cell": cell, "fits": [{"fit_s": 1.0, "facts": {"n_iter": 23}}],
           "cycles": [], "n_rows": 4194304, "d": 256, "chips": 1,
           "trace": {"kinds": {"bench.fit": {"calls": 1, "seconds": 1.0,
                                             "idle_pct": 60.0}}},
           "peaks": lambda: harness.peaks_for("TPU v5 lite"),
           "kernel_cost": lambda: harness.load_module(
               "kernels", cell.config["main_kernel"]["cost"]).cost}
    assert _read("spectral_embed_ms", ctx) == pytest.approx(40.0)
    assert _read("spectral_assign_ms", ctx) == pytest.approx(900.0)
    assert _read("spectral_lloyd_iters_per_fit", ctx) == 23.0
    one_read = 4194304 * 256 * 4 / 819e9
    assert _read("spectral_fit_roofline", ctx) == pytest.approx(
        100 * one_read / 0.4)
    assert _read("spectral_fit_roofline", ctx) < 100


def test_fit_cost_at_the_cells_shapes():
    """One read of the float32 X (5.24 ms at 819 GB/s) bounds the fit; the
    cross term to 100 landmarks is 1.09 ms of the MXU's bf16 peak."""
    cell = harness.load_cell(CELL)
    cost = harness.load_module("kernels", "spectral_fit").cost
    need = cost(4194304, 256, cell.config["estimator"]["params"])
    assert need == {"bytes": 4194304 * 256 * 4,
                    "flops": 2 * 4194304 * 256 * 100}
    peaks = harness.peaks_for("TPU v5 lite")
    assert need["bytes"] / peaks["hbm_bytes_per_s"] == pytest.approx(
        5.244e-3, rel=1e-3)
    assert need["flops"] / peaks["flops_bf16"] == pytest.approx(
        1.090e-3, rel=1e-3)


def test_a_program_from_before_pr_38_is_refused_before_any_data(monkeypatch):
    from dask_ml_tpu.models import spectral

    cell, _ = _tiny(harness.load_cell(CELL))
    monkeypatch.delattr(spectral, "NYSTROM_JITTER")
    with pytest.raises(harness.BenchmarkError, match="before PR 38"):
        fam.make_data(cell.config, cell.traffic, 1, 3, None)


ENTRIES = [
    ("spectral_embed_ms", "ms", "program_span", "Factorisation"),
    ("spectral_assign_ms", "ms", "program_span", "Resident solver"),
    ("spectral_lloyd_iters_per_fit", "count", "program_counter",
     "Resident solver"),
    ("spectral_fit_roofline", "%", "device_trace", "Kernels"),
]


@pytest.mark.parametrize("name,unit,source,layer", ENTRIES)
def test_the_cell_s_entries_by_name(name, unit, source, layer):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit,
                     "better": "higher" if unit == "%" else "lower",
                     "source": source, "layer": layer, "moves": "fit_s",
                     "workloads": [CELL]}
    harness.load_module("metrics", name)          # its reader is there


def test_the_cell_and_its_configuration_by_name():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "spectral_nystrom_1b_x256", "resident_4m_fit_labels", 1)
    (c,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    assert c["reduced"] == ["rows_per_chip", "chips"]
    assert len(c["source"]) <= 200 and len(w["why"]) <= 200
    cfg = harness.load_json(harness.ROOT, c["file"])
    assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    assert cfg["n_features"] == cfg["deployment"]["n_features"] == 256
    assert cfg["deployment"]["rows"] == 64 * cfg["deployment"][
        "rows_per_chip"] == 1_000_000_000
    p = cfg["estimator"]["params"]
    # no width is cut: landmarks, clusters, restarts, features
    assert (p["n_components"], p["n_clusters"], p["n_init"]) == (100, 8, 10)
    traffic = harness.load_json(harness.HERE, "traffic",
                                f"{w['traffic']}.json")
    assert traffic["rows_per_chip"] == 4194304
    assert traffic["cycle"] == ["fit", "predict"]
    assert (traffic["sample_rows"], traffic["check_rows"],
            traffic["trace_cycles"]) == (262144, "all", 3)
    for name in ("iter_ms", "fit_prep_ms"):
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert CELL in entry["workloads"]


def test_the_stated_defaults_are_the_two_classes_signatures(monkeypatch):
    cfg = harness.load_cell(CELL).config
    assert fam.stated_defaults(cfg) == []
    sig = inspect.signature(SpectralClustering.__init__).parameters
    for k, v in cfg["estimator"]["params"].items():
        assert sig[k].default == (False if k == "persist_embedding" else v)
    ksig = inspect.signature(KMeans.__init__).parameters
    assert {k: ksig[k].default for k in ("init", "oversampling_factor",
                                         "max_iter", "tol")} == {
        "init": "k-means||", "oversampling_factor": 2, "max_iter": 300,
        "tol": 1e-4}
    # a program whose default moved fails the cell at engaged
    moved = dict(cfg, kmeans_defaults=dict(cfg["kmeans_defaults"], tol=1e-3))
    assert any("KMeans's default tol" in msg
               for msg in fam.stated_defaults(moved))
    from dask_ml_tpu.models import spectral

    monkeypatch.setattr(spectral, "NYSTROM_JITTER", 1e-5)
    assert any("NYSTROM_JITTER" in msg for msg in fam.stated_defaults(cfg))


def test_the_generator_is_seeded_sharded_and_off_the_origin():
    cell, _ = _tiny(harness.load_cell(CELL))
    mesh = device_mesh(devices=jax.devices()[:2])
    a = fam.make_data(cell.config, cell.traffic, 2, 7, mesh)
    b = fam.make_data(cell.config, cell.traffic, 2, 7, mesh)
    c = fam.make_data(cell.config, cell.traffic, 2, 8, mesh)
    Xa, ga = np.asarray(a["X"].data), np.asarray(a["y"].data)
    assert Xa.shape == (4096, 256) and Xa.dtype == np.float32
    assert len(a["X"].data.sharding.device_set) == 2
    assert np.array_equal(Xa, np.asarray(b["X"].data))
    assert not np.array_equal(Xa, np.asarray(c["X"].data))
    assert not np.array_equal(Xa[:2048], Xa[2048:])   # the chips' own rows
    # 8 equal groups; within a group a squared distance of ~2, between ~10,
    # and ||x||^2 two orders above either: the expansion has to cancel
    assert set(np.unique(ga)) == set(range(8))
    assert np.bincount(ga.astype(int)).min() > 4096 / 8 * 0.7
    x = Xa.astype(np.float64)
    same = ga[:512, None] == ga[None, :512]
    d2 = ((x[:512, None] - x[None, :512]) ** 2).sum(-1)
    off = ~np.eye(512, dtype=bool)
    assert 1.7 < d2[same & off].mean() < 2.3
    assert 7.0 < d2[~same].mean() < 14.0
    assert (x ** 2).sum(axis=1).mean() > 150


def test_the_check_fails_a_wrong_label_and_a_winner_that_is_not_least():
    """Beside the control (a lower precision: the rehearsal's
    ``control_fails``), what the label and inertia limits hold."""
    rng = np.random.default_rng(0)
    E = rng.standard_normal((2048, 8)).astype(np.float32)
    E /= np.linalg.norm(E, axis=1, keepdims=True)
    labels = rng.integers(0, 8, 2048).astype(np.int32)
    want = {"E": E, "singular_values": np.ones(100)}
    out = {"E": E, "singular_values": np.ones(8), "labels": labels,
           "inertias": [3.0, 2.0, 2.5], "winner": 1}
    ref_labels = labels[:1024]
    ok = T.readings(out, want, ref_labels, 1024, 8)
    assert all(v <= lim for v, lim in ok.values())
    wrong = labels.copy()
    wrong[:8] = (wrong[:8] + 1) % 8                    # 8 of 1,024 rows
    bad = T.readings(dict(out, labels=wrong), want, ref_labels, 1024, 8)
    assert bad["label_mismatch"][0] == pytest.approx(8 / 1024)
    assert bad["label_mismatch"][0] > T.TOL_LABEL_SHARE
    bad = T.readings(dict(out, winner=2), want, ref_labels, 1024, 8)
    assert bad["winner_not_least"][0] == 1.0 > bad["winner_not_least"][1]
    bad = T.readings(dict(out, singular_values=np.ones(8) + 1e-4), want,
                     ref_labels, 1024, 8)
    assert bad["singular_values"][0] > T.TOL_SINGULAR
