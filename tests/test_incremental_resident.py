"""``Incremental(SGDClassifier)`` trained by ``partial_fit`` passes over a
resident, row-sharded X (BENCHMARK.json's ``sgd_incremental``), small, on the
CPU: the wrapper's five-pass fit against the benchmark's plain reference
(``benchmark/references/sgd.py``), the four paths and the record of which one
ran (``pass_info_``), the spans, and the limits of
``benchmark/tolerances_sgd.py`` against the faults they must catch."""

import jax
import numpy as np
import pytest

from benchmark import tolerances_sgd as T
from benchmark.families import sgd as family
from benchmark.harness import compile_counter
from benchmark.references import sgd as ref
from dask_ml_tpu import config, observability as obs
from dask_ml_tpu.linear_model import SGDClassifier
from dask_ml_tpu.models.sgd import fused_blocks
from dask_ml_tpu.parallel import as_sharded
from dask_ml_tpu.parallel.mesh import device_mesh, use_mesh
from dask_ml_tpu.wrappers import Incremental

D = 16
PASSES = 5
# the CPU's sums differ from the reference's by float32 rounding alone
TIGHT = 1e-5


def _data(n, seed=0, d=D):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    beta = rng.standard_normal(d)
    beta /= np.linalg.norm(beta)
    p = 1.0 / (1.0 + np.exp(-2.0 * (X @ beta)))
    return X, (rng.random(n) < p).astype(np.float32)


def _five_passes(Xs, ys, **kw):
    inner = SGDClassifier(**{"loss": "log_loss", **kw.pop("inner", {})})
    inc = Incremental(inner, **{"shuffle_blocks": True, "random_state": 3,
                                **kw})
    inc.fit(Xs, ys, classes=[0, 1])
    for _ in range(PASSES - 1):
        inc.partial_fit(Xs, ys)
    return inc


def _reference(inc, Xs, ys, chips, **kw):
    n = Xs.n_rows
    S = family.block_rows(n, 8, chips)
    assert fused_blocks(Xs) == (8, S)
    order = family.orders(inc.random_state, 8, PASSES, inc.shuffle_blocks)
    return ref.fit(Xs.data, ys.data, order, S, n,
                   loss=inc.estimator.loss, **kw)


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("n", [4096, 4099])
@pytest.mark.parametrize("shuffle", [True, False])
@pytest.mark.parametrize("loss", ["log_loss", "hinge", "squared_error"])
def test_five_passes_match_the_reference(loss, shuffle, n, chips):
    """fit + 4 x partial_fit = the reference's 40 steps in the same order
    on the same rows, the clock running on; 4099 rows leave a masked tail
    in the last block."""
    X, y = _data(n)
    with use_mesh(device_mesh(devices=jax.devices()[:chips])):
        Xs, ys = as_sharded(X), as_sharded(y)
        inc = _five_passes(Xs, ys, shuffle_blocks=shuffle,
                           inner={"loss": loss})
        w_ref, t = _reference(inc, Xs, ys, chips)
    assert t == 40 == inc.pass_info_["t_end"]
    assert inc.pass_info_["path"] == "fused_epoch"
    assert list(inc.classes_) == [0, 1]
    assert T.distance(family.weights(inc.estimator_), w_ref) < TIGHT


@pytest.mark.parametrize("chips", [1, 4])
def test_bfloat16_grid_matches_the_reference_at_the_stated_precision(chips):
    """A bfloat16 grid (what ``dtype="auto"`` chooses on a TPU) multiplies
    the numbers the reference rounds: the stated-precision limit holds it,
    and the float32 reference is a bfloat16 design away."""
    X, y = _data(4099)
    with use_mesh(device_mesh(devices=jax.devices()[:chips])):
        Xs, ys = as_sharded(X), as_sharded(y)
        inc = _five_passes(Xs, ys, inner={"fit_dtype": "bfloat16"})
        w = family.weights(inc.estimator_)
        stated = T.distance(w, _reference(inc, Xs, ys, chips,
                                          design_dtype="bfloat16")[0])
        f32 = T.distance(w, _reference(inc, Xs, ys, chips)[0])
    assert inc.pass_info_["fit_dtype"] == "bfloat16"
    S = family.block_rows(4099, 8, chips)
    assert inc.estimator_.solver_info_ == {
        "path": "fused_epoch", "program": "sgd.fused_epoch", "blocks": 8,
        "block_rows": S, "steps": 8, "grid_bytes": 8 * S * (D * 2 + 4),
        "grid_hit": True}
    assert stated <= TIGHT < f32 <= T.f32_band(family.block_rows(4099, 8, chips))


@pytest.mark.parametrize("fault", ["accumulate", "update", "drop", "swap",
                                   "unshuffled"])
def test_the_stated_precision_limit_fails_what_it_must(fault):
    """The reference changed in ONE way — a product's result or the weights
    rounded to bfloat16, the last step dropped, the blocks unshuffled — is
    farther from itself than ``TOL_STATED``; the last two steps swapped,
    farther than the limit these tests hold the system to."""
    n = 65536
    X, y = _data(n, d=64)
    Xd, yd = jax.numpy.asarray(X), jax.numpy.asarray(y)
    S = family.block_rows(n, 8, 1)
    order = family.orders(3, 8, PASSES, True)
    good, _ = ref.fit(Xd, yd, order, S, n, design_dtype="bfloat16")
    kw = {"design_dtype": "bfloat16"}
    if fault in ("accumulate", "update"):
        kw["lower"] = fault
    elif fault == "drop":
        order[-1] = order[-1][:-1]
    elif fault == "swap":
        order[-1][-1], order[-1][-2] = order[-1][-2], order[-1][-1]
    else:
        order = family.orders(3, 8, PASSES, False)
    bad, _ = ref.fit(Xd, yd, order, S, n, **kw)
    # (two neighbouring steps nearly commute: only the tests' own limit,
    # float32 rounding at this size, sees a swap — tolerances_sgd.py)
    assert T.distance(bad, good) > 2 * (TIGHT if fault == "swap"
                                        else T.TOL_STATED)


def _refuse(monkeypatch):
    monkeypatch.setattr(type(jax.devices()[0]), "memory_stats", lambda dev: {
        "bytes_limit": 1 << 20, "bytes_in_use": (1 << 20) - 4096})


def test_the_gate_refusing_takes_the_block_loop(monkeypatch):
    """A device that reports too little free memory for a second copy of X:
    the same blocks one ``partial_fit`` each — the same ``coef_`` to float32
    rounding — and the record says which path ran and what the gate read."""
    X, y = _data(4099)
    with use_mesh(device_mesh(devices=jax.devices()[:1])):
        Xs, ys = as_sharded(X), as_sharded(y)
        first = Incremental(SGDClassifier(loss="log_loss")).fit(
            Xs, ys, classes=[0, 1])
        fused = _five_passes(Xs, ys)
        _refuse(monkeypatch)
        loop = _five_passes(Xs, ys)
    assert fused.pass_info_["path"] == first.pass_info_["path"] \
        == "fused_epoch"
    # the gate is asked by a pass that BUILDS its grid; the fifth pass over
    # the same X reads the kept one and asks nothing
    assert first.pass_info_["headroom"] == {
        "needed": Xs.data.nbytes, "free": None, "fits": True}
    assert fused.pass_info_["headroom"] is None
    assert fused.pass_info_["grid_hit"] and not first.pass_info_["grid_hit"]
    assert loop.pass_info_["path"] == "block_loop"
    assert loop.pass_info_["headroom"] == {
        "needed": Xs.data.nbytes, "free": 4096, "fits": False}
    assert loop.pass_info_["grid_bytes"] == 0
    for inc in (fused, loop):
        assert (inc.pass_info_["blocks"], inc.pass_info_["steps"],
                inc.pass_info_["t_end"]) == (8, 8, 40)
    assert T.distance(family.weights(loop.estimator_),
                      family.weights(fused.estimator_)) < TIGHT


@pytest.mark.parametrize("kind", ["device_estimator", "host_estimator"])
def test_host_data_records_its_path(kind):
    from sklearn.linear_model import SGDClassifier as SkSGD

    X, y = _data(4096)
    est = SGDClassifier() if kind == "device_estimator" \
        else SkSGD(random_state=0)
    inc = Incremental(est, random_state=0).fit(X, y, classes=[0, 1])
    want = ("stream_pass", "host_loop") if kind == "device_estimator" \
        else ("host_loop",)
    assert inc.pass_info_["path"] in want
    assert inc.pass_info_["blocks"] >= 1 and inc.pass_info_["grid_bytes"] == 0
    assert inc.pass_info_["headroom"] is None
    if kind == "device_estimator":
        assert inc.pass_info_["steps"] == inc.pass_info_["t_end"] \
            == inc.pass_info_["blocks"]
    else:
        assert inc.pass_info_["t_end"] is None
        assert inc.pass_info_["steps"] == inc.pass_info_["blocks"]


def test_a_second_fit_at_the_same_shapes_compiles_nothing():
    X, y = _data(4099)
    with use_mesh(device_mesh(devices=jax.devices()[:1])):
        Xs, ys = as_sharded(X), as_sharded(y)
        inc = _five_passes(Xs, ys)
        inc.predict(Xs)
        compiles = compile_counter()
        before = compiles.n
        again = _five_passes(Xs, as_sharded(1.0 - y), random_state=11)
        again.predict(Xs)
    assert compiles.n == before
    assert again.pass_info_["t_end"] == 40


def _program_calls():
    return sum(r["calls"] for r in obs.programs_snapshot())


def _calls(ring):
    """[(root, {child name: record})] of the ring's roots, oldest first."""
    roots = [r for r in ring if r["parent_id"] is None]
    return [(root, {r["span"]: r for r in ring
                    if r["root_id"] == root["span_id"] and r is not root})
            for root in roots]


def test_spans_one_root_a_call_and_the_pass_record_on_it():
    X, y = _data(4099)
    obs.reset_recent_spans()
    with use_mesh(device_mesh(devices=jax.devices()[:1])), \
            config.set(obs_programs=True):
        Xs, ys = as_sharded(X), as_sharded(y)
        inc = Incremental(SGDClassifier(), random_state=3)
        infos, deltas = [], []
        for call in ("fit", "partial_fit", "partial_fit"):
            before = _program_calls()
            kw = {"classes": [0, 1]} if call == "fit" else {}
            getattr(inc, call)(Xs, ys, **kw)
            deltas.append(_program_calls() - before)
            infos.append(dict(inc.pass_info_))
        labels = inc.predict(Xs)
        calls = _calls(obs.recent_spans())
    obs.reset_recent_spans()
    assert [root["span"] for root, _ in calls] == [
        "fit", "partial_fit", "partial_fit", "predict"]
    # the X half of the grid is built by the first pass and kept: the later
    # passes dispatch no ``sgd.grid_x`` (tests/test_incremental_kept_grid.py)
    for (root, kids), info, delta, hit in zip(calls, infos, deltas,
                                              (False, True, True)):
        assert set(kids) == {"pass.validate", "pass.grid", "pass.solve"}
        assert root["component"] == "Incremental"
        assert root["estimator"] == "SGDClassifier"
        # the record, on the estimator and on the span
        assert {k: root[k] for k in info} == info
        assert info["dispatches"] == delta == (2 if hit else 3)
        assert kids["pass.grid"]["grid_hit"] is info["grid_hit"] is hit
        assert kids["pass.grid"]["grid_bytes"] == info["grid_bytes"] > 0
        assert kids["pass.solve"]["t_end"] == info["t_end"]
        starts = [kids[k]["t_start_ns"] for k in
                  ("pass.validate", "pass.grid", "pass.solve")]
        assert root["t_start_ns"] <= starts[0] <= starts[1] <= starts[2]
        assert sum(k["wall_s"] for k in kids.values()) <= root["wall_s"] + 1e-5
    assert [i["t_end"] for i in infos] == [8, 16, 24]
    # the estimator's own predict nests under the wrapper's root, with the
    # device half and the host half apart (predict_host_ms reads the latter)
    root, kids = calls[-1]
    assert root["component"] == "Incremental"
    assert set(kids) == {"predict", "predict.decision", "predict.host"}
    assert kids["predict"]["component"] == "SGDClassifier"
    assert kids["predict"]["n_rows"] == 4099
    assert kids["predict.decision"]["wall_s"] + kids["predict.host"]["wall_s"] \
        <= kids["predict"]["wall_s"] <= root["wall_s"]
    assert np.array_equal(labels, inc.estimator_.predict(Xs))
    assert set(np.unique(labels)) <= {0, 1}


def test_untraced_pass_records_no_dispatch_count():
    X, y = _data(4096)
    with use_mesh(device_mesh(devices=jax.devices()[:1])):
        inc = Incremental(SGDClassifier(), random_state=3).fit(
            as_sharded(X), as_sharded(y), classes=[0, 1])
    assert inc.pass_info_["dispatches"] is None
    assert inc.pass_info_["path"] == "fused_epoch"
    assert obs.recent_spans() == []


def test_the_estimator_alone_opens_its_own_root_and_nests_under_the_wrapper(
        monkeypatch):
    X, y = _data(4096)
    obs.reset_recent_spans()
    with use_mesh(device_mesh(devices=jax.devices()[:1])), \
            config.set(obs_programs=True):
        Xs, ys = as_sharded(X), as_sharded(y)
        SGDClassifier().partial_fit(Xs, ys, classes=[0, 1])
        alone = obs.recent_spans()
        obs.reset_recent_spans()
        _refuse(monkeypatch)           # the block loop calls partial_fit
        inc = Incremental(SGDClassifier(), random_state=3).fit(
            Xs, ys, classes=[0, 1])
        nested = obs.recent_spans()
    obs.reset_recent_spans()
    assert [(r["span"], r["parent_id"]) for r in alone] == [
        ("partial_fit", None)]
    assert alone[0]["component"] == "SGDClassifier" and alone[0]["t_end"] == 1
    roots = [r for r in nested if r["parent_id"] is None]
    assert [r["span"] for r in roots] == ["fit"]
    solve = [r for r in nested if r["span"] == "pass.solve"]
    steps = [r for r in nested if r["span"] == "partial_fit"]
    assert len(solve) == 1 and len(steps) == 8
    assert {r["parent_id"] for r in steps} == {solve[0]["span_id"]}
    assert inc.pass_info_["path"] == "block_loop"
    assert inc.pass_info_["dispatches"] >= 8
