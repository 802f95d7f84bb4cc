"""The X half of ``Incremental``'s epoch grid outlives its pass
(``models/sgd.py::_KeptGrid``): a pass handed the SAME device array under the
same key reads the kept grid and dispatches no ``sgd.grid_x``. Small, on the
CPU, on one- and four-device meshes: when it hits and misses, that the
weights are BITWISE those of passes that rebuild the grid every time, and the
whole of its lifetime - one grid a wrapper, gone with another array or key,
with its source, with the wrapper, and never copied."""

import copy
import gc
import pickle
import weakref

import jax
import numpy as np
import pytest
from sklearn.base import clone

from dask_ml_tpu import config, observability as obs, wrappers
from dask_ml_tpu.linear_model import SGDClassifier
from dask_ml_tpu.parallel import as_sharded
from dask_ml_tpu.parallel.mesh import device_mesh, use_mesh
from dask_ml_tpu.wrappers import Incremental
from tests.test_incremental_resident import _refuse

N, D = 4099, 16          # 4099 rows leave a masked tail in the last block
GRID = ("sgd.grid_x", "sgd.grid_y", "sgd.fused_epoch")


def _data(seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((N, D)).astype(np.float32)
    beta = rng.standard_normal(D)
    p = 1.0 / (1.0 + np.exp(-2.0 * (X @ beta) / np.linalg.norm(beta)))
    return X, (rng.random(N) < p).astype(np.float32)


def _new(**inner):
    return Incremental(SGDClassifier(loss="log_loss", **inner),
                       random_state=3)


def _weights(inc):
    est = inc.estimator_
    return np.r_[np.ravel(est.coef_), np.ravel(est.intercept_)]


def _ran():
    return {r["program"]: int(r["calls"]) for r in obs.programs_snapshot()
            if r["program"] in GRID}


def _delta(before):
    return {k: v - before.get(k, 0) for k, v in _ran().items()
            if v - before.get(k, 0)}


def _passes(inc, feed, rebuild=False):
    """``fit`` on the first (X, y) of ``feed``, ``partial_fit`` on the rest;
    ``rebuild`` empties the holder before each, which is a wrapper that keeps
    nothing. Returns every pass's record and what ran."""
    before, infos = _ran(), []
    for i, (Xs, ys) in enumerate(feed):
        if rebuild and "_epoch_grid" in vars(inc):
            inc._epoch_grid.clear()
        if i == 0:
            inc.fit(Xs, ys, classes=[0, 1])
        else:
            inc.partial_fit(Xs, ys)
        infos.append(dict(inc.pass_info_))
    return infos, _delta(before)


def _hits(infos):
    return [i["grid_hit"] for i in infos]


# -- the cases: each gets the placed (Xs, ys) of seed 0 and pytest's monkeypatch

def same_x_five_passes(Xs, ys, monkeypatch):
    """(a) one build, four hits, and bitwise the weights of five builds."""
    obs.reset_recent_spans()
    kept, rebuilt = _new(), _new()
    infos, ran = _passes(kept, [(Xs, ys)] * 5)
    grids = [r for r in obs.recent_spans() if r["span"] == "pass.grid"]
    roots = [r for r in obs.recent_spans() if r["parent_id"] is None]
    obs.reset_recent_spans()
    ref_infos, ref_ran = _passes(rebuilt, [(Xs, ys)] * 5, rebuild=True)
    assert _hits(infos) == [False, True, True, True, True]
    assert ran == {"sgd.grid_x": 1, "sgd.grid_y": 5, "sgd.fused_epoch": 5}
    assert _hits(ref_infos) == [False] * 5
    assert ref_ran == {"sgd.grid_x": 5, "sgd.grid_y": 5, "sgd.fused_epoch": 5}
    assert np.array_equal(_weights(kept), _weights(rebuilt))
    assert [i["t_end"] for i in infos] == [8, 16, 24, 32, 40]
    # the bytes of the grid the pass READS, hit or miss; the counter on the
    # pass's record, on its root span, on pass.grid and in solver_info_
    assert len({i["grid_bytes"] for i in infos}) == 1
    assert infos[0]["grid_bytes"] == ref_infos[0]["grid_bytes"] > 0
    assert [(g["grid_hit"], g["grid_bytes"]) for g in grids] == [
        (i["grid_hit"], i["grid_bytes"]) for i in infos]
    assert [r["grid_hit"] for r in roots] == _hits(infos)
    assert kept.estimator_.solver_info_["grid_hit"] is True
    assert [i["dispatches"] for i in infos[1:]] == [2] * 4
    assert [i["dispatches"] for i in ref_infos[1:]] == [3] * 4


def new_y_same_x(Xs, ys, monkeypatch):
    """(b) the labels are not part of what is kept: new y over the same X
    hits, and trains on the new y."""
    y2 = as_sharded(1.0 - np.asarray(ys.to_numpy()), mesh=Xs.mesh)
    feed = [(Xs, ys), (Xs, y2), (Xs, ys)]
    kept, rebuilt = _new(), _new()
    infos, ran = _passes(kept, feed)
    _passes(rebuilt, feed, rebuild=True)
    assert _hits(infos) == [False, True, True]
    assert ran["sgd.grid_x"] == 1 and ran["sgd.grid_y"] == 3
    assert np.array_equal(_weights(kept), _weights(rebuilt))
    same_y = _new()
    _passes(same_y, [(Xs, ys)] * 3)
    assert not np.array_equal(_weights(kept), _weights(same_y))


def another_x(Xs, ys, monkeypatch):
    """(c) another array of the same shape: a miss, the old grid dead
    BEFORE the gate is asked and the new one built, and the weights those
    of a wrapper that never saw the first X."""
    X2 = as_sharded(_data(1)[0], mesh=Xs.mesh)
    inc = _new()
    _passes(inc, [(Xs, ys)] * 2)
    old = weakref.ref(inc._epoch_grid.Xr)
    w = _weights(inc)
    at_gate = []
    gate = wrappers._device_headroom
    monkeypatch.setattr(wrappers, "_device_headroom", lambda *a, **k: (
        at_gate.append(old() is None), gate(*a, **k))[1])
    inc.partial_fit(X2, ys)
    assert at_gate == [True]
    assert inc.pass_info_["grid_hit"] is False
    assert inc._epoch_grid.get(X2.data, inc._epoch_grid.key) is not None
    assert inc._epoch_grid.get(Xs.data, inc._epoch_grid.key) is None
    # the same three passes with nothing ever kept
    rebuilt = _new()
    _passes(rebuilt, [(Xs, ys), (Xs, ys), (X2, ys)], rebuild=True)
    assert np.array_equal(_weights(inc), _weights(rebuilt))
    assert not np.array_equal(_weights(inc), w)
    # and a fresh wrapper's first pass on X2 alone is a fresh wrapper's
    a, b = _new(), _new()
    _passes(a, [(Xs, ys)])
    a.fit(X2, ys, classes=[0, 1])
    b.fit(X2, ys, classes=[0, 1])
    assert a.pass_info_["grid_hit"] is False
    assert np.array_equal(_weights(a), _weights(b))


def source_dies(Xs, ys, monkeypatch):
    """(d) the grid goes with the array it was built from, and with the
    wrapper (by reference count: no cycle holds 2 GiB until a collection)."""
    X2 = as_sharded(_data(1)[0], mesh=Xs.mesh)
    inc = _new()
    _passes(inc, [(X2, ys)] * 2)
    grid = weakref.ref(inc._epoch_grid.Xr)
    assert grid() is not None
    del X2
    gc.collect()
    assert grid() is None
    assert (inc._epoch_grid.Xr, inc._epoch_grid.key) == (None, None)
    inc.predict(Xs)                       # the fitted wrapper still works
    inc.partial_fit(Xs, ys)
    assert inc.pass_info_["grid_hit"] is False
    grid = weakref.ref(inc._epoch_grid.Xr)
    gc.disable()
    try:
        del inc
        assert grid() is None
    finally:
        gc.enable()


def copies_carry_no_grid(Xs, ys, monkeypatch):
    """(e) pickle, deepcopy and clone: an empty holder, the same
    predictions, and a next pass that misses and trains as the original's."""
    inc = _new()
    _passes(inc, [(Xs, ys)] * 2)
    labels = inc.predict(Xs)
    copies = [pickle.loads(pickle.dumps(inc)), copy.deepcopy(inc)]
    assert inc._epoch_grid.Xr is not None
    inc.partial_fit(Xs, ys)
    assert inc.pass_info_["grid_hit"] is True
    for other in copies:
        assert other._epoch_grid is not inc._epoch_grid
        assert other._epoch_grid.Xr is None
        assert np.array_equal(other.predict(Xs), labels)
        other.partial_fit(Xs, ys)
        assert other.pass_info_["grid_hit"] is False
        assert other.pass_info_["t_end"] == 24
        assert np.array_equal(_weights(other), _weights(inc))
        other.partial_fit(Xs, ys)
        assert other.pass_info_["grid_hit"] is True
    cloned = clone(inc)
    assert "_epoch_grid" not in vars(cloned)
    cloned.fit(Xs, ys, classes=[0, 1])
    assert cloned.pass_info_["grid_hit"] is False
    assert np.array_equal(_weights(cloned),
                          _weights(_new().fit(Xs, ys, classes=[0, 1])))


def the_gate_is_asked_to_build_only(Xs, ys, monkeypatch):
    """(f) a hit allocates nothing and asks nothing - even of a device that
    would refuse; a miss asks, and refused takes the block loop and keeps
    nothing."""
    X2 = as_sharded(_data(1)[0], mesh=Xs.mesh)
    inc = _new()
    infos, _ = _passes(inc, [(Xs, ys)] * 2)
    assert infos[0]["headroom"] == {
        "needed": Xs.data.nbytes // len(Xs.data.devices()), "free": None,
        "fits": True}
    assert infos[1]["headroom"] is None
    _refuse(monkeypatch)
    inc.partial_fit(Xs, ys)
    assert (inc.pass_info_["path"], inc.pass_info_["grid_hit"],
            inc.pass_info_["headroom"]) == ("fused_epoch", True, None)
    old = weakref.ref(inc._epoch_grid.Xr)
    inc.partial_fit(X2, ys)
    info = inc.pass_info_
    assert (info["path"], info["grid_hit"], info["grid_bytes"]) == (
        "block_loop", False, 0)
    assert info["headroom"]["fits"] is False and info["headroom"]["free"] == 4096
    assert old() is None and inc._epoch_grid.Xr is None
    assert info["t_end"] == 32


def another_fit_dtype(Xs, ys, monkeypatch):
    """(g) the grid dtype is in the key: ``set_params(fit_dtype=...)`` on
    the inner estimator between passes is a miss."""
    def run(rebuild):
        inc = _new()
        infos, _ = _passes(inc, [(Xs, ys)] * 2, rebuild=rebuild)
        inc.estimator_.set_params(fit_dtype="bfloat16")
        for _ in range(2):
            if rebuild:
                inc._epoch_grid.clear()
            inc.partial_fit(Xs, ys)
            infos.append(dict(inc.pass_info_))
        return inc, infos

    kept, infos = run(False)
    rebuilt, _ = run(True)
    assert _hits(infos) == [False, True, False, True]
    assert [i["fit_dtype"] for i in infos] == ["float32"] * 2 + ["bfloat16"] * 2
    assert kept._epoch_grid.Xr.dtype == jax.numpy.bfloat16
    assert infos[2]["grid_bytes"] < infos[1]["grid_bytes"]
    assert np.array_equal(_weights(kept), _weights(rebuilt))


def fit_again(Xs, ys, monkeypatch):
    """(h) ``fit`` again on the same wrapper and the same X: a new inner
    estimator, the same grid - a hit on its first pass."""
    inc = _new()
    _passes(inc, [(Xs, ys)] * 2)
    grid = inc._epoch_grid.Xr
    first = inc.estimator_
    infos, ran = _passes(inc, [(Xs, ys)] * 2)
    assert inc.estimator_ is not first
    assert _hits(infos) == [True, True] and "sgd.grid_x" not in ran
    assert inc._epoch_grid.Xr is grid
    assert [i["t_end"] for i in infos] == [8, 16]
    fresh = _new()
    _passes(fresh, [(Xs, ys)] * 2, rebuild=True)
    assert np.array_equal(_weights(inc), _weights(fresh))


def no_holder(Xs, ys, monkeypatch):
    """(i) ``_fused_epoch`` called alone keeps nothing and builds every
    time, as before."""
    est = SGDClassifier(loss="log_loss")
    before = _ran()
    for _ in range(2):
        est._fused_epoch(Xs, ys, list(range(8)), classes=[0, 1])
        assert est.solver_info_["grid_hit"] is False
    assert _delta(before) == {"sgd.grid_x": 2, "sgd.grid_y": 2, "sgd.fused_epoch": 2}
    inc = Incremental(SGDClassifier(loss="log_loss"), shuffle_blocks=False)
    _passes(inc, [(Xs, ys)] * 2)
    assert np.array_equal(np.r_[np.ravel(est.coef_), np.ravel(est.intercept_)],
                          _weights(inc))


def older_wrappers_give_way(Xs, ys, monkeypatch):
    """(j) a kept grid is a cache: where the gate would refuse because OLDER
    fitted wrappers still keep theirs, those go and the gate is asked again
    before the block loop is taken (a device with room for X, the grid to be
    built and ONE kept grid, not two)."""
    devs = len(Xs.data.devices())
    x_bytes = Xs.data.nbytes // devs
    wrappers_ = [_new(), _new(), _new()]
    a, b, c = wrappers_

    def stats(dev):
        kept = sum(w._epoch_grid.Xr.nbytes for w in wrappers_
                   if getattr(w, "_epoch_grid", None) is not None
                   and w._epoch_grid.Xr is not None)
        return {"bytes_limit": int(4.6 * x_bytes),
                "bytes_in_use": x_bytes + kept // devs}

    monkeypatch.setattr(type(jax.devices()[0]), "memory_stats", stats)
    for inc in (a, b):
        inc.fit(Xs, ys, classes=[0, 1])
        assert inc.pass_info_["path"] == "fused_epoch"
        assert inc.pass_info_["headroom"]["fits"] is True
        assert "grids_dropped" not in inc.pass_info_["headroom"]
    assert a._epoch_grid.Xr is not None and b._epoch_grid.Xr is not None
    c.fit(Xs, ys, classes=[0, 1])
    gate = c.pass_info_["headroom"]
    assert c.pass_info_["path"] == "fused_epoch"
    assert gate["fits"] is True and gate["grids_dropped"] >= 2
    assert gate["free"] == int(4.6 * x_bytes) - x_bytes
    assert a._epoch_grid.Xr is None and b._epoch_grid.Xr is None
    assert c._epoch_grid.Xr is not None
    assert np.array_equal(_weights(c), _weights(a))
    # an emptied wrapper's next pass rebuilds, beside the ONE grid now kept
    a.partial_fit(Xs, ys)
    assert (a.pass_info_["path"], a.pass_info_["grid_hit"]) == (
        "fused_epoch", False)
    assert "grids_dropped" not in a.pass_info_["headroom"]
    assert c._epoch_grid.Xr is not None
    c.partial_fit(Xs, ys)
    assert c.pass_info_["grid_hit"] is True
    assert np.array_equal(_weights(c), _weights(a))


CASES = [same_x_five_passes, new_y_same_x, another_x, source_dies,
         copies_carry_no_grid, the_gate_is_asked_to_build_only,
         another_fit_dtype, fit_again, no_holder, older_wrappers_give_way]


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("case", CASES, ids=lambda f: f.__name__)
def test_kept_grid(case, chips, monkeypatch):
    X, y = _data()
    with use_mesh(device_mesh(devices=jax.devices()[:chips])), \
            config.set(obs_programs=True):
        Xs, ys = as_sharded(X), as_sharded(y)
        assert len(Xs.data.sharding.device_set) == chips
        case(Xs, ys, monkeypatch)
    obs.reset_recent_spans()


def test_the_holder_never_trusts_a_reused_id():
    """A dead source never hits, whatever lives at its address now."""
    from dask_ml_tpu.models.sgd import _KeptGrid

    holder = _KeptGrid()
    src = jax.numpy.ones((8, 2))
    holder.keep(src, ("k",), "grid")
    assert holder.get(src, ("k",)) == "grid"
    assert holder.get(src, ("other",)) is None
    assert holder.get(jax.numpy.ones((8, 2)), ("k",)) is None
    # a stale callback leaves a newer grid alone
    newer = jax.numpy.zeros((8, 2))
    holder.keep(newer, ("k",), "newer grid")
    del src
    gc.collect()
    assert holder.get(newer, ("k",)) == "newer grid"
    del newer
    gc.collect()
    assert (holder.Xr, holder.key) == (None, None)
