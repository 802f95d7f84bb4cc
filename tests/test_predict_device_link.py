"""The link on the device (``models/glm.py::_decision_link``, the tracked
program ``glm.decision``): for a resident X and a binary fit, sigmoid, the
two-column probabilities, the threshold and the class choice run in the same
program as the matvec and leave it lane-dense; the host fetches once and
views. Held here, small, on the CPU's virtual devices: the values against the
host formulas the estimators had before (``expit``, ``1 - p1``, ``np.stack``;
a threshold and a fancy index into ``classes_``), the returned arrays' type,
shape, dtype and layout, the paths that stay on the host (streamed,
multiclass) and say so, and the structure — one program a predict, nothing
compiled by a second fit, no ``(n, 2)`` / ``(n, 1)`` array in the program."""

import re

import jax
import numpy as np
import pytest
from scipy.special import expit

from benchmark.harness import compile_counter
from dask_ml_tpu import config, observability as obs
from dask_ml_tpu.linear_model import (
    LinearRegression, LogisticRegression, PoissonRegression, SGDClassifier,
    SGDRegressor)
from dask_ml_tpu.models import glm
from dask_ml_tpu.parallel import as_sharded
from dask_ml_tpu.parallel.mesh import device_mesh

D = 7
CLASSES = {
    "int64": np.array([0, 1]),
    "float64": np.array([-1.0, 1.0]),
    "str": np.array(["a", "b"]),
    "bool": np.array([False, True]),
    "int16": np.array([3, 7], np.int16),
    "float32": np.array([-3.5, 1.25], np.float32),
    "int64_wide": np.array([0, 2 ** 40]),
}


def _X(n, seed=0):
    return np.random.RandomState(seed).randn(n, D).astype(np.float32)


def _fit(kind, X, classes):
    """A binary fit whose ``classes_`` keep the labels' dtype. (A RESIDENT
    ``LogisticRegression`` fit scans its labels on the device, so its
    ``classes_`` are float32 whatever came in; the streamed fit keeps
    ``np.unique(y)``.)"""
    y = classes[(X[:, 0] + 0.3 * X[:, 1] > 0).astype(int)]
    if kind == "logreg":
        with config.set(stream_block_rows=64):
            return LogisticRegression(solver="lbfgs", max_iter=30).fit(X, y)
    return SGDClassifier(loss="log_loss", max_iter=5, random_state=0).fit(X, y)


def _host_formula(est, kind, Xs):
    """What the estimators computed on the host before the link moved."""
    eta = est.decision_function(Xs)
    p1 = expit(eta)
    proba = np.stack([1.0 - p1, p1], axis=1)
    pick = proba[:, 1] > 0.5 if kind == "logreg" else eta > 0
    return proba, est.classes_[pick.astype(int)]


def _mesh(chips):
    return device_mesh(devices=jax.devices()[:chips])


# -- values, types, layouts --------------------------------------------------

@pytest.mark.parametrize("chips", [1, 4, 8])
@pytest.mark.parametrize("n", [301, 1024])
@pytest.mark.parametrize("kind", ["logreg", "sgd"])
def test_predict_proba_is_the_host_formula(kind, n, chips):
    """Within 1e-6 of ``expit`` / ``1 - p1`` / ``np.stack``; an ndarray,
    C-contiguous, ``(n, 2)`` float32, rows summing to 1; 301 rows over 4 or 8
    devices are padded on the device and the padding never comes back."""
    X = _X(n)
    est = _fit(kind, X, CLASSES["int64"])
    Xs = as_sharded(X, mesh=_mesh(chips))
    got = est.predict_proba(Xs)
    want, _ = _host_formula(est, kind, Xs)
    assert type(got) is np.ndarray and got.shape == (n, 2)
    assert got.dtype == np.float32 and got.flags.c_contiguous
    assert np.max(np.abs(got - want)) <= 1e-6
    assert np.max(np.abs(got.sum(axis=1) - 1.0)) <= 2e-7
    # a host X takes the same program
    assert np.array_equal(est.predict_proba(X), est.predict_proba(
        as_sharded(X)))


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("classes", sorted(CLASSES))
@pytest.mark.parametrize("kind", ["logreg", "sgd"])
def test_predict_is_the_host_lookup_in_every_row_and_in_dtype(kind, classes,
                                                              chips):
    """``classes_`` of any dtype: the numeric ones cross as their own bytes
    (8-byte values as two words a row), strings as a one-byte index."""
    X = _X(301, seed=1)
    est = _fit(kind, X, CLASSES[classes])
    assert est.classes_.dtype == CLASSES[classes].dtype
    Xs = as_sharded(X, mesh=_mesh(chips))
    got = est.predict(Xs)
    _, want = _host_formula(est, kind, Xs)
    assert type(got) is np.ndarray and got.shape == (301,)
    assert got.dtype == want.dtype == est.classes_.dtype
    assert got.flags.c_contiguous and np.array_equal(got, want)
    assert 0 < (got == est.classes_[1]).sum() < 301      # both classes occur


def test_resident_logreg_fit_predicts_its_float32_classes():
    X = _X(300)
    y = (X[:, 0] > 0).astype(np.float32)
    clf = LogisticRegression(solver="lbfgs", max_iter=20).fit(
        as_sharded(X), as_sharded(y))
    got = clf.predict(as_sharded(X))
    assert got.dtype == clf.classes_.dtype == np.float32
    assert clf.score(as_sharded(X), y) > 0.95
    assert np.array_equal(
        got, clf.classes_[(clf.predict_proba(X)[:, 1] > 0.5).astype(int)])
    assert np.allclose(clf.predict_log_proba(X),
                       np.log(clf.predict_proba(X)), atol=1e-6)


@pytest.mark.parametrize("kind", ["logreg", "sgd"])
def test_a_nan_row_stays_one_row(kind):
    """The lane spread is a product with zeros; a NaN must not reach the
    127 rows that share its sublane row."""
    X = _X(301)
    est = _fit(kind, X, CLASSES["int64"])
    clean = est.predict_proba(as_sharded(X))
    X[5, 2] = np.nan
    got = est.predict_proba(as_sharded(X))
    assert np.isnan(got[5]).all()
    keep = np.arange(301) != 5
    assert np.array_equal(got[keep], clean[keep])


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("model", ["poisson", "linear", "sgd_regressor"])
def test_regressions_through_the_same_program(model, chips):
    X = _X(301)
    rng = np.random.RandomState(2)
    if model == "poisson":
        y = rng.poisson(np.exp(0.3 * X[:, 0])).astype(np.float32)
        est = PoissonRegression(solver="lbfgs", max_iter=30).fit(X, y)
        want = np.exp(X @ est.coef_.astype(np.float32)
                      + np.float32(est.intercept_))
    else:
        y = (X @ rng.randn(D) + 0.5).astype(np.float32)
        est = (LinearRegression(solver="lbfgs", max_iter=30)
               if model == "linear"
               else SGDRegressor(max_iter=5, random_state=0)).fit(X, y)
        want = X @ np.ravel(est.coef_).astype(np.float32) \
            + np.float32(est.intercept_)
    got = est.predict(as_sharded(X, mesh=_mesh(chips)))
    assert type(got) is np.ndarray and got.shape == (301,)
    assert got.dtype == np.float32
    assert np.allclose(got, want, rtol=1e-5, atol=1e-5)


# -- what stays on the host, and says so ---------------------------------------

def _last_predict(ring):
    root = [r for r in ring if r["span"] == "predict"][-1]
    return root, {r["span"]: r for r in ring
                  if r["root_id"] == root["root_id"] and r is not root
                  and r["span"].startswith("predict")}


@pytest.mark.parametrize("method", ["predict_proba", "predict"])
@pytest.mark.parametrize("kind", ["logreg", "sgd"])
def test_a_streamed_input_keeps_the_host_tail(kind, method):
    X = _X(301)
    est = _fit(kind, X, CLASSES["str"])
    want = getattr(est, method)(as_sharded(X))
    obs.reset_recent_spans()
    with config.set(obs_programs=True, stream_block_rows=64):
        got = getattr(est, method)(X)
        ring = obs.recent_spans()
    obs.reset_recent_spans()
    assert got.dtype == want.dtype and got.shape == want.shape
    if method == "predict":
        assert np.array_equal(got, want)
    else:
        assert np.max(np.abs(got - want)) <= 1e-6
    if kind == "sgd" and method == "predict_proba":
        return              # no spans there, as before
    _, kids = _last_predict(ring)
    assert kids["predict.decision"]["link"] == "host"


@pytest.mark.parametrize("method", ["predict_proba", "predict"])
@pytest.mark.parametrize("kind", ["logreg", "sgd"])
def test_three_classes_keep_the_host_tail(kind, method):
    X = _X(300)
    y = np.digitize(X[:, 0], [-0.5, 0.5])
    est = (LogisticRegression(solver="lbfgs", max_iter=30) if kind == "logreg"
           else SGDClassifier(loss="log_loss", max_iter=5, random_state=0)
           ).fit(X, y)
    scores = est.decision_function(as_sharded(X))
    assert scores.shape == (300, 3)
    obs.reset_recent_spans()
    before = _decision_calls()
    with config.set(obs_programs=True):
        got = getattr(est, method)(as_sharded(X))
        ring = obs.recent_spans()
        assert _decision_calls() == before
    obs.reset_recent_spans()
    if method == "predict":
        assert np.array_equal(got, est.classes_[np.argmax(scores, axis=1)])
    else:
        p = expit(scores)
        assert np.allclose(got, p / p.sum(axis=1, keepdims=True), atol=1e-6)
    if kind == "sgd" and method == "predict_proba" \
            or kind == "logreg" and method == "predict":
        return              # no spans there, as before
    _, kids = _last_predict(ring)
    assert kids["predict.decision"]["link"] == "host"


# -- structure ----------------------------------------------------------------

def _decision_calls():
    return sum(r["calls"] for r in obs.programs_snapshot()
               if r["program"] == "glm.decision")


def _all_calls():
    return sum(r["calls"] for r in obs.programs_snapshot())


CALLS = [("logreg", "predict_proba"), ("logreg", "predict"),
         ("sgd", "predict"), ("sgd", "predict_proba")]


@pytest.mark.parametrize("kind,method", CALLS)
def test_one_program_one_fetch_and_the_spans(kind, method):
    """Registry delta 1 (``glm.decision``), the root's children exactly
    ``predict.decision`` and ``predict.host``, ``link`` and ``fetch_bytes``
    on the former; ``fetch_bytes`` is the rows padded to 128 a shard times
    the bytes a row."""
    X = _X(301)
    est = _fit(kind, X, CLASSES["int64"])
    Xs = as_sharded(X)
    getattr(est, method)(Xs)                            # compiled
    obs.reset_recent_spans()
    with config.set(obs_programs=True):
        before, every = _decision_calls(), _all_calls()
        getattr(est, method)(Xs)
        assert _decision_calls() - before == 1
        assert _all_calls() - every == 1
        ring = obs.recent_spans()
    obs.reset_recent_spans()
    root, kids = _last_predict(ring)
    assert root["component"] == type(est).__name__ and root["n_rows"] == 301
    assert set(kids) == {"predict.decision", "predict.host"}
    assert kids["predict.decision"]["link"] == "device"
    assert kids["predict.decision"]["fetch_bytes"] == 128 * 8 * 8
    assert "link" not in kids["predict.host"]
    assert kids["predict.decision"]["wall_s"] + kids["predict.host"]["wall_s"] \
        <= root["wall_s"]


@pytest.mark.parametrize("kind,method", CALLS)
def test_a_cold_predict_compiles_one_program_and_a_new_fit_none(kind, method):
    """From empty jit caches a predict over a placed X compiles ONE program:
    no eager operation rides beside ``glm.decision``. A second fit's predict
    (other weights, other class VALUES of the same dtype) compiles nothing:
    the parameters are operands."""
    X = _X(301)
    Xs = as_sharded(X)
    est = _fit(kind, X, CLASSES["int64"])
    other = _fit(kind, _X(301, seed=5), np.array([4, 9]))
    counter = compile_counter()
    jax.clear_caches()
    n0 = counter.n
    getattr(est, method)(Xs)
    assert counter.n - n0 == 1
    got = getattr(other, method)(Xs)
    assert counter.n - n0 == 1
    if method == "predict":
        assert set(np.unique(got)) <= {4, 9}


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("link,words", [
    ("identity", None), ("exp", None), ("proba2", None),
    ("label", np.uint8), ("label", np.uint32), ("label_proba", "u4x2")])
def test_no_two_column_array_in_the_program(link, words, chips):
    """The lowered program at a sharded, padded shape: every output is 2-D
    with 128 or 256 lanes, and no ``(n, 1)`` / ``(n, 2)`` shape appears
    anywhere in its text, parameter, temporary or result (on a TPU such an
    array is tiled to 128 lanes: 64 or 128 times its bytes)."""
    n = 4096 * chips
    mesh = _mesh(chips)
    X = as_sharded(np.zeros((n, D), np.float32), mesh=mesh)
    if words == "u4x2":
        words = np.zeros((2, 2), np.uint32)
    elif words is not None:
        words = np.zeros((2, 1), words)
    lowered = glm._decision_link.__wrapped_jit__.lower(
        X.data, np.zeros(D + 1, np.float32), words, link=link,
        quantum=128 * chips)
    (out,) = jax.tree.leaves(lowered.out_info)
    lanes = 256 if link == "proba2" or (words is not None
                                        and words.shape[1] == 2) else 128
    assert out.shape == (n // 128, lanes)
    text = lowered.as_text()
    thin = [m for m in re.findall(r"tensor<(\d+)x([12])x", text)
            if int(m[0]) > 2]
    assert not thin, thin
    assert not re.search(r"all_gather|all_reduce|all_to_all", text)


def test_label_carrier():
    for name, classes in CLASSES.items():
        words, mapped = glm._label_carrier(classes)
        assert mapped == (name == "str")
        assert words.dtype.kind == "u" and words.shape[0] == 2
        assert words.shape[1] == (2 if classes.dtype.itemsize == 8
                                  and not mapped else 1)
        if not mapped:
            assert np.array_equal(words.reshape(-1).view(classes.dtype),
                                  classes)
