"""Classification/regression metric + scorer parity vs sklearn
(ref: dask_ml/metrics/{classification,regression,scorer}.py)."""

import numpy as np
import pytest
import sklearn.metrics as skm

from dask_ml_tpu import metrics as dm


@pytest.fixture(scope="module")
def preds():
    rng = np.random.RandomState(0)
    y_true = rng.randint(0, 2, size=400).astype(np.float64)
    y_pred = np.where(rng.uniform(size=400) < 0.8, y_true,
                      1 - y_true)
    proba = np.clip(
        y_true * 0.7 + rng.uniform(size=400) * 0.3, 1e-6, 1 - 1e-6
    )
    w = rng.uniform(0.5, 2.0, size=400)
    return y_true, y_pred, proba, w


def test_accuracy(preds):
    y, p, _, w = preds
    assert np.isclose(float(dm.accuracy_score(y, p)), skm.accuracy_score(y, p))
    assert np.isclose(
        float(dm.accuracy_score(y, p, sample_weight=w)),
        skm.accuracy_score(y, p, sample_weight=w),
    )
    assert np.isclose(
        float(dm.accuracy_score(y, p, normalize=False)),
        skm.accuracy_score(y, p, normalize=False),
    )


def test_log_loss(preds):
    y, _, proba, w = preds
    assert np.isclose(float(dm.log_loss(y, proba)), skm.log_loss(y, proba),
                      rtol=1e-5)
    assert np.isclose(
        float(dm.log_loss(y, proba, sample_weight=w)),
        skm.log_loss(y, proba, sample_weight=w), rtol=1e-5,
    )
    # 2-column probability input
    P = np.stack([1 - proba, proba], axis=1)
    assert np.isclose(float(dm.log_loss(y, P)), skm.log_loss(y, P), rtol=1e-5)


def test_regression_metrics():
    rng = np.random.RandomState(1)
    y = rng.uniform(1, 10, size=300)
    p = y + rng.normal(scale=0.5, size=300)
    w = rng.uniform(0.5, 2.0, size=300)
    pairs = [
        (dm.mean_squared_error, skm.mean_squared_error),
        (dm.mean_absolute_error, skm.mean_absolute_error),
        (dm.r2_score, skm.r2_score),
        (dm.mean_squared_log_error, skm.mean_squared_log_error),
    ]
    for ours, ref in pairs:
        assert np.isclose(float(ours(y, p)), ref(y, p), rtol=1e-5), ours
        assert np.isclose(
            float(ours(y, p, sample_weight=w)), ref(y, p, sample_weight=w),
            rtol=1e-5,
        ), ours


def test_mse_squared_false():
    rng = np.random.RandomState(2)
    y = rng.uniform(size=100)
    p = rng.uniform(size=100)
    assert np.isclose(
        float(dm.mean_squared_error(y, p, squared=False)),
        np.sqrt(skm.mean_squared_error(y, p)), rtol=1e-5,
    )


def test_scorer_registry():
    from dask_ml_tpu.metrics.scorer import SCORERS, check_scoring, get_scorer

    assert "accuracy" in SCORERS and "r2" in SCORERS
    assert "neg_mean_squared_error" in SCORERS
    with pytest.raises((ValueError, KeyError)):
        get_scorer("not_a_scorer")

    from sklearn.linear_model import SGDClassifier

    est = SGDClassifier()
    scorer = check_scoring(est, "accuracy")
    X = np.random.RandomState(0).randn(50, 3)
    y = (X[:, 0] > 0).astype(int)
    est.fit(X, y)
    s = scorer(est, X, y)
    assert 0.0 <= float(s) <= 1.0


def test_scorer_greater_is_better_sign():
    """neg_* scorers must return negated losses so search maximizes."""
    from dask_ml_tpu.metrics.scorer import get_scorer

    from sklearn.linear_model import LinearRegression

    rng = np.random.RandomState(0)
    X = rng.randn(80, 3)
    y = X @ np.array([1.0, -2.0, 0.5]) + rng.normal(scale=0.1, size=80)
    est = LinearRegression().fit(X, y)
    val = get_scorer("neg_mean_squared_error")(est, X, y)
    assert float(val) <= 0.0


def test_log_loss_multiclass_matches_sklearn():
    import sklearn.metrics as skm

    from dask_ml_tpu.metrics import log_loss

    rng = np.random.RandomState(0)
    y = rng.randint(0, 4, 300).astype(np.float64)
    p = rng.dirichlet(np.ones(4), 300)
    assert abs(float(log_loss(y, p)) - skm.log_loss(y, p)) < 1e-6
    # non-contiguous labels map by sorted order, as sklearn does
    y2 = np.choose(y.astype(int), [10.0, 20.0, 30.0, 40.0])
    assert abs(float(log_loss(y2, p)) - skm.log_loss(y2, p)) < 1e-6


def test_log_loss_binary_noncanonical_labels():
    import sklearn.metrics as skm

    from dask_ml_tpu.metrics import log_loss

    rng = np.random.RandomState(1)
    y = np.where(rng.rand(200) > 0.5, 20.0, 10.0)
    p = rng.rand(200)
    assert abs(float(log_loss(y, p)) - skm.log_loss(y, p)) < 1e-6


def test_log_loss_missing_class_requires_labels():
    import pytest

    from dask_ml_tpu.metrics import log_loss

    rng = np.random.RandomState(2)
    p = rng.dirichlet(np.ones(4), 100)
    y = rng.randint(0, 3, 100).astype(np.float64)  # class 3 never occurs
    with pytest.raises(ValueError, match="labels"):
        log_loss(y, p)
    # explicit labels resolve the mapping
    import sklearn.metrics as skm

    got = float(log_loss(y, p, labels=[0.0, 1.0, 2.0, 3.0]))
    want = skm.log_loss(y, p, labels=[0.0, 1.0, 2.0, 3.0])
    assert abs(got - want) < 1e-6


def test_log_loss_single_class_and_out_of_label_raise():
    import pytest

    from dask_ml_tpu.metrics import log_loss

    # all-one-class binary without labels: ambiguous mapping must raise
    with pytest.raises(ValueError, match="single class"):
        log_loss(np.zeros(5), np.full(5, 0.1))
    # with labels the mapping is pinned and matches sklearn
    import sklearn.metrics as skm

    got = float(log_loss(np.zeros(5), np.full(5, 0.1), labels=[0.0, 1.0]))
    want = skm.log_loss(np.zeros(5), np.full(5, 0.1), labels=[0, 1])
    assert abs(got - want) < 1e-6
    # y values outside the label set raise instead of scoring a neighbor
    p4 = np.full((4, 4), 0.25)
    with pytest.raises(ValueError, match="not in labels"):
        log_loss(np.array([0.0, 1.0, 2.0, 5.0]), p4,
                 labels=[0.0, 1.0, 2.0, 3.0])


def test_neg_log_loss_scorer_fold_missing_class():
    """The scorer forwards estimator.classes_, so a fold missing a class
    still scores (the bare metric would raise)."""
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.metrics.scorer import get_scorer

    rng = np.random.RandomState(0)
    X = rng.randn(300, 6).astype(np.float32)
    y = rng.randint(0, 3, 300).astype(np.float32)
    clf = LogisticRegression(solver="lbfgs", max_iter=60).fit(X, y)
    scorer = get_scorer("neg_log_loss")
    sub = y < 2  # evaluation slice missing class 2
    s = scorer(clf, X[sub], y[sub])
    assert np.isfinite(s) and s <= 0


def test_extended_regression_metrics_match_sklearn():
    from dask_ml_tpu.metrics import (explained_variance_score, max_error,
                                     median_absolute_error)
    from dask_ml_tpu.parallel import as_sharded

    rng = np.random.RandomState(3)
    for n in (101, 200):  # odd and even valid counts
        t = rng.randn(n).astype(np.float64)
        p = t + 0.3 * rng.randn(n)
        w = rng.rand(n) + 0.05
        np.testing.assert_allclose(
            explained_variance_score(t, p),
            skm.explained_variance_score(t, p), rtol=1e-6)
        np.testing.assert_allclose(
            explained_variance_score(t, p, sample_weight=w),
            skm.explained_variance_score(t, p, sample_weight=w),
            rtol=1e-5)
        np.testing.assert_allclose(
            max_error(t, p), skm.max_error(t, p), rtol=1e-6)
        np.testing.assert_allclose(
            median_absolute_error(t, p),
            skm.median_absolute_error(t, p), rtol=1e-5)
        np.testing.assert_allclose(
            median_absolute_error(t, p, sample_weight=w),
            skm.median_absolute_error(t, p, sample_weight=w), rtol=1e-5)
        # sharded (padded) inputs agree with the host result
        np.testing.assert_allclose(
            median_absolute_error(as_sharded(np.float32(t)),
                                  as_sharded(np.float32(p))),
            skm.median_absolute_error(np.float32(t), np.float32(p)),
            rtol=1e-5)
    # zero-weight rows contribute nothing, even with extreme errors
    t2 = np.array([0.0, 0.0, 0.0, 0.0, 100.0])
    p2 = np.array([1.0, 2.0, 3.0, 4.0, 0.0])
    w2 = np.array([1.0, 1.0, 1.0, 1.0, 0.0])
    np.testing.assert_allclose(
        median_absolute_error(t2, p2, sample_weight=w2),
        skm.median_absolute_error(t2, p2, sample_weight=w2), rtol=1e-9)
    # ... and the cumulative weight lands ON the half: the two middle
    # errors are averaged (2.5), on host and on sharded (padded) inputs
    f32 = np.float32
    for wt in (w2, as_sharded(f32(w2))):
        assert median_absolute_error(
            as_sharded(f32(t2)), as_sharded(f32(p2)), sample_weight=wt
        ) == 2.5
    assert median_absolute_error(t2, p2, sample_weight=w2) == 2.5


def test_extended_scorer_strings_device_resident():
    from dask_ml_tpu.datasets import make_regression
    from dask_ml_tpu.linear_model import LinearRegression
    from dask_ml_tpu.metrics.scorer import SCORERS, get_scorer

    X, y = make_regression(n_samples=2000, n_features=8, random_state=0)
    est = LinearRegression(solver="lbfgs", max_iter=50).fit(X, y)
    for name in ("neg_root_mean_squared_error",
                 "neg_mean_squared_log_error", "neg_median_absolute_error",
                 "explained_variance", "max_error"):
        assert name in SCORERS
        if name == "neg_mean_squared_log_error":
            continue  # needs nonnegative targets; registry check enough
        s = get_scorer(name)(est, X, y)
        assert np.isfinite(s)
    # rmse/medae/max_error are negated; explained_variance is not
    assert get_scorer("neg_root_mean_squared_error")(est, X, y) <= 0
    assert get_scorer("explained_variance")(est, X, y) > 0.9


def test_constant_target_force_finite():
    from dask_ml_tpu.metrics import explained_variance_score, r2_score

    t = np.ones(6)
    assert explained_variance_score(t, np.arange(6.0)) == \
        skm.explained_variance_score(t, np.arange(6.0)) == 0.0
    assert explained_variance_score(t, t) == \
        skm.explained_variance_score(t, t) == 1.0
    assert r2_score(t, np.arange(6.0)) == \
        skm.r2_score(t, np.arange(6.0)) == 0.0
    assert r2_score(t, t) == skm.r2_score(t, t) == 1.0


def test_undefined_metric_warning_class():
    """The degenerate curve paths warn with an
    UndefinedMetricWarning-compatible class (ADVICE r5): a UserWarning
    subclass under sklearn's name, so sklearn-ported filters catch it."""
    from sklearn.exceptions import (
        UndefinedMetricWarning as SkUndefinedMetricWarning,
    )

    from dask_ml_tpu.metrics import UndefinedMetricWarning

    assert issubclass(UndefinedMetricWarning, UserWarning)
    # sklearn-ported filters target sklearn's class — ours must BE one
    assert issubclass(UndefinedMetricWarning, SkUndefinedMetricWarning)
    y = np.zeros(8)
    s = np.linspace(0, 1, 8)
    with pytest.warns(UndefinedMetricWarning):
        dm.roc_curve(y, s)
    with pytest.warns(UndefinedMetricWarning):
        dm.precision_recall_curve(y, s)
    with pytest.warns(UndefinedMetricWarning):
        assert dm.average_precision_score(y, s) == 0.0
    # sklearn-style filtering by the SPECIFIC class works
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any warning raises ...
        warnings.simplefilter("ignore", UndefinedMetricWarning)  # ... but ours
        dm.roc_curve(y, s)


def test_binary_metrics_reject_duplicate_labels():
    """labels=[v, v] passes the length check but would silently map every
    row positive (ADVICE r5) — must raise instead."""
    y = np.array([0.0, 1.0, 1.0, 0.0])
    s = np.array([0.1, 0.8, 0.7, 0.3])
    for fn in (dm.roc_auc_score, dm.roc_curve,
               dm.precision_recall_curve, dm.average_precision_score):
        with pytest.raises(ValueError, match="distinct"):
            fn(y, s, labels=[1.0, 1.0])
