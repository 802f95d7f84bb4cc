"""Split tests (ref: tests/model_selection/test_split.py)."""

import numpy as np
import pytest

from dask_ml_tpu.datasets import make_classification
from dask_ml_tpu.model_selection import KFold, ShuffleSplit, train_test_split
from dask_ml_tpu.parallel import ShardedArray


@pytest.fixture(scope="module")
def data():
    return make_classification(n_samples=500, n_features=6, random_state=0)


def test_train_test_split_shapes(data):
    X, y = data
    Xtr, Xte, ytr, yte = train_test_split(X, y, test_size=0.2, random_state=0)
    assert isinstance(Xtr, ShardedArray)
    assert Xtr.shape[0] + Xte.shape[0] == 500
    assert Xte.shape[0] == pytest.approx(100, abs=8)  # blockwise rounding
    assert ytr.shape[0] == Xtr.shape[0]


def test_train_test_split_no_overlap(data):
    X, y = data
    # tag each row with a unique value via the first feature
    Xh = X.to_numpy().copy()  # to_numpy view of a jax array is read-only
    Xh[:, 0] = np.arange(500)
    Xs = ShardedArray.from_array(Xh, X.mesh)
    Xtr, Xte = train_test_split(Xs, test_size=0.25, random_state=1)
    ids_tr = set(Xtr.to_numpy()[:, 0].astype(int))
    ids_te = set(Xte.to_numpy()[:, 0].astype(int))
    assert not ids_tr & ids_te
    assert len(ids_tr | ids_te) == 500


def test_train_test_split_blockwise_false(data):
    X, y = data
    Xtr, Xte, ytr, yte = train_test_split(
        X, y, test_size=0.2, blockwise=False, random_state=0
    )
    assert Xte.shape[0] == 100


def test_train_test_split_numpy_arrays():
    X = np.arange(100).reshape(50, 2)
    y = np.arange(50)
    Xtr, Xte, ytr, yte = train_test_split(X, y, test_size=0.2, random_state=0)
    assert isinstance(Xtr, np.ndarray)
    assert len(Xte) == 10


def test_train_test_split_errors(data):
    X, y = data
    with pytest.raises(ValueError, match="inconsistent"):
        train_test_split(X, np.arange(10))
    with pytest.raises(ValueError):
        train_test_split(X, test_size=0.9, train_size=0.9)


def test_kfold(data):
    X, _ = data
    kf = KFold(n_splits=5)
    folds = list(kf.split(X))
    assert len(folds) == 5
    all_test = np.concatenate([te for _, te in folds])
    assert sorted(all_test) == list(range(500))
    for tr, te in folds:
        assert not set(tr) & set(te)
        assert len(tr) + len(te) == 500


def test_kfold_shuffle(data):
    X, _ = data
    f1 = list(KFold(n_splits=3, shuffle=True, random_state=0).split(X))
    f2 = list(KFold(n_splits=3, shuffle=True, random_state=0).split(X))
    np.testing.assert_array_equal(f1[0][1], f2[0][1])


def test_shuffle_split(data):
    X, _ = data
    ss = ShuffleSplit(n_splits=3, test_size=0.2, random_state=0)
    folds = list(ss.split(X))
    assert len(folds) == 3
    assert ss.get_n_splits() == 3
    tr, te = folds[0]
    assert not set(tr) & set(te)


def test_unshuffled_split_is_train_leading():
    """sklearn contract: shuffle=False gives train = leading rows, test =
    trailing (the chronological-holdout idiom)."""
    import numpy as np

    from dask_ml_tpu.model_selection import train_test_split

    X = np.arange(100)[:, None].astype(np.float32)
    Xtr, Xte = train_test_split(X, test_size=0.25, shuffle=False)
    assert Xtr[0, 0] == 0 and Xtr[-1, 0] == 74
    assert Xte[0, 0] == 75 and Xte[-1, 0] == 99


@pytest.mark.parametrize("n", [1, 2, 3, 1000, 65537])
def test_native_legacy_shuffle_is_numpys(n):
    """``io.native.legacy_shuffle`` is ``RandomState.shuffle`` bit for bit:
    the same permutation, and the same generator state afterwards (also
    from the middle of the stream); what is not a contiguous int32 vector
    is numpy's own call."""
    from dask_ml_tpu.io.native import legacy_shuffle

    for seed in (0, 3, 2 ** 31 - 5):
        a, b = np.random.RandomState(seed), np.random.RandomState(seed)
        a.rand(seed % 700)
        b.rand(seed % 700)
        x = np.arange(n, dtype=np.int32)
        y = x.copy()
        a.shuffle(x)
        legacy_shuffle(b, y)
        np.testing.assert_array_equal(x, y)
        a.shuffle(x)
        legacy_shuffle(b, y)
        np.testing.assert_array_equal(x, y)
        assert a.randint(0, 2 ** 31) == b.randint(0, 2 ** 31)
        x64 = np.arange(n)
        y64 = x64.copy()
        a.shuffle(x64)
        legacy_shuffle(b, y64)                 # int64: numpy's own
        np.testing.assert_array_equal(x64, y64)
