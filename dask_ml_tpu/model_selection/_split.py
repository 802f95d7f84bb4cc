"""Data splitting: train_test_split, ShuffleSplit, KFold.

Reference: ``dask_ml/model_selection/_split.py`` (SURVEY.md §2a splits
row). ``blockwise=True`` (default, as in the reference) shuffles/splits
WITHIN each shard — no cross-shard data motion; ``blockwise=False`` draws
a global permutation. Either way the split materializes through
``take_rows`` (one XLA gather) rather than the reference's slicing task
graphs.

Splitters yield host-side index arrays (the cheap part — indices are tiny
relative to data); fold extraction gathers on device.
"""

from __future__ import annotations

import numpy as np

from ..io.native import legacy_shuffle
from ..parallel.mesh import data_shards
from ..parallel.sharded import ShardedArray, take_rows


def _validate_sizes(n, test_size, train_size):
    if test_size is None and train_size is None:
        test_size = 0.25
    if test_size is None:
        test_size = 1.0 - (
            train_size if isinstance(train_size, float) else train_size / n
        )
    n_test = (
        int(np.ceil(n * test_size)) if isinstance(test_size, float)
        else int(test_size)
    )
    if train_size is None:
        n_train = n - n_test
    else:
        n_train = (
            int(np.floor(n * train_size)) if isinstance(train_size, float)
            else int(train_size)
        )
    if n_test + n_train > n:
        raise ValueError(
            f"train_size + test_size = {n_train + n_test} > n_samples = {n}"
        )
    if n_test < 1 or n_train < 1:
        raise ValueError("resulting train/test sets would be empty")
    return n_train, n_test


def _shard_row_ranges(x: ShardedArray):
    """(start, stop) of logical rows per shard."""
    per = x.padded_shape[0] // data_shards(x.mesh)
    out = []
    for s in range(data_shards(x.mesh)):
        lo = min(s * per, x.n_rows)
        hi = min((s + 1) * per, x.n_rows)
        out.append((lo, hi))
    return out


def _blockwise_split_indices(x, test_size, train_size, rng, shuffle):
    """Indices are int32 where the rows allow it, and a one-shard split
    hands out views: the shuffle visits the same positions whatever the
    dtype (the same rows are held out), at half the bytes — at 4,194,304
    rows a resident search's fits took 0.43-0.49 s or 0.52-0.63 s, two of
    every six the slow way, with the int64 buffers of one split (32 + 28 +
    4 MiB, freed after every fit and faulted in again) and 0.43-0.47 s
    with these (my chip runs, PR 32)."""
    train_parts, test_parts = [], []
    for lo, hi in _shard_row_ranges(x):
        m = hi - lo
        if m == 0:
            continue
        n_train, n_test = _validate_sizes(m, test_size, train_size)
        idx = np.arange(lo, hi,
                        dtype=np.int32 if hi < 2 ** 31 else np.int64)
        if shuffle:
            # rng.shuffle(idx), bit for bit, by a loop that prefetches
            legacy_shuffle(rng, idx)
        test_parts.append(idx[:n_test])
        train_parts.append(idx[n_test:n_test + n_train])

    def joined(parts):
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    return joined(train_parts), joined(test_parts)


def split_indices(first, n, test_size=None, train_size=None, rng=None,
                  shuffle=True, blockwise=True):
    """``(train_idx, test_idx)`` of :func:`train_test_split` for a first
    array ``first`` of ``n`` rows — the split as host row indices, for a
    caller that gathers the rows itself (the adaptive search over a
    resident table: split and blocking in one gather)."""
    rng = np.random.RandomState(None) if rng is None else rng
    if not shuffle:
        blockwise = False
    if blockwise and isinstance(first, ShardedArray):
        return _blockwise_split_indices(first, test_size, train_size, rng,
                                        shuffle)
    n_train, n_test = _validate_sizes(n, test_size, train_size)
    if shuffle:
        idx = rng.permutation(n)
        return idx[n_test:n_test + n_train], idx[:n_test]
    # sklearn contract: unshuffled split is train = LEADING rows,
    # test = trailing (the chronological-holdout idiom)
    idx = np.arange(n)
    return idx[:n_train], idx[n_train:n_train + n_test]


def train_test_split(*arrays, test_size=None, train_size=None,
                     random_state=None, shuffle=True, blockwise=True,
                     **kwargs):
    """Ref: dask_ml/model_selection/_split.py::train_test_split."""
    if not arrays:
        raise ValueError("at least one array required")
    if not shuffle and blockwise:
        blockwise = False  # contiguous split needs no per-block handling
    rng = np.random.RandomState(random_state)
    first = arrays[0]
    from ..parallel.frames import PartitionedFrame

    if isinstance(first, PartitionedFrame):
        return _split_frames(arrays, test_size, train_size, rng, shuffle,
                             blockwise)
    # scipy sparse raises on len() ("length is ambiguous"); a sparse
    # corpus splits by row indexing like everything else — the one
    # row-count rule lives in streaming._n_rows_of
    from ..parallel.streaming import _n_rows_of

    def _rows(a):
        return a.n_rows if isinstance(a, ShardedArray) else _n_rows_of(a)

    n = _rows(first)
    for a in arrays:
        if _rows(a) != n:
            raise ValueError("arrays have inconsistent lengths")

    train_idx, test_idx = split_indices(first, n, test_size, train_size,
                                        rng, shuffle, blockwise)
    out = []
    for a in arrays:
        if isinstance(a, ShardedArray):
            out.extend([take_rows(a, train_idx), take_rows(a, test_idx)])
        else:
            from ..parallel.streaming import (_is_sparse_source,
                                              as_row_indexable)

            a = as_row_indexable(a) if _is_sparse_source(a) \
                else np.asarray(a)
            out.extend([a[train_idx], a[test_idx]])
    return out


def _split_frames(arrays, test_size, train_size, rng, shuffle, blockwise):
    """train_test_split over PartitionedFrames. ``blockwise=True`` (the
    reference's default for dd): each partition splits its own rows — no
    global shuffle crosses partitions. ``blockwise=False``: a global
    permutation over the concatenated frame, re-partitioned afterwards."""
    from ..parallel.frames import PartitionedFrame

    first = arrays[0]
    part_lens = [len(p) for p in first.partitions]
    for a in arrays:
        if not isinstance(a, PartitionedFrame) or \
                [len(p) for p in a.partitions] != part_lens:
            raise ValueError(
                "all arrays must be PartitionedFrames with identical "
                "partition lengths"
            )
    if blockwise:
        train_ix, test_ix = [], []
        for m in part_lens:
            if m == 0:  # empty partitions contribute nothing to either
                train_ix.append(np.arange(0))
                test_ix.append(np.arange(0))
                continue
            n_train, n_test = _validate_sizes(m, test_size, train_size)
            if shuffle:
                idx = rng.permutation(m)
                test_ix.append(idx[:n_test])
                train_ix.append(idx[n_test:n_test + n_train])
            else:  # sklearn contract: train = leading rows
                idx = np.arange(m)
                train_ix.append(idx[:n_train])
                test_ix.append(idx[n_train:n_train + n_test])
        out = []
        for a in arrays:
            out.append(PartitionedFrame([
                p.iloc[ix] for p, ix in zip(a.partitions, train_ix)
            ]))
            out.append(PartitionedFrame([
                p.iloc[ix] for p, ix in zip(a.partitions, test_ix)
            ]))
        return out
    n = sum(part_lens)
    n_train, n_test = _validate_sizes(n, test_size, train_size)
    if shuffle:
        idx = rng.permutation(n)
        test_idx, train_idx = idx[:n_test], idx[n_test:n_test + n_train]
    else:
        # sklearn contract: unshuffled split is train = LEADING rows,
        # test = trailing (the chronological-holdout idiom)
        idx = np.arange(n)
        train_idx, test_idx = idx[:n_train], idx[n_train:n_train + n_test]
    out = []
    for a in arrays:
        host = a.compute()
        out.append(PartitionedFrame.from_pandas(
            host.iloc[train_idx], a.npartitions))
        out.append(PartitionedFrame.from_pandas(
            host.iloc[test_idx], a.npartitions))
    return out


class ShuffleSplit:
    """Ref: dask_ml/model_selection/_split.py::ShuffleSplit."""

    def __init__(self, n_splits=10, test_size=0.1, train_size=None,
                 blockwise=True, random_state=None):
        self.n_splits = n_splits
        self.test_size = test_size
        self.train_size = train_size
        self.blockwise = blockwise
        self.random_state = random_state

    def split(self, X, y=None, groups=None):
        rng = np.random.RandomState(self.random_state)
        from ..parallel.streaming import _n_rows_of

        n = X.n_rows if isinstance(X, ShardedArray) else _n_rows_of(X)
        for _ in range(self.n_splits):
            if self.blockwise and isinstance(X, ShardedArray):
                yield _blockwise_split_indices(
                    X, self.test_size, self.train_size, rng, shuffle=True
                )
            else:
                n_train, n_test = _validate_sizes(
                    n, self.test_size, self.train_size
                )
                idx = rng.permutation(n)
                yield idx[n_test:n_test + n_train], idx[:n_test]

    def get_n_splits(self, X=None, y=None, groups=None):
        return self.n_splits


class KFold:
    """Ref: dask_ml/model_selection/_split.py::KFold."""

    def __init__(self, n_splits=5, shuffle=False, random_state=None):
        self.n_splits = n_splits
        self.shuffle = shuffle
        self.random_state = random_state

    def _order(self, n):
        """The row order the folds are cut from: ``arange(n)``, shuffled
        by ``random_state`` where asked."""
        idx = np.arange(n)
        if self.shuffle:
            np.random.RandomState(self.random_state).shuffle(idx)
        return idx

    def _bounds(self, n):
        """(starts, stops) of the test folds in :meth:`_order`: the first
        ``n % n_splits`` folds one row longer."""
        if self.n_splits > n:
            raise ValueError(
                f"n_splits={self.n_splits} > n_samples={n}"
            )
        sizes = np.full(self.n_splits, n // self.n_splits)
        sizes[: n % self.n_splits] += 1
        stops = np.cumsum(sizes)
        return stops - sizes, stops

    def split(self, X, y=None, groups=None):
        from ..parallel.streaming import _n_rows_of

        n = X.n_rows if isinstance(X, ShardedArray) else _n_rows_of(X)
        starts, stops = self._bounds(n)
        idx = self._order(n)
        for lo, hi in zip(starts, stops):
            test = idx[lo:hi]
            train = np.concatenate([idx[:lo], idx[hi:]])
            yield train, test

    def get_n_splits(self, X=None, y=None, groups=None):
        return self.n_splits
