"""Input validation / canonicalization.

Reference equivalent: ``dask_ml/utils.py::check_array / check_X_y /
check_chunks`` (SURVEY.md §2a "Support" row). Here canonicalization means:
accept numpy / jax arrays / ShardedArray, end with a row-sharded padded
device array on the estimator's mesh.
"""

from __future__ import annotations

import numpy as np

from ..parallel.mesh import resolve_mesh
from ..parallel.sharded import ShardedArray, as_sharded


def _assert_all_finite(arr, name="Input", allow_nan=False):
    """sklearn-parity finiteness gate for HOST float arrays (the
    reference inherits it from sklearn's check_array force_all_finite;
    ``allow_nan`` is its 'allow-nan' mode — NaN passes, inf never does).
    Device-resident inputs skip this — the solver-loop sanitizers
    (SURVEY.md §5 row 2) guard those without an extra device pass."""
    if not (isinstance(arr, np.ndarray)
            and np.issubdtype(arr.dtype, np.floating)):
        return
    if allow_nan:
        if np.isinf(arr).any():
            raise ValueError(f"{name} contains infinity.")
    elif not np.isfinite(arr).all():
        raise ValueError(f"{name} contains NaN or infinity.")


def check_array(x, mesh=None, dtype=None, ensure_2d=True, copy=False,
                allow_nan=False) -> ShardedArray:
    if not isinstance(x, ShardedArray):
        arr = np.asarray(x)
        if arr.ndim == 1 and ensure_2d:
            raise ValueError(
                f"Expected 2D array, got 1D array instead: shape {arr.shape}."
            )
        if arr.ndim > 2:
            raise ValueError(f"Expected <=2D array, got shape {arr.shape}.")
        if dtype is not None and np.issubdtype(np.dtype(dtype), np.floating):
            # validate AFTER the target-dtype cast: a finite float64 can
            # overflow to inf in float32 (sklearn checks post-conversion)
            arr = arr.astype(dtype, copy=False)
        _assert_all_finite(arr, "X", allow_nan=allow_nan)
        x = arr
    return as_sharded(x, mesh=resolve_mesh(mesh), dtype=dtype)


def check_X_y(X, y, mesh=None, dtype=None):
    mesh = resolve_mesh(mesh)
    n_X = X.n_rows if isinstance(X, ShardedArray) else np.asarray(X).shape[0]
    n_y = y.n_rows if isinstance(y, ShardedArray) else np.asarray(y).shape[0]
    if n_X != n_y:
        raise ValueError(f"X and y have inconsistent lengths: {n_X} vs {n_y}")
    X = check_array(X, mesh=mesh, dtype=dtype)
    if not isinstance(y, ShardedArray):
        yh = np.asarray(y)
        if dtype is not None and np.issubdtype(np.dtype(dtype), np.floating):
            # same post-cast rule as X: a finite float64 can overflow to
            # inf in float32 and must be caught HERE, not by the solver
            # sanitizer mid-fit
            yh = yh.astype(dtype, copy=False)
        _assert_all_finite(yh, "y")
        y = yh
    y = as_sharded(y, mesh=mesh, dtype=dtype)
    return X, y


def check_chunks(n_samples, n_features, chunks=None, mesh=None):
    """Normalize a dask-ml-style ``chunks`` argument to a flat
    ``(rows_per_shard, n_features)`` tuple.

    Ref: ``dask_ml/utils.py::check_chunks``. On TPU the row partitioning is
    dictated by the mesh's data axis, so when ``chunks`` is None the default
    is ``ceil(n_samples / data_shards)`` rows per shard with unchunked
    columns — the layout ``ShardedArray.from_array`` produces on ``mesh``
    (default mesh when None).
    """
    from ..parallel.mesh import data_shards

    if chunks is None:
        shards = data_shards(resolve_mesh(mesh))
        rows = max(int(np.ceil(n_samples / shards)), 1)
        return (rows, n_features)
    if isinstance(chunks, (int, np.integer)):
        # an integer is the NUMBER of blocks (reference semantics), with a
        # 100-row floor per block — not a rows-per-block count
        return (max(100, n_samples // max(int(chunks), 1)), n_features)
    if isinstance(chunks, (tuple, list)) and len(chunks) == 2:
        r, c = chunks
        # dask-ml also accepts per-dimension block-size tuples,
        # e.g. ((500, 500), (16,))
        if isinstance(r, (tuple, list)):
            r = max(int(v) for v in r) if len(r) else 0
        if isinstance(c, (tuple, list)):
            if len(c) != 1:
                raise AssertionError(
                    f"Column chunks must be a single block on TPU (got {c})"
                )
            c = c[0]
        if isinstance(r, (int, np.integer)) and isinstance(c, (int, np.integer)):
            if int(c) != n_features:
                raise AssertionError(
                    "Column chunks must span all n_features on TPU "
                    f"(got {c}, need {n_features})"
                )
            return (max(int(r), 1), n_features)
    raise AssertionError(f"Unexpected chunks value: {chunks!r}")


def data_fingerprint(a, n_sample=96) -> str:
    """Cheap content fingerprint of an array for checkpoint identity:
    same-shape different-content data must not resume stale state.
    Samples head, evenly strided middle, AND tail rows; for a
    ShardedArray that is one small device gather, never a full pull.
    Sample-based by design — collisions need identical values at every
    probed row."""
    import hashlib

    if a is None:
        return "none"
    n = a.shape[0] if hasattr(a, "shape") else len(a)
    k = max(n_sample // 3, 1)
    idx = np.unique(np.concatenate([
        np.arange(min(k, n)),
        np.linspace(0, n - 1, num=min(k, n), dtype=np.int64),
        np.arange(max(n - k, 0), n),
    ]))
    if isinstance(a, ShardedArray):
        from ..parallel.sharded import take_rows

        sample = take_rows(a, idx).to_numpy()
    else:
        from ..parallel.streaming import (_is_sparse_source, _slice_dense,
                                          as_row_sliceable)

        if _is_sparse_source(a):
            # sampled rows densify one at a time — O(sample), not O(n·d)
            a = as_row_sliceable(a)  # once, not per sampled row
            sample = np.concatenate([
                _slice_dense(a, int(i), int(i) + 1, np.float32)
                for i in idx
            ]) if len(idx) else np.empty((0,) + a.shape[1:], np.float32)
        else:
            sample = np.asarray(a)[idx]
    return hashlib.sha1(
        np.ascontiguousarray(sample).tobytes()
    ).hexdigest()


import functools as _functools


@_functools.lru_cache(maxsize=1)
def _binary_class_scan():
    """Module-cached jitted scan — defining the jit inside
    ``device_binary_classes`` recompiled it (~0.3 s) on EVERY call,
    which dominated every Incremental fit's wall clock."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _scan(data, mask):
        valid = mask > 0
        if data.dtype == jnp.bool_:
            data = data.astype(jnp.int32)
        # dtype-native sentinels: a float32 cast would corrupt integer
        # labels beyond 2^24 (ID-like class codes)
        if jnp.issubdtype(data.dtype, jnp.floating):
            big = jnp.asarray(jnp.inf, data.dtype)
            small = -big
        else:
            info = jnp.iinfo(data.dtype)
            big = jnp.asarray(info.max, data.dtype)
            small = jnp.asarray(info.min, data.dtype)
        mn = jnp.min(jnp.where(valid, data, big))
        mx = jnp.max(jnp.where(valid, data, small))
        binary = jnp.all(~valid | (data == mn) | (data == mx))
        # one stacked f32 output = ONE device→host fetch; three separate
        # scalar pulls cost three host round trips, each a sync.
        # Integer class values ride BIT-PRESERVED
        # (bitcast), not value-cast — f32 cannot represent ints > 2^24.
        vals = jnp.stack([mn, mx])
        if jnp.issubdtype(vals.dtype, jnp.floating):
            if vals.dtype != jnp.float32:
                # f64 under x64: a value-cast would round class values —
                # fall back to native-dtype scalars (extra fetches, but
                # the non-default mode pays for its precision)
                return mn, mx, binary
        elif vals.dtype.itemsize > 4:
            # i64/u64 under x64: an int32 bitcast would WRAP wide class
            # ids — same native-dtype fallback
            return mn, mx, binary
        else:
            vals = jax.lax.bitcast_convert_type(
                vals.astype(jnp.int32), jnp.float32
            )
        return jnp.concatenate(
            [vals.astype(jnp.float32), binary.astype(jnp.float32)[None]]
        )

    return _scan


def device_binary_classes(y: ShardedArray) -> np.ndarray:
    """The two class values of a device label vector, WITHOUT pulling the
    column to host (VERDICT r2 #4: ``_encode_y`` full-column round-trip).
    One jitted masked reduction; only three scalars cross to host. Raises
    ValueError for non-binary targets (the error path falls back to a
    host ``np.unique`` for an exact class count in the message)."""
    import jax
    import jax.numpy as jnp

    out = _binary_class_scan()(y.data, y.row_mask(jnp.float32))
    if isinstance(out, tuple):  # wide-dtype (f64/i64) fallback path
        mn_h, mx_h, binary = np.asarray(out[0]), np.asarray(out[1]),             bool(out[2])
    else:
        out = np.asarray(out)
        binary = bool(out[2])
        # mirror the scan's branch: bool was cast to int32 there, so only
        # genuinely-floating labels come back as values (ints bitcast)
        if np.issubdtype(np.dtype(str(y.dtype)), np.floating):
            mn_h, mx_h = out[0], out[1]
        else:
            mn_h, mx_h = np.ascontiguousarray(out[:2]).view(np.int32)
    if not binary or mn_h == mx_h:
        classes = np.unique(y.to_numpy())  # error path only
        err = ValueError(
            f"expected binary targets; got {len(classes)} classes"
        )
        # callers falling back to a host unique (the multiclass path)
        # reuse this instead of a second full-column gather + sort
        err.classes = classes
        raise err
    # classes keep the label dtype (np.unique parity: int labels give
    # int classes, so predict() returns the caller's dtype)
    return np.stack([mn_h, mx_h]).astype(np.dtype(str(y.dtype)))


def device_classes(y: ShardedArray) -> np.ndarray:
    """All class values of a device label vector: the three-scalar
    device scan when binary, falling back to the host unique the scan's
    error path already computed (ONE column gather total, never two).
    The ``err.classes`` handoff stays private to this module."""
    try:
        return device_binary_classes(y)
    except ValueError as e:
        c = getattr(e, "classes", None)
        return c if c is not None else np.unique(y.to_numpy())


def check_is_fitted(est, attr: str):
    if not hasattr(est, attr):
        raise AttributeError(
            f"This {type(est).__name__} instance is not fitted yet; call 'fit' first."
        )
