"""Preprocessing scalers & transforms over sharded arrays.

Reference: ``dask_ml/preprocessing/data.py`` (SURVEY.md §2a Preprocessing
row): StandardScaler / MinMaxScaler / RobustScaler / QuantileTransformer /
PolynomialFeatures as lazy dask reductions + per-block transforms. Here the
fit statistics are one jitted masked reduction each (psum under sharding)
and transforms are elementwise XLA programs that keep data on device.

Quantile-based fits (RobustScaler, QuantileTransformer) use a global
device-side sort (XLA gathers the column); the reference uses approximate
t-digest quantiles — exact is affordable at this stage and flagged for a
sketch-based upgrade.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from ..base import BaseEstimator, TransformerMixin, to_host
from ..ops import reductions
from ..parallel.sharded import ShardedArray
from ..utils.validation import check_array, check_is_fitted


def _handle_zeros_in_scale(scale):
    """Ref: dask_ml/utils.py::handle_zeros_in_scale."""
    return np.where(scale == 0.0, 1.0, scale)


@functools.partial(jax.jit,
                   static_argnames=("shift_first", "do_clip"))
def _affine(data, mask, a, b, lo=0.0, hi=1.0, shift_first=True,
            do_clip=False):
    """One fused program for every scaler transform/inverse. A chain of
    eager ops is one launch and one pass over the data EACH; jitted,
    XLA fuses the whole transform into a single kernel launch.

    ``shift_first=True`` computes ``(data + b) * a`` — the
    subtract-then-scale form, which keeps the benign cancellation for
    features with |mean| >> std (``data * a + b`` would round at the
    magnitude of data before b cancels it). ``shift_first=False``
    computes ``data * a + b`` — the scale-then-shift form used by the
    inverse direction. ``mask=None`` skips padding re-zeroing (only
    valid when the shift term is zero)."""
    a = jnp.asarray(a, data.dtype)
    b = jnp.asarray(b, data.dtype)
    out = (data + b) * a if shift_first else data * a + b
    if do_clip:
        out = jnp.clip(out, lo, hi)
    if mask is not None:
        out = out * mask[:, None].astype(data.dtype)
    return out


def _frame_parts(X):
    """(partition list, kind) for frame inputs; (None, None) otherwise.

    The reference's scalers consume dd.DataFrames natively and return
    frames of the same type (ref: dask_ml/preprocessing/data.py — the
    dd path of StandardScaler etc.); here the frame types are pandas
    and :class:`~dask_ml_tpu.parallel.frames.PartitionedFrame`.
    """
    if isinstance(X, pd.DataFrame):
        return [X], "pandas"
    from ..parallel.frames import PartitionedFrame

    if isinstance(X, PartitionedFrame):
        return list(X.partitions), "partitioned"
    return None, None


def _frame_device(parts, cols):
    """Place frame partitions on the mesh, rejecting unencoded columns.
    Reuses PartitionedFrame.to_sharded as the single frame→device
    bridge."""
    from ..parallel.frames import PartitionedFrame

    bad = [
        c for c in cols
        if not (pd.api.types.is_numeric_dtype(parts[0].dtypes[c])
                or pd.api.types.is_bool_dtype(parts[0].dtypes[c]))
    ]
    if bad:
        raise ValueError(
            f"non-numeric columns {bad}: encode them first "
            "(Categorizer + DummyEncoder/OrdinalEncoder)"
        )
    return PartitionedFrame(parts).to_sharded(columns=cols)


def _frame_check_fitted_names(self, cols):
    fitted = getattr(self, "feature_names_in_", None)
    if fitted is not None and list(fitted) != list(cols):
        raise ValueError(
            f"feature names {list(cols)} do not match the names seen at "
            f"fit time {list(fitted)}"
        )


def _frame_rebuild(self, parts, kind, cols, out):
    """Rebuild the method result as the input's frame type with the
    original partition boundaries and index."""
    if not isinstance(out, ShardedArray):
        return out
    if out.shape[1] != len(cols):
        # width-changing transform (PolynomialFeatures): honor the
        # reference's preserve_dataframe switch
        if not getattr(self, "preserve_dataframe", True):
            return out
        names = list(self.get_feature_names_out(cols))
    else:
        names = cols
    arr = np.asarray(out.to_numpy())
    rebuilt, off = [], 0
    for p in parts:
        rebuilt.append(pd.DataFrame(
            arr[off:off + len(p)], index=p.index, columns=names
        ))
        off += len(p)
    if kind == "pandas":
        return rebuilt[0]
    from ..parallel.frames import PartitionedFrame

    return PartitionedFrame(rebuilt)


def _frame_aware(method, name):
    """Frame adapter for array-native transformer methods: frames are
    placed on device (all columns must already be numeric — categorical
    columns go through Categorizer/DummyEncoder/OrdinalEncoder first),
    the array method runs on the mesh, and the result is rebuilt as the
    SAME frame type with the original partition boundaries and index —
    the reference's frame-in/frame-out contract."""

    @functools.wraps(method)
    def wrapper(self, X, *args, **kwargs):
        parts, kind = _frame_parts(X)
        if kind is None:
            return method(self, X, *args, **kwargs)
        cols = list(parts[0].columns)
        if name != "fit":
            _frame_check_fitted_names(self, cols)
        out = method(self, _frame_device(parts, cols), *args, **kwargs)
        if out is self:  # fit
            self.feature_names_in_ = np.asarray(cols, dtype=object)
            return self
        return _frame_rebuild(self, parts, kind, cols, out)

    return wrapper


class _DeviceTransformer(TransformerMixin, BaseEstimator):
    def __init_subclass__(cls, **kw):
        # every subclass-defined fit/transform/inverse_transform gets the
        # frame adapter; array inputs pass straight through
        super().__init_subclass__(**kw)
        for name in ("fit", "transform", "inverse_transform"):
            if name in cls.__dict__:
                setattr(cls, name, _frame_aware(cls.__dict__[name], name))

    def fit_transform(self, X, y=None, **kw):
        parts, kind = _frame_parts(X)
        if kind is None:
            return self.fit(X, y, **kw).transform(X)
        # frame input: one host-concat + one device placement for the
        # whole fit+transform, then rebuild the frame once
        cols = list(parts[0].columns)
        Xs = _frame_device(parts, cols)
        out = self.fit(Xs, y, **kw).transform(Xs)
        self.feature_names_in_ = np.asarray(cols, dtype=object)
        return _frame_rebuild(self, parts, kind, cols, out)

    # quantile-based transformers compute NaN-skipping statistics
    # (nanquantile), so they accept NaN like sklearn's 'allow-nan' mode;
    # moment-based scalers keep strict rejection (their masked reductions
    # would silently propagate NaN into the fitted statistics)
    _allow_nan = False

    def _sharded(self, X) -> ShardedArray:
        return check_array(X, dtype=np.float32, allow_nan=self._allow_nan)


class StandardScaler(_DeviceTransformer):
    """Ref: dask_ml/preprocessing/data.py::StandardScaler."""

    def __init__(self, copy=True, with_mean=True, with_std=True):
        self.copy = copy
        self.with_mean = with_mean
        self.with_std = with_std

    def fit(self, X, y=None):
        X = self._sharded(X)
        mean, var = reductions.masked_mean_var(X.data, X.row_mask(), X.n_rows)
        self.mean_ = to_host(mean) if self.with_mean else None
        if self.with_std:
            self.var_ = to_host(var)
            self.scale_ = _handle_zeros_in_scale(np.sqrt(self.var_))
        else:
            self.var_ = self.scale_ = None
        self.n_samples_seen_ = X.n_rows
        self.n_features_in_ = X.shape[1]
        return self

    def transform(self, X):
        check_is_fitted(self, "n_samples_seen_")
        X = self._sharded(X)
        a = 1.0 / self.scale_ if self.with_std else np.float32(1.0)
        b = -self.mean_ if self.with_mean else np.float32(0.0)
        mask = X.row_mask() if self.with_mean else None
        out = _affine(X.data, mask, a, b)
        return ShardedArray(out, X.n_rows, X.mesh)

    def inverse_transform(self, X):
        check_is_fitted(self, "n_samples_seen_")
        X = self._sharded(X)
        a = self.scale_ if self.with_std else np.float32(1.0)
        b = self.mean_ if self.with_mean else np.float32(0.0)
        mask = X.row_mask() if self.with_mean else None
        out = _affine(X.data, mask, a, b, shift_first=False)
        return ShardedArray(out, X.n_rows, X.mesh)


class MinMaxScaler(_DeviceTransformer):
    """Ref: dask_ml/preprocessing/data.py::MinMaxScaler."""

    def __init__(self, feature_range=(0, 1), copy=True, clip=False):
        self.feature_range = feature_range
        self.copy = copy
        self.clip = clip

    def fit(self, X, y=None):
        X = self._sharded(X)
        mask = X.row_mask()
        dmin = to_host(reductions.masked_min(X.data, mask))
        dmax = to_host(reductions.masked_max(X.data, mask))
        lo, hi = self.feature_range
        self.data_min_, self.data_max_ = dmin, dmax
        self.data_range_ = dmax - dmin
        self.scale_ = (hi - lo) / _handle_zeros_in_scale(self.data_range_)
        self.min_ = lo - dmin * self.scale_
        self.n_features_in_ = X.shape[1]
        return self

    def transform(self, X):
        check_is_fitted(self, "scale_")
        X = self._sharded(X)
        out = _affine(X.data, X.row_mask(), self.scale_, self.min_,
                      self.feature_range[0], self.feature_range[1],
                      shift_first=False, do_clip=bool(self.clip))
        return ShardedArray(out, X.n_rows, X.mesh)

    def inverse_transform(self, X):
        check_is_fitted(self, "scale_")
        X = self._sharded(X)
        out = _affine(X.data, X.row_mask(), 1.0 / self.scale_, -self.min_)
        return ShardedArray(out, X.n_rows, X.mesh)


from functools import partial as _partial


@_partial(jax.jit, static_argnames=("n_bins",))
def _sketch_quantiles(data, mask, qs, n_bins=4096):
    """Histogram-sketch per-column quantiles (the reference's approximate
    quantiles, ``dask_ml/preprocessing/data.py::RobustScaler`` — dask's
    t-digest/percentile sketch): one min/max pass + one bucketized
    segment_sum pass, then interpolation inside the hit bin. No global
    sort — O(n·d) work and O(d·n_bins) memory instead of gathering whole
    columns, which is what makes 1B-row scaling stats feasible. Error is
    bounded by one bin width: (max-min)/n_bins per column."""
    d = data.shape[1]
    valid = mask[:, None] > 0
    big = jnp.asarray(jnp.inf, jnp.float32)
    df = data.astype(jnp.float32)
    mn = jnp.min(jnp.where(valid, df, big), axis=0)
    mx = jnp.max(jnp.where(valid, df, -big), axis=0)
    span = jnp.maximum(mx - mn, 1e-12)
    idx = jnp.clip(((df - mn) / span * n_bins).astype(jnp.int32),
                   0, n_bins - 1)
    flat = idx + jnp.arange(d, dtype=jnp.int32)[None, :] * n_bins
    weights = jnp.broadcast_to(mask[:, None].astype(jnp.float32),
                               df.shape)
    hist = jax.ops.segment_sum(
        weights.reshape(-1), flat.reshape(-1), num_segments=d * n_bins
    ).reshape(d, n_bins)
    cum = jnp.cumsum(hist, axis=1)
    q_arr = jnp.asarray(qs, jnp.float32)

    def one_col(cum_c, mn_c, span_c):
        t = q_arr * cum_c[-1]
        b = jnp.clip(jnp.searchsorted(cum_c, t), 0, n_bins - 1)
        prev = jnp.where(b > 0, cum_c[jnp.maximum(b - 1, 0)], 0.0)
        in_bin = cum_c[b] - prev
        frac = jnp.where(in_bin > 0, (t - prev) / in_bin, 0.5)
        return mn_c + (b + frac) * span_c / n_bins

    return jax.vmap(one_col)(cum, mn, span).T  # (n_q, d)


# rows above which scaling stats switch to the sketch: an exact
# nanquantile gathers and sorts whole columns, which stops being
# affordable long before BASELINE scale
_SKETCH_THRESHOLD = 1_000_000


def _masked_quantiles(X: ShardedArray, qs, sketch=None, n_bins=4096):
    """Per-column quantiles. Small inputs: exact nanquantile (padding →
    NaN). Large inputs (or ``sketch=True``): histogram sketch, matching
    the reference's approximate-quantile behavior at scale."""
    if sketch is None:
        sketch = X.n_rows > _SKETCH_THRESHOLD
    if sketch:
        return _sketch_quantiles(
            X.data, X.row_mask(jnp.float32), jnp.asarray(qs, jnp.float32),
            n_bins=n_bins,
        )
    mask = X.row_mask(X.dtype)
    data = jnp.where(mask[:, None] > 0, X.data, jnp.nan)
    return jnp.nanquantile(
        data.astype(jnp.float32), jnp.asarray(qs, jnp.float32), axis=0
    )


class RobustScaler(_DeviceTransformer):
    """Ref: dask_ml/preprocessing/data.py::RobustScaler (approximate
    quantiles there; exact here)."""

    _allow_nan = True

    def __init__(self, with_centering=True, with_scaling=True,
                 quantile_range=(25.0, 75.0), copy=True):
        self.with_centering = with_centering
        self.with_scaling = with_scaling
        self.quantile_range = quantile_range
        self.copy = copy

    def fit(self, X, y=None):
        X = self._sharded(X)
        q_lo, q_hi = self.quantile_range
        qs = _masked_quantiles(X, [q_lo / 100.0, 0.5, q_hi / 100.0])
        qs = to_host(qs)
        self.center_ = qs[1] if self.with_centering else None
        if self.with_scaling:
            self.scale_ = _handle_zeros_in_scale(qs[2] - qs[0])
        else:
            self.scale_ = None
        self.n_features_in_ = X.shape[1]
        return self

    def transform(self, X):
        check_is_fitted(self, "n_features_in_")
        X = self._sharded(X)
        a = 1.0 / self.scale_ if self.with_scaling else np.float32(1.0)
        b = -self.center_ if self.with_centering else np.float32(0.0)
        out = _affine(X.data, X.row_mask(), a, b)
        return ShardedArray(out, X.n_rows, X.mesh)

    def inverse_transform(self, X):
        check_is_fitted(self, "n_features_in_")
        X = self._sharded(X)
        a = self.scale_ if self.with_scaling else np.float32(1.0)
        b = self.center_ if self.with_centering else np.float32(0.0)
        out = _affine(X.data, X.row_mask(), a, b, shift_first=False)
        return ShardedArray(out, X.n_rows, X.mesh)


class QuantileTransformer(_DeviceTransformer):
    """Ref: dask_ml/preprocessing/data.py::QuantileTransformer — maps each
    feature through its empirical CDF via interpolation."""

    _allow_nan = True

    def __init__(self, n_quantiles=1000, output_distribution="uniform",
                 ignore_implicit_zeros=False, subsample=int(1e5),
                 random_state=None, copy=True):
        self.n_quantiles = n_quantiles
        self.output_distribution = output_distribution
        self.ignore_implicit_zeros = ignore_implicit_zeros
        self.subsample = subsample
        self.random_state = random_state
        self.copy = copy

    def fit(self, X, y=None):
        if self.ignore_implicit_zeros:
            # sklearn: only meaningful for sparse input, which TPU dense
            # arrays never are — raise rather than silently no-op
            raise ValueError(
                "ignore_implicit_zeros applies to sparse matrices only; "
                "dense input does not support it"
            )
        X = self._sharded(X)
        sub_limit = int(self.subsample) if self.subsample else None
        if sub_limit is not None and self.n_quantiles > sub_limit:
            raise ValueError(
                f"The number of quantiles ({self.n_quantiles}) cannot be "
                f"greater than subsample ({sub_limit})"
            )
        n_q = min(self.n_quantiles, X.n_rows)
        self.n_quantiles_ = n_q
        self.references_ = np.linspace(0, 1, n_q)
        sub = sub_limit if sub_limit is not None else X.n_rows
        src = X
        if X.n_rows > sub:
            # sklearn semantics: quantiles of a seeded uniform subsample
            # of `subsample` rows. The pick is a device Gumbel top-l
            # (static shapes, no host index generation at 1B rows) and
            # the gather one all-to-all (take_rows). If the sample is
            # still past the sort-affordability threshold,
            # _masked_quantiles switches to the histogram sketch — the
            # reference's approximate-quantile behavior at scale.
            import jax as _jax

            from ..models.kmeans import _gumbel_top_l
            from ..parallel.sharded import take_rows

            key = _jax.random.PRNGKey(
                0 if self.random_state is None else int(self.random_state)
            )
            idx_d = _gumbel_top_l(X.row_mask(jnp.float32), key, sub)
            if not idx_d.is_fully_addressable:
                # multi-host mesh: replicate before the host read —
                # np.asarray on a cross-process array raises
                from ..parallel.sharded import _replicator

                idx_d = _replicator(X.mesh)(idx_d)
            src = take_rows(X, np.asarray(idx_d))
        self.quantiles_ = to_host(_masked_quantiles(src, self.references_))
        self.n_features_in_ = X.shape[1]
        return self

    def transform(self, X):
        check_is_fitted(self, "quantiles_")
        return self._map(X, inverse=False)

    def inverse_transform(self, X):
        check_is_fitted(self, "quantiles_")
        return self._map(X, inverse=True)

    def _map(self, X, inverse):
        from scipy import stats

        X = self._sharded(X)
        quantiles = jnp.asarray(self.quantiles_, jnp.float32)  # (n_q, d)
        refs = jnp.asarray(self.references_, jnp.float32)
        data = X.data.astype(jnp.float32)
        normal = self.output_distribution == "normal"

        if inverse and normal:
            data = jnp.asarray(
                stats.norm.cdf(np.asarray(data)), jnp.float32
            )

        def col(vals, qcol):
            if inverse:
                return jnp.interp(vals, refs, qcol)
            # average of forward and reverse interpolation: sklearn's tie
            # handling — on runs of equal values the one-sided interp is
            # biased to the run's edge, the average lands mid-run
            fwd = jnp.interp(vals, qcol, refs)
            rev = -jnp.interp(-vals, -qcol[::-1], -refs[::-1])
            out = 0.5 * (fwd + rev)
            # boundary override, also sklearn: at/above the fitted max →
            # exactly refs[-1], then at/below the fitted min → refs[0].
            # Lower bound LAST so a constant column maps to refs[0]
            out = jnp.where(vals >= qcol[-1], refs[-1], out)
            return jnp.where(vals <= qcol[0], refs[0], out)

        out = jax.vmap(col, in_axes=(1, 1), out_axes=1)(data, quantiles)
        if not inverse and normal:
            clipped = jnp.clip(out, 1e-7, 1 - 1e-7)
            out = jnp.asarray(
                stats.norm.ppf(np.asarray(clipped)), jnp.float32
            )
        out = out * X.row_mask(out.dtype)[:, None]
        return ShardedArray(out, X.n_rows, X.mesh)


class PolynomialFeatures(_DeviceTransformer):
    """Ref: dask_ml/preprocessing/data.py::PolynomialFeatures — the
    reference maps sklearn per block; here the monomials are one fused
    elementwise program (products of gathered columns)."""

    def __init__(self, degree=2, interaction_only=False, include_bias=True,
                 preserve_dataframe=False):
        self.degree = degree
        self.interaction_only = interaction_only
        self.include_bias = include_bias
        self.preserve_dataframe = preserve_dataframe

    def _combinations(self, d):
        comb = (itertools.combinations if self.interaction_only
                else itertools.combinations_with_replacement)
        start = 0 if self.include_bias else 1
        return [c for deg in range(start, self.degree + 1)
                for c in comb(range(d), deg)]

    def fit(self, X, y=None):
        X = self._sharded(X)
        self.n_features_in_ = d = X.shape[1]
        self._combos = self._combinations(d)
        self.n_output_features_ = len(self._combos)
        return self

    def transform(self, X):
        check_is_fitted(self, "n_output_features_")
        X = self._sharded(X)
        data = X.data
        mask = X.row_mask(data.dtype)
        cols = []
        for combo in self._combos:
            if len(combo) == 0:
                cols.append(mask)  # bias column, zeroed on padding
            else:
                c = data[:, combo[0]]
                for j in combo[1:]:
                    c = c * data[:, j]
                cols.append(c)
        out = jnp.stack(cols, axis=1)
        return ShardedArray(out, X.n_rows, X.mesh)

    def get_feature_names_out(self, input_features=None):
        if input_features is None:
            input_features = [f"x{i}" for i in range(self.n_features_in_)]
        names = []
        for combo in self._combos:
            if not combo:
                names.append("1")
            else:
                counts = {}
                for j in combo:
                    counts[j] = counts.get(j, 0) + 1
                names.append(" ".join(
                    f"{input_features[j]}^{c}" if c > 1 else input_features[j]
                    for j, c in sorted(counts.items())
                ))
        return np.asarray(names, dtype=object)
