"""Masked global reductions over row-sharded arrays.

Reference equivalent: ``dask/array/reductions.py`` tree-reduce graphs
(SURVEY.md §2b row 1). Here each reduction is a ``jnp`` expression over the
global (padded) view; under ``jit`` with row sharding XLA lowers the sum to
a per-shard partial + ICI all-reduce — the same two-phase shape as dask's
tree-reduce, with zero scheduler/serialization overhead.

All functions take the padded data plus a row mask (1 = logical row,
0 = padding) so padding never biases a statistic.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
from jax import lax


def masked_sum(x, mask, axis=0):
    """Sum over rows, ignoring padded rows. x: (n, ...), mask: (n,)."""
    return jnp.tensordot(mask, x, axes=(0, 0)) if axis == 0 and x.ndim > 1 else jnp.sum(
        x * _expand(mask, x), axis=axis
    )


def masked_mean(x, mask, n_rows):
    return masked_sum(x, mask) / n_rows


def masked_mean_var(x, mask, n_rows, ddof=0):
    """Numerically-stable mean/variance in one pass (two psums under jit)."""
    mean = masked_mean(x, mask, n_rows)
    centered = (x - mean) * _expand(mask, x)
    var = jnp.sum(centered * centered, axis=0) / max(n_rows - ddof, 1)
    return mean, var


def masked_min(x, mask, axis=0):
    big = jnp.asarray(jnp.inf, dtype=x.dtype)
    return jnp.min(jnp.where(_expand(mask, x) > 0, x, big), axis=axis)


def masked_max(x, mask, axis=0):
    small = jnp.asarray(-jnp.inf, dtype=x.dtype)
    return jnp.max(jnp.where(_expand(mask, x) > 0, x, small), axis=axis)


def masked_count_nonzero(x, mask):
    return jnp.tensordot(mask, (x != 0).astype(x.dtype), axes=(0, 0))


def _expand(mask, x):
    return mask.reshape(mask.shape + (1,) * (x.ndim - 1)).astype(x.dtype)


# -- exact top-l without a full sort ---------------------------------------
# ``lax.top_k`` over n keys lowers to a FULL sort of n (key, index) pairs on
# the TPU (6.2 ms at n = 4,194,304, PERF.md section 5), whatever l is.

def top_l_tile(n, l):
    """The row tile of :func:`top_l_indices` over ``n`` keys: a multiple of
    128 near ``sqrt(n / l)``, which minimises the second stage's
    ``n / T + l * T`` keys; ``None`` where one ``lax.top_k`` over all n is
    no larger."""
    t = 128 * max(1, round(math.sqrt(n / l) / 128))
    return t if n > 2 * l * t else None


def top_l_path(n, l):
    """``"tiled"`` or ``"sort"``: which path :func:`top_l_indices` takes
    over ``n`` keys, static from the shapes."""
    return "sort" if top_l_tile(n, l) is None else "tiled"


def top_l_indices(keys, l):
    """``lax.top_k(keys, l)[1]`` for a 1-D float ``keys`` — the same
    indices in the same order — without sorting all of ``keys``: a two-level
    top-l over row tiles of T keys.

    Order the keys by (key descending, index ascending). Every member of the
    top l lies in one of the l tiles whose best key ranks highest in that
    order (a tile below those has l better keys above its best).
    ``lax.top_k`` breaks ties by the lower index, which orders contiguous
    tiles by their best keys, and the chosen tiles are concatenated in index
    order, so the second ``top_k`` breaks ties as the first would have —
    ``-inf`` keys included (the padding ranks below every key). Under a
    row-sharded ``keys`` the tile maxima are local and only the n / T maxima
    and the l gathered tiles are sorted."""
    n = keys.shape[0]
    t = top_l_tile(n, l)
    if t is None:
        return lax.top_k(keys, l)[1]
    n_t = -(-n // t)
    tiles = jnp.pad(keys, (0, n_t * t - n),
                    constant_values=-jnp.inf).reshape(n_t, t)
    _, tile = lax.top_k(jnp.max(tiles, axis=1), l)
    tile = jnp.sort(tile)
    _, j = lax.top_k(jnp.take(tiles, tile, axis=0).reshape(-1), l)
    return tile[j // t] * t + j % t


# -- counts into a few bins without a scatter -------------------------------
# ``jax.ops.segment_sum`` lowers to a scatter-add; on the TPU one of n rows
# into c << n bins collides on almost every update and runs near-serially
# (36.7 ms for 4,194,304 rows into 81 bins, PERF.md section 5).

def small_segment_count(labels, weights, c):
    """``jax.ops.segment_sum(weights, labels, num_segments=c)`` for a small
    ``c``, as a reduction over rows of the one-hot compare
    ``labels[:, None] == arange(c)`` — XLA fuses the compare into the
    reduction, so the (n, c) one-hot never reaches memory, and a label
    outside ``[0, c)`` counts nowhere, as in the scatter. O(n c) vector
    work, which a caller that took ``labels`` by an argmin over (n, c)
    distances has already paid. Sums whole-number ``weights`` (a row mask)
    exactly in any order below 2**24, so the counts equal the scatter's bit
    for bit; under row-sharded ``labels`` the sum is per-shard partials and
    one all-reduce of c values."""
    hit = labels[:, None] == jnp.arange(c, dtype=labels.dtype)[None, :]
    return jnp.sum(jnp.where(hit, weights[:, None], 0), axis=0)
