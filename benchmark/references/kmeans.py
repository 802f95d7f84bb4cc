"""Plain float32 ``jax.numpy`` reference for Lloyd's k-means: squared
distances written out as sum((x - c)^2) — no ||x||^2 - 2 x.c + ||c||^2
expansion, so no matmul, no cancellation and nothing a TPU's default matmul
precision can round — argmin labels, inertia, and the Lloyd update. Shares
no code with the program; nothing the timed path uses is imported from here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def distances_sq(X, centers):
    """(n, k) squared Euclidean distances, exact differences in f32, one
    centre at a time so the temporary stays (n, d)."""
    X = jnp.asarray(X, jnp.float32)
    centers = jnp.asarray(centers, jnp.float32)
    return jax.lax.map(lambda c: jnp.sum((X - c) ** 2, axis=1), centers).T


def labels_inertia(X, centers):
    """(labels, per-row min squared distance, inertia)."""
    d2 = distances_sq(X, centers)
    dmin = jnp.min(d2, axis=1)
    return jnp.argmin(d2, axis=1), dmin, jnp.sum(dmin)


def lloyd(X, centers0, n_iter):
    """``n_iter`` plain Lloyd iterations from ``centers0`` (an empty cluster
    keeps its centre). Returns the centres."""
    X = jnp.asarray(X, jnp.float32)
    centers = jnp.asarray(centers0, jnp.float32)
    k = centers.shape[0]
    for _ in range(n_iter):
        labels = jnp.argmin(distances_sq(X, centers), axis=1)
        sums = jax.ops.segment_sum(X, labels, num_segments=k)
        counts = jax.ops.segment_sum(jnp.ones(X.shape[0], jnp.float32),
                                     labels, num_segments=k)
        centers = jnp.where(counts[:, None] > 0,
                            sums / jnp.maximum(counts, 1.0)[:, None], centers)
    return centers
