"""The one generator of the benchmark's data. A configuration names a
distribution (``data.generator``) and its parameters; a traffic mix says
how many rows a chip holds. Everything is a function of ``--seed`` alone.

Rows are BORN sharded: every chip draws its own rows from the key
folded with its index, chunk by chunk under ``lax.map`` so the generator's
temporaries stay a chunk large — nothing is staged through the host or piled
on device 0 (``chip_smoke.make_resident``'s way, PR 21).
"""

from __future__ import annotations

import functools

import numpy as np

# distribution name -> (host parameters from a numpy rng, device chunk
# generator returning (X, y); y is None for an unlabelled distribution).


def _teacher(rng, d, p):
    beta = rng.standard_normal(d)
    # unit norm: with Gaussian rows the problem is then the same for every
    # seed up to a rotation, so iteration counts repeat across seeds
    return {"beta": (beta / np.linalg.norm(beta)).astype(np.float32)}


def _teacher_device(key, rows, d, hp, p):
    import jax
    import jax.numpy as jnp

    kx, ky = jax.random.split(key)
    X = jax.random.normal(kx, (rows, d), jnp.float32)
    return X, _relabel(X, hp["beta"], ky, p["logit_scale"])


def _mixture(rng, d, p):
    k, s = int(p["components"]), float(p["center_scale"])
    return {"centers": (s * rng.standard_normal((k, d))).astype(np.float32),
            "init": (s * rng.standard_normal((k, d))).astype(np.float32)}


def _mixture_device(key, rows, d, hp, p):
    import jax
    import jax.numpy as jnp

    kl, kx = jax.random.split(key)
    k = hp["centers"].shape[0]
    lab = jax.random.randint(kl, (rows,), 0, k)
    onehot = jax.nn.one_hot(lab, k, dtype=jnp.float32)
    # exact row selection: f32 multiplies, not the MXU's default bf16 pass
    means = jnp.dot(onehot, hp["centers"],
                    precision=jax.lax.Precision.HIGHEST)
    return means + jax.random.normal(kx, (rows, d), jnp.float32), None


def _teacher_relabel(X, seed, k, d, p):
    """New labels for the SAME rows from teacher number ``k`` of the seed:
    one jitted pass over X where it lives."""
    import jax

    hp = _teacher(np.random.default_rng([int(seed), 2, int(k)]), d, p)
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed)), 1_000_003 + k)
    return _relabel_program()(X, hp["beta"], key, float(p["logit_scale"]))


def _relabel(X, beta, key, scale):
    import jax
    import jax.numpy as jnp

    eta = jnp.dot(X, beta, precision=jax.lax.Precision.HIGHEST)
    u = jax.random.uniform(key, (X.shape[0],))
    return (u < jax.nn.sigmoid(scale * eta)).astype(jnp.float32)


@functools.cache
def _relabel_program():
    import jax

    return jax.jit(_relabel, static_argnums=3)


GENERATORS = {
    "logistic_teacher": (_teacher, _teacher_device),
    "gaussian_mixture": (_mixture, _mixture_device),
}

# distribution name -> labels for rows X from teacher k of the seed: what a
# traffic mix's "vary_per_cycle": "labels" draws
RELABEL = {"logistic_teacher": _teacher_relabel}


def relabel(gen, X, seed, k, d):
    """Labels number ``k`` of ``seed`` for the rows ``X`` (a jax Array,
    where it lives)."""
    p = dict(gen)
    return RELABEL[p.pop("generator")](X, seed, k, d, p)


def host_params(gen, d, seed):
    """The distribution's small parameters (teacher weights, centres),
    drawn on the host from the seed."""
    p = dict(gen)
    return GENERATORS[p.pop("generator")][0](
        np.random.default_rng([int(seed), 0]), d, p)


def resident_program(gen, n_rows, d, mesh, hp, chunk_rows=262_144):
    """The jitted generator ``f(key, hp) -> (X[, y])`` of rows born sharded
    over ``mesh``, and whether the distribution is labelled (apart from
    :func:`make_resident` so the compile-only rehearsal can lower it)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from dask_ml_tpu.parallel.mesh import DATA_AXIS

    p = dict(gen)
    chunk_fn = GENERATORS[p.pop("generator")][1]
    shards = int(mesh.devices.size)
    if n_rows % shards:
        raise ValueError(f"{n_rows} rows do not divide over {shards} chips")
    n_local = n_rows // shards
    rows_c = min(int(chunk_rows), n_local)
    if n_local % rows_c:
        raise ValueError(f"{n_local} rows a chip are not whole chunks of "
                         f"{rows_c}")
    chunks = n_local // rows_c
    labelled = jax.eval_shape(
        lambda k: chunk_fn(k, 8, d, hp, p), jax.random.PRNGKey(0)
    )[1] is not None

    def local(key, hp):
        key = jax.random.fold_in(key, jax.lax.axis_index(DATA_AXIS))

        def one(k):
            X, y = chunk_fn(k, rows_c, d, hp, p)
            return (X, y) if labelled else (X,)

        outs = jax.lax.map(one, jax.random.split(key, chunks))
        return tuple(o.reshape((n_local,) + o.shape[2:]) for o in outs)

    specs = (P(DATA_AXIS, None), P(DATA_AXIS)) if labelled \
        else (P(DATA_AXIS, None),)
    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P()), out_specs=specs,
        check_vma=False,
    )), labelled


def make_resident(gen, n_rows, d, seed, mesh, hp):
    """(X, y) jax Arrays of ``n_rows`` x ``d`` rows born row-sharded over
    ``mesh`` in one jitted program; ``y`` is None where the distribution has
    no labels."""
    import jax

    fn, labelled = resident_program(gen, n_rows, d, mesh, hp)
    out = fn(jax.random.PRNGKey(int(seed)), hp)
    return (out[0], out[1]) if labelled else (out[0], None)
