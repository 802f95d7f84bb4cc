"""The weighted draw without a full sort (PR 39, ``ops/reductions.py::
top_l_indices``, under ``models/kmeans.py::_gumbel_top_l``): a two-level
top-l over row tiles that returns ``lax.top_k``'s indices, in the same
order, for every input — ties and ``-inf`` keys included. The last case
lowers the draw at the ``spectral_nystrom`` cell's shape and holds that no
sort of all 4,194,304 keys is left in it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dask_ml_tpu.models import kmeans as KM
from dask_ml_tpu.ops.reductions import top_l_indices, top_l_path, top_l_tile


def _keys(kind, n, l, seed):
    rng = np.random.default_rng(seed)
    if kind == "random":
        return rng.standard_normal(n).astype(np.float32)
    if kind == "ties":                  # five values: every key ties
        return rng.integers(0, 5, n).astype(np.float32)
    # mostly -inf: fewer finite keys than l, so top_k returns -inf keys too
    k = np.full(n, -np.inf, np.float32)
    k[rng.choice(n, l // 2, replace=False)] = rng.standard_normal(l // 2)
    return k


def _plain(keys, l):
    return np.asarray(jax.lax.top_k(jnp.asarray(keys), l)[1])


_top_l = jax.jit(top_l_indices, static_argnums=1)

# (n, l, the tile the shapes choose): n not a multiple of T in most, l = 1,
# l = T (128), l = 100, and a size where one top_k is no larger
CASES = {"l1": (40_000, 1, 256), "l16": (100_003, 16, 128),
         "l100": (60_000, 100, 128), "l_eq_t": (600_000, 128, 128),
         "l1_wide": (1_000_000, 1, 1024), "fallback": (3_000, 16, None)}


@pytest.mark.parametrize("kind", ["random", "ties", "mostly_inf"])
@pytest.mark.parametrize("case", list(CASES))
def test_same_indices_in_the_same_order_as_top_k(case, kind):
    n, l, tile = CASES[case]
    assert top_l_tile(n, l) == tile
    assert top_l_path(n, l) == ("sort" if tile is None else "tiled")
    keys = _keys(kind, n, l, seed=n + l)
    np.testing.assert_array_equal(np.asarray(_top_l(jnp.asarray(keys), l)),
                                  _plain(keys, l))


def test_inside_another_jit():
    n, l = 50_000, 16
    keys = _keys("ties", n, l, seed=1)

    @jax.jit
    def outer(k):
        return top_l_indices(k * 2.0 - 1.0, l) + 0

    np.testing.assert_array_equal(np.asarray(outer(jnp.asarray(keys))),
                                  _plain(keys * 2.0 - 1.0, l))


@pytest.mark.parametrize("kind", ["random", "ties", "mostly_inf"])
def test_row_sharded_keys_on_four_devices(kind):
    """Row-sharded over four devices (a per-shard length that is not a
    multiple of the tile): the same indices as a top_k of the whole."""
    n, l = 40_004, 16
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    keys = _keys(kind, n, l, seed=7)
    ks = jax.device_put(jnp.asarray(keys), NamedSharding(mesh, P("data")))
    assert len(ks.sharding.device_set) == 4
    np.testing.assert_array_equal(np.asarray(_top_l(ks, l)), _plain(keys, l))


@pytest.mark.parametrize("l", [1, 16, 100])
def test_the_gumbel_draw_is_the_plain_draw(l):
    """``_gumbel_top_l`` on weights with zeros (masked rows) draws the rows
    the plain ``lax.top_k`` of the same Gumbel keys draws."""
    n = 30_001
    rng = np.random.default_rng(l)
    w = jnp.asarray(rng.random(n).astype(np.float32)
                    * (rng.random(n) > 0.3))
    key = jax.random.PRNGKey(l)
    plain = jax.lax.top_k(KM._gumbel_keys(w, key), l)[1]
    assert top_l_path(n, l) == "tiled"
    np.testing.assert_array_equal(np.asarray(KM._gumbel_top_l(w, key, l)),
                                  np.asarray(plain))


def _big_selections(module, n):
    """The ``top_k`` and ``sort`` ops of a lowered module with an operand of
    ``n`` elements."""
    found = []

    def walk(op):
        name = op.operation.name
        if "top_k" in name or "sort" in name:
            for v in op.operation.operands:
                shape = getattr(v.type, "shape", None)
                if shape is not None and int(np.prod(shape)) == n:
                    found.append(name)
        for region in op.operation.regions:
            for block in region.blocks:
                for inner in block.operations:
                    walk(inner)

    for op in module.body.operations:
        walk(op)
    return found


def test_no_sort_of_every_key_at_the_cells_shape():
    """Lowered, not compiled, at ``spectral_nystrom``'s 4,194,304 rows and
    l = 16: no ``top_k`` or ``sort`` reads all the keys. The plain draw,
    lowered the same way, has one — so the walk sees what it looks for."""
    n = 4_194_304
    args = (jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert top_l_tile(n, 16) == 512 and top_l_tile(n, 100) == 256
    tiled = KM._gumbel_top_l.lower(*args, l=16).compiler_ir("stablehlo")
    assert _big_selections(tiled, n) == []
    plain = jax.jit(lambda w, k: jax.lax.top_k(KM._gumbel_keys(w, k), 16)[1])
    assert _big_selections(plain.lower(*args).compiler_ir("stablehlo"), n)
