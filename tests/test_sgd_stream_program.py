"""The one streamed SGD scan (``models/sgd.py::_sgd_stream_program``).

- parity: every (block reader, carry, mesh) cell of the scan against the
  per-block loop of ``_sgd_step_many`` / ``_sgd_step_multi`` over the same
  blocks — a ragged tail block and padding slots in the super-block, a
  cohort rung with a partial activity mask. The (xla, binary, 8-device)
  and (xla, one-vs-rest, 8-device) cells are held by
  ``tests/test_superblock.py::TestSGDParity`` at the estimator level;
- the gradient of the data-parallel dense scan under a bfloat16 design
  is the f32 product ``_design_matvec`` states, at ``HIGHEST``, never
  rounded to bfloat16.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from dask_ml_tpu import config

N_ROWS, D, BLOCK = 2600, 8, 1024          # blocks of 1024, 1024, 552
ALPHA, L2W, L1W = 1e-3, 0.7, 0.3
SLOTS, IDX = 6, np.array([0, 2, 3, 5], np.int32)
# the loss each carry trains: every loss runs through every reader
LOSS = {"binary": "hinge", "ovr": "log_loss", "cohort": "squared_error"}


def _data(source, carry):
    rng = np.random.RandomState(3)
    if source == "sparse":
        X = sp.random(N_ROWS, 64, density=0.1, format="csr",
                      random_state=rng, dtype=np.float32)
    else:
        X = rng.randn(N_ROWS, D).astype(np.float32)
    if carry == "ovr":
        y = rng.randint(0, 3, N_ROWS).astype(np.float32)
    else:
        y = (rng.rand(N_ROWS) > 0.5).astype(np.float32)
    return X, y


def _superblocks(X, y, mesh_n):
    """The stream's super-blocks of two blocks: [b0, b1], [b2, padding]."""
    from dask_ml_tpu.parallel.streaming import BlockStream

    with config.set(stream_block_rows=BLOCK, stream_mesh=mesh_n,
                    superblock_k=2):
        stream = BlockStream((X, y), block_rows=BLOCK)
        assert stream.block_rows == BLOCK
        for sb in stream.superblocks():
            assert int(sb.counts.shape[0]) == 2
            yield sb


def _per_block(X, y, carry, W, lrs, act, iflags):
    """The per-block loop the streamed scan must reproduce."""
    from dask_ml_tpu.models.sgd import _sgd_step_many, _sgd_step_multi

    loss = LOSS[carry]
    Xd = X.toarray() if sp.issparse(X) else X
    for b, lo in enumerate(range(0, N_ROWS, BLOCK)):
        Xb, yb = jnp.asarray(Xd[lo:lo + BLOCK]), jnp.asarray(y[lo:lo + BLOCK])
        n = Xb.shape[0]
        mask, nv = jnp.ones(n, jnp.float32), jnp.float32(n)
        if carry == "ovr":
            W, _ = _sgd_step_multi(Xb, yb, mask, nv, W, lrs[b], ALPHA, L2W,
                                   L1W, iflags, loss)
            continue
        R = W.shape[0]
        W2, _ = _sgd_step_many(
            Xb, yb, mask, nv, W, jnp.broadcast_to(lrs[b], (R,)),
            jnp.full((R,), ALPHA), jnp.full((R,), L2W), jnp.full((R,), L1W),
            jnp.broadcast_to(iflags, (R,)), loss)
        W = jnp.where(act[b][:, None] > 0, W2, W)
    return np.asarray(W)


CELLS = [(source, carry, mesh_n)
         for source in ("xla", "pallas", "sparse")
         for carry in ("binary", "ovr", "cohort")
         for mesh_n in (1, 8)
         if not (source == "xla" and carry != "cohort" and mesh_n == 8)]


@pytest.mark.parametrize("source,carry,mesh_n", CELLS)
def test_scan_matches_per_block_loop(source, carry, mesh_n):
    from dask_ml_tpu.models.sgd import (_sgd_stream_program,
                                        _stream_flavor, _stream_operands)

    X, y = _data(source, carry)
    rng = np.random.RandomState(5)
    d1 = X.shape[1] + 1
    cohort = carry == "cohort"
    R = len(IDX) if cohort else 1
    lrs = rng.uniform(0.02, 0.08, (4, R)).astype(np.float32)
    act = np.ones((4, R), np.float32)
    if cohort:
        W0 = rng.randn(SLOTS, d1).astype(np.float32) * 0.1
        act[1, 1] = act[0, 3] = act[2, 2] = 0.0     # partial activity
        iflags = np.array([1.0, 0.0, 1.0, 1.0], np.float32)
        hp = tuple(jnp.full((R,), v) for v in (ALPHA, L2W, L1W)) \
            + (jnp.asarray(iflags),)
    else:
        W0 = rng.randn(*((3, d1) if carry == "ovr" else (d1,))) \
            .astype(np.float32) * 0.1
        iflags = np.float32(1.0)
        hp = tuple(jnp.float32(v) for v in (ALPHA, L2W, L1W, 1.0))
    rows = {"binary": None, "ovr": 3, "cohort": SLOTS}[carry]
    W = jnp.asarray(W0)
    n_seen = 0
    for i, sb in enumerate(_superblocks(X, y, mesh_n)):
        with config.set(pallas_stream_interpret=source == "pallas"):
            assert _stream_flavor(sb, rows, None)[0] == source
        mesh, blk, S = _stream_operands(sb)
        assert (mesh is None) == (mesh_n == 1)
        run = _sgd_stream_program(mesh, source, LOSS[carry], cohort,
                                  3 if carry == "ovr" else None,
                                  interpret=source == "pallas", S=S)
        assert run.program_name.endswith(".psum") == (mesh_n == 8)
        k = slice(2 * i, 2 * i + 2)
        if cohort:
            W, losses = run(W, blk, sb.arrays[1], sb.counts,
                            jnp.asarray(lrs[k]), *hp,
                            shard_counts=sb.shard_counts,
                            idx=jnp.asarray(IDX), act=jnp.asarray(act[k]))
            assert losses.shape == (2, R)
        else:
            W, losses = run(W, blk, sb.arrays[1], sb.counts,
                            jnp.asarray(lrs[k, 0]), *hp,
                            shard_counts=sb.shard_counts)
            assert W.shape == W0.shape and losses.shape == (2,)
        n_seen += sb.n_blocks
    assert n_seen == 3
    W = np.asarray(W)
    if cohort:
        want = _per_block(X, y, carry, jnp.asarray(W0[IDX]), lrs, act,
                          jnp.asarray(iflags))
        np.testing.assert_allclose(W[IDX], want, rtol=1e-5, atol=1e-5)
        rest = np.setdiff1d(np.arange(SLOTS), IDX)
        np.testing.assert_array_equal(W[rest], W0[rest])
        return
    W_ref = jnp.asarray(W0 if carry == "ovr" else W0[None])
    want = _per_block(X, y, carry, W_ref, lrs[:, 0], act, iflags)
    np.testing.assert_allclose(W, want.reshape(W0.shape), rtol=1e-5,
                               atol=1e-5)


def _eqns(jaxpr):
    """(equation, its jaxpr) of a jaxpr and every jaxpr nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn, jaxpr
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("cohort", [False, True])
def test_sharded_bf16_scan_gradient_is_f32_highest(cohort):
    """Under ``mxu=bfloat16`` the data-parallel dense scan differentiates
    through ``_design_matvec``: the gradient product is an f32 product at
    ``HIGHEST``, and no product's output is rounded to bfloat16."""
    from dask_ml_tpu.models.sgd import _sgd_stream_program
    from dask_ml_tpu.parallel.mesh import stream_data_mesh

    mesh = stream_data_mesh()
    assert mesh.devices.size == 8
    K, S, d, R = 2, 64, 5, 4
    run = _sgd_stream_program(mesh, "xla", "log_loss", cohort,
                              mxu=jnp.bfloat16)
    assert run.program_name == ("superblock.sgd_cohort.psum" if cohort
                                else "superblock.sgd_scan.psum")
    z = jnp.float32(0.0)
    hp = (jnp.zeros(R),) * 4 if cohort else (z,) * 4
    extra = dict(idx=jnp.arange(R, dtype=jnp.int32),
                 act=jnp.ones((K, R))) if cohort else {}
    jaxpr = jax.make_jaxpr(
        lambda W, Xs, ys, c, sc, lrs, *h: run.__wrapped__(
            W, (Xs,), ys, c, lrs, *h, shard_counts=sc, **extra)
    )(jnp.zeros((R, d + 1) if cohort else (d + 1,)),
      jnp.zeros((K, S, d)), jnp.zeros((K, S)), jnp.zeros(K, jnp.int32),
      jnp.zeros((8, K), jnp.int32),
      jnp.zeros((K, R) if cohort else (K,)), *hp).jaxpr
    eqns = list(_eqns(jaxpr))
    assert any(e.primitive.name == "shard_map" for e, _ in eqns)
    dots = [e for e, _ in eqns if e.primitive.name == "dot_general"]
    grads = [e for e in dots if e.outvars[0].aval.shape[-1] == d]
    assert grads, [str(e) for e in dots]
    for e in grads:
        assert e.outvars[0].aval.dtype == jnp.float32, str(e)
        assert "HIGHEST" in str(e.params["precision"]), str(e)
    dot_outs = {id(e.outvars[0]) for e in dots}
    rounded = [str(e) for e, _ in eqns
               if e.primitive.name == "convert_element_type"
               and e.params["new_dtype"] == jnp.bfloat16
               and id(e.invars[0]) in dot_outs]
    assert not rounded, rounded
