"""BlockStream overlap instrumentation + epoch-boundary block autotune
(VERDICT r4 weak #2 / next-round #7): the double buffer is measured, not
assumed, and transfer-dominated epochs grow their blocks."""

import numpy as np
import pytest

import dask_ml_tpu.config as config
from dask_ml_tpu.parallel.streaming import BlockStream

X = np.random.RandomState(0).rand(4096, 8).astype(np.float32)


def test_pass_stats_populated():
    stream = BlockStream((X,), block_rows=256)
    for blk in stream:
        pass
    st = stream.stats
    for key in ("host_s", "put_s", "wait_s", "consume_s", "pass_s",
                "n_blocks", "block_rows"):
        assert key in st, key
    assert st["n_blocks"] == stream.n_blocks
    assert st["pass_s"] > 0

def test_autotune_grows_transfer_bound_blocks():
    # no compute at all between blocks: moving time dominates, and with
    # 32 blocks the autotune has room to double (twice at most)
    stream = BlockStream((X,), block_rows=128)
    assert stream.n_blocks == 32
    for blk in stream.epochs(3, autotune=True):
        pass
    assert stream.block_rows > 128
    assert stream.n_blocks < 32


def test_autotune_respects_flag_and_small_streams():
    s1 = BlockStream((X,), block_rows=128)
    for blk in s1.epochs(3, autotune=False):
        pass
    assert s1.block_rows == 128
    # <16 blocks: never resized even when transfer-bound
    s2 = BlockStream((X,), block_rows=512)
    assert s2.n_blocks == 8
    for blk in s2.epochs(3, autotune=True):
        pass
    assert s2.block_rows == 512


def test_plain_iteration_never_resizes():
    # per-block solver state (ADMM) iterates the stream directly; the
    # partition must be stable across passes
    stream = BlockStream((X,), block_rows=128)
    for _ in range(3):
        for blk in stream:
            pass
    assert stream.block_rows == 128
    assert stream.n_blocks == 32


def test_all_rows_seen_after_resize():
    stream = BlockStream((X,), block_rows=128)
    seen = 0
    for blk in stream.epochs(3, autotune=True):
        seen += blk.n_rows
    assert seen == 3 * len(X)  # every epoch covers every row exactly


def test_grid_partition_single_device():
    """A 1-device mesh must still yield multiple minibatch steps per
    epoch — a D-only split once collapsed host fits to one block."""
    from dask_ml_tpu.parallel.streaming import grid_partition

    B, S = grid_partition(100_000, 1)
    assert B >= 8
    assert S * B >= 100_000
    B8, S8 = grid_partition(100_000, 8)
    assert B8 == 8 and S8 == 12504  # unchanged on the 8-device mesh


def test_wait_measured_only_when_consumed(monkeypatch):
    """No logger bound and no autotune: the readiness sync (which costs
    overlap) is skipped; wait_s stays zero."""
    stream = BlockStream((X,), block_rows=256)
    for blk in stream:
        pass
    assert stream.stats["wait_s"] == 0.0
    for blk in stream.epochs(2, autotune=True):
        pass
    assert "wait_s" in stream.stats  # measured (possibly ~0) when tuning


def test_config_env_parsing(monkeypatch):
    monkeypatch.setenv("DASK_ML_TPU_STREAM_BLOCK_ROWS", "123")
    monkeypatch.setenv("DASK_ML_TPU_STREAM_AUTOTUNE", "false")
    cfg = config._from_env()
    assert cfg.stream_block_rows == 123
    assert cfg.stream_autotune is False


def test_stats_logged_to_ambient_logger(tmp_path):
    import json

    from dask_ml_tpu.utils.observability import MetricsLogger, active_logger

    path = tmp_path / "m.jsonl"
    with MetricsLogger(str(path)) as lg, active_logger(lg):
        stream = BlockStream((X,), block_rows=512)
        for blk in stream:
            pass
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert any("stream_pass" in r for r in recs)


# -- one staging path: ring slabs, native readers, one worker thread --------
# (stream_mesh=1 below: the single-device stream is where a source could
# once be imported as an alias instead of staged)

def _aligned_copy(a, lead_bytes=0):
    """``a`` copied into memory whose base address is ``lead_bytes`` past
    a 64-byte boundary (0: what XLA:CPU's device_put aliases)."""
    raw = np.empty(a.size + 32, a.dtype)
    lead = ((-raw.ctypes.data) % 64 + lead_bytes) // a.itemsize
    out = raw[lead:lead + a.size].reshape(a.shape)
    out[...] = a
    assert out.ctypes.data % 64 == lead_bytes
    return out


def _sgd_fit(Xs, ys, block_rows=512):
    from dask_ml_tpu import observability as obs
    from dask_ml_tpu.models.sgd import SGDClassifier

    with config.set(stream_block_rows=block_rows, stream_mesh=1):
        obs.counters_reset()
        clf = SGDClassifier(max_iter=2, random_state=0,
                            shuffle=False).fit(Xs, ys)
        return clf, obs.counters_snapshot()


Y = (X[:, 0] > 0.5).astype(np.float32)


def test_readonly_memmap_fit_equals_in_memory_and_reads_natively(tmp_path):
    from dask_ml_tpu.io.native import native_available
    from dask_ml_tpu.linear_model import LogisticRegression

    if not native_available():
        pytest.skip("native toolchain unavailable")
    path = str(tmp_path / "x.f32")
    mm = np.memmap(path, dtype=np.float32, mode="w+", shape=X.shape)
    mm[:] = X
    mm.flush()
    del mm
    Xr = np.memmap(path, dtype=np.float32, mode="r", shape=X.shape)
    assert not Xr.flags.writeable

    def fit(Xs):
        with config.set(stream_block_rows=512, stream_mesh=1):
            return LogisticRegression(solver="lbfgs", max_iter=5).fit(Xs, Y)

    on_disk, in_mem = fit(Xr), fit(X.copy())
    st = on_disk._last_stream_stats
    assert st["native_reader"] is True and st["superblock_k"] > 1
    assert in_mem._last_stream_stats["native_reader"] is False
    np.testing.assert_array_equal(on_disk.coef_, in_mem.coef_)
    np.testing.assert_array_equal(on_disk.intercept_, in_mem.intercept_)


@pytest.mark.parametrize("source", ["aligned", "misaligned", "fortran"])
def test_every_source_layout_stages_the_same_slabs(source):
    """A source's address and memory order decide nothing: every pass
    copies it into the ring's (K, rows, d) slabs, so the fit and the
    bytes put on the device are the same."""
    Xs = {"aligned": lambda: _aligned_copy(X),
          "misaligned": lambda: _aligned_copy(X, lead_bytes=4),
          "fortran": lambda: np.asfortranarray(X)}[source]()
    ref, _ = _sgd_fit(X.copy(), Y)
    clf, snap = _sgd_fit(Xs, Y)
    np.testing.assert_array_equal(clf.coef_, ref.coef_)
    st = clf._last_stream_stats
    k, rows = st["superblock_k"], st["block_rows"]
    slab_bytes = k * rows * X.shape[1] * 4 + k * rows * 4 + k * 4
    passes = 2
    assert snap["h2d_bytes"] == passes * st["dispatches_per_pass"] * slab_bytes


def test_a_staged_superblock_is_a_copy_of_the_source():
    """No alias of user memory outlives ``fill``: rows overwritten after a
    super-block was staged do not reach the held super-block, and do
    reach the next pass."""
    Xs = _aligned_copy(X)
    with config.set(stream_mesh=1):
        stream = BlockStream((Xs,), block_rows=256)
        k = stream.resolve_superblock_k()
        assert k > 1
        passes = stream.superblocks()
        held = next(passes)
        Xs[: k * 256] = np.nan
        assert np.isfinite(np.asarray(held.arrays[0])).all()
        passes.close()
        again = next(iter(stream.superblocks()))
        assert np.isnan(np.asarray(again.arrays[0])).all()
