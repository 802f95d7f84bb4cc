"""Native loader tests (native/fast_loader.cpp via ctypes)."""

import numpy as np
import pytest

from dask_ml_tpu.io import load_library, read_csv_f32, read_csv_sharded


def test_native_library_builds():
    assert load_library() is not None, "g++ build of fast_loader failed"


def test_read_csv_matches_numpy(tmp_path):
    rng = np.random.RandomState(0)
    X = rng.randn(1000, 7).astype(np.float32)
    p = tmp_path / "data.csv"
    np.savetxt(p, X, delimiter=",", fmt="%.6f")
    got = read_csv_f32(str(p))
    ref = np.loadtxt(p, delimiter=",", dtype=np.float32, ndmin=2)
    assert got.shape == ref.shape
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_read_csv_multithreaded_consistent(tmp_path):
    rng = np.random.RandomState(1)
    X = rng.randn(5000, 3).astype(np.float32)
    p = tmp_path / "big.csv"
    np.savetxt(p, X, delimiter=",", fmt="%.5f")
    a = read_csv_f32(str(p), n_threads=1)
    b = read_csv_f32(str(p), n_threads=8)
    np.testing.assert_array_equal(a, b)


def test_read_csv_malformed(tmp_path):
    p = tmp_path / "bad.csv"
    p.write_text("1.0,2.0\n3.0\n")
    with pytest.raises(ValueError, match="malformed"):
        read_csv_f32(str(p))


def test_read_csv_missing():
    with pytest.raises(IOError):
        read_csv_f32("/nonexistent/file.csv")


def test_read_csv_sharded(tmp_path):
    X = np.arange(24, dtype=np.float32).reshape(12, 2)
    p = tmp_path / "s.csv"
    np.savetxt(p, X, delimiter=",", fmt="%.1f")
    sx = read_csv_sharded(str(p))
    np.testing.assert_allclose(sx.to_numpy(), X)


def test_native_block_reader_matches_numpy(tmp_path):
    """The C++ readahead reader yields byte-identical blocks to numpy
    slicing, including the ragged tail, and BlockStream picks it for
    sequential memmap passes."""
    import numpy as np

    from dask_ml_tpu.io.native import NativeBlockReader, load_block_reader
    from dask_ml_tpu.parallel.streaming import BlockStream

    if load_block_reader() is None:
        import pytest

        pytest.skip("native toolchain unavailable")
    rng = np.random.RandomState(0)
    X = rng.randn(1003, 7).astype(np.float32)
    path = str(tmp_path / "X.f32")
    mm = np.memmap(path, dtype=np.float32, mode="w+", shape=X.shape)
    mm[:] = X
    mm.flush()
    mm = np.memmap(path, dtype=np.float32, mode="r", shape=X.shape)

    r = NativeBlockReader(mm, block_rows=100)
    got = []
    while True:
        blk = r.next()
        if blk is None:
            break
        got.append(blk.copy())
    r.close()
    np.testing.assert_array_equal(np.concatenate(got), X)

    # BlockStream parity: native path (sequential) == numpy slicing
    stream = BlockStream((mm,), block_rows=96)
    assert stream._native_plan() == ([True], None)
    blocks = [np.asarray(b.arrays[0])[: b.n_rows] for b in stream]
    np.testing.assert_allclose(np.concatenate(blocks), X, rtol=1e-6)
    assert stream.stats["native_reader"] is True
    assert stream.stats["native_reader_reason"] is None

    # sliced memmap views (offset no longer authoritative) are detected
    # by the block-0 verification and fall back to numpy slicing
    view = mm[100:]
    s2 = BlockStream((view,), block_rows=96)
    assert s2._native_plan() == ([False], "memmap-view-offset")
    blocks2 = [np.asarray(b.arrays[0])[: b.n_rows] for b in s2]
    np.testing.assert_allclose(np.concatenate(blocks2), X[100:], rtol=1e-6)
    assert s2.stats["native_reader"] is False
    assert s2.stats["native_reader_reason"] == "memmap-view-offset"


def test_native_build_is_content_keyed_and_errors_surface(tmp_path,
                                                          monkeypatch):
    """A library loads only from the path keyed by its source's hash; a
    source that does not compile raises every time it is asked for
    (no latch onto a Python path), and nothing is left half-built."""
    import os
    import shutil

    from dask_ml_tpu.io import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable")
    real = native._NATIVE_DIR
    shutil.copy(os.path.join(real, "fast_loader.cpp"), tmp_path)
    monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_libs", {})
    assert native.load_library() is not None
    built = os.listdir(tmp_path / "_build")
    assert len(built) == 1 and built[0].startswith("fast_loader-")

    (tmp_path / "fast_loader.cpp").write_text("this is not C++\n")
    monkeypatch.setattr(native, "_libs", {})
    for _ in range(2):
        with pytest.raises(RuntimeError, match="building .*fast_loader"):
            native.load_library()
    assert os.listdir(tmp_path / "_build") == built


@pytest.mark.slow
def test_streamed_fit_with_native_reader(tmp_path):
    """End-to-end: an out-of-core GLM fit through the native readahead
    path matches the in-memory fit."""
    import numpy as np

    from dask_ml_tpu import config
    from dask_ml_tpu.io.native import load_block_reader
    from dask_ml_tpu.linear_model import LinearRegression

    if load_block_reader() is None:
        import pytest

        pytest.skip("native toolchain unavailable")
    rng = np.random.RandomState(1)
    X = rng.randn(2400, 6).astype(np.float32)
    w = rng.randn(6)
    y = (X @ w + 0.3).astype(np.float32)
    path = str(tmp_path / "Xn.f32")
    mm = np.memmap(path, dtype=np.float32, mode="w+", shape=X.shape)
    mm[:] = X
    mm.flush()
    mm = np.memmap(path, dtype=np.float32, mode="r", shape=X.shape)

    ref = LinearRegression(solver="lbfgs", max_iter=60, tol=1e-7).fit(X, y)
    with config.set(stream_block_rows=500):
        streamed = LinearRegression(solver="lbfgs", max_iter=60,
                                    tol=1e-7).fit(mm, y)
    np.testing.assert_allclose(streamed.coef_, ref.coef_, rtol=1e-2,
                               atol=1e-3)
