"""ctypes bindings for the native helpers (native/*.cpp): the
multithreaded CSV parser and the readahead block reader that feeds
``parallel/streaming.BlockStream``.

Each library is compiled on first use with g++ (the image has the
toolchain but no pybind11) from the source IN THIS TREE into
``native/_build/<name>-<sha256 of source + flags>.so``: a binary is only
ever loaded under the hash of the source it was built from, so a stale
or foreign ``.so`` lying in the tree can never be picked up, and an
edited source rebuilds whatever the mtimes say. A host without g++ has
no native helpers (``native_available()`` is False and callers take
their numpy path, on record); a compile or dlopen that FAILS raises.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import tempfile
import threading

import numpy as np

_lock = threading.Lock()
_libs: dict = {}

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native")
_CXX = "g++"
_CXXFLAGS = ("-O3", "-shared", "-fPIC", "-std=c++17", "-pthread")


def native_available() -> bool:
    """Whether this host can build the native helpers at all."""
    return shutil.which(_CXX) is not None


def _build_and_load(name, configure):
    """The configured library for ``native/<name>.cpp``, compiled into
    its content-hashed path when that path does not exist yet; None on
    a host without a C++ compiler. Build and dlopen errors propagate."""
    if not native_available():
        return None
    with _lock:
        lib = _libs.get(name)
        if lib is not None:
            return lib
        src = os.path.join(_NATIVE_DIR, name + ".cpp")
        with open(src, "rb") as f:
            digest = hashlib.sha256(
                f.read() + " ".join(_CXXFLAGS).encode()
            ).hexdigest()[:16]
        build_dir = os.path.join(_NATIVE_DIR, "_build")
        so = os.path.join(build_dir, f"{name}-{digest}.so")
        if not os.path.exists(so):
            os.makedirs(build_dir, exist_ok=True)
            # compile beside the target and rename: a concurrent builder
            # (two processes importing at once) never sees a half-written
            # library under the final name
            fd, tmp = tempfile.mkstemp(suffix=".so", dir=build_dir)
            os.close(fd)
            try:
                proc = subprocess.run(
                    [_CXX, *_CXXFLAGS, "-o", tmp, src],
                    capture_output=True, text=True,
                )
                if proc.returncode != 0:
                    raise RuntimeError(
                        f"building {src} failed "
                        f"(exit {proc.returncode}):\n{proc.stderr}"
                    )
                os.replace(tmp, so)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        lib = ctypes.CDLL(so)
        configure(lib)
        _libs[name] = lib
        return lib


def _configure_fast_loader(lib):
    lib.csv_dims.restype = ctypes.c_int64
    lib.csv_dims.argtypes = [ctypes.c_char_p,
                             ctypes.POINTER(ctypes.c_int64)]
    lib.csv_parse_f32.restype = ctypes.c_int64
    lib.csv_parse_f32.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
    ]


def load_library():
    """The CSV parser library (built on first use); None on a host
    without a C++ compiler."""
    return _build_and_load("fast_loader", _configure_fast_loader)


def read_csv_f32(path, n_threads=None) -> np.ndarray:
    """Parse a numeric CSV (comma/space/tab separated, no header) into a
    float32 array with the native multithreaded parser (numpy's text
    parser on a host without a C++ compiler)."""
    path = os.path.abspath(path)
    lib = load_library()
    if lib is None:
        return np.loadtxt(path, delimiter=",", dtype=np.float32, ndmin=2)
    if n_threads is None:
        n_threads = min(os.cpu_count() or 1, 16)
    n_cols = ctypes.c_int64(0)
    n_rows = lib.csv_dims(path.encode(), ctypes.byref(n_cols))
    if n_rows < 0:
        raise IOError(f"cannot read {path!r} (code {n_rows})")
    out = np.empty((n_rows, n_cols.value), np.float32)
    got = lib.csv_parse_f32(
        path.encode(), out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n_rows, n_cols.value, n_threads,
    )
    if got < 0:
        raise ValueError(
            f"malformed CSV {path!r} (code {got}); expected "
            f"{n_cols.value} numeric columns per row"
        )
    return out[:got]


def read_csv_sharded(path, mesh=None, n_threads=None):
    """CSV straight onto the mesh: native parse -> ShardedArray."""
    from ..parallel.sharded import as_sharded

    return as_sharded(read_csv_f32(path, n_threads=n_threads), mesh=mesh)


# -- native block reader (native/block_reader.cpp) --------------------------

def _configure_block_reader(lib):
    lib.br_open.restype = ctypes.c_void_p
    lib.br_open.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
    ]
    lib.br_next.restype = ctypes.c_int64
    lib.br_next.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.br_close.restype = None
    lib.br_close.argtypes = [ctypes.c_void_p]


def load_block_reader():
    """The threaded-readahead reader library (built on first use); None
    on a host without a C++ compiler."""
    return _build_and_load("block_reader", _configure_block_reader)


class NativeBlockReader:
    """Sequential fixed-size row blocks of a memmap-backed file, read
    AHEAD by a C++ thread into a buffer ring (native/block_reader.cpp) —
    disk latency overlaps the previous block's device_put + compute even
    with a cold page cache."""

    def __init__(self, mm: np.memmap, block_rows: int, depth: int = 2):
        lib = load_block_reader()
        if lib is None:
            raise RuntimeError("native block reader unavailable")
        self._lib = lib
        self._shape_tail = mm.shape[1:]
        self._dtype = mm.dtype
        row_items = int(np.prod(self._shape_tail, dtype=np.int64) or 1)
        self._row_bytes = int(mm.dtype.itemsize) * row_items
        self._block_rows = int(block_rows)
        self.n_rows = int(mm.shape[0])
        self._buf = np.empty((self._block_rows,) + tuple(self._shape_tail),
                             mm.dtype)
        self._h = lib.br_open(
            str(mm.filename).encode(), int(mm.offset), self._row_bytes,
            self.n_rows, self._block_rows, int(depth),
        )
        if not self._h:
            raise RuntimeError(f"br_open failed for {mm.filename}")

    def next(self):
        """Next block as an ndarray VIEW of the internal buffer (valid
        until the following call), or None at end-of-stream."""
        rows = self._lib.br_next(
            self._h, self._buf.ctypes.data_as(ctypes.c_char_p)
        )
        if rows < 0:
            raise IOError("native block reader failed mid-stream")
        if rows == 0:
            return None
        return self._buf[: int(rows)]

    def close(self):
        if getattr(self, "_h", None):
            self._lib.br_close(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover - GC path
        try:
            self.close()
        except Exception:
            pass


# -- numpy's legacy shuffle, sooner (native/legacy_shuffle.cpp) --------------

def _configure_legacy_shuffle(lib):
    lib.legacy_shuffle_i32.restype = None
    lib.legacy_shuffle_i32.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_int32),
    ]


def legacy_shuffle(rng, x):
    """``rng.shuffle(x)`` for a ``np.random.RandomState`` and a
    one-dimensional array, in place: the same permutation and the same
    generator state afterwards. A contiguous int32 ``x`` takes the native
    loop (the draws a batch ahead, their targets prefetched); anything
    else, or a host without a compiler, is numpy's own call."""
    lib = _build_and_load("legacy_shuffle", _configure_legacy_shuffle) \
        if (isinstance(x, np.ndarray) and x.ndim == 1
            and x.dtype == np.int32 and x.flags.c_contiguous
            and x.flags.writeable) else None
    state = rng.get_state() if lib is not None else None
    if state is None or state[0] != "MT19937":
        rng.shuffle(x)
        return
    key = np.ascontiguousarray(state[1], np.uint32).copy()
    pos = ctypes.c_int32(int(state[2]))
    lib.legacy_shuffle_i32(
        x.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), int(x.shape[0]),
        key.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        ctypes.byref(pos))
    rng.set_state((state[0], key, int(pos.value)) + tuple(state[3:]))
