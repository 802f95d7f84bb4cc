"""Test harness (SURVEY.md §4): run on a virtual 8-device CPU mesh so
N-way sharding logic is exercised without a pod — the analog of the
reference's in-process ``gen_cluster`` scheduler+workers."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# hermetic compiles: tests assert on compile counts, which must not
# depend on what an earlier run left in <checkout>/.jax_cache (the
# package places jax's persistent cache there at import). Set in the
# environment so spawned worker processes inherit it.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

from dask_ml_tpu._platform import force_cpu_platform  # noqa: E402

force_cpu_platform(n_devices=8)

import gc  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


try:
    with open("/proc/sys/vm/max_map_count") as _f:
        _MAX_MAPPINGS = int(_f.read())
except (OSError, ValueError):
    _MAX_MAPPINGS = 65530  # the Linux default


def _mappings():
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:  # not Linux: nothing to count, nothing to do
        return 0


@pytest.fixture(autouse=True, scope="module")
def _release_executables():
    """XLA:CPU mmaps the code of every executable it compiles (~6
    mappings each) and jax keeps them all alive in its jit caches. Run
    as ONE process, the suite compiles ~10,000 programs and walks up to
    the kernel's vm.max_map_count (65,530 here) about nine tenths of the
    way through; the next mmap fails inside LLVM and the compile dies
    with a segmentation fault, taking every later test with it. After
    any module that leaves the process past half the limit, drop jax's
    caches (the executables unmap; later modules recompile what they
    need) — and the plan layer's build cache and warmup registry with
    them, which would otherwise vouch for programs that are gone."""
    yield
    if _mappings() > _MAX_MAPPINGS // 2:
        from dask_ml_tpu.plans import plans_reset

        jax.clear_caches()
        plans_reset()
        gc.collect()


@pytest.fixture(scope="session")
def mesh():
    from dask_ml_tpu.parallel import default_mesh

    return default_mesh()


@pytest.fixture(scope="session")
def xy_classification():
    from sklearn.datasets import make_classification

    X, y = make_classification(
        n_samples=500, n_features=10, n_informative=5, random_state=0
    )
    return X.astype(np.float64), y.astype(np.float64)


@pytest.fixture(scope="session")
def xy_regression():
    from sklearn.datasets import make_regression

    X, y = make_regression(
        n_samples=500, n_features=10, n_informative=5, noise=5.0, random_state=0
    )
    return X.astype(np.float64), y.astype(np.float64)
