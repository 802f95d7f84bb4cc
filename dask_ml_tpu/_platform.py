"""Force the CPU platform with N virtual devices — what the tests and
the CPU dev loop run on (an 8-device mesh exercises N-way sharding
without chips). It must happen BEFORE anything touches a backend, so the
rule lives in one place, shared by tests/conftest.py and the smoke
scripts.
"""

import os
import re

_COUNT_FLAG = "xla_force_host_platform_device_count"


def force_cpu_platform(n_devices: int | None = None) -> None:
    """Force the CPU platform, optionally with at least ``n_devices``
    virtual devices. Must be called before any JAX backend is initialized —
    calling it later is a silent no-op on already-cached backends.

    An ambient ``--xla_force_host_platform_device_count`` in XLA_FLAGS is
    respected when it is >= n_devices and RAISED when it is smaller, so a
    caller that needs N devices actually gets N.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"  # inherited by subprocesses
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(rf"--{_COUNT_FLAG}=(\d+)", flags)
        if m is None:
            os.environ["XLA_FLAGS"] = (
                flags + f" --{_COUNT_FLAG}={n_devices}"
            ).strip()
        elif int(m.group(1)) < n_devices:
            os.environ["XLA_FLAGS"] = flags.replace(
                m.group(0), f"--{_COUNT_FLAG}={n_devices}"
            )

    import jax

    jax.config.update("jax_platforms", "cpu")
