"""Back-compat shim: the observability subsystem grew into the
``dask_ml_tpu.observability`` package (span tracing, counters, report
CLI). Every name that ever lived here re-exports from there — including
the module-global ``_active_loggers`` sink registry, which tests bind
directly."""

from ..observability import *  # noqa: F401,F403
from ..observability import (  # noqa: F401
    _active_lock,
    _active_loggers,
)
from ..observability._metrics import _jit_step_cb  # noqa: F401
