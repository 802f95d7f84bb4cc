"""Compiled-program registry: per-program cost/memory attribution.

The observability core answers "where did the time go"; this module
answers "where did the FLOPs and HBM go". Every jitted hot path
(GLM/SGD/KMeans solver chunks, super-block scan programs, serving batch
entry points) is wrapped with :func:`track_program`, which keeps one
registry row per program name:

- ``compiles`` / ``compile_s`` — fresh XLA specializations this program
  paid (detected via jit-cache growth) and their measured compile time;
- ``flops_per_call`` / ``bytes_per_call`` — XLA's own
  ``Compiled.cost_analysis()`` for the latest specialization (measured
  program cost, not a hand-written analytic formula);
- ``hbm_peak_bytes`` (argument + output + temp) — ``memory_analysis()``
  of the latest specialization;
- ``calls`` / ``exec_s`` / ``flops_total`` — invocation accounting.
  ``exec_s`` is host-side dispatch time (no barrier is ever inserted —
  blocking would destroy the async-dispatch overlap the hot paths rely
  on): exact on the synchronous CPU backend, enqueue-only under TPU/GPU
  async dispatch. Per-span MFU (span wall + sync) is the measured
  number everywhere; the report only renders program-level MFU for cpu
  runs.

Each tracked call also feeds the flat counter registry
(``program_flops``), so span records pick up ``ctr_program_flops``
deltas and the report CLI computes **measured MFU per span** against
the peak table in ``_peak.py``.

FLOP semantics: ``cost_analysis`` counts a ``lax.scan`` body times its
(static) trip count, so super-block scan programs and fused epochs are
exact; a ``lax.while_loop`` body (the in-core solvers' outer iteration)
is counted ONCE because XLA cannot know the trip count — those
programs' flops_per_call, and any span MFU built on them, are honest
LOWER bounds (one iteration's worth per call).

Each tracked call is also one DISPATCH of the handoff ledger
(``_spans.py``): it runs under a bare ``dmt.dispatch.<program>``
annotation and charges the innermost open span with ``dispatches``,
``dispatch_s`` (the same host wall that feeds ``exec_s``),
``host_operands`` and ``host_operand_bytes`` — the call's array leaves
that are host values (numpy arrays and scalars, and Python numbers the
jit does not take as static): each is placed on the device with the
dispatch, on every device of the mesh, while the chip waits.

Gating: ``config.obs_programs`` (default OFF). Disabled, a tracked call
is one config read and a plain passthrough — nothing enters traced
code, the registry stays empty, and no extra compile ever runs. Enabled,
each fresh compile pays ONE extra AOT ``lower().compile()`` of the same
program (in-memory cached by jax thereafter) to fetch the analyses; that
extra compile also increments the ``recompiles``/``compile_secs``
counters, which is why zero-recompile perf gates keep the knob off.
"""

from __future__ import annotations

import functools
import inspect
import threading
import time

import jax
import numpy as np

from ._counters import counter_add, counters_enabled
from ._spans import ANNOTATION_PREFIX, current_span

_lock = threading.Lock()
_programs: dict[str, dict] = {}


def programs_enabled() -> bool:
    from ..config import get_config

    return bool(get_config().obs_programs)


def _entry(name: str) -> dict:
    e = _programs.get(name)
    if e is None:
        e = _programs[name] = {
            "program": name,
            "compiles": 0,
            "compile_s": 0.0,
            "calls": 0,
            "exec_s": 0.0,
            "flops_per_call": None,
            "bytes_per_call": None,
            "flops_total": 0.0,
            # warm-call slice of flops_total: the numerator matching
            # exec_s (which excludes compiling calls' wall) — the
            # program-table MFU divides these two, never
            # flops_total/exec_s (inflated by N/(N-1) at low call
            # counts)
            "flops_exec": 0.0,
            "argument_bytes": None,
            "output_bytes": None,
            "temp_bytes": None,
            "generated_code_bytes": None,
            "hbm_peak_bytes": None,
        }
    return e


def programs_snapshot() -> list[dict]:
    """Registry rows (copies), most FLOPs-total first. Rows of
    plan-built programs carry their ``plan`` (owning plan group) and
    ``ladder_rung`` attribution (ISSUE 15) so a surprise recompile
    names the ladder that minted it."""
    with _lock:
        rows = [{k: v for k, v in e.items() if not k.startswith("_")}
                for e in _programs.values()]
    try:
        from ..plans.plan import annotate_programs

        annotate_programs(rows)
    except Exception:  # pragma: no cover - attribution never breaks it
        pass
    rows.sort(key=lambda e: -(e["flops_total"] or 0.0))
    return rows


def programs_reset() -> None:
    with _lock:
        _programs.clear()


def unwrap(fn):
    """Innermost callable under any stack of trackers/jits — the raw
    Python body super-block reducers lift into their scans."""
    while hasattr(fn, "__wrapped__"):
        fn = fn.__wrapped__
    return fn


def _abstractify(x):
    """Concrete leaf -> ShapeDtypeStruct so the analysis lowering never
    touches buffers (tracked programs donate their carries — the data is
    gone by the time the post-call analysis runs; shape/dtype/sharding
    metadata survives deletion). The sharding rides along where the leaf
    has one: without it, an SPMD program would be re-lowered as the
    unsharded replicated specialization, misreporting per-device HBM
    (~n_devices too high) and timing a compile the workload never ran."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        import jax

        try:
            sharding = getattr(x, "sharding", None)
            # MULTI-device shardings only: a single-device sharding on
            # an uncommitted leaf (solver carries, host-built scalars)
            # would be treated as committed by the lowering and clash
            # with the data's mesh ("incompatible devices"); the real
            # call left those leaves free to be placed, so the analysis
            # must too
            if sharding is not None and len(sharding.device_set) <= 1:
                sharding = None
        except Exception:
            sharding = None
        if sharding is not None:
            try:
                return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                            sharding=sharding)
            except Exception:
                pass  # exotic sharding object: fall back unsharded
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


def _shape_key(args, kwargs):
    """Hashable signature of one call's argument shapes/dtypes (array
    metadata survives donation). None when any leaf is unhashable."""
    import jax

    leaves = jax.tree_util.tree_leaves((args, kwargs))
    try:
        return hash(tuple(
            (tuple(x.shape), str(x.dtype))
            if hasattr(x, "shape") and hasattr(x, "dtype") else x
            for x in leaves
        ))
    except TypeError:
        return None


def _static_params(fn):
    """(every parameter name in order, the names ``fn``'s jit takes as
    static), from the jit's own record; nothing static where ``fn`` keeps
    none (not a ``jax.jit``)."""
    info = getattr(fn, "_jit_info", None)
    if info is None:
        return (), frozenset()
    try:
        params = tuple(inspect.signature(fn).parameters)
    except (TypeError, ValueError):
        params = ()
    static = set(info.static_argnames or ())
    static.update(params[i] for i in info.static_argnums or ()
                  if -len(params) <= i < len(params))
    return params, frozenset(static)


_HOST_NUMBERS = (bool, int, float, complex, np.generic, np.ndarray)


def _host_operands(args, kwargs, params, static):
    """(count, bytes) of one call's operands that cross from the host with
    the dispatch: the array leaves of its non-static arguments that are not
    ``jax.Array`` — numpy arrays and scalars, Python numbers."""
    dynamic = [a for i, a in enumerate(args)
               if i >= len(params) or params[i] not in static]
    dynamic += [v for k, v in kwargs.items() if k not in static]
    count = nbytes = 0
    for x in jax.tree_util.tree_leaves(dynamic):
        if isinstance(x, _HOST_NUMBERS):    # a jax.Array is none of them
            count += 1
            nbytes += x.nbytes if hasattr(x, "nbytes") else \
                jax.dtypes.canonicalize_dtype(type(x)).itemsize
    return count, nbytes


def _cost_dict(compiled):
    return compiled.cost_analysis() or {}


def _analyze(name: str, fn, args, kwargs, skey=None, by_shape=None) -> None:
    """AOT-lower the program at the shapes just called and record XLA's
    cost/memory analysis + the measured compile time. Never raises —
    attribution must not kill the fit it observes."""
    import jax

    try:
        abs_args = jax.tree.map(_abstractify, args)
        abs_kwargs = jax.tree.map(_abstractify, kwargs)
        t0 = time.perf_counter()
        compiled = fn.lower(*abs_args, **abs_kwargs).compile()
        compile_s = time.perf_counter() - t0
        cost = _cost_dict(compiled)
        mem = compiled.memory_analysis()
        flops = cost.get("flops")
        nbytes = cost.get("bytes accessed")
        arg_b = getattr(mem, "argument_size_in_bytes", None)
        out_b = getattr(mem, "output_size_in_bytes", None)
        tmp_b = getattr(mem, "temp_size_in_bytes", None)
        code_b = getattr(mem, "generated_code_size_in_bytes", None)
    except Exception:
        with _lock:
            e = _entry(name)
            e["compiles"] += 1
        return
    with _lock:
        e = _entry(name)
        e["compiles"] += 1
        e["compile_s"] += compile_s
        if flops is not None:
            e["flops_per_call"] = float(flops)
            if skey is not None and by_shape is not None:
                by_shape[skey] = float(flops)
        if nbytes is not None:
            e["bytes_per_call"] = float(nbytes)
        for key, v in (("argument_bytes", arg_b), ("output_bytes", out_b),
                       ("temp_bytes", tmp_b),
                       ("generated_code_bytes", code_b)):
            if v is not None:
                e[key] = int(v)
        known = [v for v in (arg_b, out_b, tmp_b) if v is not None]
        if known:
            e["hbm_peak_bytes"] = int(sum(known))


def track_program(name: str):
    """Decorator registering a jitted callable in the program registry.

    Stacks OUTSIDE ``jax.jit`` (``track_program(n)(jax.jit(f))``); the
    wrapper never enters traced code. ``__wrapped__`` is pinned to the
    innermost raw function so existing ``.__wrapped__`` unwraps (the
    super-block reducers lift block-kernel bodies into scans) keep
    working; the jitted callable stays reachable as ``__wrapped_jit__``.
    """

    def deco(fn):
        cache_size = getattr(fn, "_cache_size", None)
        # per-specialization cost, PER WRAPPED CALLABLE: one program
        # name may cover several distinct jits (lru-cached reducer
        # flavors, multiple fitted estimators of one class) — a shared
        # per-name map would let one variant's analysis overwrite
        # another's at the same shapes and credit the wrong kernel
        by_shape: dict = {}
        params, static = _static_params(fn)
        annotation = ANNOTATION_PREFIX + "dispatch." + name

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            if not programs_enabled():
                return fn(*args, **kwargs)
            before = None
            if cache_size is not None:
                try:
                    before = cache_size()
                except Exception:
                    before = None
            with jax.profiler.TraceAnnotation(annotation):
                t0 = time.perf_counter()
                out = fn(*args, **kwargs)
                t1 = time.perf_counter()
            dt = t1 - t0
            sp = current_span()
            if sp.ledger:
                sp.dispatched(
                    dt, t1, *_host_operands(args, kwargs, params, static))
            skey = _shape_key(args, kwargs)
            grew = False
            if before is not None:
                try:
                    grew = cache_size() > before
                except Exception:
                    grew = False
                if grew:
                    _analyze(name, fn, args, kwargs, skey=skey,
                             by_shape=by_shape)
            with _lock:
                e = _entry(name)
                e["calls"] += 1
                # a compiling call's wall is trace+compile, not
                # execution (and compile_s already records it) — only
                # warm calls accumulate exec_s
                if not grew:
                    e["exec_s"] += dt
                # credit THIS call's specialization; one program name
                # spans many shapes (serving bucket grid). A shape whose
                # analysis failed credits NOTHING — borrowing another
                # shape's cost would silently skew flops_total and every
                # MFU built on it. (skey None = unhashable leaves: the
                # latest analysis is the only estimate available.)
                flops = by_shape.get(skey) if skey is not None \
                    else e["flops_per_call"]
                if flops:
                    e["flops_total"] += flops
                    if not grew:
                        e["flops_exec"] += flops
            if flops and counters_enabled():
                counter_add("program_flops", flops)
            return out

        # preserve the raw-body unwrap call sites rely on, and keep the
        # jit object reachable for AOT/introspection
        wrapped.__wrapped__ = unwrap(fn)
        wrapped.__wrapped_jit__ = fn
        if cache_size is not None:
            wrapped._cache_size = cache_size
        wrapped.program_name = name
        return wrapped

    return deco


def log_programs(logger, peak=True, **extra) -> list[dict]:
    """Emit one JSONL record holding the program registry snapshot (plus
    the device's published peak when ``peak`` and the device has one, so
    an offline report can compute MFU); returns the snapshot. The report CLI reads the LAST
    such record as the run's programs table."""
    snap = programs_snapshot()
    if logger is None:
        return snap
    rec = {"programs": snap}
    # the plans table rides the same record (ISSUE 15): which plan /
    # ladder rung minted each warmed specialization
    try:
        from ..plans import plans_snapshot

        plrows = plans_snapshot()
    except Exception:
        plrows = None
    if plrows:
        rec["plans"] = plrows
    if peak:
        import jax

        from ._peak import PEAKS, peak_for

        # a device without a published peak (any CPU run) records none:
        # the report then skips its MFU columns ("not measured")
        kind = jax.devices()[0].device_kind
        if kind in PEAKS:
            pk = peak_for(kind)
            rec.update(
                peak_flop_per_s_per_chip=pk["flops"],
                peak_source=pk["source"],
                device_kind=kind,
                n_chips=len(jax.local_devices()),
            )
    logger.log(**rec, **extra)
    return snap
