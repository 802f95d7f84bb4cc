"""Hierarchical span tracing.

``span(name, **attrs)`` is the ambient, nesting-aware timer the rest of
the package wraps its hot paths in (fit → epoch/pass → solve): each
closed span appends one JSONL record carrying wall time, accumulated
device-sync time (``Span.sync`` barriers), its id/parent id/depth, the
caller's attributes, and the counter deltas it caused (``ctr_*`` fields
from the registry in ``_counters``). The parent chain is per-thread, so
concurrent fits trace independent trees into the shared sink.

Sink resolution, per span open (cheap: one list peek + one config read):

1. the innermost ``active_logger`` binding OF THIS THREAD — spans
   inside a fit land in that fit's logger with its ``component``
   extras (another thread's concurrent binding is never borrowed: its
   extras would mislabel this thread's records);
2. ``config.trace_dir`` → a shared append-only ``trace.jsonl`` there;
3. ``config.metrics_path`` → the same file the step metrics use;
4. none of those set → the span is the singleton no-op: no record, no
   id allocation, no counter snapshot. The disabled path is a dict
   lookup and a None check — nothing is ever traced into jitted code.

A span that has a sink — or that is opened under ``config.obs_programs``
(the flight-recorder switch: no file, the same records) — also records
into memory and onto the profiler's timeline:

- the closed record is appended to a bounded in-process ring
  (:func:`recent_spans`, newest last; :func:`reset_recent_spans`), with
  ``root_id`` (the outermost open span of the thread: every span of one
  ``fit`` or one ``predict`` shares it) and ``t_start_ns`` /
  ``t_end_ns`` from ``time.time_ns()``;
- while it is open it holds a ``jax.profiler.TraceAnnotation`` named
  ``dmt.<span name>``, so a ``jax.profiler`` trace shows the same
  interval on a host thread's line beside the device's ``XLA Ops``.

Under ``config.obs_programs`` a span also keeps the HANDOFF LEDGER: what
the host handed the device and took back while the span was open, each
recorded where it happens and charged to the innermost open span —

- by ``track_program``'s wrapper (``Span.dispatched``): ``dispatches``,
  ``dispatch_s`` (the host's wall inside the jitted calls),
  ``host_operands`` / ``host_operand_bytes`` (the calls' array leaves that
  were host values: each is placed on the device with the dispatch);
- by ``base.to_host`` and the solvers' ``_fetch`` (``Span.fetch``):
  ``fetches``, ``fetch_bytes``, ``fetch_s`` (the wall of the whole
  device-to-host read, wait and copy; where the caller synced first the
  wait is ``sync_s`` and ``fetch_s`` the copy);
- ``host_gap_s``, the host's own count of a starved chip. Per thread,
  beside the span stack, "in flight" is set at the END of a tracked
  dispatch and cleared at the RETURN of a wait (``Span.sync``,
  ``Span.fetch``); a span's ``host_gap_s`` is the time inside it with the
  flag clear: from a root's open, or a wait's return, to the end of the
  next tracked dispatch, or to the span's close. It assumes that a wait
  drains the queue and it sees no untracked launch, so it is an upper
  bound of the chip's idle time.

The seven totals are inclusive, as ``wall_s`` is: at close a span adds its
own to its parent's, so the root record of a ``fit`` or a ``predict``
carries the call's. Each dispatch and each fetch is also a bare
``dmt.dispatch.<program>`` / ``dmt.fetch`` annotation (no ring record).
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time

import jax

from ._counters import counters_enabled, counters_snapshot
from ._metrics import thread_bound_logger

# span ids carry the pid in their high bits: config.trace_dir is a
# persistent knob and _FileSink APPENDS, so two processes recording
# into one trace.jsonl must not collide ids — the report's parent-chain
# walk (nested-of-group dedup) would silently cross runs. 16M spans per
# process before ranges could touch.
_ids = itertools.count(((os.getpid() & 0xFFFFFF) << 24) | 1)
_tls = threading.local()

# live view of every OPEN span (id -> start time/name/thread): the stall
# watchdog's working set. Maintained only on the recording path — the
# disabled (no-sink) path never touches it.
_open_lock = threading.Lock()
_open_spans: dict[int, dict] = {}

# live tracker count (armed by _watchdog.Watchdog.start/stop and by the
# telemetry plane's span observers): while a watchdog polls or a live
# observer listens, spans register in the open-span registry even when
# NO sink is configured — otherwise a run without metrics_path/
# trace_dir (bench's timed fits, a hung device init) would be
# invisible to the very threads meant to watch it. Sinkless tracked
# spans write no JSONL record; the disabled path (no sink, no tracker)
# stays the zero-cost no-op.
_armed_trackers = 0


def _track_arm(delta: int) -> None:
    global _armed_trackers
    with _open_lock:
        _armed_trackers += delta


# span-close observers (the live telemetry plane subscribes while its
# HTTP server runs): each gets the SAME record dict the sink receives —
# for sinkless tracked spans, a record without counter deltas. The list
# is empty unless something subscribed, so the default path never
# builds a record it won't use.
_span_observers: list = []


def add_span_observer(fn) -> None:
    """Subscribe ``fn(record)`` to every span close; arms span tracking
    (like a watchdog) so observers see spans even with no sink
    configured."""
    with _open_lock:
        _span_observers.append(fn)
    _track_arm(+1)


def remove_span_observer(fn) -> None:
    with _open_lock:
        try:
            _span_observers.remove(fn)
        except ValueError:
            return
    _track_arm(-1)


# closed span records of this process, newest last: what a benchmark
# reader or a notebook reaches without a file. Bounded, so a service that
# leaves obs_programs on keeps the last few hundred calls, not all.
RING_SIZE = 4096
_ring: collections.deque = collections.deque(maxlen=RING_SIZE)
ANNOTATION_PREFIX = "dmt."
# what a record folded into its ancestor's ring record leaves behind: its
# ids and close stamps are the ancestor's business (``fold_nested``)
_FOLDED_OUT = ("span_id", "parent_id", "root_id", "t_unix", "thread")


def recent_spans():
    """The last ``RING_SIZE`` closed span records, oldest first (copies of
    the list, not of the records: treat them as read-only)."""
    with _open_lock:
        return list(_ring)


def reset_recent_spans() -> None:
    with _open_lock:
        _ring.clear()


def open_spans_snapshot():
    """[{span_id, span, thread, t_open_unix, parent_id, ...}] for every
    span currently open anywhere in the process, oldest first."""
    with _open_lock:
        out = [dict(v) for v in _open_spans.values()]
    out.sort(key=lambda r: r["t_open_unix"])
    return out

# "time" origin for fallback-sink span records (relative to process
# start, matching MetricsLogger's fit-relative convention in spirit)
_T0 = time.time()
_trace_lock = threading.Lock()


def _stack():
    """This thread's open spans, outermost first."""
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current_span_id():
    """Id of the innermost open span on this thread (None outside any)."""
    st = getattr(_tls, "stack", None)
    return st[-1].span_id if st else None


def current_span():
    """The innermost open span on this thread, ``NOOP_SPAN`` outside any:
    how code below a span's ``with`` block (a solver's scalar fetch, an
    estimator's ``to_host``) charges a sync the program needs anyway to
    the phase it ends — ``current_span().sync(x)``."""
    st = getattr(_tls, "stack", None)
    return st[-1] if st else NOOP_SPAN


# -- the handoff ledger -----------------------------------------------------
# the seven inclusive totals of a span, in the order ``span._ledger`` keeps
# them; ``host_gap_s`` is the eighth attribute, a difference of _gap_until
LEDGER_KEYS = ("dispatches", "dispatch_s", "host_operands",
               "host_operand_bytes", "fetches", "fetch_bytes", "fetch_s")


def _gap_until(t):
    """Seconds up to ``t`` (``perf_counter``) in which this thread had
    nothing in flight. ``_tls.idle_t0`` is when the flag was last cleared,
    None while a dispatch is in flight."""
    idle_t0 = getattr(_tls, "idle_t0", None)
    return getattr(_tls, "gap_s", 0.0) + (
        0.0 if idle_t0 is None else t - idle_t0)


def _in_flight(t):
    """The end of a tracked dispatch: the device has work from ``t`` on."""
    _tls.gap_s = _gap_until(t)
    _tls.idle_t0 = None


def _drained(t):
    """The return of a wait: nothing is in flight from ``t`` on."""
    if getattr(_tls, "idle_t0", None) is None:
        _tls.idle_t0 = t


def _nbytes(out):
    return sum(getattr(a, "nbytes", 0)
               for a in jax.tree_util.tree_leaves(out))


class _FileSink:
    """Open-per-record append sink: no file descriptor outlives the
    write (a long-lived process tracing many distinct paths must not
    accumulate open handles), and each record gets a fresh timestamp.
    Spans are per-fit/pass frequency, so the open cost is noise."""

    __slots__ = ("path",)

    def __init__(self, path):
        self.path = path

    def log(self, **rec):
        line = json.dumps(
            {"time": round(time.time() - _T0, 6), **rec}
        ) + "\n"
        with _trace_lock, open(self.path, "a") as fh:
            fh.write(line)


def _resolve():
    """(sink, armed) for a span opened now on this thread: where its JSONL
    record goes (None: nowhere) and whether it records at all — into the
    ring and onto the profiler's timeline. Any sink arms it, and so does
    ``config.obs_programs`` alone."""
    lg = thread_bound_logger()
    if lg is not None:
        return lg, True
    from ..config import get_config

    cfg = get_config()
    if cfg.trace_dir:
        try:
            os.makedirs(cfg.trace_dir, exist_ok=True)
        except OSError:
            # unusable sink disables the record, never the fit
            return None, bool(cfg.obs_programs)
        return _FileSink(os.path.join(cfg.trace_dir, "trace.jsonl")), True
    if cfg.metrics_path:
        return _FileSink(cfg.metrics_path), True
    return None, bool(cfg.obs_programs)


def _trace_sink():
    return _resolve()[0]


class _NoopSpan:
    """Shared zero-cost stand-in when no sink is configured."""

    __slots__ = ()

    recording = False
    ledger = False

    def add(self, **attrs):
        return self

    def count(self, name, n=1):
        return self

    def sync(self, value):
        return value

    def fetch(self, read, value):
        return read(value)


NOOP_SPAN = _NoopSpan()


class span:
    """Context manager producing one nested JSONL span record.

    ``with span("fit", component="KMeans", n_rows=n) as sp:`` — the
    yielded object accepts late attributes (``sp.add(n_iter=7)``) and
    device barriers (``out = sp.sync(out)`` runs ``block_until_ready``
    and accumulates the stall into the record's ``sync_s``). With no
    sink configured the context yields the shared no-op span.

    ``fold_nested=True`` (another estimator's public call inside one
    phase: a search's refit) keeps the records of the spans opened under
    it out of the in-memory ring, which holds them instead IN this span's
    ring record, as the list ``nested`` (innermost first, without their
    ids): there the call stays one phase of its root, whose children stay
    flat. Observers and the sink see every such record as their own, and
    the ledgers add up as always.
    """

    __slots__ = ("name", "attrs", "span_id", "parent_id", "root_id",
                 "sync_s", "_sink", "_t0", "_t0_ns", "_ctr0", "_tracked",
                 "_annotation", "_ledger", "_gap0", "_fold", "_fold_into",
                 "_nested")

    def __init__(self, name, fold_nested=False, **attrs):
        self.name = name
        self.attrs = attrs
        self.sync_s = 0.0
        self._sink = None
        self._tracked = False
        self._annotation = None
        self._ledger = None
        self._fold = bool(fold_nested)
        self._fold_into = None
        self._nested = None

    @property
    def ledger(self):
        """True when this span keeps the handoff ledger (it records, and
        ``config.obs_programs`` was on when it opened)."""
        return self._ledger is not None

    @property
    def recording(self):
        """True when this span will write a record to a sink at close —
        False for spans tracked only for the watchdog (armed timeout, no
        sink) and for spans that record into the in-memory ring alone
        (``obs_programs``, no sink). The public signal call sites gate
        record-dependent work on (e.g. the stream's wait_s readiness
        syncs, which change what a timed run does and so stay off until
        someone asks for a file)."""
        return self._sink is not None

    def add(self, **attrs):
        self.attrs.update(attrs)
        return self

    def count(self, name, n=1):
        """Add ``n`` to the integer attribute ``name`` (0 where unset):
        what code under the span did so often."""
        self.attrs[name] = self.attrs.get(name, 0) + n
        return self

    def sync(self, value):
        """block_until_ready barrier whose wall time is charged to this
        span's ``sync_s`` — the honest "time the host stalled on the
        device" number under async dispatch."""
        import jax

        t0 = time.perf_counter()
        out = jax.block_until_ready(value)
        t1 = time.perf_counter()
        self.sync_s += t1 - t0
        _drained(t1)
        return out

    def dispatched(self, dt, t_end, operands, operand_bytes):
        """One tracked program call that ended at ``t_end`` after ``dt``
        seconds on the host, with so many host operands: the ledger's
        dispatch side (``track_program``'s wrapper calls it, on a span
        that keeps a ledger)."""
        _in_flight(t_end)
        led = self._ledger
        led[0] += 1
        led[1] += dt
        led[2] += operands
        led[3] += operand_bytes

    def fetch(self, read, value):
        """``read(value)``, a blocking device-to-host read the program
        makes anyway, as one fetch of the ledger: its wall is ``fetch_s``,
        what came back ``fetch_bytes``, under a bare ``dmt.fetch``
        annotation. Without a ledger it is ``read(value)``."""
        led = self._ledger
        if led is None:
            return read(value)
        with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + "fetch"):
            t0 = time.perf_counter()
            out = read(value)
            t1 = time.perf_counter()
        _drained(t1)
        led[4] += 1
        led[5] += _nbytes(out)
        led[6] += t1 - t0
        return out

    def __enter__(self):
        sink, armed = _resolve()
        if not armed and not _armed_trackers:
            return NOOP_SPAN
        # not armed but a watchdog/observer is: track the span
        # (open-span registry + span stack); close emits to observers
        # only, no record
        self._sink = sink
        self._tracked = True
        st = _stack()
        self.span_id = next(_ids)
        self.parent_id = st[-1].span_id if st else None
        self.root_id = st[0].root_id if st else self.span_id
        if st:
            parent = st[-1]
            self._fold_into = parent if parent._fold else parent._fold_into
        st.append(self)
        with _open_lock:
            _open_spans[self.span_id] = {
                "span_id": self.span_id,
                "span": self.name,
                "parent_id": self.parent_id,
                "thread": threading.current_thread().name,
                # the ident disambiguates same-named threads (every
                # ModelServer worker is "dask-ml-tpu-serving") so the
                # watchdog dumps THIS thread's stack, not a namesake's
                "thread_id": threading.get_ident(),
                "t_open_unix": time.time(),
            }
        self._ctr0 = (counters_snapshot()
                      if armed and counters_enabled() else None)
        # the two clocks are read side by side at each end, so the
        # record's wall_s and its [t_start_ns, t_end_ns] are the same
        # interval (readers subtract t_start_ns values and expect what
        # wall_s says)
        self._t0_ns = time.time_ns()
        self._t0 = time.perf_counter()
        if armed:
            from ..config import get_config

            if get_config().obs_programs:
                self._ledger = [0, 0.0, 0, 0, 0, 0, 0.0]
                if self.parent_id is None:
                    # a root's open: whatever an earlier call left in
                    # flight, its caller has waited for
                    _tls.idle_t0 = self._t0
                self._gap0 = _gap_until(self._t0)
            # the same interval on the profiler's clock: opened last and
            # closed first, so it lies inside both
            self._annotation = jax.profiler.TraceAnnotation(
                ANNOTATION_PREFIX + self.name)
            self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if not self._tracked:
            return False
        armed = self._annotation is not None
        if armed:
            self._annotation.__exit__(exc_type, exc, tb)
        t_end = time.perf_counter()
        wall = t_end - self._t0
        t_end_ns = time.time_ns()
        st = _stack()
        # pop down to (and including) OUR frame: frames above ours are
        # spans abandoned mid-block (a generator dropped between yields)
        # — leaving them would corrupt every later span's parent id
        abandoned = []
        if self in st:
            while st and st[-1] is not self:
                abandoned.append(st.pop())
            if st:
                st.pop()
        with _open_lock:
            _open_spans.pop(self.span_id, None)
            for sp in abandoned:  # their __exit__ will never run
                _open_spans.pop(sp.span_id, None)
            observers = list(_span_observers)
        for sp in abandoned:
            if sp._annotation is not None:
                sp._annotation.__exit__(None, None, None)
                sp._annotation = None   # a late __exit__ records nothing
        if not armed and not observers:
            return False  # watchdog-only tracking: no record to emit
        rec = {
            "span": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "root_id": self.root_id,
            "depth": len(st),
            # absolute close time: the relative "time" field's origin
            # differs by sink (fit logger's t0 vs process start), so
            # cross-record correlation uses this one
            "t_unix": round(time.time(), 6),
            "wall_s": round(wall, 6),
            "sync_s": round(self.sync_s, 6),
            # which OS thread closed the span — Perfetto export lanes
            # spans by it, and the watchdog correlates stall dumps to it
            "thread": threading.current_thread().name,
        }
        if exc_type is not None:
            rec["error"] = exc_type.__name__
        rec.update(self.attrs)
        led = self._ledger
        if led is not None:
            # after the caller's attributes: the ledger's names are its own
            parent = st[-1]._ledger if st else None
            if parent is not None:
                for i, v in enumerate(led):
                    parent[i] += v
            rec.update(zip(LEDGER_KEYS, (
                round(v, 6) if isinstance(v, float) else v for v in led)))
            rec["host_gap_s"] = round(_gap_until(t_end) - self._gap0, 6)
        if self._ctr0 is not None:
            now = counters_snapshot()
            for k, v in now.items():
                d = v - self._ctr0.get(k, 0)
                if d:
                    rec[f"ctr_{k}"] = round(d, 6) if isinstance(
                        d, float) else d
        if armed:
            rec["t_start_ns"] = self._t0_ns
            rec["t_end_ns"] = t_end_ns
        if armed and self._fold_into is not None:
            # the ring keeps it in the folding ancestor's record
            if self._fold_into._nested is None:
                self._fold_into._nested = []
            self._fold_into._nested.append(
                {k: v for k, v in rec.items() if k not in _FOLDED_OUT})
        elif armed:
            with _open_lock:
                _ring.append(rec if self._nested is None
                             else {**rec, "nested": self._nested})
        for fn in observers:
            # the live plane sees every closed span, recorded or not —
            # a failing observer must never surface into the fit
            try:
                fn(rec)
            except Exception:
                pass
        if self._sink is not None:
            try:
                self._sink.log(**rec)
            except Exception:
                # telemetry must never kill the fit it observes (a full
                # disk mid-run would otherwise raise out of this
                # __exit__ — replacing the in-flight exception when one
                # is unwinding)
                pass
        return False
