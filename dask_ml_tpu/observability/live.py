"""Live telemetry plane: metric registry + /metrics + /status exporter.

Everything before this module was post-hoc: spans, counters and program
rows land in JSONL and are read AFTER the run by the report CLI. A
production serving system (ROADMAP north star) is scraped LIVE — when a
fit wedges or a server sheds load, the operator curls an endpoint while
it happens instead of tailing a trace after the kill. This module is
that plane:

- a process-wide **metric registry** unifying three kinds of signal:
  the existing flat counter registry (``_counters``), new **gauges**
  (last-value-wins: fit progress, queue depth, inflight rows), and
  log-spaced **histograms** (``_hist``: serving latency per
  (method, bucket), fit pass seconds);
- a background :class:`TelemetryServer` — stdlib ``http.server`` on a
  daemon thread, armed by ``config.obs_http_port`` (0 = off =
  the pre-existing zero-overhead path), serving

  ========== =============================================
  endpoint   content
  ========== =============================================
  /metrics   Prometheus text exposition v0.0.4 (counters,
             gauges, histograms)
  /healthz   liveness (200 "ok" even mid-stall — the
             server thread never touches the device)
  /status    JSON: open-span stack, recent-span report
             (program/span/counter tables via
             ``report.report_data``), serving windows,
             watchdog stalls
  ========== =============================================

- **fit progress publication** with zero new device syncs: a span-close
  observer (``_spans.add_span_observer``) turns the pass records the
  streamed fits already emit (``stream_pass`` / ``n_rows`` / ``pass_s``
  — host floats) into ``fit_pass`` / ``fit_rows_per_sec`` /
  ``fit_eta_seconds`` gauges and a ``fit_pass_seconds`` histogram;
  solvers with host-resident loss publish ``fit_loss`` the same way.

Overhead contract: with the port unset nothing here ever runs — no
observer is registered, every ``publish_*`` call is one module-global
bool check, no thread exists, and no jaxpr changes (asserted in
``tests/test_observability.py``). The scrape path reads pure host
dicts: serving a request can never trigger an XLA compile (asserted by
``tests/test_live_telemetry.py`` via the recompile counter).
"""

from __future__ import annotations

import http.server
import json
import math
import os
import re
import threading
import time
from collections import deque

from ._counters import counters_snapshot
from ._hist import DEFAULT_BOUNDS, Histogram
from ._spans import add_span_observer, open_spans_snapshot, \
    remove_span_observer

__all__ = [
    "TelemetryServer", "ensure_telemetry", "stop_telemetry",
    "telemetry_server", "live_publishing", "gauge_set", "gauges_snapshot",
    "histogram", "histograms_snapshot", "drop_labeled_series",
    "render_prometheus",
    "status_data", "fleet_status_data", "publish_progress", "note_stall",
    "register_server", "unregister_server", "register_registry",
    "register_fleet_provider", "unregister_fleet_provider",
]

_PREFIX = "dask_ml_tpu_"
_T0 = time.time()

# -- registry ----------------------------------------------------------------
# Counters stay in _counters (the span-delta / report machinery reads
# them there); this module adds the other two metric kinds and the one
# exposition view over all three.

_lock = threading.Lock()
_gauges: dict[tuple, float] = {}          # (name, labels) -> value
_hists: dict[tuple, Histogram] = {}       # (name, labels) -> Histogram
# labeled-series count per family name: the cardinality guard's ledger.
# Per-feature drift gauges (and any future labeled family) could mint
# unbounded series from unbounded label values; past
# config.obs_max_series new labeled children of a family are DROPPED
# and counted (telemetry_series_dropped_total) instead of growing the
# registry without bound. Unlabeled series are never capped.
_family_series: dict[str, int] = {}
# shared sink for rejected histogram series: callers still get a
# working Histogram, its observations just never render
_overflow_hist: Histogram | None = None
# series keys already rejected by the cap: the drop counter counts
# DROPPED SERIES, not rejected writes — a publisher re-setting the same
# over-cap gauge every monitor tick must not inflate it forever (and
# the known-rejected path must stay one set lookup, no config read)
_dropped_series: set = set()

# recent closed-span records (the observer feeds it while a server is
# live): /status renders them through report.report_data so the live
# view and the post-hoc CLI agree on shape
_recent_spans: deque = deque(maxlen=256)
# recent watchdog stall dumps (fed by _watchdog._report)
_recent_stalls: deque = deque(maxlen=8)

# live ModelServer instances (weakly referenced): /status lists their
# stats() windows
_servers: "weakref.WeakSet" = None  # type: ignore[name-defined]


def _server_set():
    global _servers
    if _servers is None:
        import weakref

        _servers = weakref.WeakSet()
    return _servers


def register_server(srv) -> None:
    """A ModelServer announces itself for the /status serving window."""
    try:
        _server_set().add(srv)
    except Exception:
        pass


# live ModelRegistry instances (weakly referenced): /status renders
# their per-name current/archived versions in the ``registry`` block
_registries = None


def _registry_set():
    global _registries
    if _registries is None:
        import weakref

        _registries = weakref.WeakSet()
    return _registries


def register_registry(reg) -> None:
    """A ModelRegistry announces itself for the /status registry block
    (what is serving, archived versions, last publish, publisher)."""
    try:
        _registry_set().add(reg)
    except Exception:
        pass


def unregister_server(srv) -> None:
    try:
        _server_set().discard(srv)
    except Exception:
        pass


# fleet-metrics providers (observability/fleet.MetricsFederator,
# registered by a FederatedFleet router with obs_fleet_federate on):
# each contributes merged dask_ml_tpu_fleet_* exposition lines to
# /metrics and a JSON block to /status + /status/fleet. Strong refs on
# purpose — the fed's stop() unregisters; a weak set could drop the
# provider mid-scrape
_fleet_providers: list = []


def register_fleet_provider(provider) -> None:
    """A MetricsFederator (or anything with ``render_lines()`` +
    ``fleet_block()``) joins the router's own exposition."""
    with _lock:
        if provider not in _fleet_providers:
            _fleet_providers.append(provider)


def unregister_fleet_provider(provider) -> None:
    with _lock:
        try:
            _fleet_providers.remove(provider)
        except ValueError:
            pass


def fleet_status_data() -> dict:
    """The combined ``/status/fleet`` block ({} when no federator is
    registered — federation of telemetry is off by default)."""
    out = {}
    for p in list(_fleet_providers):
        try:
            out.update(p.fleet_block())
        except Exception:
            continue
    return out


def _admit_series_locked(name: str, labels: tuple) -> bool:
    """Cardinality guard (caller holds ``_lock``): may a NEW labeled
    series join ``name``'s family? Past ``config.obs_max_series`` the
    series is dropped and the drop counted — /metrics stays bounded and
    parseable no matter what label values a caller mints."""
    if not labels:
        return True
    if (name, labels) in _dropped_series:
        return False
    from ..config import get_config

    cap = int(get_config().obs_max_series)
    if cap > 0 and _family_series.get(name, 0) >= cap:
        from ._counters import record_telemetry_series_dropped

        _dropped_series.add((name, labels))
        record_telemetry_series_dropped()
        return False
    _family_series[name] = _family_series.get(name, 0) + 1
    return True


def gauge_set(name: str, value, labels: tuple = ()) -> None:
    try:
        value = float(value)
    except (TypeError, ValueError):
        return
    key = (name, labels)
    with _lock:
        if key not in _gauges and not _admit_series_locked(name, labels):
            return
        _gauges[key] = value


def drop_labeled_series(name_prefix: str, label_kvs: tuple) -> int:
    """Remove every labeled gauge series whose family name starts with
    ``name_prefix`` and whose label set contains all of ``label_kvs``,
    releasing their slots in the cardinality ledger. Drift's version
    eviction rides this: an evicted model version must not leave its
    ``drift_score{version=...}`` series latched at a stale value on
    /metrics — or pinning cap room the live versions need."""
    kvs = set(label_kvs)
    with _lock:
        doomed = [k for k in _gauges
                  if k[0].startswith(name_prefix) and kvs <= set(k[1])]
        for k in doomed:
            del _gauges[k]
            left = _family_series.get(k[0], 0) - 1
            if left > 0:
                _family_series[k[0]] = left
            else:
                _family_series.pop(k[0], None)
            _dropped_series.discard(k)
        return len(doomed)


def gauges_snapshot() -> dict:
    with _lock:
        return dict(_gauges)


def histogram(name: str, labels: tuple = (), bounds=None) -> Histogram:
    """Create-or-get the histogram keyed (name, labels). ``labels`` is
    a tuple of (key, value) string pairs; label sets under one name
    must share boundaries (the first creation wins)."""
    global _overflow_hist
    key = (name, labels)
    with _lock:
        h = _hists.get(key)
        if h is None:
            if not _admit_series_locked(name, labels):
                # callers observe into a shared sink that never renders
                # — the write contract survives the cap, the page stays
                # bounded
                if _overflow_hist is None:
                    _overflow_hist = Histogram(bounds)
                return _overflow_hist
            h = _hists[key] = Histogram(bounds)
        return h


def histograms_snapshot() -> dict:
    with _lock:
        return dict(_hists)


def metrics_reset() -> None:
    """Clear gauges/histograms/rings (counters have their own reset) —
    test isolation."""
    with _lock:
        _gauges.clear()
        _hists.clear()
        _family_series.clear()
        _dropped_series.clear()
        _recent_spans.clear()
        _recent_stalls.clear()
        del _fleet_providers[:]


# -- publishers --------------------------------------------------------------
# every publish path is gated on this module-global bool: with no
# telemetry server live the calls cost one load + one branch, and
# NOTHING is registered with the span layer (its disabled path stays
# the shared no-op).

_publishing = 0
_pub_lock = threading.Lock()


def live_publishing() -> bool:
    return _publishing > 0


def _publishing_arm(delta: int) -> None:
    global _publishing
    with _pub_lock:
        _publishing += delta


def publish_progress(**gauges) -> None:
    """Host-side fit progress (loss, grad_norm, pass, blocks...) as
    ``fit_<name>`` gauges. No-op unless a telemetry server is live;
    callers only ever pass values they already hold on host — this path
    must never force a device sync."""
    if not _publishing:
        return
    for k, v in gauges.items():
        if v is not None:
            gauge_set(f"fit_{k}", v)


def note_stall(rec: dict) -> None:
    """Watchdog stall dump -> the /status ring (the ``watchdog_stalls``
    counter itself is incremented by the watchdog, so /metrics and the
    report counters table see it with or without a live server)."""
    try:
        with _lock:  # /status iterates this ring from the HTTP thread
            _recent_stalls.append({
                k: v for k, v in rec.items() if k != "stacks"
            })
    except Exception:
        pass


def _on_span_record(rec: dict) -> None:
    """Span-close observer (registered only while a server is live):
    stream-pass records become progress gauges + the pass-time
    histogram; everything lands in the recent-span ring for /status."""
    try:
        if "stream_pass" in rec:
            p = int(rec["stream_pass"])
            wall = float(rec.get("pass_s") or rec.get("wall_s") or 0.0)
            gauge_set("fit_pass", p)
            if wall > 0:
                histogram("fit_pass_seconds").observe(wall)
                gauge_set("fit_last_pass_seconds", wall)
                n = float(rec.get("n_rows") or 0.0)
                if n > 0:
                    gauge_set("fit_rows_per_sec", n / wall)
            tot = rec.get("passes_total")
            if tot:
                gauge_set("fit_passes_total", int(tot))
                if wall > 0:
                    # ETA from the pass clock: remaining passes at the
                    # measured per-pass wall (host arithmetic only)
                    gauge_set("fit_eta_seconds",
                              max(int(tot) - p, 0) * wall)
        elif rec.get("span") == "fit":
            gauge_set("fit_wall_s", rec.get("wall_s", 0.0))
        with _lock:  # /status iterates this ring from the HTTP thread
            _recent_spans.append(rec)
    except Exception:
        pass  # telemetry must never raise into the span layer


# -- Prometheus text exposition v0.0.4 ---------------------------------------

_NAME_RE = re.compile(r"[^a-zA-Z0-9_]")


def _san(name: str) -> str:
    name = _NAME_RE.sub("_", str(name))
    return name if name and not name[0].isdigit() else f"_{name}"


def _fmt(v) -> str:
    f = float(v)
    if math.isinf(f):
        return "+Inf" if f > 0 else "-Inf"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _labels_str(labels: tuple) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{_san(k)}="{str(v)}"' for k, v in labels
    )
    return "{" + inner + "}"


def _merge_label(labels: tuple, key: str, value: str) -> str:
    return _labels_str(tuple(labels) + ((key, value),))


def render_prometheus() -> str:
    """The /metrics body: counters (``_total`` suffix), gauges, and
    histograms (cumulative ``le`` buckets + ``_sum``/``_count``), all
    under the ``dask_ml_tpu_`` namespace. Pure host dicts — no jax call
    anywhere on this path (scraping must never compile or sync)."""
    lines = []
    counters = counters_snapshot()
    for name in sorted(counters):
        v = counters[name]
        if not isinstance(v, (int, float)) or isinstance(v, bool) \
                or not math.isfinite(float(v)):
            continue
        n = f"{_PREFIX}{_san(name)}_total"
        lines.append(f"# TYPE {n} counter")
        lines.append(f"{n} {_fmt(v)}")
    hist_by_name: dict[str, list] = {}
    for (name, labels), h in sorted(histograms_snapshot().items()):
        hist_by_name.setdefault(_san(name), []).append((labels, h))
    by_name: dict[str, list] = {}
    for (name, labels), v in sorted(gauges_snapshot().items()):
        # a gauge sharing a histogram's family name would emit a second
        # TYPE line for that family — invalid exposition; histogram wins
        if math.isfinite(v) and _san(name) not in hist_by_name:
            by_name.setdefault(_san(name), []).append((labels, v))
    for name, series in by_name.items():
        n = f"{_PREFIX}{name}"
        lines.append(f"# TYPE {n} gauge")
        for labels, v in series:
            lines.append(f"{n}{_labels_str(labels)} {_fmt(v)}")
    for name, series in hist_by_name.items():
        n = f"{_PREFIX}{name}"
        lines.append(f"# TYPE {n} histogram")
        for labels, h in series:
            snap = h.snapshot()
            cum = 0
            for i, bound in enumerate(snap["bounds"]):
                cum += snap["counts"][i]
                lines.append(
                    f"{n}_bucket"
                    f"{_merge_label(labels, 'le', _fmt(bound))} {cum}"
                )
            cum += snap["counts"][-1]
            lines.append(
                f"{n}_bucket{_merge_label(labels, 'le', '+Inf')} {cum}"
            )
            ls = _labels_str(labels)
            lines.append(f"{n}_sum{ls} {_fmt(snap['sum'])}")
            lines.append(f"{n}_count{ls} {snap['count']}")
    # fleet-merged families (dask_ml_tpu_fleet_*, a disjoint namespace
    # — one TYPE line per family holds across the whole page) from any
    # registered federator; a provider error must never 500 the scrape
    for p in list(_fleet_providers):
        try:
            lines.extend(p.render_lines())
        except Exception:
            continue
    up = f"{_PREFIX}uptime_seconds"
    lines.append(f"# TYPE {up} gauge")
    lines.append(f"{up} {_fmt(time.time() - _T0)}")
    return "\n".join(lines) + "\n"


# -- /status -----------------------------------------------------------------

def status_data() -> dict:
    """The live JSON a hung run needs: what the process
    believes it is doing RIGHT NOW (open-span stack), what it has done
    recently (report tables over the recent-span ring + the program
    registry), the serving windows, and any watchdog stalls."""
    from ._programs import programs_snapshot
    from .report import report_data

    now = time.time()
    open_spans = []
    for s in open_spans_snapshot():
        s = dict(s)
        s["age_s"] = round(now - s.pop("t_open_unix"), 3)
        open_spans.append(s)
    counters = counters_snapshot()
    # the recent-span ring + synthetic counters/programs records render
    # through the SAME aggregator as the post-hoc CLI — one shape for
    # live and recorded views
    with _lock:  # fit threads append concurrently; unlocked iteration
        records = list(_recent_spans)     # raises "deque mutated" -> 500
        stalls = list(_recent_stalls)
    records.append({"counters": True, **counters})
    progs = programs_snapshot()
    if progs:
        records.append({"programs": progs})
    # the plans table (ISSUE 15): which plan/ladder rung minted each
    # warmed specialization — rides the same report aggregator
    try:
        from ..plans import plans_snapshot

        plrows = plans_snapshot()
    except Exception:
        plrows = None
    if plrows:
        records.append({"plans": plrows})
    # the incident plane (ISSUE 20): alert rules + captured bundles —
    # blocks for operators, synthetic records for the shared report
    # aggregator (one serialization path for live and post-hoc views)
    try:
        from . import alerts as _alerts

        alerts_block = _alerts.alerts_data()
    except Exception:
        alerts_block = {}
    try:
        from . import incidents as _incidents

        incidents_block = _incidents.incidents_data()
    except Exception:
        incidents_block = {}
    if alerts_block.get("rules") or alerts_block.get("events"):
        records.append({"alerts": alerts_block})
    if incidents_block.get("captured"):
        records.append({"incidents": incidents_block["captured"]})
    hists = {}
    for (name, labels), h in histograms_snapshot().items():
        key = f"{name}{_labels_str(labels)}"
        snap = h.snapshot()
        hists[key] = {
            "count": snap["count"], "sum": round(snap["sum"], 6),
            **{k: (None if isinstance(v, float) and math.isnan(v)
                   else round(v, 6))
               for k, v in h.percentiles((50, 90, 99)).items()},
        }
    serving = []
    for srv in list(_server_set()):
        try:
            serving.append(srv.stats())
        except Exception:
            continue
    # the registry block: every live ModelRegistry's per-name view
    # (current version, archived versions, last publish, publisher) —
    # fleet operators see what is serving without instrumenting code
    registry = {}
    for reg in list(_registry_set()):
        try:
            registry.update(reg.status_snapshot())
        except Exception:
            continue
    # the drift block: last computed train-vs-serve / window scores,
    # recent hot-swap canaries, and the tracked sketch keys
    try:
        from . import drift as _drift

        drift_block = _drift.status_block()
    except Exception:
        drift_block = {}
    # the reliability block: armed fault plan + per-site fired counts,
    # retry/quarantine/resume/restart counters — "is chaos armed, what
    # has it hit, what did the hardening absorb"
    try:
        from ..reliability import status_block as _rel_status

        reliability_block = _rel_status()
    except Exception:
        reliability_block = {}
    # the structured telemetry block the fleet federator merges from:
    # gauges and RAW histogram buckets as [name, labels, payload]
    # triples (the display "gauges"/"histograms" blocks bake labels
    # into string keys — fine to read, lossy to re-parse). Bounds ride
    # each histogram so the bucket-for-bucket merge can refuse a
    # mismatched ladder instead of corrupting quantiles.
    telem_g = [[n, [list(kv) for kv in ls], v]
               for (n, ls), v in sorted(gauges_snapshot().items())]
    telem_h = []
    for (name, labels), h in sorted(histograms_snapshot().items()):
        snap = h.snapshot()
        telem_h.append([name, [list(kv) for kv in labels], {
            "bounds": list(snap["bounds"]), "counts": snap["counts"],
            "sum": snap["sum"], "count": snap["count"],
            "min": snap["min"], "max": snap["max"],
        }])
    out = {
        "pid": os.getpid(),
        "t_unix": round(now, 3),
        "uptime_s": round(now - _T0, 3),
        "open_spans": open_spans,
        "counters": counters,
        "gauges": {f"{n}{_labels_str(ls)}": v
                   for (n, ls), v in gauges_snapshot().items()},
        "histograms": hists,
        "telemetry": {"gauges": telem_g, "histograms": telem_h},
        "serving": serving,
        "registry": registry,
        "drift": drift_block,
        "reliability": reliability_block,
        "watchdog_stalls": stalls,
        "alerts": alerts_block,
        "incidents": incidents_block,
        "report": report_data(records),
    }
    try:
        from ._counters import device_memory_gauges

        out["device_memory"] = device_memory_gauges()
    except Exception:
        out["device_memory"] = {}
    fleet = fleet_status_data()
    if fleet:
        out["fleet"] = fleet
    return out


# -- HTTP server -------------------------------------------------------------

def _json_default(o):
    """Non-JSON leaves (numpy scalars riding span attrs) -> float/str."""
    try:
        return float(o)
    except (TypeError, ValueError):
        return str(o)


class _Handler(http.server.BaseHTTPRequestHandler):
    server_version = "dask-ml-tpu-telemetry/1"
    protocol_version = "HTTP/1.1"

    def log_message(self, *args):  # silent: stderr belongs to the fit
        pass

    def _reply(self, code, body: bytes, ctype: str, headers=()):
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        for k, v in dict(headers).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def do_POST(self):
        # the federation request/publish surface (serving/federation):
        # POST /fleet/<name>/<op> routes to the live-registered
        # FleetServer carrying <name> in this process. Kept out of
        # do_GET so scrapers stay read-only.
        path = self.path.split("?", 1)[0].rstrip("/")
        try:
            if path == "/profile":
                # on-demand deep profiling: a bounded jax.profiler
                # window into config.incident_dir (real device traces
                # on TPU; no-op-with-reason off-TPU). POST, not GET —
                # it changes on-disk state and blocks for the window.
                from urllib.parse import parse_qs, urlparse

                from . import incidents as _incidents

                q = parse_qs(urlparse(self.path).query)
                seconds = (q.get("seconds") or ["5"])[0]
                out = _incidents.deep_profile(seconds)
                self._reply(
                    200 if out.get("profiled") else 400,
                    (json.dumps(out, default=_json_default)
                     + "\n").encode(),
                    "application/json",
                )
            elif path.startswith("/fleet/"):
                from ..serving import federation

                n = int(self.headers.get("Content-Length", 0) or 0)
                body = self.rfile.read(n) if n > 0 else b""
                code, out, ctype, extra = federation.handle_http(
                    path, dict(self.headers.items()), body
                )
                self._reply(code, out, ctype, extra)
            else:
                self._reply(404, b"not found\n",
                            "text/plain; charset=utf-8")
        except Exception as exc:  # never take the server thread down
            try:
                self._reply(500, f"error: {exc}\n".encode(),
                            "text/plain; charset=utf-8")
            except Exception:
                pass

    def do_GET(self):
        path = self.path.split("?", 1)[0].rstrip("/") or "/"
        try:
            if path == "/healthz":
                self._reply(200, b"ok\n", "text/plain; charset=utf-8")
            elif path == "/metrics":
                self._reply(
                    200, render_prometheus().encode(),
                    "text/plain; version=0.0.4; charset=utf-8",
                )
            elif path == "/traces":
                # the request trace plane's JSON surface: sampler
                # counters, retained sampled traces, per-stage exemplar
                # histograms. Exemplars live HERE, not in /metrics —
                # the Prometheus text exposition stays grammar-clean
                from ._requests import traces_data

                self._reply(
                    200,
                    (json.dumps(traces_data(), default=_json_default)
                     + "\n").encode(),
                    "application/json",
                )
            elif path == "/alerts":
                # the alert engine's view alone: rule rows with
                # firing/resolved state, the transition ring, and the
                # crossing ledger — what a pager/autoscaler polls
                from . import alerts as _alerts

                self._reply(
                    200,
                    (json.dumps(_alerts.alerts_data(),
                                default=_json_default) + "\n").encode(),
                    "application/json",
                )
            elif path == "/status/fleet":
                # the fleet-scope view alone ({} until a federating
                # router registers): merged counters/quantiles + the
                # SLO burn block, without the full /status payload
                self._reply(
                    200,
                    (json.dumps(fleet_status_data(),
                                default=_json_default) + "\n").encode(),
                    "application/json",
                )
            elif path == "/status":
                # default=: span attrs can carry numpy scalars (a fit's
                # n_iter etc.) — degrade them to floats/strings instead
                # of 500ing the whole status page
                self._reply(
                    200,
                    (json.dumps(status_data(), default=_json_default)
                     + "\n").encode(),
                    "application/json",
                )
            elif path == "/":
                self._reply(
                    200,
                    b"dask_ml_tpu live telemetry: "
                    b"/metrics /status /status/fleet /traces /alerts "
                    b"/healthz (POST /profile?seconds=N)\n",
                    "text/plain; charset=utf-8",
                )
            else:
                self._reply(404, b"not found\n",
                            "text/plain; charset=utf-8")
        except Exception as exc:  # never take the server thread down
            try:
                self._reply(500, f"error: {exc}\n".encode(),
                            "text/plain; charset=utf-8")
            except Exception:
                pass


class _Server(http.server.ThreadingHTTPServer):
    daemon_threads = True
    # a fit process restarted on the same port must not wait out
    # TIME_WAIT to expose telemetry again
    allow_reuse_address = True


class TelemetryServer:
    """The background exporter. ``port=0`` binds an ephemeral port
    (tests); production sets ``config.obs_http_port``. Use as a context
    manager or ``start()``/``stop()``. Starting registers the span
    observer that feeds fit-progress gauges; stopping removes it, so a
    stopped plane restores the exact pre-live overhead profile."""

    def __init__(self, port=None, host="127.0.0.1"):
        if port is None:
            from ..config import get_config

            port = int(get_config().obs_http_port)
        self.port = int(port)
        self.host = host
        self._httpd = None
        self._thread = None

    @property
    def running(self) -> bool:
        return self._httpd is not None

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def start(self):
        if self._httpd is not None:
            return self
        httpd = _Server((self.host, self.port), _Handler)
        self.port = httpd.server_address[1]
        self._httpd = httpd
        self._thread = threading.Thread(
            target=httpd.serve_forever,
            kwargs={"poll_interval": 0.2},
            name="dask-ml-tpu-telemetry", daemon=True,
        )
        # arm publication BEFORE serving: a scrape racing start() must
        # not observe a half-armed plane
        add_span_observer(_on_span_record)
        _publishing_arm(+1)
        self._thread.start()
        return self

    def stop(self):
        if self._httpd is None:
            return
        _publishing_arm(-1)
        remove_span_observer(_on_span_record)
        try:
            self._httpd.shutdown()
            self._httpd.server_close()
        finally:
            self._httpd = None
            if self._thread is not None:
                self._thread.join(5.0)
                self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, exc_type, exc, tb):
        self.stop()
        return False


# -- process-wide singleton --------------------------------------------------

_singleton: TelemetryServer | None = None
_singleton_lock = threading.Lock()
# port -> last bind-failure time; retried after a backoff rather than
# blacklisted forever — the process that loses a port race (two bench
# children sharing one DASK_ML_TPU_OBS_HTTP_PORT) must regain its live
# endpoint once the winner exits and frees the port
_failed_ports: dict[int, float] = {}
_BIND_RETRY_S = 30.0


def telemetry_server() -> TelemetryServer | None:
    """The live singleton server, or None."""
    return _singleton


def ensure_telemetry() -> TelemetryServer | None:
    """Start the process-wide telemetry server if ``config.obs_http_port``
    asks for one and none is running (idempotent; first port wins for
    the process lifetime). Called from the hot-path entries (BlockStream
    construction, ModelServer.start, fit_logger, bench) — with the knob
    at its 0 default this is one config read. A bind failure (port
    already taken — e.g. two bench children racing) backs off for
    ``_BIND_RETRY_S`` before the next attempt, and NEVER raises into
    the fit."""
    global _singleton
    # the alert engine shares these entry points but arms on its OWN
    # knobs (obs_alert_rules / incident_dir) — rules work without an
    # HTTP port. One None check + one config read when disarmed; a bad
    # rule spec raises its typed error HERE, in the arming caller,
    # never silently inside a daemon.
    from . import alerts as _alerts

    try:
        _alerts.ensure_engine()
    except _alerts.AlertRuleError:
        raise
    except Exception:
        pass
    if _singleton is not None:
        return _singleton
    from ..config import get_config

    port = int(get_config().obs_http_port)
    if port <= 0:
        return None
    t_fail = _failed_ports.get(port)
    if t_fail is not None and time.time() - t_fail < _BIND_RETRY_S:
        return None
    with _singleton_lock:
        if _singleton is not None:
            return _singleton
        try:
            srv = TelemetryServer(port=port).start()
        except Exception:
            _failed_ports[port] = time.time()
            return None
        _failed_ports.pop(port, None)
        _singleton = srv
    return _singleton


def stop_telemetry() -> None:
    """Stop the singleton (tests / graceful shutdown)."""
    global _singleton
    with _singleton_lock:
        srv, _singleton = _singleton, None
    if srv is not None:
        srv.stop()
