"""From a ``jax.profiler`` trace to numbers: device busy time as the union
of the intervals in which an operation ran, self time per operation name,
idle gaps by the benchmark's own call annotation they lie in, collective
time, all of it INSIDE those annotations (``bench.fit``, ``bench.predict``).

Two steps, so the second can be tested on a small recorded trace
(``testdata/trace_kmeans_v5e.json``): :func:`load` turns an ``.xplane.pb``
into a plain table, :func:`reduce` turns the table into the summary the
per-layer metric readers and ``breakdown`` use. The yardstick lives here,
with the benchmark: no later PR that claims a gain can change it.

Table: ``{"planes": [{"name": str, "lines": [{"name": str, "events":
[[name, start_ns, duration_ns], ...]}]}], "names": {short name: whole
name}}`` — device event names are shortened (:func:`short_name`); ``names``
keeps the whole text of the first event of each, for reading by hand.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
WINDOW = "bench.window"
PREFIX = "bench."
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def short_name(name, limit=96):
    """A v5e trace names a device event by its whole HLO instruction
    (``%fused_lloyd_stats.5 = (f32[64,128]{1,0:T(8,128)S(1)}, ...)
    custom-call(f32[8388608,128]{...} %get-tuple-element.199, ...``, up to
    kilobytes). Keep what tells operations apart: the instruction's name,
    its opcode and its result shape without layouts —
    ``fused_lloyd_stats.5 = custom-call (f32[64,128], f32[1,64], f32[1,1])``.
    Names of another form pass through."""
    lhs, eq, rhs = name.partition(" = ")
    if not eq or not lhs.startswith("%"):
        return name[:limit]
    m = _OPCODE.search(rhs)
    if m is None:
        return lhs[1:limit + 1]
    shape = _LAYOUT.sub("", rhs[:m.start()]).strip()
    return f"{lhs[1:]} = {m.group(1)} {shape}"[:limit]


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(xplane_path, host_prefix=PREFIX):
    """The table of an ``.xplane.pb``: every event of the device planes'
    lines, and of the host planes only the benchmark's own annotations
    (names starting with ``host_prefix``) — host threads log far more than
    anything here reads."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    planes, names = [], {}
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                if not device and not ev.name.startswith(host_prefix):
                    continue
                name = short_name(ev.name) if device else ev.name
                events.append([name, float(ev.start_ns),
                               float(ev.duration_ns)])
                if device and name not in names:
                    names[name] = ev.name[:2000]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "names": names}


def _union(intervals):
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """[(name, start, end, self)] — an operation that contains others (a
    ``while`` around its body) keeps only the time no child covers."""
    out, stack = [], []
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        e = s + d
        while stack and stack[-1][2] <= s:
            stack.pop()
        rec = [name, s, e, d]
        if stack:
            stack[-1][3] -= min(d, max(0.0, stack[-1][2] - s))
        stack.append(rec)
        out.append(rec)
    return [(n, s, e, max(0.0, sf)) for n, s, e, sf in out]


def reduce(table, top=10):
    """The summary of one traced window. Times in seconds.

    Only what lies INSIDE the benchmark's call annotations counts
    (``bench.fit``, ``bench.predict``: every ``bench.*`` span but the
    window's): device events are clipped to those spans and dropped
    outside them, so what the benchmark itself does between calls (a new
    draw of labels) is neither busy time nor an operation, and the recipe's
    mix of calls moves no per-kind number.

    ``window_s`` is the summed length of the spans. Per chip, ``busy_s`` is
    the union of its ``XLA Ops`` events inside them; ``busy_s`` is the mean
    over chips, ``idle_pct`` the worst chip's. ``kinds`` gives, per span
    name, the call count, the summed length, the worst chip's idle share
    and the collective seconds (mean over chips). ``ops`` maps each
    operation name to its self seconds (mean over chips), its event count
    on the first chip and those events' whole durations. ``gaps`` lists the
    longest idle gaps of the worst chip, each labelled with the span it lies
    in and the device operation that ended where it begins. Returns None
    when no device plane has events (a CPU run): there is nothing to
    read."""
    spans = []                         # the benchmark's host annotations
    chips = []
    for plane in table["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            for line in plane["lines"]:
                if line["name"] == OPS_LINE and line["events"]:
                    chips.append(sorted(line["events"], key=lambda e: e[1]))
        else:
            for line in plane["lines"]:
                spans += [(n, s, s + d) for n, s, d in line["events"]
                          if n.startswith(PREFIX)]
    if not chips:
        return None
    w0, w1 = next(sp[1:] for sp in spans if sp[0] == WINDOW)
    calls = sorted(((n, max(s, w0), min(e, w1)) for n, s, e in spans
                    if n != WINDOW and s < w1 and e > w0),
                   key=lambda sp: sp[1])

    busy, gaps_by_chip, op_self, collective = [], [], {}, []
    first, busy_by_kind, coll_by_kind = {}, [], {}
    for ci, events in enumerate(chips):
        total, coll, gaps, kinds = 0.0, 0.0, [], {}
        for kind, c0, c1 in calls:
            clipped = [(n, max(s, c0), min(s + d, c1) - max(s, c0))
                       for n, s, d in events if s < c1 and s + d > c0]
            merged = _union([(s, s + d) for _, s, d in clipped])
            b = sum(e - s for s, e in merged) / 1e9
            total += b
            kinds[kind] = kinds.get(kind, 0.0) + b
            last_at = {s + d: n for n, s, d in clipped}   # who ended there
            edges = [c0] + [t for iv in merged for t in iv] + [c1]
            gaps += [(edges[i + 1] - edges[i], kind,
                      last_at.get(edges[i], "its start"))
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
            for n, s, e, sf in _self_times(clipped):
                op_self[n] = op_self.get(n, 0.0) + sf / 1e9
                if COLLECTIVE.search(n):
                    coll += sf / 1e9
                    coll_by_kind[kind] = coll_by_kind.get(kind, 0.0) \
                        + sf / 1e9
                if ci == 0:
                    first.setdefault(n, []).append((e - s) / 1e9)
        busy.append(total)
        busy_by_kind.append(kinds)
        gaps_by_chip.append(gaps)
        collective.append(coll)
    worst = min(range(len(chips)), key=lambda i: busy[i])
    n_chips = len(chips)
    kinds = {}
    for kind, c0, c1 in calls:
        k = kinds.setdefault(kind, {"calls": 0, "seconds": 0.0})
        k["calls"] += 1
        k["seconds"] += (c1 - c0) / 1e9
    for kind, k in kinds.items():
        k["idle_pct"] = 100.0 * (
            1.0 - min(b[kind] for b in busy_by_kind) / k["seconds"])
        k["collective_s"] = coll_by_kind.get(kind, 0.0) / n_chips
    window_s = sum(k["seconds"] for k in kinds.values())
    ops = {n: {"self_s": t / n_chips, "count": len(first.get(n, ())),
               "durations_s": first.get(n, [])}
           for n, t in op_self.items()}
    return {
        "window_s": window_s,
        "chips": n_chips,
        "busy_s": sum(busy) / n_chips,
        "busy_s_by_chip": busy,
        "idle_pct": 100.0 * (1.0 - busy[worst] / window_s),
        "kinds": kinds,
        "collective_s": sum(collective) / n_chips,
        "ops": ops,
        "gaps": [[f"{kind} after {prev.split(' = ')[0]}", g / 1e9]
                 for g, kind, prev in sorted(gaps_by_chip[worst],
                                             reverse=True)[:top]],
        "spans": [[n, (s - w0) / 1e9, (e - w0) / 1e9] for n, s, e in calls],
    }


def breakdown(summary, top=10):
    """The ``breakdown`` of the result line."""
    ops = sorted(((n, o["self_s"]) for n, o in summary["ops"].items()),
                 key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": summary["gaps"][:top]}


def matching(summary, pattern):
    """Whole durations (seconds, first chip) of the events whose name
    matches ``pattern``, longest-running name first."""
    rx = re.compile(pattern)
    out = []
    for n, o in summary["ops"].items():
        if rx.search(n):
            out += o["durations_s"]
    return out
