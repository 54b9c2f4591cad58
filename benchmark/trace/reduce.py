"""From a profiler trace to numbers: device busy and idle time, time by
operation, collective time and its exposed part, idle gaps by what the
host was doing.

The reduction works on a plain form of the trace — ``{"planes":
[{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns],
...]}]}]}`` — which :func:`load_xplane` reads from the ``.xplane.pb`` the
JAX profiler writes and which a test fixture can hold as JSON. Times
inside are nanoseconds on the trace's clock; results are seconds.
"""

from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"            # synchronous; nested (a while holds its body)
ASYNC_LINE = "Async XLA Ops"   # start-to-done spans of asynchronous ones
COLLECTIVE = re.compile(
    r"all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|ragged-all-to-all|collective-broadcast")
WINDOW_MARKER = "benchmark_window"


class TraceError(Exception):
    """The trace does not hold what the reduction needs."""


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise TraceError(f"no peaks for device kind {device_kind!r}; add it "
                         f"to trace/peaks.json with its source")
    return table[device_kind]


def load_xplane(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` in the plain form,
    device planes and the marker's host line only."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = DEVICE_PLANE.match(plane.name)
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name == WINDOW_MARKER]
            if events and (not device
                           or line.name in (OP_LINE, ASYNC_LINE)):
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def markers(trace: dict) -> list:
    """``[(start_ns, end_ns), ...]`` of the harness's window markers on
    the trace's clock, in order: together the traced window (one task,
    or a few steps with the harness's own checks between them left
    out). With the ``perf_counter`` reading the harness took as it
    opened the first, they also give the offset between the host's
    clock and the trace's."""
    found = [(start, start + dur)
             for plane in trace["planes"]
             if not DEVICE_PLANE.match(plane["name"])
             for line in plane["lines"]
             for name, start, dur in line["events"] if name == WINDOW_MARKER]
    if not found:
        raise TraceError(f"no {WINDOW_MARKER!r} annotation in the trace")
    return sorted(found)


def _union(intervals: list) -> list:
    """Sorted, merged copy of ``[(lo, hi), ...]``."""
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def _length(merged: list) -> float:
    return sum(hi - lo for lo, hi in merged)


def _minus(a: list, b: list) -> list:
    """The parts of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append([cur, hi])
    return out


_OPCODE = re.compile(r"[\s)]([a-z][a-z\-]*)\(")


def short_name(event_name: str) -> str:
    """The trace prints an operation as its whole HLO instruction; its
    name is what stands before `` = ``."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:80]


def is_collective(event_name: str) -> bool:
    """By the instruction's opcode (``... = <shape> all-to-all(...)``),
    not its name: JAX names the instruction ``%all_to_all.11``, and a
    consumer's operand list may name a collective too."""
    m = _OPCODE.search(event_name.split(" = ", 1)[-1])
    return bool(COLLECTIVE.search(m.group(1) if m else event_name))


def device_ops(trace: dict, windows: list, devices: int) -> dict:
    """Per device ``{line: [(name, lo, hi, collective), ...]}``: the
    events of its operation lines clipped to ``windows``. Raises when fewer than
    ``devices`` device planes ran an operation inside them — a traced
    run in which no operation ran on a device is no trace."""
    out = {}
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        lines = {line["name"]: [(short_name(n), max(s, lo), min(s + d, hi),
                                 is_collective(n))
                                for n, s, d in line["events"]
                                for lo, hi in windows
                                if s + d > lo and s < hi]
                 for line in plane["lines"]}
        if lines.get(OP_LINE):
            out[int(m.group(1))] = lines
    if len(out) < devices:
        raise TraceError(f"{len(out)} device planes with a {OP_LINE!r} line "
                         f"inside the window, {devices} expected")
    return dict(sorted(out.items())[:devices])


def self_seconds(events: list) -> dict:
    """``{name: ns}`` of time spent in each operation itself: events of
    one line nest (a while holds its body's operations), and a parent
    is not charged for its children."""
    by_name: dict = {}
    stack: list = []                  # [name, hi, self_ns]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            by_name[name] = by_name.get(name, 0.0) + own

    for name, lo, hi, _ in sorted(events, key=lambda e: (e[1], -e[2])):
        close(lo)
        if stack:
            stack[-1][2] -= min(hi, stack[-1][1]) - lo
        stack.append([name, hi, hi - lo])
    close(float("inf"))
    return by_name


def summarize(trace: dict, devices: int, units: int = 1) -> dict:
    """The numbers the trace-backed readers and the breakdown take:

    - ``window_s``: the markers' summed length; ``busy_s``: union of
      operation intervals inside them, averaged over devices;
      ``idle_share`` = 1 - busy/window;
    - ``busy_per_unit_s``, ``window_per_unit_s``: busy and window over
      ``units`` (the tasks or steps traced);
    - ``ops``: ``[[name, seconds]]`` by time in the operation itself
      (children not charged to their parent), averaged over devices,
      longest first;
    - ``collective_s``: union of collective operations' intervals
      (synchronous, or start to done) per unit, averaged over devices;
      ``collective_exposed_s``: the part of it during which no other
      operation ran on that device;
    - ``gaps``: device 0's idle intervals ``[(lo_ns, hi_ns)]`` inside
      the markers, longest first, for the host-side attribution.
    """
    windows = markers(trace)
    per_device = device_ops(trace, windows, devices)
    window_ns = sum(hi - lo for lo, hi in windows)
    busy = coll = exposed = 0.0
    by_name: dict = {}
    gaps: list = []
    for i, lines in enumerate(per_device.values()):
        events = lines[OP_LINE]
        all_ops = _union([(s, e) for _, s, e, _ in events])
        busy += _length(all_ops)
        c = _union([(s, e) for _, s, e, coll in
                    events + lines.get(ASYNC_LINE, []) if coll])
        # a while or a call that holds a collective is not "other work"
        leaves = [(s, e) for n, s, e, coll in events if not coll
                  and not n.startswith(("while", "call", "conditional"))]
        coll += _length(c)
        exposed += _length(_minus(c, _union(leaves)))
        for n, ns in self_seconds(events).items():
            by_name[n] = by_name.get(n, 0.0) + ns
        if i == 0:
            gaps = _minus([list(w) for w in windows], all_ops)
    n = len(per_device)
    busy_s = busy / n / 1e9
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (window_ns / 1e9),
        "busy_per_unit_s": busy_s / units,
        "window_per_unit_s": window_ns / 1e9 / units,
        "ops": sorted(([k, v / n / 1e9] for k, v in by_name.items()),
                      key=lambda kv: -kv[1]),
        "collective_s": coll / n / 1e9 / units,
        "collective_exposed_s": exposed / n / 1e9 / units,
        "gaps": sorted(gaps, key=lambda g: g[0] - g[1]),
        "first_marker_ns": windows[0][0],
    }


def attribute_gaps(summary: dict, host_spans: list, perf_at_marker: float,
                   bucket_of, priority: tuple) -> list:
    """``[[bucket, seconds]]``: device 0's idle time inside the window,
    each instant charged to the host stage active in it (one bucket per
    instant, by ``priority``; none open = ``unattributed``: the host is
    not idle there, nothing names what it does), longest
    first. ``host_spans`` are ``{"name", "ts", "dur"}`` on
    ``perf_counter``; ``perf_at_marker`` is that clock's reading as the
    first window marker opened."""
    offset_ns = summary["first_marker_ns"] - perf_at_marker * 1e9
    per_bucket: dict = {}
    for s in host_spans:
        lo = s["ts"] * 1e9 + offset_ns
        per_bucket.setdefault(bucket_of(s["name"]), []).append(
            (lo, lo + s["dur"] * 1e9))
    gaps = _union(summary["gaps"])
    charged: dict = {}
    remaining = gaps
    for b in priority:
        if b not in per_bucket:
            continue
        active = _union(per_bucket[b])
        left = _minus(remaining, active)
        took = _length(remaining) - _length(left)
        if took > 0:
            charged[b] = took / 1e9
        remaining = left
    if _length(remaining) > 0:
        charged["unattributed"] = _length(remaining) / 1e9
    return sorted(([k, v] for k, v in charged.items()), key=lambda kv: -kv[1])


def finish(out: dict, trace, chips: int, units: int, host_spans: list,
           bucket_of, priority: tuple) -> None:
    """Reduce the run's trace into the driver's outcome: the summary the
    trace-backed readers take, ``busy_s``/``window_s`` on the device
    stamp, and the breakdown (at most 10 entries a list)."""
    summary = summarize(load_xplane(trace.trace_dir), chips, units)
    out["obs"]["trace"] = summary
    out["device"]["busy_s"] = summary["busy_s"]
    out["device"]["window_s"] = summary["window_s"]
    out["breakdown"] = {
        "device_ops": summary["ops"][:10],
        "idle_gaps": attribute_gaps(summary, host_spans,
                                    trace.perf_at_marker, bucket_of,
                                    priority)[:10]}
