"""From a `jax.profiler` trace (`*.xplane.pb`) to device busy time, idle
gaps and the heaviest operations.

The arithmetic works on plain tuples so that it can be checked by hand
(`benchmark/tests/test_reduction.py`); only `load` touches jax.

Vocabulary. A trace has planes; a device plane is one chip
(`/device:TPU:<n>`), host planes hold threads. A plane has lines; on a
device plane the line `XLA Ops` holds one event per executed HLO op and
`XLA Modules` one per executed program. An event is (name, start_ns,
duration_ns), all on one clock.

- busy: the union of a chip's op intervals, clipped to the window;
- idle gap: a maximal stretch of the window with no op on that chip;
- a gap's label: the benchmark's own host span (names starting with
  `SPAN_PREFIX`) that was open at the gap's middle, joined with the
  innermost other host event open there on any thread (what the host was
  doing; with several busy threads it names the most specific one).
"""

from __future__ import annotations

import bisect
import heapq
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.slice"
TOP_N = 10


def find_xplane(trace_dir: str):
    """Newest `*.xplane.pb` under `trace_dir`, or None."""
    paths = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> dict:
    """{plane name: {line name: [(event name, start_ns, duration_ns)]}}.
    Lines of one name on one plane are merged."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for e in line.events:
                events.append((e.name, float(e.start_ns),
                               float(e.duration_ns)))
    return out


def union(intervals) -> list:
    """Merge [(start, end)] into disjoint, sorted intervals."""
    merged = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def clip(intervals, t0: float, t1: float) -> list:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(merged, t0: float, t1: float) -> list:
    """The stretches of [t0, t1] that `merged` (disjoint, sorted, clipped
    to the window) leaves uncovered."""
    out, cursor = [], t0
    for start, end in merged:
        if start > cursor:
            out.append((cursor, start))
        cursor = max(cursor, end)
    if t1 > cursor:
        out.append((cursor, t1))
    return out


def label_points(host_events, points) -> list:
    """For each time in `points`, what the host was doing: the benchmark's
    own innermost open span (names starting with `SPAN_PREFIX`, the window
    span aside) joined with the innermost other host event open there, on
    any thread. One sweep over the events, so every gap gets a label."""
    events = sorted((e for e in host_events if e[0] != WINDOW_SPAN),
                    key=lambda e: e[1])
    order = sorted(range(len(points)), key=lambda i: points[i])
    labels, active, nxt = [None] * len(points), [], 0
    for i in order:
        t = points[i]
        while nxt < len(events) and events[nxt][1] <= t:
            name, start, dur = events[nxt]
            heapq.heappush(active, (start + dur, dur, name))
            nxt += 1
        while active and active[0][0] < t:
            heapq.heappop(active)
        span = doing = None
        for _, dur, name in active:
            if name.startswith(SPAN_PREFIX):
                if span is None or dur < span[0]:
                    span = (dur, name)
            elif doing is None or dur < doing[0]:
                doing = (dur, name)
        labels[i] = ((span[1] if span else "outside " + SPAN_PREFIX + "*")
                     + " | " + (doing[1] if doing else "nothing traced"))
    return labels


def host_events(planes: dict) -> list:
    """Every event with a duration on a plane that is no device."""
    out = []
    for name, lines in planes.items():
        if DEVICE_PLANE.match(name):
            continue
        for events in lines.values():
            out.extend(e for e in events if e[2] > 0)
    return out


def window_of(planes: dict):
    """(t0, t1) of the benchmark's `WINDOW_SPAN`, else the extent of the
    device ops, else None."""
    for e in host_events(planes):
        if e[0] == WINDOW_SPAN:
            return e[1], e[1] + e[2]
    starts, ends = [], []
    for name, lines in planes.items():
        if DEVICE_PLANE.match(name):
            for n, s, d in lines.get(OP_LINE, []):
                starts.append(s)
                ends.append(s + d)
    return (min(starts), max(ends)) if starts else None


def self_times(ops) -> list:
    """[(name, self seconds)] for [(name, start, end)]: an op's own time
    is its duration less that of the ops nested inside it (a `while` op
    spans its whole body), so the times add up to the busy time."""
    out, stack = [], []
    for name, start, end in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= start:
            top = stack.pop()
            out.append((top[0], top[3] / 1e9))
        if stack:
            stack[-1][3] -= min(end, stack[-1][2]) - start
        stack.append([name, start, end, end - start])
    out.extend((top[0], top[3] / 1e9) for top in stack)
    return out


def short_op(name: str) -> str:
    """An op event is named by its whole HLO line; keep the op's name and
    the shape of its (first) result:
    `%fusion.5 = bf16[64,9]{1,0:T(8,128)} fusion(...)` -> `fusion.5 bf16[64,9]`."""
    op, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    shape = re.match(r"\(?([a-z]+[0-9]*\[[0-9,]*\])", rest)
    return (op.lstrip("%") + (" " + shape.group(1) if shape else ""))[:80]


def _module_of(modules_sorted, starts, t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0:
        name, start, dur = modules_sorted[i]
        if start <= t <= start + dur:
            # "jit_anakin_fn(123456)" -> "jit_anakin_fn"
            return re.sub(r"\(\d+\)$", "", name)
    return "?"


def reduce(planes: dict, top_n: int = TOP_N):
    """The reduction. Returns None when the trace holds no device op.

    {"window_s", "busy_s" (mean over chips), "chips", "busy_s_per_chip",
     "device_ops": [[module/op, seconds a chip]], top_n by time,
     "idle_gaps": [[label, seconds a chip]], top_n by time,
     "longest_gap_s"}"""
    window = window_of(planes)
    if window is None:
        return None
    t0, t1 = window
    hosts = [e for e in host_events(planes)
             if e[1] < t1 and e[1] + e[2] > t0]
    busy, op_time, gap_time = [], defaultdict(float), defaultdict(float)
    longest = 0.0
    for name in sorted(planes):
        if not DEVICE_PLANE.match(name):
            continue
        ops = planes[name].get(OP_LINE, [])
        if not ops:
            continue
        modules = sorted(planes[name].get(MODULE_LINE, []),
                         key=lambda e: e[1])
        mod_starts = [m[1] for m in modules]
        merged = union(clip(((s, s + d) for _, s, d in ops), t0, t1))
        busy.append(total(merged) / 1e9)
        inside = [(_module_of(modules, mod_starts, s) + "/" + short_op(op),
                   max(s, t0), min(s + d, t1)) for op, s, d in ops
                  if min(s + d, t1) > max(s, t0)]
        for key, seconds in self_times(inside):
            op_time[key] += seconds
        idle = gaps(merged, t0, t1)
        labels = label_points(hosts, [(g0 + g1) / 2.0 for g0, g1 in idle])
        for (g0, g1), label in zip(idle, labels):
            longest = max(longest, (g1 - g0) / 1e9)
            gap_time[label] += (g1 - g0) / 1e9
    if not busy or sum(busy) <= 0:
        return None
    chips = len(busy)

    def top(table):
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top_n]
        return [[k, v / chips] for k, v in rows]

    return {"window_s": (t1 - t0) / 1e9, "busy_s": sum(busy) / chips,
            "chips": chips, "busy_s_per_chip": busy,
            "device_ops": top(op_time), "idle_gaps": top(gap_time),
            "longest_gap_s": longest}


def describe(planes: dict) -> list:
    """One text line per plane/line: for reading a trace by hand."""
    out = []
    for pname in sorted(planes):
        for lname, events in sorted(planes[pname].items()):
            if not events:
                continue
            first = min(e[1] for e in events)
            last = max(e[1] + e[2] for e in events)
            out.append(f"{pname} | {lname} | n={len(events)} "
                       f"first_ns={first:.0f} last_ns={last:.0f} "
                       f"e.g. {events[0][0][:60]}")
    return out
