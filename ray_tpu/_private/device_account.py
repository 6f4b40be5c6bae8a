"""The program's account of its own device trace.

`profiling.phase()` writes every loop thread's steps onto a running
`jax.profiler` session's clock (`TraceAnnotation("ray_tpu.<name>")`) and
`jax.named_scope` writes the programs' own names into every op's `tf_op`.
This module reduces one such trace (`*.xplane.pb`: `profiling.run_capture`'s,
the benchmark's, anybody's) by those names:

- device time by scope: the self time of each `XLA Ops` event (its duration
  less the ops nested in it, so the rows add up to busy) keyed by the
  scopes in its `tf_op`, by `XLA Modules` name, and the collectives apart;
- idle time by phase: each chip's idle gaps overlapped with the
  `ray_tpu.*` phases of every host thread that has any, a row a thread
  (host lines are told apart by id: every Python thread's can be named
  `python3`), so that each row adds up to the chip's idle time.

    python -m ray_tpu._private.device_account <file-or-dir> [--window-span NAME]

prints the whole tables. The trace is parsed with `google.protobuf` from
the schema declared below (field numbers of tsl's `xplane.proto`): importing
this module loads neither jax nor tensorflow. Times are whole nanoseconds
on the trace's one clock, truncated from its picoseconds as
`jax.profiler.ProfileData` truncates them, so that the seconds that leave
here equal those the benchmark's `lib/trace.py` reduces from the same file.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
PHASE_PREFIX = "ray_tpu."  # profiling.phase's TraceAnnotation names
OUTER_SCOPE = re.compile(r"(?<![\w.])((?:anakin|train|sebulba)/\w+)")
POLICY_SCOPE = re.compile(r"(?<![\w.])(policy/\w+)")
# The two halves of a rollout that learns in minibatches enclose the other
# `anakin/*` scopes and name the op first (anakin_optimizer's rule).
ENCLOSING = ("anakin/decode", "anakin/learn")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
UNSCOPED = "unscoped|"
OTHER = "other"  # idle seconds of a thread that none of its phases covers
NS = 1e9


@functools.lru_cache(maxsize=None)
def _schema():
    """The message class of `XSpace`, built once from the fields this
    module reads (numbers as in `tsl/profiler/protobuf/xplane.proto`; a
    field left out is skipped by the parser). A pool of its own, so that a
    process that also holds tensorflow's copy of the schema has no clash."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    pkg = "ray_tpu_xplane"
    messages = {
        "XSpace": [(1, "planes", "XPlane", True)],
        "XPlane": [(1, "id", F.TYPE_INT64), (2, "name", F.TYPE_STRING),
                   (3, "lines", "XLine", True),
                   (4, "event_metadata", "EventMetadataEntry", True),
                   (5, "stat_metadata", "StatMetadataEntry", True)],
        "EventMetadataEntry": [(1, "key", F.TYPE_INT64),
                               (2, "value", "XEventMetadata")],
        "StatMetadataEntry": [(1, "key", F.TYPE_INT64),
                              (2, "value", "XStatMetadata")],
        "XLine": [(1, "id", F.TYPE_INT64), (2, "name", F.TYPE_STRING),
                  (3, "timestamp_ns", F.TYPE_INT64),
                  (4, "events", "XEvent", True)],
        "XEvent": [(1, "metadata_id", F.TYPE_INT64),
                   (2, "offset_ps", F.TYPE_INT64),
                   (3, "duration_ps", F.TYPE_INT64),
                   (4, "stats", "XStat", True)],
        "XStat": [(1, "metadata_id", F.TYPE_INT64),
                  (5, "str_value", F.TYPE_STRING),
                  (7, "ref_value", F.TYPE_UINT64)],
        "XEventMetadata": [(1, "id", F.TYPE_INT64), (2, "name", F.TYPE_STRING),
                           (5, "stats", "XStat", True)],
        "XStatMetadata": [(1, "id", F.TYPE_INT64), (2, "name", F.TYPE_STRING)],
    }
    fd = descriptor_pb2.FileDescriptorProto(
        name=pkg + ".proto", package=pkg, syntax="proto3")
    for name, fields in messages.items():
        m = fd.message_type.add(name=name)
        for number, fname, kind, *repeated in fields:
            f = m.field.add(
                name=fname, number=number,
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if isinstance(kind, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".{pkg}.{kind}"
            else:
                f.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(pkg + ".XSpace"))


def find_xplane(path: str) -> Optional[str]:
    """`path` if it is a file, else the newest `*.xplane.pb` under it."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load(path: str):
    """The parsed `XSpace` of a trace file or of a trace directory's newest."""
    found = find_xplane(path)
    if found is None:
        raise FileNotFoundError(f"no *.xplane.pb at or under {path!r}")
    space = _schema()()
    with open(found, "rb") as f:
        space.ParseFromString(f.read())
    return space


# ---------------------------------------------------------------------
# Names
# ---------------------------------------------------------------------

def op_kind(name: str) -> str:
    """`%copy-done.105 = bf16[..] copy-done(..)` -> `copy-done`: the op's
    own name with its trailing number removed."""
    return re.sub(r"\.\d+$", "", name.partition(" = ")[0].lstrip("%"))[:80]


def scope_key(tf_op: str, kind: str) -> str:
    """The row an op's self time goes to: the innermost `anakin/*`,
    `train/*` or `sebulba/*` component of its `tf_op` (`anakin/decode` and
    `anakin/learn` before what they enclose) joined with the innermost
    `policy/*` one; `unscoped|<op kind>` where it has neither."""
    outer = OUTER_SCOPE.findall(tf_op)
    policy = POLICY_SCOPE.findall(tf_op)
    parts = []
    if outer:
        parts.append(next((s for s in ENCLOSING if s in outer), outer[-1]))
    if policy:
        parts.append(policy[-1])
    return "|".join(parts) if parts else UNSCOPED + kind


def _stat_strings(message, stat_names: Dict[int, str]) -> Dict[str, str]:
    """The string-valued stats of an event or of its metadata, by name
    (a `ref_value` names an interned string of the plane's stat table)."""
    out = {}
    for stat in message.stats:
        value = stat.str_value or (
            stat_names.get(stat.ref_value, "") if stat.ref_value else "")
        if value:
            out[stat_names.get(stat.metadata_id, "")] = value
    return out


# ---------------------------------------------------------------------
# Interval arithmetic (whole nanoseconds)
# ---------------------------------------------------------------------

def _union(intervals) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


class _Cover:
    """How much of [t0, t] a set of disjoint sorted intervals covers, by
    bisection: overlaps with many other intervals cost a lookup each."""

    def __init__(self, merged: List[Tuple[int, int]]):
        self._starts = [s for s, _ in merged]
        self._ends = [e for _, e in merged]
        self._before = [0]
        for s, e in merged:
            self._before.append(self._before[-1] + e - s)
        self.total = self._before[-1]

    def upto(self, t: int) -> int:
        i = bisect.bisect_right(self._starts, t)
        if i == 0:
            return 0
        return self._before[i - 1] + min(t, self._ends[i - 1]) \
            - self._starts[i - 1]

    def overlap(self, intervals) -> int:
        return sum(self.upto(e) - self.upto(s) for s, e in intervals)


def _gaps(merged, t0: int, t1: int) -> List[Tuple[int, int]]:
    out, cursor = [], t0
    for start, end in merged:
        if start > cursor:
            out.append((cursor, start))
        cursor = max(cursor, end)
    if t1 > cursor:
        out.append((cursor, t1))
    return out


def _self_times(ops: List[Tuple[int, int, int]]) -> Dict[int, int]:
    """{key: self ns} for [(start, end, key)]: an op's duration less what
    the ops nested inside it cover (a `while` spans its whole body)."""
    out: Dict[int, int] = defaultdict(int)
    stack: List[list] = []
    for start, end, key in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= start:
            top = stack.pop()
            out[top[2]] += top[3]
        if stack:
            stack[-1][3] -= min(end, stack[-1][1]) - start
        stack.append([start, end, key, end - start])
    for top in stack:
        out[top[2]] += top[3]
    return out


# ---------------------------------------------------------------------
# Reading the planes
# ---------------------------------------------------------------------

def _events(line, only=None):
    """(start_ns, end_ns, metadata_id) of every event of a line, or of
    those whose metadata id is in `only`."""
    base = line.timestamp_ns
    for e in line.events:
        if only is None or e.metadata_id in only:
            start = base + e.offset_ps // 1000
            yield start, start + e.duration_ps // 1000, e.metadata_id


def _names(plane) -> Dict[int, str]:
    return {entry.key: entry.value.name for entry in plane.event_metadata}


def _host_planes(space):
    return [p for p in space.planes if not DEVICE_PLANE.match(p.name)]


def _device_planes(space):
    return sorted((p for p in space.planes if DEVICE_PLANE.match(p.name)),
                  key=lambda p: int(p.name.rsplit(":", 1)[1]))


def _span(space, name: str) -> Optional[Tuple[int, int]]:
    """(t0, t1) of the first host event called `name`."""
    for plane in _host_planes(space):
        ids = {k for k, n in _names(plane).items() if n == name}
        if not ids:
            continue
        for line in plane.lines:
            for start, end, _ in _events(line, ids):
                if end > start:
                    return start, end
    return None


def _threads(space) -> List[dict]:
    """Every host line that holds `ray_tpu.*` annotations: its id, its
    name and its phases as {phase: [(start_ns, end_ns)]}."""
    out = []
    for plane in _host_planes(space):
        names = {k: n[len(PHASE_PREFIX):] for k, n in _names(plane).items()
                 if n.startswith(PHASE_PREFIX)}
        if not names:
            continue
        for line in plane.lines:
            phases = defaultdict(list)
            for start, end, key in _events(line, names):
                if end > start:
                    phases[names[key]].append((start, end))
            if phases:
                out.append({"line_id": line.id, "name": line.name,
                            "phases": dict(phases)})
    return out


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def _family(seconds: Dict[str, float]) -> str:
    """A thread's family is the prefix of its phases (`sebulba` = an actor
    thread, `learner`, `anakin`): the one it spent most time in."""
    by_prefix = defaultdict(float)
    for name, s in seconds.items():
        by_prefix[name.partition(".")[0]] += s
    return max(sorted(by_prefix), key=by_prefix.get)


def thread_phases(path: str, window=None) -> List[dict]:
    """The trace's view of each loop thread, to hold against its
    `PhaseClock`: [{"line_id", "name", "family", "seconds": {phase: host
    seconds inside the window}, "counts": {phase: n}}], a row a host line
    that holds `ray_tpu.*` annotations. `window` as `account`'s; None is
    the whole trace (a trace without a device op has phases all the same)."""
    space = load(path)
    bounds = _window(space, window)
    return [_thread_view(t, _inside(t, bounds)) for t in _threads(space)]


def _inside(thread: dict, bounds) -> Dict[str, list]:
    """The thread's phases clipped to the window, those it leaves empty
    dropped; all of them without a window."""
    if bounds is None:
        return thread["phases"]
    clipped = {name: _clip(spans, *bounds)
               for name, spans in thread["phases"].items()}
    return {name: spans for name, spans in clipped.items() if spans}


def _thread_view(thread: dict, inside: Dict[str, list]) -> dict:
    seconds = {name: sum(e - s for s, e in spans) / NS
               for name, spans in inside.items()}
    return {"line_id": thread["line_id"], "name": thread["name"],
            "family": _family(seconds) if seconds else None,
            "seconds": seconds,
            "counts": {name: len(spans) for name, spans in inside.items()}}


def _window(space, window) -> Optional[Tuple[int, int]]:
    """(t0, t1) in ns: of the named host span, of an explicit pair of
    nanoseconds, or (None) of the extent of the device ops; None where
    there is no device op to take an extent of."""
    if isinstance(window, str):
        bounds = _span(space, window)
        if bounds is None:
            raise ValueError(f"the trace holds no host span {window!r}")
        return bounds
    if window is not None:
        return int(window[0]), int(window[1])
    t0 = t1 = None
    for plane in _device_planes(space):
        for line in plane.lines:
            if line.name == OP_LINE:
                for start, end, _ in _events(line):
                    t0 = start if t0 is None else min(t0, start)
                    t1 = end if t1 is None else max(t1, end)
    return None if t0 is None else (t0, t1)


# ---------------------------------------------------------------------
# The account
# ---------------------------------------------------------------------

def _chip(plane, t0: int, t1: int) -> Optional[dict]:
    """One device plane inside the window, in ns: busy, the cover of its
    idle gaps, self time by scope row, collective self time, and
    [ns, launches] by module; None where its `XLA Ops` line is empty."""
    lines = {line.name: line for line in plane.lines}
    if OP_LINE not in lines or not lines[OP_LINE].events:
        return None
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    metadata = {e.key: e.value for e in plane.event_metadata}
    ops = list(_events(lines[OP_LINE]))
    inside = [(max(s, t0), min(e, t1), k) for s, e, k in ops
              if min(e, t1) > max(s, t0)]
    merged = _union((s, e) for s, e, _ in inside)
    rows, collective = defaultdict(int), 0
    for key, ns in _self_times(inside).items():
        meta = metadata.get(key)
        kind = op_kind(meta.name) if meta is not None else "?"
        stats = _stat_strings(meta, stat_names) if meta is not None else {}
        rows[scope_key(stats.get("tf_op", ""), kind)] += ns
        if COLLECTIVE.match(stats.get("hlo_category", "")) \
                or COLLECTIVE.match(kind):
            collective += ns
    modules = defaultdict(lambda: [0, 0])
    launches = list(_events(lines[MODULE_LINE])) \
        if MODULE_LINE in lines else []
    for s, e, k in launches:
        if min(e, t1) > max(s, t0):
            meta = metadata.get(k)
            # "jit_train_fn(123456)" -> "jit_train_fn"
            name = re.sub(r"\(\d+\)$", "", meta.name) if meta else "?"
            modules[name][0] += min(e, t1) - max(s, t0)
            modules[name][1] += 1
    return {"busy": sum(e - s for s, e in merged),
            "idle": _Cover(_gaps(merged, t0, t1)), "rows": rows,
            "collective": collective, "modules": modules,
            "events": len(ops) + len(launches)}


def _idle_tables(threads: List[dict], covers: List[_Cover], t0: int,
                 t1: int) -> dict:
    """The idle side of the account: `threads`, `idle`, `idle_any` and
    `phases`, from each loop thread's phases and each chip's idle gaps."""
    chips = len(covers)
    idle_s = sum(c.total for c in covers) / chips / NS

    def idle_under(spans) -> float:
        return sum(c.overlap(spans) for c in covers) / chips / NS

    views, phase_rows = [], defaultdict(lambda: [0.0, 0, 0])
    open_anywhere = defaultdict(list)
    for thread in threads:
        inside = _inside(thread, (t0, t1))
        if not inside:
            continue
        view = _thread_view(thread, inside)
        row, covered = {}, []
        for name, spans in inside.items():
            row[name] = idle_under(spans)
            covered.extend(spans)
            open_anywhere[name].extend(spans)
            cell = phase_rows[name]
            cell[0] += view["seconds"][name]
            cell[1] += view["counts"][name]
            cell[2] += 1
        # Phases of a thread do not nest (PhaseClock refuses it), so what
        # their union leaves of the idle time is the thread's `other`.
        row[OTHER] = idle_s - idle_under(_union(covered))
        view["idle"] = row
        views.append(view)
    families = defaultdict(list)
    for view in views:
        families[view["family"]].append(view["idle"])
    # A step that several families have (`sebulba.lock_wait` and
    # `learner.lock_wait` wait for one lock) also gets a row `*.<step>`.
    by_step = defaultdict(list)
    for name in open_anywhere:
        by_step["*." + name.partition(".")[2]].append(name)
    for star, names in by_step.items():
        if len(names) > 1:
            open_anywhere[star] = [s for n in names for s in open_anywhere[n]]
    return {
        "threads": views,
        "idle": {family: {"threads": len(rows), "seconds": {
            name: sum(r.get(name, 0.0) for r in rows) / len(rows)
            for name in sorted({n for r in rows for n in r})}}
            for family, rows in sorted(families.items())},
        "idle_any": {name: idle_under(_union(spans))
                     for name, spans in sorted(open_anywhere.items())},
        "phases": {name: {"host_s": c[0], "count": c[1], "threads": c[2]}
                   for name, c in sorted(phase_rows.items())}}


def account(path: str, window=None) -> Optional[dict]:
    """Reduce one trace (a `*.xplane.pb`, or a `jax.profiler` trace
    directory's newest) inside `window`: the name of a host span (the
    benchmark's `bench.slice`), a pair `(t0_ns, t1_ns)`, or None for the
    extent of the device ops. None where the trace holds no device op in
    the window (every CPU run). Seconds are a chip's: the mean over the
    chips, with the per-chip list beside it where it says so.

    {"window_s", "busy_s", "idle_s", "chips", "events",
     "busy_s_per_chip", "idle_s_per_chip",
     "scopes": {scope row: s}, "scopes_per_chip": {scope row: [s]},
     "unscoped_s", "collective_s", "collective_s_per_chip",
     "modules": {module: {"seconds", "launches", "seconds_per_chip"}},
     "threads": [{"line_id", "name", "family", "idle": {phase|"other": s},
                  "seconds": {phase: host s}, "counts": {phase: n}}],
     "idle": {family: {"threads": n, "seconds": {phase|"other": mean s}}},
     "idle_any": {phase: idle s with the phase open on some thread; and
                  "*.<step>" where several families have the step},
     "phases": {phase: {"host_s", "count", "threads"}}}

    The scope rows add up to `busy_s` (`unscoped_s` is the sum of the
    `unscoped|*` rows among them); each thread's `idle` adds up to
    `idle_s`; `launches` are a chip's too. `events` is the number of
    device and phase events read."""
    space = load(path)
    bounds = _window(space, window)
    if bounds is None:
        return None
    t0, t1 = bounds
    found = [_chip(plane, t0, t1) for plane in _device_planes(space)]
    found = [c for c in found if c is not None]
    chips = len(found)
    if not chips or sum(c["busy"] for c in found) <= 0:
        return None

    def per_chip(of) -> List[float]:
        return [of(c) / NS for c in found]

    def mean(values) -> float:
        return sum(values) / chips

    scopes_per_chip = {
        name: per_chip(lambda c: c["rows"].get(name, 0))
        for name in sorted({n for c in found for n in c["rows"]})}
    scopes = {name: mean(v) for name, v in scopes_per_chip.items()}
    modules = {}
    for name in sorted({n for c in found for n in c["modules"]}):
        cells = [c["modules"].get(name, (0, 0)) for c in found]
        seconds = [ns / NS for ns, _ in cells]
        modules[name] = {
            "seconds": mean(seconds), "seconds_per_chip": seconds,
            "launches": mean([n for _, n in cells])}
    threads = _threads(space)
    busy = per_chip(lambda c: c["busy"])
    idle = per_chip(lambda c: c["idle"].total)
    collective = per_chip(lambda c: c["collective"])
    return {
        "window_s": (t1 - t0) / NS, "busy_s": mean(busy),
        "idle_s": mean(idle), "chips": chips,
        "events": sum(c["events"] for c in found) + sum(
            len(spans) for t in threads for spans in t["phases"].values()),
        "busy_s_per_chip": busy, "idle_s_per_chip": idle,
        "scopes": scopes, "scopes_per_chip": scopes_per_chip,
        "unscoped_s": sum(s for n, s in scopes.items()
                          if n.startswith(UNSCOPED)),
        "collective_s": mean(collective),
        "collective_s_per_chip": collective, "modules": modules,
        **_idle_tables(threads, [c["idle"] for c in found], t0, t1)}


# ---------------------------------------------------------------------
# For people
# ---------------------------------------------------------------------

def render(acct: Optional[dict], top: Optional[int] = None,
           indent: str = "") -> List[str]:
    """The account as text lines: scopes by time (`top` of them, all when
    None), modules, collectives, idle by family and phase, `idle_any`."""
    if acct is None:
        return [indent + "no device op in the trace: nothing to account for"]
    busy, idle = acct["busy_s"], acct["idle_s"]

    def share(s, of):
        return f"{100.0 * s / of:6.2f} %" if of > 0 else "      -"

    out = [f"{indent}window {acct['window_s']:.6f} s  busy {busy:.6f} s  "
           f"idle {idle:.6f} s ({share(idle, acct['window_s']).strip()})  "
           f"chips {acct['chips']}  events {acct['events']}",
           f"{indent}device seconds a chip by scope (self time; share of "
           f"busy); unscoped {acct['unscoped_s']:.6f} s "
           f"({share(acct['unscoped_s'], busy).strip()}), collectives "
           f"{acct['collective_s']:.6f} s "
           f"({share(acct['collective_s'], busy).strip()})"]
    rows = sorted(acct["scopes"].items(), key=lambda kv: -kv[1])
    for name, s in rows[:top]:
        out.append(f"{indent}  {s:10.6f} s {share(s, busy)}  {name}")
    if top is not None and len(rows) > top:
        rest = sum(s for _, s in rows[top:])
        out.append(f"{indent}  {rest:10.6f} s {share(rest, busy)}  "
                   f"({len(rows) - top} more rows)")
    if top is None:
        out.append(f"{indent}by module (seconds a chip, launches):")
        for name, m in sorted(acct["modules"].items(),
                              key=lambda kv: -kv[1]["seconds"]):
            out.append(f"{indent}  {m['seconds']:10.6f} s n={m['launches']:<7g}"
                       f" {name}")
    out.append(f"{indent}idle seconds a chip by what each loop thread had "
               f"open (mean over a family's threads; share of idle):")
    for family, table in acct["idle"].items():
        out.append(f"{indent}  {family} x {table['threads']}")
        for name, s in sorted(table["seconds"].items(),
                              key=lambda kv: -kv[1]):
            out.append(f"{indent}    {s:10.6f} s {share(s, idle)}  {name}")
    if acct["idle_any"]:
        out.append(f"{indent}idle seconds with the phase open on at least "
                   f"one thread:")
        for name, s in sorted(acct["idle_any"].items(),
                              key=lambda kv: -kv[1]):
            out.append(f"{indent}    {s:10.6f} s {share(s, idle)}  {name}")
    if top is None and acct["phases"]:
        out.append(f"{indent}host seconds by phase inside the window "
                   f"(all threads; count; threads):")
        for name, p in acct["phases"].items():
            out.append(f"{indent}    {p['host_s']:10.6f} s n={p['count']:<7d}"
                       f" threads={p['threads']}  {name}")
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m ray_tpu._private.device_account",
        description="Device time by named_scope and idle time by phase of "
                    "one jax.profiler trace.")
    ap.add_argument("path", help="a *.xplane.pb, or a trace directory")
    ap.add_argument("--window-span", default=None, metavar="NAME",
                    help="account inside the first host span of this name "
                         "(default: the extent of the device ops)")
    args = ap.parse_args(argv)
    print("\n".join(render(account(args.path, window=args.window_span))))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
