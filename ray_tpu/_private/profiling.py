"""Span profiler: per-process event buffers flushed to the head.

Parity: `src/ray/core_worker/profiling.h:14` (`Profiler`/`ProfileEvent`
batching spans to the GCS ProfileTable) + `python/ray/profiling.py:17`
(`ray.profile` user spans) + `python/ray/state.py:672`
(`chrome_tracing_dump`). Spans are (category, name, start, end) tuples
tagged with pid/role; the head aggregates them and `ray_tpu.timeline()`
renders Chrome-trace JSON viewable in chrome://tracing / Perfetto.

Cross-process causality: spans whose `extra` carries a `flow_id` plus a
`flow` phase ("s" submit / "t" step / "f" finish) additionally emit
Chrome flow events (`ph:"s"/"t"/"f"`, keyed by the task id), so Perfetto
draws arrows from a driver's submit span to the worker's exec span and
the object-transfer spans of that task's results — instead of
disconnected per-process lanes.

On-demand captures (the active profiling plane) also live here:

  - `StackSampler` — a stdlib sampling profiler: a service thread reads
    `sys._current_frames()` at RAY_TPU_PROFILE_HZ and accumulates
    per-thread folded stacks (flamegraph-ready) plus a bounded raw
    sample list with drop accounting. Started/stopped per capture
    window by `run_capture()`, which adds a `jax.profiler` trace for
    the same window in device-owning processes.
  - `sample_once()` — one-shot folded stacks of the current process's
    threads, used by the flight recorder's `profiling` postmortem
    section.
  - `samples_to_chrome()` — re-emits raw samples as Chrome-trace "X"
    events on the same wall clock (`ts = time.time()*1e6`) and pid
    convention (`role:pid`) as the span events above, so sampled
    frames, host spans, and device traces line up in one timeline.
  - `device_memory_stats()` / `publish_device_gauges()` — per-device
    HBM used/peak/limit via `device.memory_stats()`, degrading to
    nothing on backends (CPU) that return None.

The hot loops name their own time with `phase()` (below): one `with`
that is a `jax.profiler.TraceAnnotation` ("ray_tpu.<name>", on the
device trace's clock while a profiler session runs, a no-op outside
one) and an entry in the calling thread's `PhaseClock` (always on;
cumulative seconds and counts that partition the thread's wall time,
and beside each phase's wall seconds the CPU seconds the thread ran in
it, read at the phase boundaries inside a `phase_cpu_reads()` window,
which a capture opens). `clocks()`
lists every bound clock, `process_cpu()` reads the whole
process, and `host_account()` is the delta of two `host_snapshot()`s:
each Python thread's wall and CPU by phase, and the process's CPU less
theirs, which is its native threads'. `run_capture()` returns it as
`host_account` for its window.

`device_account.py`, beside this file, reads those annotations and the
programs' `jax.named_scope`s back out of a trace: device seconds by
scope, module and collective, and the chip's idle seconds by the phase
each loop thread had open. `run_capture()` returns it as
`device_account` for the trace it took, and `scripts profile
--summarize` prints it.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import weakref
from typing import Dict, List, Optional, Set

FLUSH_INTERVAL = 1.0
MAX_BUFFER = 5000

# Flow phases (Chrome trace event format): start / step / end.
FLOW_START, FLOW_STEP, FLOW_END = "s", "t", "f"


class ProfileEvent:
    __slots__ = ("category", "name", "start", "end", "pid", "tid", "extra")

    def __init__(self, category: str, name: str, start: float, end: float,
                 pid: int, tid: int, extra: Optional[dict] = None):
        self.category = category
        self.name = name
        self.start = start
        self.end = end
        self.pid = pid
        self.tid = tid
        self.extra = extra

    def view(self) -> dict:
        d = {"cat": self.category, "name": self.name, "start": self.start,
             "end": self.end, "pid": self.pid, "tid": self.tid}
        if self.extra:
            d["extra"] = self.extra
        return d


class Profiler:
    """Buffers spans; a background thread flushes them to the head."""

    def __init__(self, runtime, role: str):
        self._runtime = runtime
        self.role = role
        self._buf: List[dict] = []
        self._lock = threading.Lock()
        self._stop_event = threading.Event()
        self._dropped_unreported = 0
        self._thread = threading.Thread(
            target=self._flush_loop, daemon=True, name="profiler-flush")
        self._thread.start()

    @property
    def _stopped(self) -> bool:
        return self._stop_event.is_set()

    def record(self, category: str, name: str, start: float, end: float,
               extra: Optional[dict] = None):
        ev = ProfileEvent(category, name, start, end, os.getpid(),
                          threading.get_ident() % 100000, extra).view()
        ev["role"] = self.role
        dropped = 0
        with self._lock:
            self._buf.append(ev)
            if len(self._buf) > MAX_BUFFER:
                # Drop a chunk, not one-by-one: a submit-heavy process
                # overflowing between flushes would otherwise pay an
                # O(buffer) shift per span.
                dropped = len(self._buf) - MAX_BUFFER + MAX_BUFFER // 10
                del self._buf[:dropped]
                self._dropped_unreported += dropped
        if dropped:
            # Silent truncation would make a saturated timeline look
            # complete; count the loss where the metrics plane sees it.
            from . import metrics
            metrics.inc("profile_events_dropped", dropped)

    def span(self, category: str, name: str, extra: Optional[dict] = None):
        return _Span(self, category, name, extra)

    def _flush_loop(self):
        while not self._stop_event.wait(FLUSH_INTERVAL):
            self.flush()

    def flush(self):
        with self._lock:
            if not self._buf and not self._dropped_unreported:
                return
            batch, self._buf = self._buf, []
            dropped, self._dropped_unreported = self._dropped_unreported, 0
        try:
            msg = {"kind": "profile_events", "events": batch}
            if dropped:
                msg["dropped"] = dropped
            self._runtime.head.send(msg)
        except Exception:
            with self._lock:
                self._dropped_unreported += dropped

    def stop(self):
        """Stop flushing and JOIN the flush thread before the final
        flush, so shutdown can't race the loop and lose the last
        batch."""
        self._stop_event.set()
        self._thread.join(timeout=2.0)
        self.flush()


class _Span:
    __slots__ = ("_profiler", "_category", "_name", "_extra", "_start")

    def __init__(self, profiler, category, name, extra):
        self._profiler = profiler
        self._category = category
        self._name = name
        self._extra = extra

    def __enter__(self):
        self._start = time.time()
        return self

    def __exit__(self, *exc):
        self._profiler.record(self._category, self._name, self._start,
                              time.time(), self._extra)
        return False


def chrome_trace(events: List[dict], dropped: int = 0) -> List[dict]:
    """Convert head-collected span dicts to Chrome-trace 'X' events
    (parity: `GlobalState.chrome_tracing_dump`, state.py:672), plus flow
    events (`ph:"s"/"t"/"f"`) for spans carrying a flow context, and a
    metadata record with the cluster-wide dropped-span count."""
    out = []
    for e in events:
        extra = e.get("extra") or {}
        pid = f"{e.get('role', '?')}:{e['pid']}"
        if e.get("cat") == "transfer" and extra.get("bytes"):
            # Derived wire attrs on transfer spans: effective
            # throughput and codec ratio read directly off the slice.
            dur = max(1e-9, e["end"] - e["start"])
            extra = dict(extra)
            extra["mbps"] = round(extra["bytes"] / dur / 1e6, 2)
            if extra.get("wire_bytes"):
                extra["wire_ratio"] = round(
                    extra["wire_bytes"] / extra["bytes"], 3)
        out.append({
            "cat": e.get("cat", ""),
            "name": e.get("name", ""),
            "ph": "X",
            "ts": e["start"] * 1e6,          # microseconds
            "dur": (e["end"] - e["start"]) * 1e6,
            "pid": pid,
            "tid": e["tid"],
            "args": extra,
        })
        flow_id = extra.get("flow_id")
        phase = extra.get("flow")
        if flow_id and phase in (FLOW_START, FLOW_STEP, FLOW_END):
            # Flow events bind by (cat, name, id); the ts sits inside the
            # emitting span so viewers attach the arrow to that slice.
            flow = {"cat": "task_flow", "name": "task_flow", "ph": phase,
                    "id": flow_id, "ts": e["start"] * 1e6,
                    "pid": pid, "tid": e["tid"]}
            if phase == FLOW_END:
                flow["bp"] = "e"  # bind to the enclosing slice
            out.append(flow)
    if dropped:
        out.append({"ph": "M", "name": "ray_tpu_profile_events_dropped",
                    "pid": 0, "tid": 0, "args": {"count": dropped}})
    return out


def dump_chrome_trace(events: List[dict], filename: str,
                      dropped: int = 0) -> str:
    with open(filename, "w") as f:
        json.dump(chrome_trace(events, dropped=dropped), f)
    return filename


# ---------------------------------------------------------------------
# Stack sampling (coordinated on-demand capture)
# ---------------------------------------------------------------------

MAX_STACK_DEPTH = 64
MAX_RAW_SAMPLES = 20_000  # per capture window, per process


def _fold_frame(frame, thread_name: str) -> str:
    """Walk a frame's f_back chain into a root-first folded stack:
    `thread;file:func;file:func;...` — the flamegraph.pl input line
    format (minus the trailing count)."""
    stack = []
    f = frame
    depth = 0
    while f is not None and depth < MAX_STACK_DEPTH:
        code = f.f_code
        stack.append("%s:%s" % (os.path.basename(code.co_filename),
                                code.co_name))
        f = f.f_back
        depth += 1
    stack.reverse()
    return thread_name + ";" + ";".join(stack)


class StackSampler:
    """Stdlib sampling profiler for one bounded capture window.

    A service thread snapshots `sys._current_frames()` at `hz`
    (default RAY_TPU_PROFILE_HZ) and accumulates (a) folded-stack
    counts per thread — flamegraph-ready — and (b) a bounded raw
    sample list (wall-clock timestamped) for Chrome-trace re-emission.
    Overrun ticks and samples past the cap are counted in `dropped`
    rather than silently lost. Lifecycle matches every other service
    thread: `start()`, then `stop()` sets the event and JOINS.
    `thread_names` restricts sampling to those threads (targeted
    straggler captures)."""

    def __init__(self, hz: Optional[float] = None,
                 thread_names: Optional[Set[str]] = None,
                 max_samples: int = MAX_RAW_SAMPLES):
        from . import config
        self.hz = float(hz if hz else config.get("RAY_TPU_PROFILE_HZ"))
        self.hz = max(1.0, min(self.hz, 1000.0))
        self.period = 1.0 / self.hz
        self.thread_names = set(thread_names) if thread_names else None
        self.max_samples = int(max_samples)
        self.folded: Dict[str, int] = {}
        self.samples: List[tuple] = []  # (ts, tid, thread_name, folded)
        self.ticks = 0
        self.dropped = 0
        self.started_at: Optional[float] = None
        self.stopped_at: Optional[float] = None
        self._seen_threads: Set[str] = set()
        self._stop_event = threading.Event()
        self._thread = threading.Thread(
            target=self._sample_loop, daemon=True, name="stack-sampler")

    def start(self) -> "StackSampler":
        self.started_at = time.time()
        self._thread.start()
        return self

    def stop(self) -> "StackSampler":
        self._stop_event.set()
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)
        if self.stopped_at is None:
            self.stopped_at = time.time()
        return self

    def _sample_loop(self):
        next_tick = time.monotonic()
        while not self._stop_event.is_set():
            self._sample_tick()
            next_tick += self.period
            delay = next_tick - time.monotonic()
            if delay <= 0:
                # Sampling overran the period: account the missed ticks
                # and resync instead of spinning to catch up.
                self.dropped += int(-delay / self.period) + 1
                next_tick = time.monotonic() + self.period
                delay = self.period
            self._stop_event.wait(delay)

    def _sample_tick(self):
        now = time.time()
        names = {t.ident: t.name for t in threading.enumerate()}
        me = threading.get_ident()
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue  # never profile the profiler
            name = names.get(tid) or ("tid-%d" % tid)
            if self.thread_names is not None and name not in self.thread_names:
                continue
            folded = _fold_frame(frame, name)
            self.folded[folded] = self.folded.get(folded, 0) + 1
            self._seen_threads.add(name)
            if len(self.samples) < self.max_samples:
                self.samples.append((now, tid % 100000, name, folded))
            else:
                self.dropped += 1
        self.ticks += 1

    def result(self) -> dict:
        return {
            "folded": dict(self.folded),
            "samples": list(self.samples),
            "ticks": self.ticks,
            "dropped": self.dropped,
            "threads": sorted(self._seen_threads),
            "hz": self.hz,
            "start": self.started_at,
            "end": self.stopped_at,
        }


def sample_once() -> Dict[str, str]:
    """One-shot folded stacks of every thread in THIS process (keyed by
    thread name) — the flight recorder's 'what was everyone doing when
    it died' snapshot."""
    names = {t.ident: t.name for t in threading.enumerate()}
    me = threading.get_ident()
    out: Dict[str, str] = {}
    for tid, frame in sys._current_frames().items():
        if tid == me:
            continue
        name = names.get(tid) or ("tid-%d" % tid)
        out[name] = _fold_frame(frame, name)
    return out


# The calling thread's CPU seconds (`CLOCK_THREAD_CPUTIME_ID`); called only
# for a clock that found the thread's CPU clock id at its bind.
_thread_cpu = getattr(time, "thread_time", None)

# Reading that clock is a system call: 0.35 us on a plain Linux kernel and
# 6-44 us on a sandboxed one (the benchmark's TPU hosts, whose clock also
# ticks in steps of 10 ms), where two reads a phase cost an inline cell 8.6 %
# of its rate. So the phases' CPU is read only while somebody asks for it
# (`phase_cpu_reads()`: a capture's window), on every host alike. A thread's
# own total (`cpu_s`) is read at snapshot time and is always there.
_asked = 0  # `phase_cpu_reads()` windows that are open: the phases read
# A plain Linux kernel keeps a thread's run-queue wait in its `schedstat`;
# a sandboxed one (the benchmark's TPU hosts) keeps no such file at all.
_HAS_SCHEDSTAT = os.path.exists("/proc/self/schedstat")
_policy_lock = threading.Lock()  # guards _asked and _clocks


class phase_cpu_reads:
    """`with phase_cpu_reads(): ...` — every phase of every clocked thread
    reads its CPU at both ends while a window is open, and no phase does
    outside one. Windows may overlap."""

    def __enter__(self):
        global _asked
        with _policy_lock:
            _asked += 1
        return self

    def __exit__(self, *exc):
        global _asked
        with _policy_lock:
            _asked -= 1
        return False


class PhaseClock:
    """One loop thread's time by phase since the thread bound the clock:
    `{name: [wall_s, count, cpu_s, read_wall_s]}`, wall seconds beside the
    seconds the thread was RUNNING (its CPU clock), so that a phase's wall
    less its CPU is what the thread spent off the CPU inside it: waiting
    for the GIL, a lock, the device or a core. `cpu_s` and `read_wall_s`
    are of the instances whose CPU was read: those that began inside a
    `phase_cpu_reads()` window. A
    single writer (the bound thread, through `phase()`) and no lock; any
    thread may `snapshot()`. Phases of one thread do not nest, so that
    they partition its wall time and what no phase covers is a number of
    its own (`other_s`, `other_cpu_s`). Where the platform has no
    per-thread CPU clock (`time.pthread_getcpuclockid`) every CPU reading
    is None and the wall side is what it always was."""

    __slots__ = ("_phases", "_open", "_t_start", "_thread", "_native_id",
                 "_cpu_id", "_cpu_start", "_cpu_base", "_cpu_last",
                 "__weakref__")

    def __init__(self):
        self._phases: Dict[str, list] = {}
        # (name, t0, cpu0) of the phase the thread is inside; cpu0 is None
        # for an instance whose CPU is not read
        self._open = None
        self._t_start = time.perf_counter()
        self._thread = None     # the bound threading.Thread
        self._native_id = None  # its kernel id, for /proc/self/task/<id>
        self._cpu_id = None     # its CPU clock, readable from any thread
        self._cpu_start = 0.0   # that clock at the bind
        self._cpu_base = 0.0    # CPU seconds on threads bound before it
        self._cpu_last = 0.0    # the clock as the thread last wrote it

    def bind(self) -> "PhaseClock":
        """Make this the calling thread's clock (the thread's owner calls
        it from that thread; a no-op when it already is). Wall and CPU
        time count from the first bind. A clock handed on to another
        thread keeps what the earlier one spent."""
        me = threading.current_thread()
        if getattr(_thread, "clock", None) is not self \
                or self._thread is not me:
            if not self._phases:
                self._t_start = time.perf_counter()
            if self._thread is not me:
                self._attach(me)
            _thread.clock = self
        return self

    def _attach(self, me: threading.Thread) -> None:
        self._cpu_base += self._cpu_last - self._cpu_start
        getid = getattr(time, "pthread_getcpuclockid", None)
        try:
            cpu_id = getid(me.ident) if getid is not None else None
        except OSError:
            cpu_id = None
        now = 0.0
        if cpu_id is not None:
            now = _thread_cpu()
        self._cpu_start = self._cpu_last = now
        self._native_id = getattr(me, "native_id", None)
        self._thread = me
        self._cpu_id = cpu_id
        with _policy_lock:
            _clocks.add(self)

    @property
    def thread_name(self) -> Optional[str]:
        return None if self._thread is None else self._thread.name

    def seconds(self, name: str) -> float:
        cell = self._phases.get(name)
        return cell[0] if cell else 0.0

    def count(self, name: str) -> int:
        cell = self._phases.get(name)
        return cell[1] if cell else 0

    def _cpu_now(self) -> float:
        """The bound thread's CPU clock, from any thread; the value the
        thread itself last wrote once it is gone."""
        if self._thread.is_alive():
            try:
                return time.clock_gettime(self._cpu_id)
            except OSError:  # it ended between the two lines
                pass
        return self._cpu_last

    def _run_delay(self) -> Optional[float]:
        """Seconds the bound thread was runnable and had no core: field 2
        of its `schedstat`. None where the kernel keeps no such file or
        the thread is gone (its id may by then be another thread's)."""
        if not _HAS_SCHEDSTAT or not self._thread.is_alive():
            return None
        try:
            with open("/proc/self/task/%d/schedstat"
                      % self._native_id) as f:
                return int(f.read().split()[1]) / 1e9
        except (OSError, TypeError, ValueError, IndexError):
            return None

    def snapshot(self) -> dict:
        """Cumulative and monotone: `seconds` and `counts` per phase,
        `wall_s` since the first bind, `other_s` = wall - sum(seconds); on
        the CPU side `cpu_seconds` and `cpu_read_seconds` per phase (the
        CPU and the wall seconds of the instances whose CPU was read, so
        that over a `phase_cpu_reads()` window `cpu_read_seconds` moves as
        `seconds` does), `cpu_s` (the thread's own clock since the first bind,
        whatever was read), `other_cpu_s` = cpu_s - sum(cpu_seconds), and
        `run_delay_s` (the thread's, since it started; read here and
        nowhere else). A phase that is open now counts up to now on both
        clocks, so a thread blocked in one (a full learner queue) does not
        read as `other`."""
        now = time.perf_counter()
        has_cpu = self._cpu_id is not None
        cpu_now = self._cpu_now() if has_cpu else None
        open_ = self._open
        # One C-level copy of the table, safe against the writer; a cell as
        # (wall, count, cpu, read wall).
        cells = {k: tuple(v) for k, v in dict(self._phases).items()}
        if open_ is not None:
            name, t0, c0 = open_
            wall, count, cpu, read_wall = cells.get(name, (0.0, 0, 0.0, 0.0))
            if c0 is not None:
                cpu += max(0.0, cpu_now - c0)
                read_wall += now - t0
            cells[name] = (wall + now - t0, count, cpu, read_wall)
        seconds = {k: v[0] for k, v in cells.items()}
        wall = now - self._t_start
        out = {"wall_s": wall, "other_s": wall - sum(seconds.values()),
               "seconds": seconds,
               "counts": {k: v[1] for k, v in cells.items()},
               "cpu_seconds": None, "cpu_read_seconds": None, "cpu_s": None,
               "other_cpu_s": None, "run_delay_s": None}
        if has_cpu:
            cpu = {k: v[2] for k, v in cells.items()}
            cpu_s = self._cpu_base + cpu_now - self._cpu_start
            out.update(cpu_seconds=cpu,
                       cpu_read_seconds={k: v[3] for k, v in cells.items()},
                       cpu_s=cpu_s, other_cpu_s=cpu_s - sum(cpu.values()),
                       run_delay_s=self._run_delay())
        return out


def _add(a, b):
    return None if a is None or b is None else a + b


def _sub(a, b):
    return None if a is None or b is None else a - b


def sum_snapshots(snapshots: List[dict]) -> dict:
    """Snapshots of several threads added up key by key (shares of the
    summed `wall_s` are then means over the threads). A CPU key that one
    of them lacks is None in the sum."""
    tables = ("seconds", "counts", "cpu_seconds", "cpu_read_seconds")
    scalars = ("wall_s", "other_s", "cpu_s", "other_cpu_s", "run_delay_s")
    out = {key: {} for key in tables}
    out.update({key: 0.0 for key in scalars})
    for snap in snapshots:
        for key in scalars:
            out[key] = _add(out[key], snap.get(key))
        for key in tables:
            table = snap.get(key)
            if table is None or out[key] is None:
                out[key] = None
                continue
            for name, v in table.items():
                out[key][name] = out[key].get(name, 0) + v
    return out


def off_cpu_s(now: dict, was: dict, names) -> Optional[float]:
    """Seconds a thread spent OFF the CPU inside the phases `names` between
    two snapshots of its clock: each phase's wall at the off-CPU share of
    its instances whose CPU was read. None where the clock keeps no CPU
    seconds or no instance of them was read in between."""
    if now.get("cpu_seconds") is None or was.get("cpu_seconds") is None:
        return None
    total, read_any = 0.0, False
    for name in names:
        def delta(key):
            return now[key].get(name, 0.0) - was[key].get(name, 0.0)
        read = delta("cpu_read_seconds")
        if read > 0:
            read_any = True
            total += delta("seconds") * (1.0 - delta("cpu_seconds") / read)
    return total if read_any else None


_thread = threading.local()  # .clock: the PhaseClock bound to this thread
_clocks = weakref.WeakSet()  # every clock a thread has bound, while owned


def clocks() -> List[tuple]:
    """`(thread name, clock)` of every bound `PhaseClock` whose owner
    still holds it, in thread-name order: the Python threads that account
    for their time."""
    with _policy_lock:
        bound = list(_clocks)
    return sorted(((c.thread_name, c) for c in bound),
                  key=lambda pair: pair[0])


def process_cpu() -> dict:
    """CPU seconds of the whole process, native threads included
    (`time.process_time()`), and the cores it may run on."""
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # no affinity on this platform
        cores = os.cpu_count()
    return {"cpu_s": time.process_time(), "cores": cores}


def host_snapshot(named_clocks=None) -> dict:
    """The cumulative host account: a snapshot a clocked thread, the
    process's CPU and the time of the reading. `host_account` takes the
    delta of two. `named_clocks` are `(name, clock)` pairs (default: every
    registered clock of a live thread); a name that is there twice gets
    `#2`, `#3`."""
    if named_clocks is None:  # a thread that has ended accounts for nothing
        named_clocks = [(name, clock) for name, clock in clocks()
                        if clock._thread.is_alive()]
    threads = {}
    for name, clock in named_clocks:
        key, n = name, 1
        while key in threads:
            n += 1
            key = "%s#%d" % (name, n)
        threads[key] = clock.snapshot()
    return {"threads": threads, "process": process_cpu(),
            "t": time.perf_counter()}


def host_account(before: dict, after: dict) -> dict:
    """What the host's threads did between two `host_snapshot`s: a
    thread's wall and CPU seconds by phase ("other" is what no phase
    covers, so a thread's phases add up to its wall; `cpu_s` is of the
    instances whose CPU was read, `cpu_read_s` their wall: the phase's
    wall where every instance was read, 0 where none was, and then the
    thread's CPU in it stands under "other"), its `cpu_s` and
    `run_delay_s`; the process's CPU seconds, its clocked (Python)
    threads' part, the rest (native threads: PJRT's), and `python_cores`
    = clocked CPU seconds over the window, which one GIL holds at 1.
    CPU readings are None where the platform gives none."""
    window = after["t"] - before["t"]
    threads = {}
    python_cpu = 0.0
    for name, now in after["threads"].items():
        was = before["threads"].get(name)
        if was is None:
            continue  # bound inside the window: no delta to take
        has_cpu = now["cpu_seconds"] is not None \
            and was["cpu_seconds"] is not None

        def of_phase(key, p):
            return now[key].get(p, 0) - was[key].get(p, 0)

        phases = {
            p: {"wall_s": of_phase("seconds", p),
                "count": of_phase("counts", p),
                "cpu_s": of_phase("cpu_seconds", p) if has_cpu else None,
                "cpu_read_s": (of_phase("cpu_read_seconds", p)
                               if has_cpu else None)}
            for p in now["seconds"]}
        other_wall = now["other_s"] - was["other_s"]
        phases["other"] = {
            "wall_s": other_wall, "count": 0,
            "cpu_s": _sub(now["other_cpu_s"], was["other_cpu_s"]),
            "cpu_read_s": other_wall if has_cpu else None}
        threads[name] = {
            "wall_s": now["wall_s"] - was["wall_s"],
            "cpu_s": _sub(now["cpu_s"], was["cpu_s"]),
            "run_delay_s": _sub(now["run_delay_s"], was["run_delay_s"]),
            "phases": phases}
        python_cpu = _add(python_cpu, threads[name]["cpu_s"])
    cpu = after["process"]["cpu_s"] - before["process"]["cpu_s"]
    return {"window_s": window, "threads": threads, "process": {
        "cpu_s": cpu, "cores": after["process"]["cores"],
        "python_cpu_s": python_cpu,
        "native_cpu_s": _sub(cpu, python_cpu),
        "python_cores": (python_cpu / window
                         if python_cpu is not None and window > 0
                         else None)}}


def render_host_account(acct: dict, indent: str = "") -> List[str]:
    """The host account as text lines: a table a thread, wall and CPU
    seconds a phase and the share of the wall the thread was off the CPU;
    wall seconds alone where the platform gave no CPU clock. "runnable
    without a core" is `run_delay_s`: printed on a plain Linux box, whose
    kernel keeps `schedstat`, and on no sandboxed host."""
    proc = acct["process"]
    has_cpu = proc["python_cpu_s"] is not None
    out = ["%shost account over %.3f s: " % (indent, acct["window_s"])
           + ("process cpu %.3f s = python threads %.3f s + native threads "
              "%.3f s; python_cores %.2f of %d (1.0 = a full GIL)" % (
                  proc["cpu_s"], proc["python_cpu_s"], proc["native_cpu_s"],
                  proc["python_cores"], proc["cores"])
              if has_cpu else
              "process cpu %.3f s; no per-thread CPU clock here: wall "
              "seconds only" % proc["cpu_s"])]
    for name, t in acct["threads"].items():
        head = "%s  %s: wall %.3f s" % (indent, name, t["wall_s"])
        if t["cpu_s"] is not None:
            head += ", cpu %.3f s" % t["cpu_s"]
        if t["run_delay_s"] is not None:
            head += ", runnable without a core %.3f s" % t["run_delay_s"]
        out.append(head)
        rows = sorted(t["phases"].items(), key=lambda kv: -kv[1]["wall_s"])
        for phase_name, p in rows:
            if p["wall_s"] <= 0 and not p["count"]:
                continue
            line = "%s    %10.6f s wall" % (indent, p["wall_s"])
            if p["cpu_s"] is not None and p["cpu_read_s"] > 0:
                line += " %10.6f s cpu %6.1f %% off-cpu" % (
                    p["cpu_s"], 100.0 * (1.0 - p["cpu_s"] / p["cpu_read_s"]))
                if p["cpu_read_s"] < 0.999 * p["wall_s"]:
                    line += " (of the %.6f s read)" % p["cpu_read_s"]
            elif p["cpu_s"] is not None:
                line += "  cpu not read"
            out.append("%s n=%-7d %s" % (line, p["count"], phase_name))
    return out


class phase:
    """`with phase("sebulba.upload"): ...` — a named step of a loop
    thread. Opens `jax.profiler.TraceAnnotation("ray_tpu.<name>")` (taken
    from `sys.modules`, as `_live_devices()` takes jax: this module never
    imports it) and adds the `perf_counter` delta, the thread's CPU-clock
    delta and a count to the calling thread's `PhaseClock`. A thread with
    no clock gets the annotation only. There is no switch: the
    accumulators are always on (the CPU side while a `phase_cpu_reads()`
    window is open), and "tracing on" means
    a `jax.profiler` session is running."""

    __slots__ = ("_name", "_span", "_clock", "_t0", "_c0")

    def __init__(self, name: str):
        # The phase's time starts here, not in __enter__: `with phase(..)`
        # does both at once, and with several loop threads under one GIL
        # every call is a point where the thread may have to hand the GIL
        # over. Reading the clocks first keeps that wait inside the phase
        # instead of in no phase at all.
        self._t0 = time.perf_counter()
        clock = self._clock = getattr(_thread, "clock", None)
        # None: an instance whose CPU is not read.
        self._c0 = _thread_cpu() if _asked and clock is not None \
            and clock._cpu_id is not None else None
        self._name = name

    def __enter__(self):
        clock = self._clock
        if clock is not None:
            if __debug__ and clock._open is not None:
                raise RuntimeError(
                    f"phase {self._name!r} opened inside "
                    f"{clock._open[0]!r}: phases of a thread do not nest")
            clock._open = (self._name, self._t0, self._c0)
        profiler = sys.modules.get("jax.profiler")
        if profiler is None:
            self._span = None
        else:
            self._span = profiler.TraceAnnotation("ray_tpu." + self._name)
            self._span.__enter__()
        return self

    def then(self, name: str) -> None:
        """End this phase and begin `name`, inside the one `with` — for a
        step that changes its name half way, as when a lock is taken:
        the wait for it, then the work under it. One reading of each
        clock ends the one and begins the other."""
        c0 = self._c0
        self.__exit__(None, None, None)
        self._name = name
        if c0 is None:  # as a phase that begins here
            clock = self._clock
            self._c0 = _thread_cpu() if _asked \
                and clock is not None and clock._cpu_id is not None else None
        self.__enter__()

    def __exit__(self, *exc):
        if self._span is not None:
            self._span.__exit__(*exc)
        clock = self._clock
        if clock is not None:
            clock._open = None
            t0, c0 = self._t0, self._c0
            # Left on the phase for `then()`: where this one ended is
            # where the next begins.
            t1 = self._t0 = time.perf_counter()
            cell = clock._phases.get(self._name)
            if cell is None:
                cell = clock._phases[self._name] = [0.0, 0, 0.0, 0.0]
            cell[0] += t1 - t0
            cell[1] += 1
            if c0 is not None:
                c1 = self._c0 = clock._cpu_last = _thread_cpu()
                cell[2] += c1 - c0
                cell[3] += t1 - t0
        return False


def _live_devices() -> list:
    """This process's local XLA devices, or [] when its own code has not
    brought a jax backend up yet. Telemetry runs on background threads in
    every runtime process; `jax.local_devices()` from one of them would
    *initialise* the backend — and on a TPU host the first process to do
    that takes the chip, whoever was meant to own it. Errors from a live
    backend propagate: a device that stops answering is not "no device".

    Looks only at modules already loaded: an `import` here can run while
    the main thread is halfway through its own `import jax` and hand it a
    partially initialised module.
    """
    bridge = sys.modules.get("jax._src.xla_bridge")
    initialized = getattr(bridge, "backends_are_initialized", None)
    if initialized is None or not initialized():
        return []
    return sys.modules["jax"].local_devices()


def owns_device() -> bool:
    """True when this process has a non-CPU XLA device attached (so a
    `jax.profiler` trace would capture real device activity)."""
    return any(d.platform != "cpu" for d in _live_devices())


def run_capture(duration_s: float, hz: Optional[float] = None,
                thread_names: Optional[Set[str]] = None,
                xla_dir: Optional[str] = None,
                abort_event: Optional[threading.Event] = None) -> dict:
    """Run one bounded capture window in THIS process: stack sampling
    for `duration_s` plus, when `xla_dir` is given and the process owns
    a device, a `jax.profiler` trace over the same window. Returns the
    sampler result augmented with pid/HBM/XLA fields — the per-process
    payload a coordinated capture ships back to the head. Where a trace
    was written, `device_account` is its reduction by the program's own
    names (`device_account.account`; None where it holds no device op);
    whatever goes wrong with the trace or its account is `xla_error`.
    `host_account` is what this process's clocked threads and the process
    as a whole spent over the window (`host_account()` above; the window
    is a `phase_cpu_reads()` one: the phases' CPU is read in it)."""
    from . import config
    duration_s = max(0.05, min(float(duration_s),
                               config.get("RAY_TPU_PROFILE_MAX_S")))
    sampler = StackSampler(hz=hz, thread_names=thread_names).start()
    xla_trace_dir = None
    xla_error = None
    tracing = False
    if xla_dir and owns_device():
        try:
            import jax
            os.makedirs(xla_dir, exist_ok=True)
            # The benchmark's options, so that the two traces are of one
            # kind: host events, no Python frames (the sampler has those).
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(xla_dir, profiler_options=options)
            tracing = True
            xla_trace_dir = xla_dir
        except Exception as e:
            xla_error = "%s: %s" % (type(e).__name__, e)
    with phase_cpu_reads():  # the window's phases read their CPU
        host_before = host_snapshot()
        if abort_event is not None:
            abort_event.wait(duration_s)
        else:
            time.sleep(duration_s)
        host_after = host_snapshot()
    if tracing:
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:
            xla_error = "%s: %s" % (type(e).__name__, e)
            xla_trace_dir = None
    sampler.stop()
    out = sampler.result()
    out["pid"] = os.getpid()
    out["duration_s"] = duration_s
    out["xla_trace_dir"] = xla_trace_dir
    out["host_account"] = host_account(host_before, host_after)
    if xla_trace_dir:
        try:
            from . import device_account
            out["device_account"] = device_account.account(xla_trace_dir)
        except Exception as e:
            xla_error = "%s: %s" % (type(e).__name__, e)
    if xla_error:
        out["xla_error"] = xla_error
    hbm = device_memory_stats()
    if hbm:
        out["hbm"] = hbm
    return out


def samples_to_chrome(proc: dict) -> List[dict]:
    """Re-emit one process's raw stack samples as Chrome-trace "X"
    events on the SAME clock (`ts = wall_time*1e6`) and pid convention
    (`role:pid`) as span events from `chrome_trace()`, so sampled
    frames interleave with task spans in one timeline. Each sample
    renders as a slice one sample-period wide named after its leaf
    frame, with the full folded stack in args."""
    hz = float(proc.get("hz") or 99.0)
    dur_us = 1e6 / hz
    pid = "%s:%s" % (proc.get("role", "?"), proc.get("pid", 0))
    out = []
    for (ts, tid, _name, folded) in proc.get("samples") or ():
        out.append({
            "cat": "stack_sample",
            "name": folded.rsplit(";", 1)[-1],
            "ph": "X",
            "ts": ts * 1e6,
            "dur": dur_us,
            "pid": pid,
            "tid": tid,
            "args": {"stack": folded},
        })
    return out


def top_frames(folded: Dict[str, int], n: int = 10) -> List[tuple]:
    """Hottest leaf frames of a folded-stack dict, as (frame, count,
    share) tuples — the `scripts profile --summarize` view."""
    counts: Dict[str, int] = {}
    total = 0
    for stack, c in folded.items():
        leaf = stack.rsplit(";", 1)[-1]
        counts[leaf] = counts.get(leaf, 0) + c
        total += c
    ranked = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return [(frame, c, (c / total if total else 0.0))
            for frame, c in ranked]


# ---------------------------------------------------------------------
# Device (HBM) telemetry
# ---------------------------------------------------------------------

def device_memory_stats() -> List[dict]:
    """Per-device HBM stats via `device.memory_stats()` for the devices
    this process already holds (`_live_devices`). Backends that report
    none (the CPU backend returns None) contribute no rows."""
    out = []
    for d in _live_devices():
        stats = d.memory_stats()
        if not stats:
            continue
        out.append({
            "device": "d%d" % d.id,
            "platform": d.platform,
            "kind": d.device_kind,
            "used": stats.get("bytes_in_use"),
            "peak": stats.get("peak_bytes_in_use"),
            "limit": stats.get("bytes_limit"),
        })
    return out


def publish_device_gauges() -> int:
    """Publish per-device HBM used/peak/limit into this process's
    metric registry as max-rollup gauges (`hbm_used_bytes.d0`, ...).
    Called from the periodic metric push loops (runtime + node agent);
    returns the number of gauge series set (0 on CPU-only hosts)."""
    stats = device_memory_stats()
    if not stats:
        return 0
    from . import metrics
    n = 0
    for s in stats:
        tag = s["device"]
        for key, gauge in (("used", "hbm_used_bytes"),
                           ("peak", "hbm_peak_bytes"),
                           ("limit", "hbm_limit_bytes")):
            v = s.get(key)
            if v is not None:
                metrics.set_gauge("%s.%s" % (gauge, tag), float(v),
                                  rollup="max")
                n += 1
    return n
