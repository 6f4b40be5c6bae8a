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
cumulative seconds and counts that partition the thread's wall time).

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


class PhaseClock:
    """One loop thread's wall time by phase: `{name: [seconds, count]}`
    since the thread bound the clock. A single writer (the bound thread,
    through `phase()`) and no lock; any thread may `snapshot()`. Phases
    of one thread do not nest, so that they partition its wall time and
    what no phase covers is a number of its own (`other_s`)."""

    __slots__ = ("_phases", "_open", "_t_start")

    def __init__(self):
        self._phases: Dict[str, list] = {}
        self._open = None  # (name, t0) of the phase the thread is inside
        self._t_start = time.perf_counter()

    def bind(self) -> "PhaseClock":
        """Make this the calling thread's clock (the thread's owner calls
        it from that thread; a no-op when it already is). Wall time
        counts from the first bind."""
        if getattr(_thread, "clock", None) is not self:
            if not self._phases:
                self._t_start = time.perf_counter()
            _thread.clock = self
        return self

    def seconds(self, name: str) -> float:
        cell = self._phases.get(name)
        return cell[0] if cell else 0.0

    def snapshot(self) -> dict:
        """Cumulative and monotone: `seconds` and `counts` per phase,
        `wall_s` since the first bind, `other_s` = wall - sum(seconds).
        A phase that is open now counts up to now, so a thread blocked
        in one (a full learner queue) does not read as `other`."""
        now = time.perf_counter()
        open_ = self._open
        cells = dict(self._phases)  # one C-level copy: safe against the writer
        seconds = {k: v[0] for k, v in cells.items()}
        counts = {k: v[1] for k, v in cells.items()}
        if open_ is not None:
            seconds[open_[0]] = seconds.get(open_[0], 0.0) + now - open_[1]
            counts.setdefault(open_[0], 0)
        wall = now - self._t_start
        return {"wall_s": wall, "other_s": wall - sum(seconds.values()),
                "seconds": seconds, "counts": counts}


def sum_snapshots(snapshots: List[dict]) -> dict:
    """Snapshots of several threads added up key by key (shares of the
    summed `wall_s` are then means over the threads)."""
    out = {"wall_s": 0.0, "other_s": 0.0, "seconds": {}, "counts": {}}
    for snap in snapshots:
        out["wall_s"] += snap["wall_s"]
        out["other_s"] += snap["other_s"]
        for key in ("seconds", "counts"):
            for name, v in snap[key].items():
                out[key][name] = out[key].get(name, 0) + v
    return out


_thread = threading.local()  # .clock: the PhaseClock bound to this thread


class phase:
    """`with phase("sebulba.upload"): ...` — a named step of a loop
    thread. Opens `jax.profiler.TraceAnnotation("ray_tpu.<name>")` (taken
    from `sys.modules`, as `_live_devices()` takes jax: this module never
    imports it) and adds the `perf_counter` delta and a count to the
    calling thread's `PhaseClock`. A thread with no clock gets the
    annotation only. There is no switch: the accumulators are always on,
    and "tracing on" means a `jax.profiler` session is running."""

    __slots__ = ("_name", "_span", "_clock", "_t0")

    def __init__(self, name: str):
        # The phase's time starts here, not in __enter__: `with phase(..)`
        # does both at once, and with several loop threads under one GIL
        # every call is a point where the thread may have to hand the GIL
        # over. Reading the clock first keeps that wait inside the phase
        # instead of in no phase at all.
        self._t0 = time.perf_counter()
        self._name = name

    def __enter__(self):
        clock = self._clock = getattr(_thread, "clock", None)
        if clock is not None:
            if __debug__ and clock._open is not None:
                raise RuntimeError(
                    f"phase {self._name!r} opened inside "
                    f"{clock._open[0]!r}: phases of a thread do not nest")
            clock._open = (self._name, self._t0)
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
        the wait for it, then the work under it."""
        self.__exit__(None, None, None)
        self.__init__(name)
        self.__enter__()

    def __exit__(self, *exc):
        if self._span is not None:
            self._span.__exit__(*exc)
        clock = self._clock
        if clock is not None:
            clock._open = None
            dt = time.perf_counter() - self._t0
            cell = clock._phases.get(self._name)
            if cell is None:
                clock._phases[self._name] = [dt, 1]
            else:
                cell[0] += dt
                cell[1] += 1
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
    whatever goes wrong with the trace or its account is `xla_error`."""
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
    if abort_event is not None:
        abort_event.wait(duration_s)
    else:
        time.sleep(duration_s)
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
