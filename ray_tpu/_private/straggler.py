"""Straggler detection: robust fleet-median outlier flagging.

Podracer-style fleets (PAPERS: "Podracer architectures for scalable
RL") live or die on spotting the slow actor: one delayed rollout worker
drags every batch barrier while the mean throughput still looks
healthy. This module renders per-actor verdicts from two signals the
optimizer already tracks — sampling throughput and fetch latency —
against the FLEET MEDIAN with a MAD-scaled sigma, so one straggler
cannot drag the baseline toward itself the way a mean/stddev test
would (with 1 slow actor of 4, the slow actor inflates the stddev it
is judged against; the median absolute deviation stays anchored on the
healthy majority).

An actor is flagged when

    throughput   <  median - k * sigma      (too slow), or
    fetch latency >  median + k * sigma     (too blocked)

with sigma = 1.4826 * MAD (the normal-consistency constant), floored at
a fraction of the median so a fleet of identical actors (MAD = 0) still
flags a genuinely divergent one instead of dividing by zero.

A reading becomes a flag only when the next window repeats it
(CONFIRM_WINDOWS): throughput is a count of whole fragments over the
window, so in a short window on a busy host a healthy actor thread that
was not scheduled reads one fragment, or none, below its peers, and a
single window cannot tell that from a slow actor. A slow actor is slow
in every window; a starved one is not starved twice in a row.

Consumers (rllib/optimizers/async_samples_optimizer.py): verdicts bump
`straggler_flags_total` (+ a per-actor `straggler_flags.<tag>` series),
annotate the flagged worker's task records via task_events.ANNOTATE,
and ride the optimizer's stats() into the trainer's iteration results
(`result["stragglers"]`). k and the minimum fleet size are the
RAY_TPU_STRAGGLER_K / RAY_TPU_STRAGGLER_MIN_PEERS knobs.

`TriggeredCapture` turns a flag into a diagnosis: with
RAY_TPU_STRAGGLER_PROFILE=1 the optimizer hands each flagged tag to
`maybe_trigger()`, which runs a short stack capture (profiling.py
StackSampler) restricted to exactly the flagged actor's thread and
writes the folded stacks to <session>/logs/ — the flamegraph of what
the slow actor was doing, taken while it was still slow.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Dict, List, Optional

logger = logging.getLogger(__name__)

# MAD -> sigma consistency constant for a normal distribution.
MAD_SIGMA = 1.4826
# sigma floor as a fraction of |median|: identical fleets (MAD = 0)
# still flag an actor deviating by more than k * floor * median.
SIGMA_FLOOR_FRAC = 0.05
# Consecutive windows an actor must read as an outlier to be flagged.
CONFIRM_WINDOWS = 2


def median(values: List[float]) -> float:
    s = sorted(values)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


def robust_sigma(values: List[float], med: Optional[float] = None) -> float:
    if med is None:
        med = median(values)
    mad = median([abs(v - med) for v in values])
    return max(MAD_SIGMA * mad, SIGMA_FLOOR_FRAC * abs(med))


class StragglerDetector:
    """Per-window verdicts + cumulative per-actor flag counts.

    `update()` takes one window's per-actor samples:

        {tag: {"throughput": steps/s, "fetch_latency_s": s-or-None}}

    and returns {tag: verdict} where a verdict carries the `reasons`
    that tripped in this window ("throughput" / "fetch_latency"), the
    fleet baseline it was judged against, `outlier_windows` (how many
    windows in a row it has tripped) and `flagged`: tripped in
    CONFIRM_WINDOWS windows in a row.
    """

    def __init__(self, k: Optional[float] = None,
                 min_peers: Optional[int] = None):
        from . import config
        self.k = config.get("RAY_TPU_STRAGGLER_K") if k is None else k
        self.min_peers = config.get("RAY_TPU_STRAGGLER_MIN_PEERS") \
            if min_peers is None else min_peers
        self.flag_counts: Dict[str, int] = {}
        self.windows = 0
        self._outlier_windows: Dict[str, int] = {}

    def update(self, samples: Dict[str, dict]) -> Dict[str, dict]:
        self.windows += 1
        out: Dict[str, dict] = {
            tag: {"flagged": False, "reasons": [], "outlier_windows": 0,
                  "throughput": s.get("throughput"),
                  "fetch_latency_s": s.get("fetch_latency_s")}
            for tag, s in samples.items()}
        if len(samples) < max(2, self.min_peers):
            self._outlier_windows = {}
            return out

        thr = {t: s["throughput"] for t, s in samples.items()
               if s.get("throughput") is not None}
        if len(thr) >= max(2, self.min_peers):
            med = median(list(thr.values()))
            sigma = robust_sigma(list(thr.values()), med)
            for tag, v in thr.items():
                out[tag]["throughput_median"] = med
                if v < med - self.k * sigma:
                    out[tag]["reasons"].append("throughput")

        lat = {t: s["fetch_latency_s"] for t, s in samples.items()
               if s.get("fetch_latency_s") is not None}
        if len(lat) >= max(2, self.min_peers):
            med = median(list(lat.values()))
            sigma = robust_sigma(list(lat.values()), med)
            for tag, v in lat.items():
                out[tag]["fetch_latency_median"] = med
                if v > med + self.k * sigma:
                    out[tag]["reasons"].append("fetch_latency")

        self._outlier_windows = {
            t: self._outlier_windows.get(t, 0) + 1
            for t, v in out.items() if v["reasons"]}
        for tag, n in self._outlier_windows.items():
            out[tag]["outlier_windows"] = n
            out[tag]["flagged"] = n >= CONFIRM_WINDOWS
        flagged = [t for t, v in out.items() if v["flagged"]]
        if flagged:
            from . import metrics
            for tag in flagged:
                self.flag_counts[tag] = self.flag_counts.get(tag, 0) + 1
                metrics.inc("straggler_flags_total")
                metrics.inc(f"straggler_flags.{tag}")
        return out

    def report(self, verdicts: Dict[str, dict]) -> dict:
        """The stats()/trainer-results view of one window's verdicts."""
        return {
            "flagged": sorted(t for t, v in verdicts.items()
                              if v["flagged"]),
            "flag_counts": dict(self.flag_counts),
            "per_actor": verdicts,
        }


class TriggeredCapture:
    """Straggler flag -> targeted stack capture (the
    RAY_TPU_STRAGGLER_PROFILE plane).

    Each `maybe_trigger(tag, thread_name)` spawns one short bounded
    StackSampler window restricted to `thread_name` and writes the
    folded stacks to `<out_dir>/straggler_profile_<tag>_<n>.folded`
    (flamegraph.pl input). Per-tag throttled: a persistently slow actor
    yields one flamegraph per `min_interval_s`, not one per detector
    window. `paths()` exposes completed captures for the trainer
    report; `stop()` aborts in-flight windows and joins, like every
    other service-thread owner."""

    def __init__(self, out_dir: str, duration_s: float = 0.5,
                 hz: Optional[float] = None,
                 min_interval_s: float = 60.0):
        self.out_dir = out_dir
        self.duration_s = duration_s
        self.hz = hz
        self.min_interval_s = min_interval_s
        self._lock = threading.Lock()
        self._last_trigger: Dict[str, float] = {}
        self._paths: Dict[str, str] = {}
        self._threads: List[threading.Thread] = []
        self._counter = 0
        self._stop_event = threading.Event()

    def maybe_trigger(self, tag: str, thread_name: str) -> bool:
        """Start a capture of `thread_name` for flagged actor `tag`
        unless one ran recently. Returns True when a capture started."""
        now = time.monotonic()
        with self._lock:
            if self._stop_event.is_set():
                return False
            last = self._last_trigger.get(tag)
            if last is not None and now - last < self.min_interval_s:
                return False
            self._last_trigger[tag] = now
            self._counter += 1
            n = self._counter
            self._threads = [t for t in self._threads if t.is_alive()]
            t = threading.Thread(
                target=self._capture, args=(tag, thread_name, n),
                daemon=True, name=f"straggler-profile-{tag}")
            self._threads.append(t)
        t.start()
        return True

    def _capture(self, tag: str, thread_name: str, n: int):
        from . import metrics, profiling
        try:
            res = profiling.run_capture(
                self.duration_s, hz=self.hz,
                thread_names={thread_name},
                abort_event=self._stop_event)
            os.makedirs(self.out_dir, exist_ok=True)
            path = os.path.join(
                self.out_dir, f"straggler_profile_{tag}_{n}.folded")
            with open(path, "w") as f:
                for stack, count in sorted(res["folded"].items()):
                    f.write(f"{stack} {count}\n")
            with self._lock:
                self._paths[tag] = path
            metrics.inc("straggler_profiles_total")
            logger.warning(
                "straggler %s: captured %d stack sample(s) of thread "
                "%r -> %s", tag, sum(res["folded"].values()),
                thread_name, path)
        except Exception:
            logger.warning("straggler capture for %s failed", tag,
                           exc_info=True)

    def paths(self) -> Dict[str, str]:
        """tag -> folded-stack file of the latest completed capture."""
        with self._lock:
            return dict(self._paths)

    def stop(self):
        self._stop_event.set()
        with self._lock:
            threads = list(self._threads)
        me = threading.current_thread()
        for t in threads:
            if t is not me:
                t.join(timeout=2.0)
