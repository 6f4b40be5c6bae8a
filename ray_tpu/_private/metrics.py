"""Metrics registry: counters + gauges + histograms, cluster-aggregated.

Parity: the reference's OpenCensus measures + Prometheus exposer
(`src/ray/stats/metric.h:7-10`, definitions `metric_defs.h:23`, wired in
daemon mains `raylet/main.cc:27-30`). The TPU re-architecture keeps the
shape — every process owns a cheap in-process registry; the head
aggregates (sum per metric name, per node) from periodic pushes — and
serves both machine formats:

  - JSON over the control protocol (`get_metrics`) for `ray_tpu stat
    --metrics` and programmatic use;
  - Prometheus text exposition over HTTP when `RAY_TPU_METRICS_PORT` is
    set (the head binds it; scrape `/metrics`).

Usage from anywhere inside the runtime (driver, worker, head):

    from ray_tpu._private import metrics
    metrics.inc("tasks_executed")
    metrics.set_gauge("store_used_bytes", n)
    metrics.observe("get_wall_s", dt)          # histogram sample
    with metrics.timer("serve_route_s"): ...   # timed block

Three series kinds with distinct merge semantics:

  - counters: monotone totals; merge = sum (cluster-lifetime).
  - gauges: point-in-time; merge per the gauge's DECLARED roll-up —
    sum (default: store bytes, queue depths), mean (percentages,
    per-actor utilization shares; a fleet of 4 actors at ~97% must
    read ~97%, not 387%), or max (high-water marks). Declarations
    travel inside each snapshot so the head applies them without
    sharing registry state.
  - histograms: log-bucketed distributions (`observe`/`timer`).
    Buckets are geometric with ratio HIST_FACTOR; merging across
    processes is exact (bucket counts sum), and any quantile estimate
    read off a bucket upper bound is within a factor of HIST_FACTOR
    of a true sample — the relative error bound the quantile tests
    assert. Exposed as Prometheus `histogram` type (`_bucket{le=}` /
    `_sum` / `_count`) and as p50/p95/p99 in the JSON aggregate.

Data-plane series (striped transfers + wire codec, runtime.py):
counters `wire_bytes_on_wire` / `wire_bytes_raw` / `wire_bytes_saved` /
`wire_bytes_recv` / `wire_chunks_compressed` / `wire_chunks_raw` /
`wire_stripe_retries`; gauges `wire_stripes_active` (objects currently
striping out) and `wire_send_mbps` (per-peer throughput EMA summed per
process — the per_node breakdown keeps it attributable); histogram
`wire_chunk_send_s` (per-chunk stripe send wall time).

Distribution-plane series (location directory + tree broadcast,
runtime.py): counters `object_fetch_source.owner` / `.replica` /
`.local_shm` (every borrowed-object fetch attributed to its source),
`object_fetch_dedup_waits` (same-node fetches coalesced into a
sibling's wire transfer), `object_fetch_redirects_issued` /
`object_fetch_redirects_followed` (owner fan-out cap), and
`object_fetch_replica_fallbacks` (stale/dead replica -> owner); gauge
`broadcast_fanout` (owner's peak concurrent uploads of one object).

Tail-plane series (this PR): histograms `get_wall_s` / `put_wall_s`
(driver-visible object plane), `task_queue_wait_s` / `task_exec_s`
(derived head-side from the task-lifecycle ring on terminal
transitions), `weight_sync_encode_s` / `weight_sync_apply_s`,
`serve_route_s`, `learner_queue_wait_s` / `learner_grad_s`; counter
`straggler_flags_total` (straggler.py detector verdicts).

Sebulba pipeline series (inline-actor device rollouts,
rllib/optimizers/async_samples_optimizer.py `InlineActorThread`):
per-actor gauges `sebulba_action_fetch_pct.aK` (share of the actor's
wall-clock blocked on the device action round-trip — the r5 wall this
plane exists to watch), `sebulba_env_step_pct.aK` (host env stepping),
`sebulba_gil_wait_pct.aK` (wall less CPU seconds of the actor's env-step
and record phases, which block on nothing but the interpreter: the share
of its wall-clock it wanted the GIL or a core and had neither; updated
for fragments sampled inside a `profiling.phase_cpu_reads()` window, as
`ray_tpu profile` opens one, and absent where the platform has no
per-thread CPU clock),
and `sebulba_policy_lag_steps.aK` (mean behavior-policy selection lag
per transition under `sebulba_onchip_steps` windows). Updated at
sample-fragment boundaries; declared with mean roll-up so the cluster
series stays a percentage; per-actor values remain under `per_node`.

Profiling-plane series (profiling.py + the coordinated-capture
tentpole): max-rollup gauges `hbm_used_bytes.dK` / `hbm_peak_bytes.dK`
/ `hbm_limit_bytes.dK` (per-device `device.memory_stats()` watermarks,
published continuously by the node agents and every runtime process
that imported jax; absent on CPU-only hosts) and `node_mem_frac`
(host-memory pressure, the heartbeat field promoted to a proper gauge
with per-node series); counter `straggler_profiles_total`
(RAY_TPU_STRAGGLER_PROFILE auto-captures fired).

Fleet-plane series (_private/fleet.py FleetController): gauge
`fleet_size` (live remote-sampler count; default sum roll-up so
several optimizers' fleets read as one cluster total), counters
`fleet_joins_total` / `fleet_evictions_total` (every membership
change, including chaos preemptions), and histogram `actor_recovery_s`
(evict/death to the replacement's first harvested sample — the
recovery-latency distribution `scripts fleet`, `scripts stat
--metrics`, debug_dump and the bench snapshot report).

Head-shard-plane series (_private/head_shards.py + the partitioned
control plane): histogram `head_lock_wait_s` (wait time of every
CONTENDED head-shard lock acquire — uncontended acquires record
nothing, so the histogram directly measures convoying; the saturation
bench reports its tails before/after sharding); mean-rollup gauges
`head_shard_occupancy.s<k>` (per-shard lock duty cycle over the
monitor loop's ~2s windows) plus `head_shard_kv.s<k>` /
`head_shard_locations.s<k>` table sizes; client-side directory-cache
counters `object_dir_lookups` / `object_dir_cache_hits` /
`object_dir_rpcs` (steady-state routed fetches must show lookups
growing while rpcs stay flat — the zero-RPC acceptance gate).
"""

from __future__ import annotations

import contextlib
import math
import re
import time
from typing import Dict, Optional

from .graftcheck import racecheck
from .graftcheck.runtime_trace import make_lock


def _fresh_registry():
    """Registry tables + their lock, built through the graftcheck
    factories: plain dicts and a plain threading.Lock normally; under
    RAY_TPU_RACECHECK/RAY_TPU_LOCKCHECK, access-recording proxies and a
    traced lock (the metrics registry is one of the instrumented hot
    shared structures — every process thread incs/observes into it
    while the push loop snapshots)."""
    return (make_lock("metrics._lock"),
            racecheck.traced_shared({}, "metrics._counters"),
            racecheck.traced_shared({}, "metrics._gauges"),
            racecheck.traced_shared({}, "metrics._hists"),
            racecheck.traced_shared({}, "metrics._rollups"))


_lock, _counters, _gauges, _hists, _rollups = _fresh_registry()

# Geometric bucket ratio for histograms. 2**0.25 bounds any quantile
# estimate's relative error by HIST_FACTOR - 1 (~18.9%) while keeping
# the bucket count for a 1us..1000s latency range around 80.
HIST_FACTOR = 2.0 ** 0.25
_LOG_FACTOR = math.log(HIST_FACTOR)
# Non-positive samples land in one underflow bucket below every real
# sample (observe() clamps to this floor).
_HIST_MIN = 1e-9

ROLLUPS = ("sum", "mean", "max")


def inc(name: str, value: float = 1.0) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0.0) + value


def set_gauge(name: str, value: float, rollup: Optional[str] = None) -> None:
    with _lock:
        _gauges[name] = float(value)
        if rollup is not None and rollup != "sum":
            _rollups[name] = rollup


def declare_gauge(name: str, rollup: str) -> None:
    """Declare a gauge's cross-process roll-up: sum (default), mean, or
    max. The declaration ships inside every snapshot so the head merges
    correctly without shared registry state."""
    if rollup not in ROLLUPS:
        raise ValueError(f"rollup must be one of {ROLLUPS}: {rollup!r}")
    with _lock:
        if rollup == "sum":
            _rollups.pop(name, None)
        else:
            _rollups[name] = rollup


def bucket_index(value: float) -> int:
    """Index i such that HIST_FACTOR**(i-1) < value <= HIST_FACTOR**i."""
    v = max(float(value), _HIST_MIN)
    # ceil with a tolerance so exact bucket bounds stay in their bucket.
    return math.ceil(math.log(v) / _LOG_FACTOR - 1e-9)


def bucket_upper(index: int) -> float:
    return HIST_FACTOR ** index


def observe(name: str, value: float) -> None:
    """Record one sample into the named log-bucketed histogram."""
    v = float(value)
    idx = bucket_index(v)
    with _lock:
        h = _hists.get(name)
        if h is None:
            h = _hists[name] = {"buckets": {}, "sum": 0.0, "count": 0.0,
                                "min": v, "max": v}
        b = h["buckets"]
        b[idx] = b.get(idx, 0.0) + 1.0
        h["sum"] += v
        h["count"] += 1.0
        if v < h["min"]:
            h["min"] = v
        if v > h["max"]:
            h["max"] = v


@contextlib.contextmanager
def timer(name: str):
    """Time a block into histogram `name` (seconds)."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        observe(name, time.perf_counter() - t0)


def snapshot() -> Dict[str, dict]:
    """This process's registry: counters, gauges, histograms, and the
    gauge roll-up declarations that travel with them."""
    with _lock:
        return {"counters": dict(_counters), "gauges": dict(_gauges),
                "hists": {k: {"buckets": dict(h["buckets"]),
                              "sum": h["sum"], "count": h["count"],
                              "min": h["min"], "max": h["max"]}
                          for k, h in _hists.items()},
                "rollups": dict(_rollups)}


def reset() -> None:
    """Test helper: drops the registry and rebuilds it through the
    traced factories, re-reading the RACECHECK/LOCKCHECK knobs — so a
    harness that arms the race plane mid-process (graftcheck/stress.py)
    gets an instrumented registry, and disarming restores raw tables."""
    global _lock, _counters, _gauges, _hists, _rollups
    _lock, _counters, _gauges, _hists, _rollups = _fresh_registry()


def merge_hist(dst: dict, src: dict) -> None:
    """Fold one histogram snapshot into an accumulator in place. Exact:
    bucket counts/sums add, min/max extend."""
    b = dst.setdefault("buckets", {})
    for k, v in (src.get("buckets") or {}).items():
        k = int(k)
        b[k] = b.get(k, 0.0) + v
    dst["sum"] = dst.get("sum", 0.0) + (src.get("sum") or 0.0)
    dst["count"] = dst.get("count", 0.0) + (src.get("count") or 0.0)
    for key, pick in (("min", min), ("max", max)):
        if src.get(key) is not None:
            dst[key] = src[key] if dst.get(key) is None \
                else pick(dst[key], src[key])


def hist_quantile(h: dict, q: float) -> Optional[float]:
    """Quantile estimate from bucket counts: the upper bound of the
    bucket holding the q-th sample, clamped to the observed min/max.
    Within a factor of HIST_FACTOR of a true sample value."""
    count = h.get("count") or 0.0
    if count <= 0:
        return None
    target = q * count
    cum = 0.0
    for idx in sorted(int(k) for k in (h.get("buckets") or {})):
        cum += h["buckets"][idx]
        if cum >= target - 1e-9:
            est = bucket_upper(idx)
            if h.get("max") is not None:
                est = min(est, h["max"])
            if h.get("min") is not None:
                est = max(est, h["min"])
            return est
    return h.get("max")


def hist_summary(h: dict) -> dict:
    """p50/p95/p99 + count/mean for the JSON aggregate and the CLI."""
    count = h.get("count") or 0.0
    return {
        "count": count,
        "sum": h.get("sum") or 0.0,
        "mean": (h.get("sum") or 0.0) / count if count else None,
        "min": h.get("min"),
        "max": h.get("max"),
        "p50": hist_quantile(h, 0.50),
        "p95": hist_quantile(h, 0.95),
        "p99": hist_quantile(h, 0.99),
    }


def aggregate(per_process: Dict[str, dict]) -> Dict[str, dict]:
    """Merge per-process snapshots. Counters sum. Gauges merge per their
    declared roll-up (sum by default — per-process quantities like store
    bytes want a cluster total; mean for percentages; max for
    high-water marks). Histogram buckets sum exactly. The cluster
    totals lose where the bytes/tasks actually live, so `per_node`
    additionally carries the same roll-up grouped by node, letting the
    dashboard and Prometheus label series by node. `quantiles` carries
    a p50/p95/p99 summary per histogram for JSON consumers."""
    counters: Dict[str, float] = {}
    hists: Dict[str, dict] = {}
    rollups: Dict[str, str] = {}
    gauge_samples: Dict[str, list] = {}
    per_node: Dict[str, dict] = {}
    node_gauge_samples: Dict[str, Dict[str, list]] = {}
    for snap in per_process.values():
        node_id = snap.get("node") or "node0"
        node = per_node.setdefault(
            node_id, {"counters": {}, "gauges": {}, "hists": {}})
        for k, v in (snap.get("counters") or {}).items():
            counters[k] = counters.get(k, 0.0) + v
            node["counters"][k] = node["counters"].get(k, 0.0) + v
        for k, r in (snap.get("rollups") or {}).items():
            if r in ROLLUPS:
                rollups[k] = r
        for k, v in (snap.get("gauges") or {}).items():
            gauge_samples.setdefault(k, []).append(v)
            node_gauge_samples.setdefault(node_id, {}) \
                .setdefault(k, []).append(v)
        for k, h in (snap.get("hists") or {}).items():
            merge_hist(hists.setdefault(k, {}), h)
            merge_hist(node["hists"].setdefault(k, {}), h)

    def _roll(name: str, samples: list) -> float:
        r = rollups.get(name, "sum")
        if r == "mean":
            return sum(samples) / len(samples)
        if r == "max":
            return max(samples)
        return sum(samples)

    gauges = {k: _roll(k, vs) for k, vs in gauge_samples.items()}
    for node_id, node in per_node.items():
        node["gauges"] = {
            k: _roll(k, vs)
            for k, vs in node_gauge_samples.get(node_id, {}).items()}
    return {"counters": counters, "gauges": gauges, "hists": hists,
            "quantiles": {k: hist_summary(h) for k, h in hists.items()},
            "rollups": rollups, "per_node": per_node}


_INVALID_METRIC_CHARS = re.compile(r"[^a-zA-Z0-9_:]")


def sanitize_name(name: str) -> str:
    """Prometheus metric names allow [a-zA-Z_:][a-zA-Z0-9_:]* — a metric
    like `store.used-bytes` must not emit an invalid exposition line."""
    s = _INVALID_METRIC_CHARS.sub("_", name)
    if not s or s[0].isdigit():
        s = "_" + s
    return s


def escape_label_value(value: str) -> str:
    """Prometheus label-value escaping: backslash, quote, newline."""
    return str(value).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _fmt_le(bound: float) -> str:
    # Stable short form for bucket bounds (repr noise like
    # 1.1892071150027212 would make the exposition unreadable).
    return f"{bound:.6g}"


def prometheus_text(agg: Dict[str, dict],
                    prefix: str = "ray_tpu_") -> str:
    """Prometheus text exposition format (one TYPE line per metric).
    Counters and gauges additionally expose per-node labeled series
    when the aggregate carries a `per_node` breakdown; histograms emit
    the standard cumulative `_bucket{le=}` / `_sum` / `_count` trio."""
    per_node = agg.get("per_node") or {}
    out = []
    for name, value in sorted((agg.get("counters") or {}).items()):
        n = prefix + sanitize_name(name)
        out.append(f"# TYPE {n} counter")
        out.append(f"{n} {value:g}")
        for node_id in sorted(per_node):
            v = per_node[node_id]["counters"].get(name)
            if v is not None:
                node_l = escape_label_value(node_id)
                out.append(f'{n}{{node="{node_l}"}} {v:g}')
    for name, value in sorted((agg.get("gauges") or {}).items()):
        n = prefix + sanitize_name(name)
        out.append(f"# TYPE {n} gauge")
        out.append(f"{n} {value:g}")
        for node_id in sorted(per_node):
            v = per_node[node_id].get("gauges", {}).get(name)
            if v is not None:
                node_l = escape_label_value(node_id)
                out.append(f'{n}{{node="{node_l}"}} {v:g}')
    for name, h in sorted((agg.get("hists") or {}).items()):
        n = prefix + sanitize_name(name)
        out.append(f"# TYPE {n} histogram")
        cum = 0.0
        for idx in sorted(int(k) for k in (h.get("buckets") or {})):
            cum += h["buckets"][idx]
            out.append(
                f'{n}_bucket{{le="{_fmt_le(bucket_upper(idx))}"}} {cum:g}')
        out.append(f'{n}_bucket{{le="+Inf"}} {h.get("count", 0.0):g}')
        out.append(f'{n}_sum {h.get("sum", 0.0):g}')
        out.append(f'{n}_count {h.get("count", 0.0):g}')
    return "\n".join(out) + "\n"
