"""Central config registry: every tunable, typed, in one place.

Parity: `src/ray/common/ray_config_def.h:17-200` — the reference
declares every knob once (name, type, default) and generates accessors;
scattered env reads don't exist. Same contract here: modules call
`config.get("RAY_TPU_X")`, the registry owns the type/default/doc, env
vars override, and `ray_tpu.scripts stat --config` dumps the effective
values. Adding a knob = adding one `_def(...)` line; `get()` on an
unregistered name raises, which is what keeps ad-hoc `os.environ`
tunables from creeping back in.

Identity/plumbing variables (RAY_TPU_NODE_ID, RAY_TPU_WORKER_TOKEN,
RAY_TPU_ADDRESS, session paths) are not tunables and stay out.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Dict, Optional


@dataclass(frozen=True)
class ConfigDef:
    name: str
    type: type
    default: Any
    doc: str


_DEFS: Dict[str, ConfigDef] = {}


def _def(name: str, typ: type, default, doc: str) -> None:
    _DEFS[name] = ConfigDef(name, typ, default, doc)


# --- object store / eviction -----------------------------------------
_def("RAY_TPU_OBJECT_STORE_CAPACITY", int, None,
     "Node object-store capacity in bytes (default: 30% of the shm "
     "filesystem)")
_def("RAY_TPU_SHM_DIR", str, "/dev/shm",
     "Directory backing the node-shared object store")
_def("RAY_TPU_EVICTION_GRACE_S", float, 10.0,
     "Eviction grace for refs exported OUTSIDE a protocol send "
     "(unknown destination; the ack_export pin protocol covers the "
     "rest)")
_def("RAY_TPU_EXPORT_PIN_TIMEOUT_S", float, 120.0,
     "Leak backstop for export pins whose ack never arrives")
_def("RAY_TPU_LINEAGE_MAX_SPECS", int, 10000,
     "Retained task specs for owner-side result reconstruction (LRU)")

# --- inter-node data plane (striped transfers + wire codec) -----------
_def("RAY_TPU_TRANSFER_STREAMS", int, min(4, os.cpu_count() or 1),
     "Transfer connections per peer for large-object striping "
     "(<=1 reverts to the single-stream control-connection path; "
     "default scales with cores — stripe threads on a 1-core box "
     "only add handoffs)")
_def("RAY_TPU_OBJECT_CHUNK_SIZE", int, 8 * 1024 * 1024,
     "Max bytes per inter-node object chunk")
_def("RAY_TPU_WIRE_STRIPE_MIN", int, 512 * 1024,
     "Objects at or below this ship as one message on the control "
     "connection; larger ones stripe across the transfer pool")
_def("RAY_TPU_WIRE_COMPRESSION", str, "auto",
     "Per-chunk wire compression: on | off | auto (auto skips the "
     "codec on links faster than the codec itself)")
_def("RAY_TPU_WIRE_COMPRESSION_MIN_RATIO", float, 0.9,
     "Probe/chunk compression ratio that must be beaten for a chunk "
     "to ship compressed")
_def("RAY_TPU_WIRE_COMPRESSION_MAX_LINK_MBPS", float, 200.0,
     "In auto mode, peers whose observed wire throughput exceeds this "
     "skip the codec (compressing for a link faster than the codec "
     "only adds latency)")
_def("RAY_TPU_GET_PREFETCH", int, 8,
     "Parallel fetch window for multi-ref get()/wait(): pending "
     "foreign refs are requested concurrently up to this many at once")

# --- weight-sync delta plane (_private/weight_sync.py) ----------------
_def("RAY_TPU_WEIGHT_CODEC", str, "q8_delta",
     "Weight broadcast codec when trainers leave weight_sync_codec="
     "'auto': full (ship the whole float32 tree every sync) | q8_delta "
     "(int8 block-quantized deltas with sender-side error feedback; "
     "receivers with a stale/missing base transparently get a full "
     "blob via the version handshake)")
_def("RAY_TPU_WEIGHT_SHARDS", int, 1,
     "Shard count for weight-sync payloads: the flattened f32 "
     "parameter vector splits into this many equal byte ranges that "
     "encode/ship/apply independently (each learner replica broadcasts "
     "only its shard)")
_def("RAY_TPU_PARAM_SHARDING", str, "replicate",
     "Learner parameter/optimizer-state partition rule table "
     "(spec_layout.RULE_TABLES): replicate (legacy layout) | fsdp "
     "(shard large params + optax moments over the dp axis so each "
     "replica owns only its slice of the weight update)")

# --- learner compute precision (parallel/precision.py) ----------------
_def("RAY_TPU_COMPUTE_DTYPE", str, "f32",
     "Learner forward/backward compute dtype when trainers leave "
     "compute_dtype='auto': f32 | bf16 (parameters cast to bfloat16 at "
     "the loss boundary only — fp32 master weights, f32 gradients and "
     "optax state; bf16's f32-equal exponent range needs no loss "
     "scaling)")

# --- object distribution (location directory + tree broadcast) --------
_def("RAY_TPU_LOCATION_FETCH", bool, True,
     "Location-aware object distribution: nodes register sealed "
     "fetched copies in the head's location directory, fetches prefer "
     "a local/least-loaded replica over the owner, same-node fetches "
     "of one object coalesce into a single wire transfer, and owners "
     "at their upload cap redirect borrowers to a finished replica "
     "(0 reverts to owner-only point-to-point fetch)")
_def("RAY_TPU_MAX_UPLOADS_PER_OBJECT", int, 2,
     "Concurrent outbound transfers of ONE object an owner serves "
     "before redirecting further borrowers to an already-complete "
     "replica — the bounded fan-out that turns a 1->N broadcast into "
     "a tree (only enforced while RAY_TPU_LOCATION_FETCH is on)")

# --- head sharding (partitioned control plane; _private/head_shards.py)
_def("RAY_TPU_HEAD_SHARDS", int, min(8, max(2, (os.cpu_count() or 2) // 2)),
     "Shard count for the head's hot tables (KV store, object-location "
     "directory, metric snapshots, task ring): keys route to "
     "crc32(key) % N planes each behind its own lock, so concurrent "
     "clients stop convoying on one global RLock. 1 = the unsharded "
     "layout (single plane, still behind a shard lock). Default scales "
     "with cores; cross-shard reads merge per-shard snapshots without "
     "a global freeze")
_def("RAY_TPU_DIR_CACHE", bool, True,
     "Client-side object-location directory cache: runtime clients "
     "subscribe to the head's per-shard objloc:<k> pub/sub channels and "
     "serve routed-fetch source picks from a local bounded cache "
     "invalidated by location deltas (add/remove/drop_addr), so the "
     "steady-state fetch path issues zero head RPCs (0 reverts to one "
     "object_locations RPC per routed fetch)")
_def("RAY_TPU_DIR_CACHE_MAX", int, 4096,
     "Max entries in the client-side directory cache (LRU; mirrors the "
     "head directory cap)")
_def("RAY_TPU_HEAD_SPAWNED_MAX", int, 4096,
     "Reaped worker-spawn records retained by the head (live spawns "
     "are never pruned; the bound keeps worker churn from growing the "
     "table forever)")
_def("RAY_TPU_HEAD_DEAD_ACTORS_MAX", int, 4096,
     "DEAD actor records retained by the head for resolve_actor error "
     "reporting (oldest dead records beyond the cap are pruned)")

# --- worker leases ----------------------------------------------------
_def("RAY_TPU_DISABLE_LEASES", bool, False,
     "Route every task through the head instead of worker leases")
_def("RAY_TPU_LEASE_PIPELINE_DEPTH", int, 64,
     "In-flight tasks per leased worker for fast (overhead-bound) "
     "tasks")
_def("RAY_TPU_LEASE_FAST_TASK_MS", float, 25.0,
     "Completion-latency threshold (ms) below which tasks pipeline "
     "deep")
_def("RAY_TPU_LEASE_FAST_TASK_MAX_LEASES", int, os.cpu_count() or 1,
     "Lease-count cap for fast tasks (more workers than cores just "
     "thrashes)")
_def("RAY_TPU_LEASE_LINGER_S", float, 2.0,
     "Idle time before a lease returns its worker to the pool")

# --- liveness / observability ----------------------------------------
_def("RAY_TPU_HEARTBEAT_INTERVAL_S", float, 0.5,
     "Node-agent heartbeat period")
_def("RAY_TPU_HEARTBEAT_TIMEOUT_S", float, 30.0,
     "Heartbeat silence after which the head declares a node dead")
_def("RAY_TPU_METRICS_INTERVAL_S", float, 2.0,
     "Per-process metric push period (0 disables)")
_def("RAY_TPU_METRICS_PORT", int, 0,
     "Head HTTP port for /metrics + dashboard (0 disables)")
_def("RAY_TPU_LOG_TO_DRIVER", bool, True,
     "Stream worker logs to the driver console")
_def("RAY_TPU_LOG_LEVEL", str, "WARNING",
     "Python logging level for daemon processes")
_def("RAY_TPU_TASK_LOG_MAX", int, 4096,
     "Task-lifecycle records retained in the head's bounded ring "
     "(ray_tpu.tasks() / task_summary() / stat --tasks)")
_def("RAY_TPU_RATE_RING_INTERVAL_S", float, 2.0,
     "Head rate-ring sampling period: each tick appends a (timestamp, "
     "cluster counter totals) slot the trailing-window rates in `stat "
     "--rates` and the dashboard are computed from (0 disables)")
_def("RAY_TPU_RATE_RING_SLOTS", int, 150,
     "Rate-ring capacity (bounded deque of counter snapshots; 150 "
     "slots x 2s default interval = a 5-minute history)")
_def("RAY_TPU_RATE_WINDOW_S", float, 30.0,
     "Trailing window rates are computed over: newest ring slot vs the "
     "oldest slot still inside the window")
_def("RAY_TPU_STRAGGLER_K", float, 3.0,
     "Straggler detector outlier threshold in robust sigmas: an actor "
     "whose throughput or fetch latency sits more than k sigma (MAD-"
     "scaled) below/above the fleet median is flagged "
     "(straggler_flags_total, task annotations, trainer results)")
_def("RAY_TPU_STRAGGLER_MIN_PEERS", int, 3,
     "Minimum fleet size before the straggler detector renders "
     "verdicts (a median over 2 actors flags coin flips)")
_def("RAY_TPU_FLIGHT_RECORDER", bool, True,
     "Install the driver-fatal excepthook that writes a flight-"
     "recorder postmortem (task-ring tail + metrics/histograms + "
     "recent spans + node health) before the driver dies; "
     "ray_tpu.debug_dump() works regardless")
_def("RAY_TPU_FLIGHT_RECORDER_PATH", str, None,
     "Flight-recorder output path (default: "
     "<session_dir>/logs/flight_recorder.json); pretty-print with "
     "`ray_tpu.scripts dump <path>`")
_def("RAY_TPU_PROFILE_HZ", float, 99.0,
     "Stack-sampling frequency for coordinated captures "
     "(ray_tpu.profile(duration_s) / `scripts profile`): "
     "sys._current_frames() snapshots per second per process. 99 Hz "
     "(not 100) deliberately avoids lockstep with 10ms-periodic "
     "application timers")
_def("RAY_TPU_PROFILE_MAX_S", float, 30.0,
     "Upper bound on one coordinated capture window; requested "
     "durations are clamped to it so a fat-fingered `--duration` "
     "cannot pin sampler threads cluster-wide for minutes")
_def("RAY_TPU_STRAGGLER_PROFILE", bool, False,
     "Auto-trigger a short targeted stack capture of exactly the actor "
     "the straggler detector flags; folded stacks land in "
     "<session>/logs/ and the trainer result's stragglers.profiles")

# --- elastic fleet (fleet controller; _private/fleet.py) --------------
_def("RAY_TPU_STRAGGLER_EVICT", bool, False,
     "Turn straggler flags into remediation: an actor the detector "
     "flags is evicted and replaced by the fleet controller (per-tag "
     "throttled via RAY_TPU_FLEET_EVICT_INTERVAL_S and capped per "
     "window via RAY_TPU_FLEET_EVICTIONS_PER_WINDOW). Off = flags stay "
     "annotations")
_def("RAY_TPU_FLEET_MIN", int, 1,
     "Floor on the remote sampler fleet size: shrinks and straggler "
     "evictions without a replacement never go below it")
_def("RAY_TPU_FLEET_MAX", int, 64,
     "Ceiling on the remote sampler fleet size: grows/joins never "
     "exceed it")
_def("RAY_TPU_FLEET_EVICT_INTERVAL_S", float, 30.0,
     "Per-tag eviction throttle: the same actor tag is evicted at most "
     "once per this many seconds (mirrors the straggler-profile "
     "capture throttle)")
_def("RAY_TPU_FLEET_EVICT_WINDOW_S", float, 60.0,
     "Width of the fleet-wide eviction budget window")
_def("RAY_TPU_FLEET_EVICTIONS_PER_WINDOW", int, 2,
     "Max straggler evictions inside one RAY_TPU_FLEET_EVICT_WINDOW_S "
     "window: a fleet-wide slowdown (learner stall, shared-host "
     "contention) must not evict every sampler at once")

# --- actors -----------------------------------------------------------
_def("RAY_TPU_NUM_ACTOR_CHECKPOINTS_TO_KEEP", int, 20,
     "Checkpoint ids retained per Checkpointable actor")

# --- chaos plane (fault injection; _private/chaos.py) -----------------
_def("RAY_TPU_CHAOS", str, None,
     "Deterministic fault-injection schedule, armed in every process "
     "that sees it (spec grammar: seed=<int>;site:kind:trigger[:param];"
     "... — see README 'Fault tolerance & chaos testing'). Empty/unset "
     "disables chaos; disabled hooks cost one global read")
_def("RAY_TPU_CHAOS_TRACE", str, None,
     "JSONL file every chaos injection is appended to (pid/seq/site/"
     "kind/occurrence); pretty-print or replay-verify it with "
     "`ray_tpu.scripts chaos`")
_def("RAY_TPU_LEASED_PROBE_S", float, 10.0,
     "Age after which an unfinished leased task's worker is probed for "
     "liveness of that exact task; a worker that no longer knows the "
     "task (dropped dispatch, or result push lost in flight) triggers "
     "a head-path resubmit instead of an indefinite hang")

# --- correctness tooling (graftcheck) ---------------------------------
_def("RAY_TPU_LOCKCHECK", bool, False,
     "Wrap runtime locks in order-tracing shims (graftcheck runtime "
     "mode): real acquisition orders are recorded per thread and "
     "inversions surface via graftcheck.runtime_trace.get_violations()."
     " Test-time knob; off = plain threading locks, zero overhead")
_def("RAY_TPU_RACECHECK", bool, False,
     "Arm the Eraser-style lockset data-race detector (graftcheck "
     "GC300 plane): hot shared containers are wrapped in access-"
     "recording proxies and writes that no common lock protects "
     "surface as GC301/GC302 findings via graftcheck.racecheck."
     "get_findings(). Also arms the traced locks of RAY_TPU_LOCKCHECK "
     "(locksets need them). Test-time knob; off = raw containers, "
     "zero added indirection")
_def("RAY_TPU_RACE_STRESS_SEED", int, 1234,
     "Default seed for the deterministic interleaving stress harness "
     "(graftcheck/stress.py; `ray_tpu.scripts check --race`). The "
     "same seed replays the same per-thread op scripts byte-for-byte")

# --- native components ------------------------------------------------
_def("RAY_TPU_NATIVE", bool, True,
     "Use compiled C++ components (0 forces pure-Python fallbacks)")
_def("RAY_TPU_NATIVE_CACHE", str, None,
     "Directory for compiled native components "
     "(default ~/.cache/ray_tpu_native)")

# --- memory monitor ---------------------------------------------------
_def("RAY_TPU_MEMORY_USAGE_THRESHOLD", float, 0.95,
     "Node memory fraction above which new tasks fail with "
     "RayOutOfMemoryError and the head stops placing work on the node "
     "(<=0 disables; reference memory_monitor.py:64)")
_def("RAY_TPU_MEMORY_MONITOR_INTERVAL_S", float, 0.25,
     "Min seconds between real memory checks on the worker hot path")

# --- streaming --------------------------------------------------------
_def("RAY_TPU_STREAMING_CREDITS", int, 32,
     "Max unprocessed items in flight per streaming operator edge")
_def("RAY_TPU_STREAMING_OPERATOR_RESTARTS", int, 2,
     "max_restarts for streaming operator actors; senders replay their "
     "credit window into the restarted instance (at-least-once)")


def get(name: str):
    """Effective value: env override parsed to the declared type, else
    the registered default. Unregistered names raise (tunables must be
    declared here)."""
    d = _DEFS.get(name)
    if d is None:
        raise KeyError(
            f"{name} is not a registered tunable; declare it in "
            f"_private/config.py")
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return d.default
    if d.type is bool:
        return raw.strip().lower() not in ("0", "false", "no", "off")
    try:
        return d.type(raw)
    except (TypeError, ValueError):
        raise ValueError(
            f"{name}={raw!r} is not a valid {d.type.__name__}")


def set_override(name: str, value) -> None:
    """Programmatic env override for a REGISTERED tunable (e.g.
    `ray_tpu.init(chaos=...)` arming RAY_TPU_CHAOS for the session's
    spawned processes). Keeps raw os.environ writes of tunables out of
    the rest of the tree — the registry stays the single chokepoint."""
    if name not in _DEFS:
        raise KeyError(
            f"{name} is not a registered tunable; declare it in "
            f"_private/config.py")
    os.environ[name] = str(value)


def clear_override(name: str) -> None:
    os.environ.pop(name, None)


def defs() -> Dict[str, ConfigDef]:
    return dict(_DEFS)


def dump() -> list:
    """Effective config for `stat --config`: one row per tunable."""
    out = []
    for name in sorted(_DEFS):
        d = _DEFS[name]
        overridden = os.environ.get(name) not in (None, "")
        out.append({
            "name": name,
            "type": d.type.__name__,
            "default": d.default,
            "value": get(name),
            "overridden": overridden,
            "doc": d.doc,
        })
    return out
