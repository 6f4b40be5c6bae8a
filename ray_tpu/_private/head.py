"""Head server: cluster metadata + lease-based scheduler + node registry.

Parity: this component plays the roles of the reference's GCS
(`src/ray/gcs/gcs_server/` — metadata tables, pub/sub, actor directory, KV),
the raylet NodeManager (`src/ray/raylet/node_manager.h` — resource
accounting, worker leases, dispatch, spillback between nodes), the
WorkerPool (`src/ray/raylet/worker_pool.h`) and the raylet monitor
(`src/ray/raylet/monitor.cc` — death detection). It runs as threads inside
the driver process and speaks the protocol in `protocol.py`.

Multi-node: the head owns a registry of nodes. Its own node ("node0")
spawns workers directly; additional nodes register a NodeAgent connection
(`node_agent.py`) which spawns and supervises workers on that node
(reference: one raylet per node; here spawn requests flow head→agent and
death notifications agent→head, standing in for raylet heartbeats +
`HandleUnexpectedWorkerFailure`, `node_manager.h:125`). Task placement
walks nodes in registration order and leases a worker on the first node
whose resource vector fits — the degenerate one-node case reduces to the
reference's local lease path, and a remote fit is the reference's
spillback (`scheduling_policy.h:35`).

Workers are matched to their spawn records by a token minted at spawn
time and echoed in the worker's hello (avoids pid races across nodes).
"""

from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Set

from ..exceptions import ActorDiedError, WorkerCrashedError
from .ids import ActorID, TaskID
from .task_spec import ACTOR_CREATION_TASK, TaskSpec
from . import chaos, config, head_shards, protocol, task_events
from .graftcheck import racecheck
from .graftcheck.runtime_trace import make_rlock

logger = logging.getLogger(__name__)

# Actor states (reference: ActorTableData states, src/ray/gcs/tables.h:710).
PENDING, ALIVE, RESTARTING, DEAD = "PENDING", "ALIVE", "RESTARTING", "DEAD"


class ActorInfo:
    def __init__(self, spec: TaskSpec):
        self.spec = spec
        self.state = PENDING
        self.addr: Optional[str] = None
        self.worker_pid: Optional[int] = None
        self.restarts_left = spec.max_restarts
        self.death_reason: str = ""
        # Checkpointable actors (parity: GCS ActorCheckpointIdData,
        # `src/ray/gcs/tables.h:777`): newest-first (id, timestamp).
        self.checkpoints: list = []

    def view(self) -> dict:
        return {
            "actor_id": self.spec.actor_id,
            "state": self.state,
            "addr": self.addr,
            "name": self.spec.name,
            "death_reason": self.death_reason,
            "restarts_left": self.restarts_left,
        }


class WorkerInfo:
    def __init__(self, node_id: str, token: str,
                 proc: Optional[subprocess.Popen] = None):
        self.node_id = node_id
        self.token = token
        self.proc = proc  # only for node0 (head-local) workers
        self.pid: Optional[int] = proc.pid if proc else None
        self.returncode: Optional[int] = None
        self.addr: Optional[str] = None
        self.conn: Optional[protocol.Connection] = None
        self.registered = threading.Event()
        self.current_task: Optional[TaskSpec] = None
        self.actor_id: Optional[ActorID] = None  # dedicated actor worker
        self.dedicated = False
        self.started_at = time.monotonic()
        self._reaped = False
        # Worker lease (reference: `direct_task_transport.h:68,89` —
        # steady-state task dispatch goes caller->worker directly; the
        # head only grants/returns leases).
        self.leased_to: Optional[str] = None  # caller addr
        self.lease_resources: Optional[Dict[str, float]] = None


class NodeInfo:
    """One schedulable node: its resource vector + worker pool state."""

    def __init__(self, node_id: str, resources: Dict[str, float],
                 conn: Optional[protocol.Connection] = None):
        self.node_id = node_id
        self.total = dict(resources)
        self.available = dict(resources)
        self.conn = conn  # None for the head-local node
        self.idle: deque = deque()  # addrs of idle pool workers
        self.spawning_pool = 0  # pool workers requested but unregistered
        self.alive = True
        self.last_heartbeat = time.monotonic()
        # Low-memory gate (reference memory_monitor.py:64 + the
        # raylet's heartbeat resource view): set from agent heartbeats;
        # a low-memory node takes no NEW placements until it recovers.
        self.mem_frac = 0.0
        self.low_memory = False

    def fits(self, resources: Dict[str, float]) -> bool:
        if self.low_memory:
            return False
        return all(self.available.get(k, 0.0) + 1e-9 >= v
                   for k, v in resources.items())

    def acquire(self, resources: Dict[str, float]):
        for k, v in resources.items():
            self.available[k] = self.available.get(k, 0.0) - v

    def release(self, resources: Dict[str, float]):
        for k, v in resources.items():
            self.available[k] = self.available.get(k, 0.0) + v

    def view(self) -> dict:
        return {"node_id": self.node_id, "alive": self.alive,
                "total_resources": dict(self.total),
                "available_resources": dict(self.available),
                "mem_frac": self.mem_frac,
                "low_memory": self.low_memory}


class HeadServer:
    def __init__(self, session_dir: str, session_name: str,
                 resources: Dict[str, float],
                 worker_env: Optional[dict] = None,
                 enable_tcp: bool = False):
        self.session_dir = session_dir
        self.session_name = session_name
        self.sock_path = os.path.join(session_dir, "head.sock")
        self.worker_env = worker_env or {}
        # Chaos plane: the head arms the same schedule every other
        # process parses from RAY_TPU_CHAOS (chaos.py).
        ctl = chaos.install_from_env()
        if ctl is not None and not ctl.once_dir:
            ctl.once_dir = session_dir

        # Residual global lock: scheduler state only (nodes, workers,
        # leases, pending queue, actors, conns, subs). The hot tables —
        # KV, object-location directory, metric snapshots, task ring —
        # live in crc32-routed shard planes (head_shards.py), each
        # behind its own lock. Ordering: HeadServer._lock may be held
        # while taking a HeadShard._lock, never the reverse.
        self._lock = make_rlock("HeadServer._lock")
        self._shards = head_shards.HeadShards(obj_locations_max=4096)
        self._subs: Dict[str, Set[protocol.Connection]] = {}
        self._nodes: Dict[str, NodeInfo] = {
            "node0": NodeInfo("node0", resources)}
        self._workers: Dict[str, WorkerInfo] = {}  # by addr once registered
        self._spawned: Dict[str, WorkerInfo] = {}  # by token
        self._pending: deque = racecheck.traced_shared(
            deque(), "HeadServer._pending")  # TaskSpec queue
        self._inflight: Dict[TaskID, str] = racecheck.traced_shared(
            {}, "HeadServer._inflight")  # task -> worker addr
        # Unserved lease demand: [caller_addr, resources, remaining].
        self._lease_queue: List[list] = racecheck.traced_shared(
            [], "HeadServer._lease_queue")
        self._actors: Dict[ActorID, ActorInfo] = {}
        self._drivers: Set[protocol.Connection] = set()
        self._conns_by_addr: Dict[str, protocol.Connection] = {}
        self._shutdown = False
        self._token_counter = 0
        self._unregistered_deaths = 0
        self._profile_events: List[dict] = []
        self._profile_dropped = 0
        # Coordinated captures in flight (profiling.py StackSampler +
        # per-process jax traces): capture_id -> {expected, results,
        # event}; coordinator threads are tracked for shutdown join.
        self._captures: Dict[str, dict] = {}
        self._capture_threads: List[threading.Thread] = []
        self._capture_counter = 0
        # Task-lifecycle transitions land in the shard planes' ring
        # segments (routed by task id); `_shards.task_list()` etc.
        # merge them for the state API + dashboard.
        # Bounded-table caps for the residual global tables: reaped
        # spawn records and DEAD actor records survive for diagnostics
        # but must not grow with cluster-lifetime churn.
        self._spawned_max = max(16, config.get("RAY_TPU_HEAD_SPAWNED_MAX"))
        self._dead_actors_max = max(
            16, config.get("RAY_TPU_HEAD_DEAD_ACTORS_MAX"))
        # Deadline-driven node liveness (reference: 100 ms heartbeats x
        # num_heartbeats_timeout=300, `ray_config_def.h:24,28` +
        # `raylet/monitor.cc`): agents heartbeat into the head; a node
        # whose beats stop — even with a live TCP connection (wedged
        # process, SIGSTOP) — is declared dead after the timeout.
        self._heartbeat_timeout = config.get(
            "RAY_TPU_HEARTBEAT_TIMEOUT_S")
        # Low-memory placement gate (memory_monitor.py module doc).
        self._memory_threshold = config.get(
            "RAY_TPU_MEMORY_USAGE_THRESHOLD") or 0.0
        # Checkpoint ids kept per Checkpointable actor (parity:
        # `ray_config_def.h` num_actor_checkpoints_to_keep).
        self._num_actor_checkpoints_to_keep = config.get(
            "RAY_TPU_NUM_ACTOR_CHECKPOINTS_TO_KEEP")
        # Dashboard ring buffers (dashboard.py): recent error/log tails.
        self._recent_errors: deque = deque(maxlen=50)
        self._recent_logs: deque = deque(maxlen=200)
        # Object location directory (parity: the reference
        # ObjectDirectory over GCS object tables, `object_directory.h`)
        # and per-process metric snapshots both live in the shard
        # planes now. Location deltas additionally publish on the
        # per-shard `objloc:<k>` channels so runtime clients keep a
        # local directory cache (zero head RPCs on the steady-state
        # routed-fetch path).
        self._metrics_http = None
        # Per-shard occupancy sampling state (monitor loop): last
        # (monotonic ts, [lock_held_s per shard]).
        self._occ_last: Optional[tuple] = None
        # Rate ring: bounded trailing window of (ts, counter totals)
        # snapshots the monitor loop appends, so rates() can report
        # tasks/s / wire bytes/s deltas instead of lifetime totals.
        self._rate_ring: deque = deque(
            maxlen=max(2, config.get("RAY_TPU_RATE_RING_SLOTS")))
        self._rate_interval = config.get("RAY_TPU_RATE_RING_INTERVAL_S")
        self._rate_last_sample = 0.0

        self.server = protocol.Server(
            self.sock_path, self._handle, on_connect=self._on_connect,
            on_close=self._on_conn_close)
        # Optional TCP plane for node agents / remote-node workers
        # (reference: the gRPC services every raylet/worker exposes).
        self.tcp_server = None
        self.tcp_addr = None
        if enable_tcp:
            self.tcp_server = protocol.Server(
                "tcp://127.0.0.1:0", self._handle,
                on_connect=self._on_connect, on_close=self._on_conn_close)
            self.tcp_addr = self.tcp_server.path
        self._log_tailer = None
        self._monitor_thread = threading.Thread(
            target=self._monitor_loop, daemon=True, name="head-monitor")
        self._monitor_thread.start()
        # Worker-log tailing to the driver console (parity:
        # `python/ray/log_monitor.py:36` -> `worker.py:910`). The head
        # tails node0's log dir; node agents tail theirs.
        if config.get("RAY_TPU_LOG_TO_DRIVER"):
            from .log_tailer import LogTailer
            self._log_tailer = LogTailer(
                os.path.join(self.session_dir, "logs"), "node0",
                publish=lambda data: self._publish("logs", data))
            self._log_tailer.start()
        # Prometheus exposition (reference: `src/ray/stats/metric.h`'s
        # prometheus exposer, enabled in daemon mains).
        port = config.get("RAY_TPU_METRICS_PORT")
        if port:
            self._start_metrics_http(port)

    # ------------------------------------------------------------------
    # connection lifecycle
    # ------------------------------------------------------------------
    def _on_connect(self, conn: protocol.Connection, hello: dict):
        role = hello.get("role")
        # Peer pid, used by coordinated captures to skip fanning a
        # profile_start to a conn that is THIS process (in-process head:
        # the driver's loopback connection) — the head's local sample
        # already covers those threads.
        conn.hello_pid = hello.get("pid")
        with self._lock:
            self._conns_by_addr[conn.peer_addr] = conn
            if role == "driver":
                self._drivers.add(conn)
            elif role == "node":
                node_id = hello["node_id"]
                self._nodes[node_id] = NodeInfo(
                    node_id, hello.get("resources") or {}, conn=conn)
                conn.node_id = node_id
                logger.info("node %s registered (%s)", node_id,
                            hello.get("resources"))
            elif role == "worker":
                token = hello.get("token", "")
                w = self._spawned.get(token)
                if w is None:
                    logger.warning("unknown worker registered token=%s "
                                   "pid=%s", token, hello.get("pid"))
                else:
                    w.addr = conn.peer_addr
                    w.conn = conn
                    w.pid = hello.get("pid", w.pid)
                    node = self._nodes.get(w.node_id)
                    if node is None:
                        # Its node died while it was booting: orphan.
                        try:
                            conn.send({"kind": "shutdown"})
                        except protocol.ConnectionClosed:
                            pass
                        return
                    self._workers[conn.peer_addr] = w
                    if not w.dedicated:
                        node.spawning_pool -= 1
                        node.idle.append(conn.peer_addr)
                    w.registered.set()
            self._schedule_locked()

    def _on_conn_close(self, conn: protocol.Connection):
        node_id = getattr(conn, "node_id", None)
        with self._lock:
            self._conns_by_addr.pop(conn.peer_addr, None)
            self._drivers.discard(conn)
            for subs in self._subs.values():
                subs.discard(conn)
        # Shard-plane cleanup (outside the global lock): fold the dead
        # process's counters and drop its directory registrations so
        # fetches stop routing at it.
        self._shards.shard_for(conn.peer_addr).fold_dead(conn.peer_addr)
        self._shards.drop_addr(conn.peer_addr)
        # One batched invalidation per shard channel: client directory
        # caches scrub every entry naming the dead addr (cheaper than
        # one remove delta per object, and it also covers entries the
        # head's bounded directory already LRU-evicted).
        for k in range(self._shards.nshards):
            self._publish(head_shards.objloc_channel(k),
                          {"op": "drop_addr", "addr": conn.peer_addr})
        self._release_leases_of(conn.peer_addr)
        if node_id is not None:
            self._handle_node_death(node_id)

    # ------------------------------------------------------------------
    # message handling
    # ------------------------------------------------------------------
    def _handle(self, conn: protocol.Connection, msg: dict):
        kind = msg["kind"]
        fn = getattr(self, "_h_" + kind, None)
        if fn is None:
            logger.warning("head: unknown message %s", kind)
            return
        fn(conn, msg)

    # -- kv / pubsub (shard planes; the global lock is never taken) ------
    def _h_kv_put(self, conn, msg):
        stored, existed = self._shards.shard_for(msg["key"]).kv_put(
            msg["key"], msg["value"], msg.get("overwrite", True))
        if "seq" in msg:
            conn.reply(msg, ok=stored, existed=existed)

    def _h_kv_get(self, conn, msg):
        val = self._shards.shard_for(msg["key"]).kv_get(msg["key"])
        conn.reply(msg, value=val)

    def _h_kv_del(self, conn, msg):
        self._shards.shard_for(msg["key"]).kv_del(msg["key"])
        if "seq" in msg:
            conn.reply(msg, ok=True)

    def _h_kv_keys(self, conn, msg):
        # Cross-shard merge: per-shard snapshots, no global freeze.
        conn.reply(msg, keys=self._shards.kv_keys(msg.get("prefix", "")))

    def _h_head_shard_info(self, conn, msg):
        """Shard topology for runtime clients: the shard count fixes
        the objloc:<k> channel set a directory cache subscribes to."""
        conn.reply(msg, shards=self._shards.nshards)

    def _h_set_resource(self, conn, msg):
        """Live per-node resource adjustment (parity:
        `python/ray/experimental/dynamic_resources.py` set_resource +
        the GCS DynamicResourceTable, `tables.h:647`): retunes the
        node's capacity; in-use amounts are preserved (available moves
        by the capacity delta, possibly below zero until tasks
        finish). capacity == 0 deletes the resource."""
        name = msg["resource"]
        capacity = float(msg["capacity"])
        node_id = msg.get("node_id") or "node0"
        with self._lock:
            node = self._nodes.get(node_id)
            if node is None or not node.alive:
                missing = True
            else:
                missing = False
                self._apply_resource_locked(node, name, capacity)
        if missing:
            # Reply serialization is socket I/O: outside the lock.
            conn.reply(msg, ok=False, message=f"no live node {node_id!r}")
            return
        conn.reply(msg, ok=True)

    def _apply_resource_locked(self, node: NodeInfo, name: str,
                               capacity: float):
        old = node.total.get(name, 0.0)
        if capacity <= 0:
            # Deletion must keep in-use amounts as debt: dropping
            # `available` outright would let running tasks' release
            # resurrect phantom capacity on a deleted resource.
            node.total.pop(name, None)
            remaining = node.available.get(name, 0.0) - old
            if remaining == 0:
                node.available.pop(name, None)
            else:
                node.available[name] = remaining
        else:
            node.total[name] = capacity
            node.available[name] = node.available.get(name, 0.0) \
                + (capacity - old)
        self._schedule_locked()
        self._serve_lease_queue_locked()

    def _h_subscribe(self, conn, msg):
        with self._lock:
            self._subs.setdefault(msg["channel"], set()).add(conn)
        if "seq" in msg:
            conn.reply(msg, ok=True)

    def _h_publish(self, conn, msg):
        self._publish(msg["channel"], msg["data"])

    def _h_heartbeat(self, conn, msg):
        c = chaos.controller
        if c is not None \
                and c.fire("head.heartbeat", msg.get("node_id", "")):
            # 'drop': one-way partition — the agent believes it is
            # beating; the head hears silence and must walk the node
            # through the ordinary heartbeat-timeout death path.
            return
        with self._lock:
            node = self._nodes.get(msg["node_id"])
            if node is not None:
                node.last_heartbeat = time.monotonic()
                if "mem_frac" in msg:
                    was_low = node.low_memory
                    node.mem_frac = float(msg["mem_frac"])
                    node.low_memory = (
                        self._memory_threshold > 0
                        and node.mem_frac > self._memory_threshold)
                    if node.low_memory and not was_low:
                        logger.warning(
                            "node %s memory %.0f%% > %.0f%% threshold:"
                            " pausing new placements on it",
                            node.node_id, 100 * node.mem_frac,
                            100 * self._memory_threshold)
                    elif was_low and not node.low_memory:
                        # Recovery: work queued while the node was
                        # gated has no other wake-up edge (no task
                        # completion, no new submission) — kick the
                        # scheduler now.
                        self._schedule_locked()
                        self._serve_lease_queue_locked()

    # -- metrics (reference: src/ray/stats/ + reporter.py) ---------------
    def _h_metrics_push(self, conn, msg):
        # Snapshot storage is sharded by pusher address; no global lock,
        # no reply (fire-and-forget push).
        self._shards.shard_for(conn.peer_addr).metrics_push(
            conn.peer_addr, {
                "node": msg.get("node", ""),
                "counters": msg.get("counters") or {},
                "gauges": msg.get("gauges") or {},
                "hists": msg.get("hists") or {},
                "rollups": msg.get("rollups") or {},
            })

    def _merged_metric_snaps(self) -> dict:
        """Per-shard metric snapshots + folded dead-process counters,
        merged one shard lock at a time (no global freeze)."""
        snaps, dead_counters = self._shards.metrics_merged()
        for node, dead in dead_counters.items():
            snaps[f"__dead__{node}"] = {
                "node": node, "counters": dict(dead), "gauges": {}}
        return snaps

    def _aggregated_metrics(self) -> dict:
        from . import metrics as metrics_mod
        snaps = self._merged_metric_snaps()
        with self._lock:
            head_counters = {
                "head_pending_tasks": float(len(self._pending)),
                "head_inflight_tasks": float(len(self._inflight)),
                "head_lease_queue_depth": float(len(self._lease_queue)),
                "nodes_alive": float(sum(
                    1 for n in self._nodes.values() if n.alive)),
                "workers_registered": float(len(self._workers)),
                "workers_leased": float(sum(
                    1 for w in self._workers.values()
                    if w.leased_to is not None)),
                "actors_alive": float(sum(
                    1 for a in self._actors.values()
                    if a.state == ALIVE)),
            }
        # Shard-plane health: per-shard table sizes and lock contention
        # totals, merged without a global freeze.
        for st in self._shards.stats():
            k = st["shard"]
            head_counters[f"head_shard_kv.s{k}"] = float(st["kv_keys"])
            head_counters[f"head_shard_locations.s{k}"] = \
                float(st["obj_locations"])
        agg = metrics_mod.aggregate(snaps)
        # Head-derived quantities are point-in-time gauges.
        agg["gauges"].update(head_counters)
        agg["rates"] = self.rates()
        return agg

    def _h_get_metrics(self, conn, msg):
        conn.reply(msg, metrics=self._aggregated_metrics())

    # -- rate ring: trailing-window rates from counter deltas ------------
    def _sample_rate_ring(self):
        """Append one (monotonic ts, cluster counter totals) slot. Driven
        by the monitor loop on the RAY_TPU_RATE_RING_INTERVAL_S cadence;
        rates() reads deltas off the ring, so `stat --rates` and the
        dashboard report tasks/s and wire bytes/s over a trailing window
        instead of lifetime totals."""
        snaps = self._merged_metric_snaps()
        counters: Dict[str, float] = {}
        for snap in snaps.values():
            for k, v in (snap.get("counters") or {}).items():
                counters[k] = counters.get(k, 0.0) + v
        with self._lock:
            self._rate_ring.append((time.monotonic(), counters))

    def rates(self, window_s: Optional[float] = None) -> Dict[str, float]:
        """Per-second rate of every cluster counter over the trailing
        window. Counters fold monotonically — dead-process totals move
        into _dead_counters, never shrink — so deltas are >= 0.

        Each counter is baselined at the oldest in-window slot that
        already CARRIES it, not at the window edge: a process's first
        metrics push lands its whole lifetime total in one ring slot,
        and measuring from a slot before that push would read the join
        as a window-long phantom rate spike (a driver reattaching with
        tasks_submitted=N told the autoscaler the backlog was growing
        by N for a full window — suppressing idle scale-down)."""
        if window_s is None:
            window_s = config.get("RAY_TPU_RATE_WINDOW_S")
        with self._lock:
            ring = list(self._rate_ring)
        if len(ring) < 2:
            return {}
        now_ts, now_counters = ring[-1]
        window = [(ts, counters) for ts, counters in ring[:-1]
                  if now_ts - ts <= window_s]
        if not window:
            window = [ring[-2]]
        out = {}
        for k, v in now_counters.items():
            for ts, counters in window:
                if k in counters:
                    dt = now_ts - ts
                    delta = v - counters[k]
                    if dt > 0 and delta > 0:
                        out[k] = delta / dt
                    break
        return out

    # -- flight recorder (postmortem bundle; scripts dump) ---------------
    def debug_dump_data(self) -> dict:
        """One JSON-serializable postmortem: task-ring tail, metrics +
        histogram aggregate, recent spans, per-node health. The bundle
        `ray_tpu.debug_dump()` and the driver-fatal excepthook write."""
        agg = self._aggregated_metrics()
        now = time.monotonic()
        with self._lock:
            nodes = [{
                "node_id": n.node_id,
                "alive": n.alive,
                "resources": dict(n.total),
                "available": dict(n.available),
                "heartbeat_age_s": (now - n.last_heartbeat)
                if n.conn is not None else None,
            } for n in self._nodes.values()]
            workers = len(self._workers)
            spans = list(self._profile_events[-500:])
            errors = list(self._recent_errors)
            host_mem = {n.node_id: n.mem_frac
                        for n in self._nodes.values()}
        # Profiling postmortem: last HBM/host-memory watermarks plus a
        # one-shot folded-stack sample of this process's threads — what
        # was everyone doing when it died.
        from . import profiling as profiling_mod
        profiling_sec = {
            "hbm_gauges": {k: v for k, v in agg["gauges"].items()
                           if k.startswith("hbm_")},
            "host_mem_frac": host_mem,
            "node_mem_frac_gauge": agg["gauges"].get("node_mem_frac"),
            "head_stacks": profiling_mod.sample_once(),
        }
        # Elastic-fleet postmortem: what the membership looked like and
        # how churn recovered (gauge/counters roll up from publishers;
        # the event ledger is whatever the FleetController last pushed
        # into the KV).
        fleet_sec = {
            "fleet_size": agg["gauges"].get("fleet_size"),
            "joins_total": agg["counters"].get("fleet_joins_total"),
            "evictions_total": agg["counters"].get(
                "fleet_evictions_total"),
            "recovery_s": (agg.get("quantiles") or {}).get(
                "actor_recovery_s"),
        }
        raw_events = self._shards.shard_for(
            "ikv:fleet:events").kv_get("ikv:fleet:events")
        if raw_events:
            try:
                fleet_sec["events"] = json.loads(raw_events)
            except (TypeError, ValueError):
                pass
        return {
            "ts": time.time(),
            "session_dir": self.session_dir,
            "metrics": agg,
            "tasks": self._shards.task_list(limit=200),
            "task_state_counts": self._shards.task_state_counts(),
            "spans": spans,
            "nodes": nodes,
            "workers_registered": workers,
            "recent_errors": errors,
            "profiling": profiling_sec,
            "fleet": fleet_sec,
            "head_shards": self._shards.stats(),
        }

    def _h_debug_dump(self, conn, msg):
        conn.reply(msg, dump=self.debug_dump_data())

    def _start_metrics_http(self, port: int):
        import http.server

        from . import metrics as metrics_mod
        head = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                if self.path.startswith("/metrics.json"):
                    import json as _json
                    body = _json.dumps(
                        head._aggregated_metrics()).encode()
                    ctype = "application/json"
                elif self.path.startswith("/metrics"):
                    body = metrics_mod.prometheus_text(
                        head._aggregated_metrics()).encode()
                    ctype = "text/plain; version=0.0.4"
                else:
                    # Dashboard-lite page (dashboard.py; parity:
                    # `python/ray/dashboard/dashboard.py:91`).
                    from .dashboard import render
                    body = render(head).encode()
                    ctype = "text/html; charset=utf-8"
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):
                pass

        self._metrics_http = http.server.ThreadingHTTPServer(
            ("127.0.0.1", port), Handler)
        threading.Thread(target=self._metrics_http.serve_forever,
                         daemon=True, name="metrics-http").start()
        logger.info("metrics endpoint on 127.0.0.1:%d/metrics", port)

    def _publish(self, channel: str, data):
        with self._lock:
            subs = set(self._subs.get(channel, ()))
            if channel in ("error", "logs"):
                # Driver consoles always receive error + log streams
                # (parity: worker.py:910/:1006 listener threads).
                subs |= self._drivers
            # Dashboard ring buffers (dashboard.py): recent tails of
            # every error/log stream flowing through the head.
            if channel == "error":
                self._recent_errors.append(str(data)[:500])
            elif channel == "logs":
                lines = data.get("lines", []) if isinstance(data, dict) \
                    else [str(data)]
                prefix = data.get("file", "") if isinstance(data, dict) \
                    else ""
                for line in lines:
                    self._recent_logs.append(f"[{prefix}] {line}"[:300])
        for c in subs:
            try:
                c.send({"kind": "publish", "channel": channel, "data": data})
            except protocol.ConnectionClosed:
                pass

    # -- object location directory (distribution plane, sharded) ---------
    def _h_object_location_add(self, conn, msg):
        """A node sealed a fetched copy: register it (fire-and-forget)
        on the object's shard plane, then publish the delta on that
        shard's `objloc:<k>` channel so client directory caches update
        without polling the head."""
        oid = msg["object_id"]
        k = self._shards.shard_index(oid)
        fresh = self._shards.planes[k].location_add(
            oid, msg["addr"], msg.get("node_id", ""))
        if fresh:
            self._publish(head_shards.objloc_channel(k), {
                "op": "add", "object_id": oid,
                "addr": msg["addr"], "node": msg.get("node_id", "")})

    def _h_object_location_remove(self, conn, msg):
        """Eviction/free deregisters the copy (fire-and-forget) and
        publishes an invalidation delta to the shard channel."""
        oid = msg["object_id"]
        k = self._shards.shard_index(oid)
        removed = self._shards.planes[k].location_remove(oid, msg["addr"])
        if removed:
            self._publish(head_shards.objloc_channel(k), {
                "op": "remove", "object_id": oid, "addr": msg["addr"]})

    def _h_object_locations(self, conn, msg):
        """Resolve an object's replica set, least-loaded first. The
        shard bumps the grant count of the replica it lists first (the
        borrower's predicted pick), so consecutive borrowers spread
        over the copies instead of dog-piling one."""
        oid = msg["object_id"]
        locs = self._shards.shard_for(oid).locations(oid)
        conn.reply(msg, locations=[{"addr": a, "node": n}
                                   for a, n in locs])

    def object_location_counts(self) -> Dict[str, int]:
        """Replica count per tracked object (`ray_tpu stat`, tests)."""
        return self._shards.location_counts()

    # -- tasks -----------------------------------------------------------
    def _h_submit_task(self, conn, msg):
        spec: TaskSpec = msg["spec"]
        # Head-dispatched tasks must report task_done (a stale leased
        # flag from a reconstruction resubmit would wedge the worker's
        # accounting).
        spec.leased = False
        self._record_task(spec, task_events.QUEUED)
        with self._lock:
            self._pending.append(spec)
            self._schedule_locked()

    def _record_task(self, spec: TaskSpec, state: str, **attrs):
        kind = "actor_creation" if spec.kind == ACTOR_CREATION_TASK \
            else "task"
        self._shards.apply_task_event({
            "task_id": spec.task_id.hex(), "state": state,
            "ts": time.time(), "name": spec.describe(), "kind": kind,
            "caller": spec.caller_addr or None,
            "parent": spec.parent_task_id.hex()
            if spec.parent_task_id else None,
            **attrs})

    # -- worker leases (reference: `HandleRequestWorkerLease`,
    # `node_manager.h:542`; caller-side pipelining lives in runtime.py) --
    def _h_request_lease(self, conn, msg):
        resources: Dict[str, float] = msg["resources"]
        count: int = msg.get("count", 1)
        granted: List[str] = []
        with self._lock:
            for _ in range(count):
                addr = self._grant_lease_locked(conn.peer_addr, resources)
                if addr is None:
                    break
                granted.append(addr)
            remaining = count - len(granted)
            if remaining > 0:
                self._lease_queue.append(
                    [conn.peer_addr, dict(resources), remaining])
                self._grow_pool_for_leases_locked(resources, remaining)
        if granted:
            try:
                conn.send({"kind": "lease_granted", "addrs": granted,
                           "resources": resources})
            except protocol.ConnectionClosed:
                self._release_leases_of(conn.peer_addr)

    def _grant_lease_locked(self, caller: str,
                            resources: Dict[str, float]) -> Optional[str]:
        for node in self._nodes.values():
            if not node.alive or not node.fits(resources):
                continue
            # Drain stale idle entries (dead workers not yet reaped)
            # instead of abandoning the node after one stale addr.
            while node.idle:
                addr = node.idle.popleft()
                w = self._workers.get(addr)
                if w is None:
                    continue
                w.leased_to = caller
                w.lease_resources = dict(resources)
                node.acquire(resources)
                return addr
        return None

    def _grow_pool_for_leases_locked(self, resources: Dict[str, float],
                                     need: int):
        """Spawn pool workers toward unserved lease demand (reference:
        WorkerPool starts workers on lease requests). Growth per node is
        capped at what its resource vector can actually lease
        concurrently (counting workers already spawning), so demand
        beyond one node's capacity spreads to the next — the lease-plane
        equivalent of task spillback."""
        for node in self._nodes.values():
            if need <= 0:
                break
            if not node.alive:
                continue
            cap = self._lease_capacity(node, resources) \
                - node.spawning_pool - len(node.idle)
            for _ in range(min(need, max(0, cap))):
                try:
                    self._spawn_worker_locked(node, dedicated=False)
                except Exception:
                    # One bad node must not block growth on the others.
                    logger.exception("failed to grow pool on %s",
                                     node.node_id)
                    break
                need -= 1

    @staticmethod
    def _lease_capacity(node: NodeInfo, resources: Dict[str, float]) -> int:
        """How many `resources`-shaped leases the node's available
        vector still fits."""
        cap = 8  # zero-resource leases: bounded pool growth per node
        for k, v in resources.items():
            if v > 0:
                cap = min(cap, int(node.available.get(k, 0.0) / v + 1e-9))
        return cap

    def _serve_lease_queue_locked(self):
        still: List[list] = []
        for req in self._lease_queue:
            caller, resources, remaining = req
            conn = self._conns_by_addr.get(caller)
            if conn is None or conn.closed:
                continue  # caller gone: drop its demand
            addrs: List[str] = []
            while remaining > 0:
                addr = self._grant_lease_locked(caller, resources)
                if addr is None:
                    break
                addrs.append(addr)
                remaining -= 1
            req[2] = remaining
            if addrs:
                try:
                    conn.send({"kind": "lease_granted", "addrs": addrs,
                               "resources": resources})
                except protocol.ConnectionClosed:
                    self._release_leases_of(caller)
                    continue
            if remaining > 0:
                # Capacity may exist on OTHER nodes than the ones that
                # served earlier demand: keep growing toward the deficit.
                self._grow_pool_for_leases_locked(resources, remaining)
                still.append(req)
        self._lease_queue[:] = still

    def _h_cancel_lease_requests(self, conn, msg):
        """Caller's backlog drained before its queued lease demand was
        served: shrink/remove the stale entries."""
        count = msg["count"]
        resources = msg["resources"]
        with self._lock:
            kept = []
            for req in self._lease_queue:
                if count > 0 and req[0] == conn.peer_addr \
                        and req[1] == resources:
                    taken = min(count, req[2])
                    req[2] -= taken
                    count -= taken
                if req[2] > 0:
                    kept.append(req)
            self._lease_queue[:] = kept

    def _h_return_lease(self, conn, msg):
        with self._lock:
            for addr in msg["addrs"]:
                w = self._workers.get(addr)
                if w is None or w.leased_to != conn.peer_addr:
                    continue
                node = self._nodes.get(w.node_id)
                if node is not None:
                    node.release(w.lease_resources or {})
                    node.idle.append(addr)
                w.leased_to = None
                w.lease_resources = None
            self._schedule_locked()

    def _release_leases_of(self, caller: str):
        """Caller process died/disconnected: its queued lease demand
        evaporates and its leased workers are shut down — they may still
        be executing a pipeline of the dead caller's tasks, so re-idling
        them would stall the next tenant behind orphaned work."""
        victims = []
        with self._lock:
            for w in self._workers.values():
                if w.leased_to == caller:
                    node = self._nodes.get(w.node_id)
                    if node is not None:
                        node.release(w.lease_resources or {})
                    w.leased_to = None
                    w.lease_resources = None
                    victims.append(w)
            self._lease_queue[:] = [r for r in self._lease_queue
                                    if r[0] != caller]
            self._schedule_locked()
        for w in victims:
            if w.conn is not None:
                try:
                    w.conn.send({"kind": "shutdown"})
                except protocol.ConnectionClosed:
                    pass

    def _h_task_done(self, conn, msg):
        task_id: TaskID = msg["task_id"]
        with self._lock:
            addr = self._inflight.pop(task_id, None)
            if addr is None:
                return
            w = self._workers.get(addr)
            if w is not None and w.current_task is not None \
                    and w.current_task.task_id == task_id:
                node = self._nodes.get(w.node_id)
                if node is not None:
                    node.release(w.current_task.resources)
                w.current_task = None
                if not w.dedicated and node is not None:
                    node.idle.append(addr)
            self._schedule_locked()

    # -- worker lifecycle from node agents -------------------------------
    def _h_worker_died(self, conn, msg):
        with self._lock:
            w = self._spawned.get(msg["token"])
            if w is None or w._reaped:
                return
            w._reaped = True
            w.returncode = msg.get("returncode")
        self._handle_worker_death(w)

    # -- actors ----------------------------------------------------------
    def _h_create_actor(self, conn, msg):
        spec: TaskSpec = msg["spec"]
        # Claim the name on its KV shard BEFORE touching scheduler
        # state: the shard's put-if-absent is the atomic registration
        # primitive, and doing it first keeps the error reply (socket
        # I/O) outside every lock and avoids global->shard nesting.
        if spec.name:
            key = "named_actor:" + spec.name
            claimed = self._shards.shard_for(key).kv_put_if_absent(
                key, spec.actor_id.binary())
            if not claimed:
                conn.reply(msg, error=ValueError(
                    f"actor name {spec.name!r} already taken"))
                return
        self._record_task(spec, task_events.QUEUED)
        with self._lock:
            info = ActorInfo(spec)
            self._actors[spec.actor_id] = info
            self._pending.append(spec)
            self._schedule_locked()
        conn.reply(msg, ok=True)

    def _h_actor_ready(self, conn, msg):
        actor_id: ActorID = msg["actor_id"]
        with self._lock:
            info = self._actors.get(actor_id)
            if info is None:
                return
            # Stale ready from an incarnation whose worker already died
            # (death handler ran first): ignore — accepting it would
            # resurrect a DEAD/RESTARTING actor at a dead address and
            # double-release the creation lease.
            w = self._workers.get(msg["addr"])
            if w is None or w._reaped or w.actor_id != actor_id:
                return
            info.state = ALIVE
            info.addr = msg["addr"]
            self._inflight.pop(info.spec.task_id, None)
            # Creation lease resources are released on the node they were
            # acquired on; if that node is already gone, so is its
            # accounting — releasing elsewhere would over-credit it.
            # Clearing current_task stops the worker's eventual death
            # from releasing the same lease a second time.
            self._release_creation_resources_locked(info,
                                                    clear_task=True)
            view = info.view()
        self._publish("actor:" + actor_id.hex(), view)

    def cluster_load(self) -> dict:
        """Autoscaler snapshot: per-node resource vectors + unplaceable
        demand (parity: the load the reference's raylet heartbeats carry
        to `monitor.py`, autoscaler.py:155).

        `pending_demand` carries the unplaceable work's resource
        VECTORS (capped sample), so the autoscaler can launch the node
        type that actually fits the backlog rather than scaling a
        homogeneous pool on a scalar count (VERDICT r4 next #5; ref
        LoadMetrics resource-shape tracking, autoscaler.py:155)."""
        with self._lock:
            demand = [dict(spec.resources or {"CPU": 1.0})
                      for spec in list(self._pending)[:200]]
            for _, resources, remaining in self._lease_queue:
                demand.extend(
                    [dict(resources)] * min(int(remaining), 50))
                if len(demand) >= 400:
                    break
            return {
                "nodes": [n.view() for n in self._nodes.values()
                          if n.alive],
                "pending_tasks": len(self._pending),
                "lease_queue_depth": sum(
                    req[2] for req in self._lease_queue),
                "pending_demand": demand[:400],
            }

    def _h_cluster_load(self, conn, msg):
        conn.reply(msg, load=self.cluster_load())

    def _h_actor_checkpoint_saved(self, conn, msg):
        """Register a checkpoint id; reply with ids that fell off the
        keep-window so the actor can delete their payloads
        (parity: `tables.h:777` + num_actor_checkpoints_to_keep)."""
        import time as _time
        with self._lock:
            info = self._actors.get(msg["actor_id"])
            expired = []
            if info is not None:
                info.checkpoints.insert(
                    0, (msg["checkpoint_id"], _time.time()))
                keep = self._num_actor_checkpoints_to_keep
                expired = [cid for cid, _ in info.checkpoints[keep:]]
                del info.checkpoints[keep:]
        conn.reply(msg, expired=expired)

    def _h_get_actor_checkpoints(self, conn, msg):
        with self._lock:
            info = self._actors.get(msg["actor_id"])
            cps = list(info.checkpoints) if info is not None else []
        conn.reply(msg, checkpoints=cps)

    def _h_actor_creation_failed(self, conn, msg):
        actor_id: ActorID = msg["actor_id"]
        with self._lock:
            info = self._actors.get(actor_id)
            if info is None:
                return
            info.state = DEAD
            info.death_reason = f"creation failed: {msg.get('error')}"
            self._inflight.pop(info.spec.task_id, None)
            self._release_creation_resources_locked(info, clear_task=True)
            self._release_actor_name_locked(info)
            view = info.view()
        self._publish("actor:" + actor_id.hex(), view)

    def _release_creation_resources_locked(self, info: ActorInfo,
                                           clear_task: bool = False):
        # Find the node the creation lease was placed on via its worker
        # (the newest live one — restarts leave reaped records behind);
        # a vanished node's accounting died with it — never release onto
        # a different node.
        candidates = [w for w in self._spawned.values()
                      if w.actor_id == info.spec.actor_id]
        w = next((x for x in reversed(candidates) if not x._reaped),
                 candidates[-1] if candidates else None)
        if w is not None:
            node = self._nodes.get(w.node_id)
            if node is not None:
                node.release(info.spec.resources)
            if clear_task and w.current_task is info.spec:
                w.current_task = None

    def _h_resolve_actor(self, conn, msg):
        actor_id: ActorID = msg["actor_id"]
        with self._lock:
            info = self._actors.get(actor_id)
            # Auto-subscribe the caller to updates.
            self._subs.setdefault("actor:" + actor_id.hex(), set()).add(conn)
            view = info.view() if info else None
        conn.reply(msg, info=view)

    def _h_get_named_actor(self, conn, msg):
        # Name lookup on the KV shard, then the actor view under the
        # global lock — sequential, never nested.
        key = "named_actor:" + msg["name"]
        raw = self._shards.shard_for(key).kv_get(key)
        with self._lock:
            info = self._actors.get(ActorID(raw)) if raw else None
            view = info.view() if info else None
        conn.reply(msg, info=view)

    def _h_kill_actor(self, conn, msg):
        actor_id: ActorID = msg["actor_id"]
        no_restart = msg.get("no_restart", True)
        with self._lock:
            info = self._actors.get(actor_id)
            gone = info is None or info.state == DEAD
            w = None
            if not gone:
                if no_restart:
                    info.restarts_left = 0
                w = self._workers.get(info.addr) if info.addr else None
        # Reply serialization is socket I/O: outside the lock (GC109).
        if gone:
            if "seq" in msg:
                conn.reply(msg, ok=True)
            return
        if w is not None:
            self._kill_worker(w)
        if "seq" in msg:
            conn.reply(msg, ok=True)

    def _kill_worker(self, w: WorkerInfo):
        if w.proc is not None:
            try:
                w.proc.kill()
            except OSError:
                pass
            return
        node = self._nodes.get(w.node_id)
        if node is not None and node.conn is not None:
            try:
                node.conn.send({"kind": "kill_worker", "token": w.token})
            except protocol.ConnectionClosed:
                pass

    def _h_session_info(self, conn, msg):
        """Bootstrap info for late-attaching drivers (`ray_tpu.init(
        address=...)` — parity: connecting to a running `ray start`
        cluster)."""
        conn.reply(msg, session_name=self.session_name,
                   session_dir=self.session_dir)

    # -- introspection ---------------------------------------------------
    def _h_cluster_info(self, conn, msg):
        # Directory counts merge per-shard snapshots outside the global
        # lock (consistent-per-shard cut; no global freeze).
        loc_counts = sorted(self._shards.location_counts().items(),
                            key=lambda kv: -kv[1])
        with self._lock:
            nodes = {nid: n.view() for nid, n in self._nodes.items()}
            total: Dict[str, float] = {}
            avail: Dict[str, float] = {}
            for n in self._nodes.values():
                for k, v in n.total.items():
                    total[k] = total.get(k, 0.0) + v
                for k, v in n.available.items():
                    avail[k] = avail.get(k, 0.0) + v
            info = {
                "total_resources": total,
                "available_resources": avail,
                "nodes": nodes,
                "num_workers": len(self._workers),
                "num_pending_tasks": len(self._pending),
                "actors": {a.hex(): i.view() for a, i in self._actors.items()},
                "session_name": self.session_name,
                "session_dir": self.session_dir,
                # Distribution plane: how many nodes hold a sealed copy
                # of each directory-tracked object (top 20 by count).
                "object_locations": {
                    "objects": len(loc_counts),
                    "replicas": sum(n for _, n in loc_counts),
                    "top": loc_counts[:20],
                },
            }
        conn.reply(msg, info=info)

    def _h_report_error(self, conn, msg):
        self._publish("error", msg["data"])

    # -- profiling (parity: GCS ProfileTable, tables.h:841) --------------
    def _h_profile_events(self, conn, msg):
        with self._lock:
            self._profile_events.extend(msg["events"])
            self._profile_dropped += msg.get("dropped", 0)
            if len(self._profile_events) > 200_000:
                n = len(self._profile_events) - 200_000
                del self._profile_events[:n]
                self._profile_dropped += n

    def _h_get_profile_events(self, conn, msg):
        with self._lock:
            events = list(self._profile_events)
            dropped = self._profile_dropped
        conn.reply(msg, events=events, dropped=dropped)

    # -- coordinated on-demand capture (profiling.py StackSampler) -------
    def _h_profile_capture(self, conn, msg):
        """Entry point of `ray_tpu.profile(duration_s)` / `scripts
        profile`. The capture window blocks for its full duration, so
        coordination runs on its own thread — handlers share the conn's
        recv loop and must never sleep there."""
        t = threading.Thread(target=self._run_profile_capture,
                             args=(conn, msg), daemon=True,
                             name="profile-capture")
        with self._lock:
            self._capture_threads = [
                th for th in self._capture_threads if th.is_alive()]
            self._capture_threads.append(t)
        t.start()

    def _run_profile_capture(self, conn, msg):
        try:
            bundle = self._coordinate_capture(msg)
            conn.reply(msg, bundle=bundle)
        except protocol.ConnectionClosed:
            logger.warning("profile capture requester went away")
        except Exception as e:
            logger.warning("profile capture failed", exc_info=True)
            try:
                conn.reply_error(msg, e)
            except protocol.ConnectionClosed:
                pass

    def _capture_peers_locked(self, target: str) -> List[tuple]:
        """(descriptor, conn) pairs the capture fans out to. `target`:
        "all" | "head" | "workers" | "drivers" | "nodes" | "learner"
        (every process; non-device ones reply with a skip marker) | an
        explicit process addr."""
        peers: List[tuple] = []
        if target in ("all", "workers", "learner") or ":" in target:
            for w in self._workers.values():
                if w.conn is not None:
                    peers.append(({"role": "worker", "node": w.node_id,
                                   "pid": w.pid, "addr": w.addr}, w.conn))
        if target in ("all", "drivers", "learner") or ":" in target:
            for d in self._drivers:
                peers.append(({"role": "driver", "node": "node0",
                               "pid": getattr(d, "hello_pid", None),
                               "addr": d.peer_addr}, d))
        if target in ("all", "nodes", "learner") or ":" in target:
            for n in self._nodes.values():
                if n.conn is not None:
                    peers.append((
                        {"role": "node_agent", "node": n.node_id,
                         "pid": getattr(n.conn, "hello_pid", None),
                         "addr": n.conn.peer_addr}, n.conn))
        if ":" in target:  # explicit addr: keep only the match
            peers = [(d, c) for d, c in peers if d["addr"] == target]
        return peers

    def _coordinate_capture(self, msg: dict) -> dict:
        from . import profiling as profiling_mod
        duration = max(0.05, min(float(msg.get("duration_s") or 2.0),
                                 config.get("RAY_TPU_PROFILE_MAX_S")))
        hz = msg.get("hz") or config.get("RAY_TPU_PROFILE_HZ")
        target = msg.get("target") or "all"
        my_pid = os.getpid()
        with self._lock:
            self._capture_counter += 1
            cid = "cap%d-%d" % (self._capture_counter, my_pid)
            peers = [(d, c) for d, c in self._capture_peers_locked(target)
                     if d.get("pid") != my_pid]
            entry = {"results": {}, "event": threading.Event(),
                     "expected": {d["addr"] for d, _ in peers}}
            self._captures[cid] = entry
        xla_root = os.path.join(self.session_dir, "logs",
                                "xla_profile_%s" % cid)
        t0 = time.time()
        for d, c in peers:
            try:
                c.send({"kind": "profile_start", "capture_id": cid,
                        "duration_s": duration, "hz": hz,
                        "target": target,
                        "xla_dir": os.path.join(
                            xla_root, "%s-%s" % (d["role"], d["pid"]))})
            except protocol.ConnectionClosed:
                with self._lock:
                    entry["expected"].discard(d["addr"])
        # The head samples its own process inline (also covering the
        # in-process driver's threads, skipped above by pid).
        local = None
        if target in ("all", "head") or (
                target == "learner" and profiling_mod.owns_device()):
            local = profiling_mod.run_capture(
                duration, hz=hz,
                xla_dir=os.path.join(xla_root, "head-%d" % my_pid))
            local.update({"role": "head", "node": "node0",
                          "addr": "head"})
        # Wait out the window plus shipping grace for remote results.
        deadline = t0 + duration + 10.0
        while True:
            with self._lock:
                missing = entry["expected"] - set(entry["results"])
            if not missing:
                break
            remaining = deadline - time.time()
            if remaining <= 0:
                logger.warning("profile capture %s: no result from %s",
                               cid, sorted(missing))
                break
            entry["event"].wait(min(remaining, 0.5))
            entry["event"].clear()
        t1 = time.time()
        with self._lock:
            results = dict(self._captures.pop(cid)["results"])
            spans = [e for e in self._profile_events
                     if e.get("end", 0.0) >= t0
                     and e.get("start", float("inf")) <= t1]
        processes = ([local] if local else []) + [
            results[a] for a in sorted(results)]
        trace = profiling_mod.chrome_trace(spans)
        for p in processes:
            trace.extend(profiling_mod.samples_to_chrome(p))
            # Raw samples are re-emitted above; the bundle keeps the
            # (much smaller) folded stacks + counters per process.
            p.pop("samples", None)
        return {"capture_id": cid, "duration_s": duration, "hz": hz,
                "target": target, "t0": t0, "t1": t1,
                "processes": processes, "trace_events": trace,
                "spans_in_window": len(spans),
                "missing": sorted(missing)}

    def _h_profile_result(self, conn, msg):
        with self._lock:
            entry = self._captures.get(msg.get("capture_id"))
            if entry is None:
                logger.warning("profile result for unknown capture %s",
                               msg.get("capture_id"))
                return
            addr = msg.get("addr") or conn.peer_addr
            entry["results"][addr] = msg.get("result") or {}
            entry["event"].set()

    # -- task lifecycle state API (task_events.py) -----------------------
    def _h_task_events(self, conn, msg):
        for ev in msg.get("events", ()):
            self._shards.apply_task_event(ev)

    def _h_task_alive(self, conn, msg):
        """Owner-side lost-update backstop (runtime._producer_confirmed):
        is this head-path task still queued or dispatched? 'No' while
        the owner's ledger says in-flight means the task finished but
        its result push was dropped — the owner then reconstructs."""
        tid: TaskID = msg["task_id"]
        with self._lock:
            alive = tid in self._inflight \
                or any(spec.task_id == tid for spec in self._pending)
        conn.reply(msg, alive=alive)

    def _h_get_tasks(self, conn, msg):
        conn.reply(
            msg,
            tasks=self._shards.task_list(state=msg.get("state"),
                                         name=msg.get("name"),
                                         limit=msg.get("limit", 100)),
            summary=self._shards.task_summary(),
            state_counts=self._shards.task_state_counts())

    # ------------------------------------------------------------------
    # scheduling (lease grant) — runs under self._lock
    # ------------------------------------------------------------------
    def _pick_node_locked(self, spec: TaskSpec,
                          planned_get=None) -> Optional[NodeInfo]:
        """First-fit across nodes, local node first (the remote fit is the
        reference's spillback, `scheduling_policy.h:35`). `planned_get`
        supplies in-drain tentative commitments to subtract."""
        for node in self._nodes.values():
            if not node.alive:
                continue
            planned = planned_get(node.node_id) if planned_get else None
            if planned:
                ok = all(node.available.get(k, 0.0)
                         - planned.get(k, 0.0) + 1e-9 >= v
                         for k, v in spec.resources.items())
            else:
                ok = node.fits(spec.resources)
            if ok:
                return node
        return None

    def _schedule_locked(self):
        if self._shutdown:
            return
        # Lease demand is served first: leased callers bypass this queue
        # entirely in steady state, so keeping them fed maximizes the
        # work that never touches the head again.
        if self._lease_queue:
            self._serve_lease_queue_locked()
        remaining = deque()
        # pool-worker deficit per node for runnable-but-unassigned tasks
        need_worker: Dict[str, int] = {}
        try:
            self._drain_pending_locked(remaining, need_worker)
        finally:
            # Never lose queued tasks, even if a spawn/send throws
            # mid-drain (e.g. an agent connection breaking).
            self._pending = remaining
        for node_id, need in need_worker.items():
            node = self._nodes.get(node_id)
            if node is None:
                continue
            for _ in range(max(0, need - node.spawning_pool)):
                try:
                    self._spawn_worker_locked(node, dedicated=False)
                except Exception:
                    logger.exception("failed to grow pool on %s", node_id)
                    break

    def _drain_pending_locked(self, remaining: deque,
                              need_worker: Dict[str, int]):
        # Tentative per-node resource commitments for queued tasks that
        # will get a fresh pool worker: caps pool growth at what the
        # node's resource vector can actually run concurrently (a 100-task
        # fan-out on a 4-CPU node spawns 4 workers, not 100).
        planned: Dict[str, Dict[str, float]] = {}
        while self._pending:
            spec = self._pending.popleft()
            node = self._pick_node_locked(spec, planned.get)
            if node is None:
                remaining.append(spec)
                continue
            if spec.kind == ACTOR_CREATION_TASK:
                info = self._actors.get(spec.actor_id)
                if info is None:
                    continue
                try:
                    w = self._spawn_worker_locked(
                        node, dedicated=True, resources=spec.resources,
                        extra_env=spec.env_vars)
                except Exception as e:
                    # A bad spawn (e.g. unpicklable env) must not abort the
                    # drain loop and strand other queued tasks.
                    logger.exception("failed to spawn actor worker")
                    info.state = DEAD
                    info.death_reason = f"worker spawn failed: {e}"
                    self._release_actor_name_locked(info)
                    self._publish("actor:" + spec.actor_id.hex(),
                                  info.view())
                    continue
                w.actor_id = spec.actor_id
                w.current_task = spec
                info.worker_pid = w.pid
                node.acquire(spec.resources)
                self._inflight[spec.task_id] = f"token:{w.token}"
                self._record_task(spec, task_events.LEASED,
                                  node=node.node_id, pid=w.pid)
                threading.Thread(
                    target=self._dispatch_when_registered, args=(w, spec),
                    daemon=True).start()
            else:
                # Drain stale idle entries (dead workers not yet reaped)
                # the same way _grant_lease_locked does — indexing
                # _workers directly would KeyError mid-drain.
                w = None
                while node.idle:
                    addr = node.idle.popleft()
                    w = self._workers.get(addr)
                    if w is not None:
                        break
                if w is not None:
                    w.current_task = spec
                    node.acquire(spec.resources)
                    self._inflight[spec.task_id] = addr
                    self._record_task(spec, task_events.LEASED,
                                      node=node.node_id, pid=w.pid)
                    try:
                        w.conn.send({"kind": "execute_task", "spec": spec})
                    except protocol.ConnectionClosed:
                        pass  # death handling will requeue/fail it
                else:
                    remaining.append(spec)
                    # Pool growth happens after the drain (reference:
                    # WorkerPool starts workers on demand for leases);
                    # commit this task's resources tentatively so later
                    # queued tasks don't over-count the deficit.
                    p = planned.setdefault(node.node_id, {})
                    for k, v in spec.resources.items():
                        p[k] = p.get(k, 0.0) + v
                    need_worker[node.node_id] = \
                        need_worker.get(node.node_id, 0) + 1

    def _dispatch_when_registered(self, w: WorkerInfo, spec: TaskSpec):
        if not w.registered.wait(timeout=60):
            logger.error("worker token=%s never registered", w.token)
            return
        with self._lock:
            if w.current_task is not spec:
                return
            self._inflight[spec.task_id] = w.addr
            try:
                w.conn.send({"kind": "execute_task", "spec": spec})
            except protocol.ConnectionClosed:
                pass

    def _next_token(self) -> str:
        self._token_counter += 1
        return f"w{self._token_counter}-{os.urandom(3).hex()}"

    def _spawn_worker_locked(self, node: NodeInfo, dedicated: bool,
                             resources: Optional[Dict[str, float]] = None,
                             extra_env: Optional[dict] = None) -> WorkerInfo:
        # One process owns a host's chips, and the scheduler says which:
        # only a worker spawned for a "TPU" claim (an actor's — pool
        # workers hold none) may open the device. Everyone else is put on
        # the CPU out loud, so a stray `import jax` in a task can neither
        # take the chip from its owner nor fall back without saying so.
        env = {} if (resources or {}).get("TPU", 0) > 0 \
            else {"JAX_PLATFORMS": "cpu"}
        env.update(extra_env or {})
        token = self._next_token()
        if node.conn is None:
            w = self._spawn_local_worker(token, env)
        else:
            # Remote node: the agent forks the worker (reference: raylet
            # WorkerPool on the task's node).
            node.conn.send({"kind": "spawn_worker", "token": token,
                            "dedicated": dedicated, "env": env})
            w = WorkerInfo(node.node_id, token, proc=None)
        w.dedicated = dedicated
        self._spawned[token] = w
        if not dedicated:
            node.spawning_pool += 1
        return w

    def _spawn_local_worker(self, token: str,
                            extra_env: Dict[str, str]) -> WorkerInfo:
        env = dict(os.environ)
        env.update(self.worker_env)
        env.update(extra_env)
        env["RAY_TPU_SESSION_DIR"] = self.session_dir
        env["RAY_TPU_SESSION_NAME"] = self.session_name
        env["RAY_TPU_NODE_ID"] = "node0"
        env["RAY_TPU_WORKER_TOKEN"] = token
        # Workers must see the same import universe as the driver (parity:
        # the reference serializes the driver's sys.path expectations via the
        # worker command line, `services.py:1099`).
        env["PYTHONPATH"] = os.pathsep.join(
            [p for p in sys.path if p] +
            ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
        log_path = os.path.join(self.session_dir, "logs")
        os.makedirs(log_path, exist_ok=True)
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.default_worker",
             "--head-sock", self.sock_path,
             "--session-dir", self.session_dir,
             "--session-name", self.session_name],
            env=env,
            stdout=open(os.path.join(log_path, "worker-pending.out"), "ab"),
            stderr=subprocess.STDOUT,
        )
        return WorkerInfo("node0", token, proc=proc)

    def _sample_shard_occupancy(self, now: float):
        """Per-shard lock duty cycle over the last sample window —
        delta(lock_held_s) / delta(wall) — published as the
        `head_shard_occupancy.s<k>` mean gauges (`scripts stat
        --metrics`, flight recorder). Reads the shards' cumulative
        held-time counters without their locks: a torn float read only
        skews one 2s sample, and taking N locks from the monitor loop
        would perturb the very contention being measured."""
        from . import metrics as metrics_mod
        held = [p.lock_held_s for p in self._shards.planes]
        if self._occ_last is not None:
            t0, prev = self._occ_last
            dt = now - t0
            if dt > 0:
                for k in range(len(held)):
                    frac = max(0.0, min(1.0, (held[k] - prev[k]) / dt))
                    metrics_mod.set_gauge(
                        f"head_shard_occupancy.s{k}", frac, rollup="mean")
        self._occ_last = (now, held)

    # ------------------------------------------------------------------
    # death detection (reference: raylet monitor heartbeats + SIGCHLD)
    # ------------------------------------------------------------------
    def _monitor_loop(self):
        while not self._shutdown:
            time.sleep(0.05)
            dead: List[WorkerInfo] = []
            stale_nodes: List[NodeInfo] = []
            now = time.monotonic()
            if self._rate_interval > 0 \
                    and now - self._rate_last_sample >= self._rate_interval:
                self._rate_last_sample = now
                self._sample_rate_ring()
            if self._occ_last is None or now - self._occ_last[0] >= 2.0:
                self._sample_shard_occupancy(now)
            with self._lock:
                for w in self._spawned.values():
                    if w.proc is not None and w.proc.poll() is not None \
                            and not w._reaped:
                        w._reaped = True
                        w.returncode = w.proc.returncode
                        dead.append(w)
                for node in self._nodes.values():
                    # Agent-backed nodes only: node0 is this process.
                    if (node.conn is not None and node.alive
                            and now - node.last_heartbeat
                            > self._heartbeat_timeout):
                        stale_nodes.append(node)
            for w in dead:
                self._handle_worker_death(w)
            for node in stale_nodes:
                from . import metrics as metrics_mod
                metrics_mod.inc("node_heartbeat_timeouts")
                self._publish("error", (
                    f"node {node.node_id} missed heartbeats for "
                    f"{self._heartbeat_timeout:g}s; declaring it dead"))
                logger.warning("node %s heartbeat timeout", node.node_id)
                # Closing the connection routes through the normal
                # node-death path (_on_conn_close -> _handle_node_death):
                # workers declared dead, tasks rescheduled, callers
                # unblocked with errors.
                try:
                    node.conn.close()
                except Exception:
                    pass
                self._handle_node_death(node.node_id)

    def _handle_worker_death(self, w: WorkerInfo, node_death: bool = False):
        failed_boot = False
        lease_caller = None
        with self._lock:
            node = self._nodes.get(w.node_id)
            if w.addr is not None:
                self._unregistered_deaths = 0
                self._workers.pop(w.addr, None)
                if node is not None:
                    try:
                        node.idle.remove(w.addr)
                    except ValueError:
                        pass
                if w.leased_to is not None:
                    if node is not None:
                        node.release(w.lease_resources or {})
                    lease_caller = w.leased_to
                    w.leased_to = None
                    w.lease_resources = None
            else:
                if not w.dedicated and node is not None:
                    node.spawning_pool -= 1
                if not node_death:
                    # Died before registering: almost always an import/
                    # boot failure — make it visible instead of
                    # crash-looping. (A node death taking booting workers
                    # with it is NOT a boot loop.)
                    self._unregistered_deaths += 1
                    failed_boot = self._unregistered_deaths >= 3
        if lease_caller is not None:
            # Tell the lease holder explicitly: its direct connection to
            # the worker may be half-open (hung node, partition) and
            # would otherwise never error, leaving its in-flight leased
            # tasks stuck.
            with self._lock:
                caller_conn = self._conns_by_addr.get(lease_caller)
            if caller_conn is not None:
                try:
                    caller_conn.send({"kind": "leased_worker_died",
                                      "worker_addr": w.addr})
                except protocol.ConnectionClosed:
                    pass
        if w.addr is None and not node_death:
            self._publish("error", (
                f"worker pid={w.pid} exited (code {w.returncode}) "
                f"before registering; see {self.session_dir}/logs/"))
        if failed_boot:
            # Stop respawning into a boot loop: fail everything pending.
            with self._lock:
                pending = list(self._pending)
                self._pending.clear()
                self._unregistered_deaths = 0
            for spec in pending:
                self._fail_task_to_caller(spec, WorkerCrashedError(
                    "worker processes repeatedly failed to boot; see "
                    f"{self.session_dir}/logs/"))
            return

        with self._lock:
            spec = w.current_task
            w.current_task = None
            actor_id = w.actor_id
            if spec is not None:
                self._inflight.pop(spec.task_id, None)
                node = self._nodes.get(w.node_id)
                if node is not None:
                    node.release(spec.resources)
            retry = (spec is not None and actor_id is None
                     and spec.retries_used < spec.max_retries)
            if retry:
                spec.retries_used += 1
                self._pending.append(spec)
            self._schedule_locked()
            self._prune_spawned_locked()

        if actor_id is not None:
            self._handle_actor_death(actor_id, w)
        elif spec is not None and not retry:
            self._fail_task_to_caller(spec, WorkerCrashedError(
                f"worker pid={w.pid} died while running "
                f"{spec.describe()} (exit code {w.returncode})"))

    def _prune_spawned_locked(self):
        """Bound the spawn ledger: reaped records are diagnostics only,
        so once they exceed RAY_TPU_HEAD_SPAWNED_MAX the oldest go
        (insertion order ~ spawn order). Live entries are never pruned —
        lease release and death handling still need them."""
        reaped = [t for t, w in self._spawned.items() if w._reaped]
        if len(reaped) > self._spawned_max:
            for t in reaped[:len(reaped) - self._spawned_max]:
                del self._spawned[t]

    def _handle_node_death(self, node_id: str):
        """A node agent disconnected: declare its workers dead (reference:
        raylet monitor marking a node dead after missed heartbeats,
        `monitor.cc`)."""
        with self._lock:
            node = self._nodes.get(node_id)
            if node is None:
                return
            node.alive = False
            victims = [w for w in self._spawned.values()
                       if w.node_id == node_id and not w._reaped]
            for w in victims:
                w._reaped = True
            del self._nodes[node_id]
        self._publish("error", f"node {node_id} died")
        for w in victims:
            # A dead node's workers are dead with it (machine-loss
            # semantics). When the node was declared dead by heartbeat
            # timeout the worker processes may still be running — order
            # them to exit so a zombie node can't keep pushing results.
            if w.conn is not None:
                try:
                    w.conn.send({"kind": "shutdown"})
                except protocol.ConnectionClosed:
                    pass
                try:
                    w.conn.close()
                except Exception:
                    pass
            self._handle_worker_death(w, node_death=True)

    def _handle_actor_death(self, actor_id: ActorID, w: WorkerInfo):
        with self._lock:
            info = self._actors.get(actor_id)
            if info is None or info.state == DEAD:
                return
            if info.restarts_left != 0:
                if info.restarts_left > 0:
                    info.restarts_left -= 1
                info.state = RESTARTING
                info.addr = None
                view = info.view()
                # Re-run the creation task (reference semantics:
                # max_reconstructions replays the creation task,
                # doc/source/fault-tolerance.rst:48).
                self._pending.append(info.spec)
                self._schedule_locked()
            else:
                info.state = DEAD
                info.death_reason = f"worker pid={w.pid} exited"
                info.addr = None
                self._release_actor_name_locked(info)
                view = info.view()
        self._publish("actor:" + actor_id.hex(), view)

    def _release_actor_name_locked(self, info: ActorInfo):
        """Free a named actor's name when it dies for good, so the name can
        be reused (reference: named actor entries are cleaned on death).

        Called while holding the global lock; the shard compare-and-
        delete takes that shard's KV lock — the one sanctioned
        HeadServer._lock -> HeadShard._lock nesting (see
        head_shards.py docstring + the lock-graph gate)."""
        name = info.spec.name
        if name:
            key = "named_actor:" + name
            self._shards.kv_del_if_equals(
                key, info.spec.actor_id.binary())
        # Opportunistic bound on the DEAD-actor ledger: keep the most
        # recent _dead_actors_max corpses for diagnostics, drop the rest
        # (insertion order ~ creation order, so oldest go first).
        dead = [a for a, i in self._actors.items() if i.state == DEAD]
        if len(dead) > self._dead_actors_max:
            for a in dead[:len(dead) - self._dead_actors_max]:
                del self._actors[a]

    def _fail_task_to_caller(self, spec: TaskSpec, error: Exception):
        self._record_task(spec, task_events.FAILED, error=str(error)[:300])
        with self._lock:
            conn = self._conns_by_addr.get(spec.caller_addr)
        if conn is None:
            return
        try:
            for oid in spec.return_ids():
                conn.send({"kind": "push_result", "object_id": oid,
                           "error": error})
        except protocol.ConnectionClosed:
            pass

    # ------------------------------------------------------------------
    def start_pool_workers(self, n: int):
        with self._lock:
            node = self._nodes["node0"]
            for _ in range(n):
                self._spawn_worker_locked(node, dedicated=False)

    def shutdown(self):
        with self._lock:
            self._shutdown = True
            workers = list(self._spawned.values())
            agents = [n.conn for n in self._nodes.values()
                      if n.conn is not None]
        for conn in agents:
            try:
                conn.send({"kind": "shutdown"})
            except protocol.ConnectionClosed:
                pass
        for w in workers:
            if w.conn is not None:
                try:
                    w.conn.send({"kind": "shutdown"})
                except protocol.ConnectionClosed:
                    pass
        deadline = time.monotonic() + 2.0
        for w in workers:
            if w.proc is None:
                continue
            remaining = deadline - time.monotonic()
            try:
                w.proc.wait(timeout=max(0.05, remaining))
            except subprocess.TimeoutExpired:
                try:
                    w.proc.kill()
                    w.proc.wait(timeout=5)
                except OSError:
                    pass
        self.server.close()
        if self.tcp_server is not None:
            self.tcp_server.close()
        # Stop and join the head's own service threads so repeated
        # init()/shutdown() in one process does not leak them.
        if self._metrics_http is not None:
            try:
                self._metrics_http.shutdown()
                self._metrics_http.server_close()
            except Exception:
                logger.warning("metrics http shutdown failed",
                               exc_info=True)
        if self._log_tailer is not None:
            self._log_tailer.stop()
            self._log_tailer.join(timeout=1.0)
        if self._monitor_thread is not threading.current_thread():
            self._monitor_thread.join(timeout=2.0)
        # In-flight capture coordinators: unblock their waits and join.
        with self._lock:
            captures = list(self._captures.values())
            capture_threads = list(self._capture_threads)
        for entry in captures:
            entry["expected"].clear()
            entry["event"].set()
        for t in capture_threads:
            if t is not threading.current_thread():
                t.join(timeout=2.0)
