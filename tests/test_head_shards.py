"""Sharded head control plane (PR: partitioned pub/sub head).

Covers crc32 shard routing determinism, cross-shard merged reads under
concurrent mutation (consistent-per-shard, never torn), the
object-location pub/sub plane (client cache fed by `objloc:<k>` deltas,
invalidation on evict and connection death, ZERO head RPCs on the
steady-state lookup path — the acceptance counter), bounded head-side
tables, shard observability (per-shard stats + occupancy gauges), and
a 2-node A/B asserting byte-identical task results vs
``RAY_TPU_HEAD_SHARDS=1``.
"""

import hashlib
import shutil
import tempfile
import threading
import time
import types
import zlib

import pytest
from conftest import wait_until

import ray_tpu
from ray_tpu._private import config, head_shards, metrics, protocol
from ray_tpu._private import node as node_mod
from ray_tpu._private import worker_state as _ws
from ray_tpu._private.head import HeadServer
from ray_tpu._private.ids import ObjectID


def _counter(name):
    return metrics.snapshot()["counters"].get(name, 0.0)


@pytest.fixture
def raw_head():
    """A bare in-process HeadServer (no workers, no object store) —
    the control plane alone, like the saturation bench drives."""
    session_dir = tempfile.mkdtemp(prefix="ray_tpu_headshard_test_")
    head = HeadServer(session_dir, "headshardtest", {"CPU": 1.0})
    try:
        yield head
    finally:
        head.shutdown()
        shutil.rmtree(session_dir, ignore_errors=True)


# ======================================================================
# routing: stable, process-independent, spreads over shards
# ======================================================================
class TestRouting:
    def test_routing_is_crc32_and_stable(self):
        # crc32, NOT salted hash(): clients and head must agree across
        # processes and runs.
        assert head_shards.shard_index(b"alpha", 8) \
            == zlib.crc32(b"alpha") % 8
        # str and utf-8 bytes route identically; ObjectID routes by its
        # binary form.
        assert head_shards.shard_index("alpha", 8) \
            == head_shards.shard_index(b"alpha", 8)
        oid = ObjectID(hashlib.sha1(b"route").digest())
        assert head_shards.shard_index(oid, 8) \
            == head_shards.shard_index(oid.binary(), 8)
        # Single shard degenerates to 0 without hashing.
        assert head_shards.shard_index(b"anything", 1) == 0
        # Repeated calls are identical.
        assert [head_shards.shard_index(f"k{i}", 4) for i in range(32)] \
            == [head_shards.shard_index(f"k{i}", 4) for i in range(32)]

    def test_routing_spreads_over_all_shards(self):
        hits = [0, 0, 0, 0]
        for i in range(256):
            hits[head_shards.shard_index(f"key:{i}", 4)] += 1
        assert all(h > 0 for h in hits), hits
        assert max(hits) < 2.5 * (256 / 4), hits

    def test_shard_for_matches_module_routing(self):
        hs = head_shards.HeadShards(nshards=4)
        for i in range(32):
            key = f"match:{i}"
            assert hs.shard_for(key) \
                is hs.planes[head_shards.shard_index(key, 4)]
            assert hs.shard_index(key) == head_shards.shard_index(key, 4)


# ======================================================================
# cross-shard merged reads: consistent-per-shard, never torn
# ======================================================================
class TestCrossShardMerges:
    def test_merged_reads_not_torn_under_churn(self):
        hs = head_shards.HeadShards(nshards=4, obj_locations_max=4096)
        stable_keys = [f"stable:{i}" for i in range(48)]
        for k in stable_keys:
            hs.shard_for(k).kv_put(k, b"v")
        stable_oids = [ObjectID(hashlib.sha1(f"so:{i}".encode()).digest())
                       for i in range(32)]
        for o in stable_oids:
            hs.shard_for(o).location_add(o, "addr-stable", "n0")
        stop = threading.Event()
        errors = []

        def churn(t):
            o = ObjectID(hashlib.sha1(f"churn:{t}".encode()).digest())
            j = 0
            try:
                while not stop.is_set():
                    k = f"volatile:{t}:{j % 8}"
                    hs.shard_for(k).kv_put(k, b"x")
                    hs.shard_for(k).kv_del(k)
                    hs.shard_for(o).location_add(o, f"a{j % 4}", "n1")
                    hs.shard_for(o).location_remove(o, f"a{j % 4}")
                    hs.shard_for(f"p{t}").metrics_push(
                        f"p{t}", {"node": "n1",
                                  "counters": {"c": float(j)}})
                    j += 1
            except Exception as e:  # noqa: BLE001 - fail the test below
                errors.append(e)

        threads = [threading.Thread(target=churn, args=(t,))
                   for t in range(3)]
        for th in threads:
            th.start()
        try:
            want_keys = set(stable_keys)
            want_oids = {o.hex() for o in stable_oids}
            for _ in range(200):
                got = hs.kv_keys("stable:")
                assert want_keys <= set(got)
                assert len(got) == len(set(got)), "duplicate keys in merge"
                counts = hs.location_counts()
                assert want_oids <= set(counts)
                assert all(counts[h] >= 1 for h in want_oids)
                snaps, dead = hs.metrics_merged()
                assert isinstance(snaps, dict) and isinstance(dead, dict)
        finally:
            stop.set()
            for th in threads:
                th.join(timeout=10)
        assert not errors, errors

    def test_task_events_route_and_merge(self):
        hs = head_shards.HeadShards(nshards=4, task_log_max=256)
        tids = [hashlib.sha1(f"t{i}".encode()).digest()[:16].hex()
                for i in range(24)]
        for i, tid in enumerate(tids):
            hs.apply_task_event({"task_id": tid, "state": "QUEUED",
                                 "ts": float(i), "name": f"job{i % 3}"})
            hs.apply_task_event({"task_id": tid, "state": "FINISHED",
                                 "ts": float(i) + 0.5})
        assert hs.task_state_counts().get("FINISHED") == 24
        listed = hs.task_list(limit=100)
        assert {r["task_id"] for r in listed} == set(tids)
        # Merge respects the limit and newest-first ordering.
        top = hs.task_list(limit=5)
        assert len(top) == 5
        starts = [r["start"] for r in top]
        assert starts == sorted(starts, reverse=True)
        summary = hs.task_summary()
        assert sum(per.get("FINISHED", 0)
                   for per in summary.values()) == 24


# ======================================================================
# pub/sub location cache: zero-RPC steady state + invalidation
# ======================================================================
class TestLocationPubSub:
    def test_steady_state_lookups_issue_zero_head_rpcs(self, ray_start):
        """Acceptance counter: after the one snapshot miss, location
        fetches are served entirely from the client cache."""
        rt = _ws.get_runtime()
        head = node_mod._node.head
        oid = ObjectID.generate()
        head._h_object_location_add(
            None, {"object_id": oid, "addr": "tcp://127.0.0.1:7001",
                   "node_id": "nX"})
        # Priming miss: exactly one RPC, result cached.
        locs = rt._dir_locations(oid)
        assert locs == [("tcp://127.0.0.1:7001", "nX")]
        rpcs0 = _counter("object_dir_rpcs")
        hits0 = _counter("object_dir_cache_hits")
        for _ in range(50):
            assert rt._dir_locations(oid)
        assert _counter("object_dir_rpcs") == rpcs0
        assert _counter("object_dir_cache_hits") >= hits0 + 50

    def test_delta_add_refreshes_cache_without_rpc(self, ray_start):
        rt = _ws.get_runtime()
        head = node_mod._node.head
        oid = ObjectID.generate()
        head._h_object_location_add(
            None, {"object_id": oid, "addr": "tcp://a1", "node_id": "n1"})
        assert rt._dir_locations(oid)  # prime (subscribes + snapshots)
        rpcs0 = _counter("object_dir_rpcs")
        head._h_object_location_add(
            None, {"object_id": oid, "addr": "tcp://a2", "node_id": "n2"})
        wait_until(lambda: len(rt._dir_locations(oid) or ()) == 2,
                   timeout=10,
                   msg="published add delta to reach the client cache")
        assert _counter("object_dir_rpcs") == rpcs0

    def test_evict_delta_invalidates_cache(self, ray_start):
        rt = _ws.get_runtime()
        head = node_mod._node.head
        oid = ObjectID.generate()
        for addr in ("tcp://e1", "tcp://e2"):
            head._h_object_location_add(
                None, {"object_id": oid, "addr": addr, "node_id": "nE"})
        wait_until(lambda: len(rt._dir_locations(oid) or ()) == 2,
                   timeout=10, msg="both replicas visible")
        rpcs0 = _counter("object_dir_rpcs")
        head._h_object_location_remove(
            None, {"object_id": oid, "addr": "tcp://e1"})
        wait_until(
            lambda: [a for a, _ in rt._dir_locations(oid) or ()]
            == ["tcp://e2"],
            timeout=10,
            msg="published remove delta to invalidate the cached copy")
        assert _counter("object_dir_rpcs") == rpcs0

    def test_conn_death_scrubs_cached_locations(self, ray_start):
        rt = _ws.get_runtime()
        head = node_mod._node.head
        dead_addr = "probe-dying-addr"
        conn = protocol.connect(head.sock_path, dead_addr,
                                lambda c, m: None,
                                hello_extra={"role": "probe"})
        oid = ObjectID.generate()
        head._h_object_location_add(
            None, {"object_id": oid, "addr": dead_addr,
                   "node_id": "nD"})
        wait_until(lambda: rt._dir_locations(oid), timeout=10,
                   msg="replica cached")
        rpcs0 = _counter("object_dir_rpcs")
        conn.close()  # head publishes drop_addr on every shard channel
        wait_until(lambda: not rt._dir_locations(oid), timeout=10,
                   msg="drop_addr delta to scrub the dead registrant")
        assert _counter("object_dir_rpcs") == rpcs0

    def test_cache_disabled_falls_back_to_rpc_per_lookup(self, ray_start):
        rt = _ws.get_runtime()
        head = node_mod._node.head
        oid = ObjectID.generate()
        head._h_object_location_add(
            None, {"object_id": oid, "addr": "tcp://off1",
                   "node_id": "nO"})
        enabled = rt._dir_cache_enabled
        rt._dir_cache_enabled = False
        try:
            rpcs0 = _counter("object_dir_rpcs")
            for _ in range(5):
                assert rt._dir_locations(oid)
            assert _counter("object_dir_rpcs") == rpcs0 + 5
        finally:
            rt._dir_cache_enabled = enabled


# ======================================================================
# bounded tables
# ======================================================================
class TestBoundedTables:
    def test_shard_location_directory_is_lru_bounded(self):
        shard = head_shards.HeadShard(0, obj_locations_max=8,
                                      task_log_max=16)
        oids = [ObjectID(hashlib.sha1(f"b{i}".encode()).digest())
                for i in range(20)]
        for o in oids:
            shard.location_add(o, "a", "n")
        assert len(shard._obj_locations) <= 8
        # Newest survive, oldest evicted.
        assert shard.locations(oids[-1]) == [("a", "n")]
        assert shard.locations(oids[0]) == []

    def test_task_ring_segment_is_bounded(self):
        hs = head_shards.HeadShards(nshards=2, task_log_max=32)
        for i in range(200):
            tid = hashlib.sha1(f"ring{i}".encode()).digest()[:16].hex()
            hs.apply_task_event({"task_id": tid, "state": "FINISHED",
                                 "ts": float(i)})
        assert sum(hs.task_state_counts().values()) <= 32

    def test_spawned_ledger_prunes_reaped_only(self, raw_head):
        head = raw_head
        head._spawned_max = 10
        with head._lock:
            head._spawned.clear()
            for i in range(30):
                head._spawned[f"tok{i}"] = types.SimpleNamespace(
                    _reaped=(i < 25))
            head._prune_spawned_locked()
            reaped = [t for t, w in head._spawned.items() if w._reaped]
            live = [t for t, w in head._spawned.items() if not w._reaped]
            head._spawned.clear()  # fakes lack .conn; keep shutdown clean
        assert len(reaped) == 10
        # Oldest reaped pruned first; live records are never pruned.
        assert reaped == [f"tok{i}" for i in range(15, 25)]
        assert live == [f"tok{i}" for i in range(25, 30)]

    def test_client_dir_cache_is_lru_bounded(self, ray_start):
        rt = _ws.get_runtime()
        old_max = rt._dir_cache_max
        rt._dir_cache_max = 8
        try:
            for i in range(20):
                rt._dir_locations(ObjectID(
                    hashlib.sha1(f"lru{i}".encode()).digest()))
            with rt._dir_lock:
                assert len(rt._dir_cache) <= 8
        finally:
            rt._dir_cache_max = old_max

    def test_knobs_registered(self):
        assert config.get("RAY_TPU_HEAD_SHARDS") >= 1
        assert isinstance(config.get("RAY_TPU_DIR_CACHE"), bool)
        assert config.get("RAY_TPU_DIR_CACHE_MAX") > 0
        assert config.get("RAY_TPU_HEAD_SPAWNED_MAX") > 0
        assert config.get("RAY_TPU_HEAD_DEAD_ACTORS_MAX") > 0


# ======================================================================
# observability: per-shard stats, occupancy gauges, lock-wait series
# ======================================================================
class TestShardObservability:
    def test_stats_and_occupancy_gauges(self, raw_head):
        head = raw_head
        for i in range(64):
            head._shards.shard_for(f"obs:{i}").kv_put(f"obs:{i}", b"v")
        stats = head._shards.stats()
        assert len(stats) == head._shards.nshards
        assert {"shard", "kv_keys", "obj_locations", "metric_snaps",
                "task_records", "lock_wait_s", "lock_held_s",
                "contended_acquires"} <= set(stats[0])
        assert sum(s["kv_keys"] for s in stats) >= 64
        now = time.monotonic()
        head._sample_shard_occupancy(now)
        head._sample_shard_occupancy(now + 1.0)
        gauges = metrics.snapshot()["gauges"]
        for k in range(head._shards.nshards):
            assert f"head_shard_occupancy.s{k}" in gauges
            assert 0.0 <= gauges[f"head_shard_occupancy.s{k}"] <= 1.0

    def test_contended_acquire_lands_lock_wait_sample(self):
        metrics.reset()
        shard = head_shards.HeadShard(0, obj_locations_max=16,
                                      task_log_max=16)
        entered = threading.Event()
        release = threading.Event()

        def holder():
            with shard._lock:
                entered.set()
                release.wait(5.0)

        th = threading.Thread(target=holder)
        th.start()
        assert entered.wait(5.0)
        waited = []

        def contender():
            shard.kv_put("contended", b"v")
            waited.append(True)

        tc = threading.Thread(target=contender)
        tc.start()
        time.sleep(0.05)  # contender is now parked on the shard lock
        release.set()
        tc.join(5.0)
        th.join(5.0)
        assert waited
        snap = metrics.snapshot()
        h = snap["hists"].get("head_lock_wait_s")
        assert h and h["count"] >= 1
        assert shard.contended_acquires >= 1
        assert shard.lock_wait_s > 0.0


# ======================================================================
# A/B equivalence: sharded head produces byte-identical task results
# ======================================================================
def _run_cluster_workload(nshards: int):
    from ray_tpu.cluster_utils import Cluster
    config.set_override("RAY_TPU_HEAD_SHARDS", nshards)
    try:
        cluster = Cluster(head_resources={"CPU": 2})
        cluster.add_node(resources={"CPU": 2, "REMOTE": 4.0})

        @ray_tpu.remote(resources={"REMOTE": 1})
        def digest(i, blob):
            import hashlib as _h
            return _h.sha256(bytes([i % 251]) * 64 + blob).digest()

        blob_ref = ray_tpu.put(b"shard-equivalence-payload" * 64)
        out = ray_tpu.get([digest.remote(i, blob_ref)
                           for i in range(24)], timeout=180)
        kv_roundtrip = []
        rt = _ws.get_runtime()
        for i in range(8):
            rt.head.request({"kind": "kv_put", "key": f"ab:{i}",
                             "value": f"v{i}".encode()}, timeout=30)
            r = rt.head.request({"kind": "kv_get", "key": f"ab:{i}"},
                                timeout=30)
            kv_roundtrip.append(r.get("value"))
        cluster.shutdown()
        return out, kv_roundtrip
    finally:
        config.clear_override("RAY_TPU_HEAD_SHARDS")


def test_task_results_byte_identical_vs_single_shard():
    """2-node integration A/B: the same workload at
    RAY_TPU_HEAD_SHARDS=1 and =4 returns byte-identical results —
    sharding moves tables, never values."""
    tasks_1, kv_1 = _run_cluster_workload(1)
    tasks_4, kv_4 = _run_cluster_workload(4)
    assert tasks_1 == tasks_4
    assert kv_1 == kv_4
    assert all(isinstance(b, bytes) and len(b) == 32 for b in tasks_1)
