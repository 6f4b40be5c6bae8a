"""Location-aware multi-source object distribution (PR 5 tentpole).

Covers the replica directory (head-tracked locations, register on seal /
deregister on evict, stale entries tolerated), location-aware fetch
routing (local-shm short-circuit, least-loaded replica, owner
fallback), per-node single-flight fetch dedup, the bounded-fan-out
redirect tree, and the `replica.fetch` chaos site with deterministic
replay.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest
from conftest import wait_until

import ray_tpu
from ray_tpu._private import chaos, metrics, protocol, serialization
from ray_tpu._private import node as node_mod
from ray_tpu._private import worker_state as _ws
from ray_tpu._private.ids import ObjectID
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.object_store import SharedObjectStore


def _counter(name):
    return metrics.snapshot()["counters"].get(name, 0.0)


# ======================================================================
# directory: register on seal, deregister on evict, resolution order
# ======================================================================
class TestDirectory:
    def test_register_on_seal_deregister_on_evict(self, ray_start):
        rt = _ws.get_runtime()
        head = node_mod._node.head
        oid = ObjectID.generate()
        # Mark the seal as a pull-fetch landing (what _fetch_once does).
        with rt._replica_lock:
            rt._replica_expected.add(oid)
        rt.shm.put_blob(oid, b"x" * 4096)
        wait_until(
            lambda: head.object_location_counts().get(oid.hex()) == 1,
            timeout=10, msg="directory registration")
        with rt._replica_lock:
            assert oid in rt._replica_oids
        # Eviction (any shm delete: free, chaos evict, corrupt
        # recovery) deregisters through the store hook.
        rt.shm.delete(oid)
        wait_until(
            lambda: oid.hex() not in head.object_location_counts(),
            timeout=10, msg="directory deregistration")

    def test_owned_seals_do_not_register(self, ray_start):
        head = node_mod._node.head
        ref = ray_tpu.put(np.zeros(300_000, dtype=np.uint8))
        time.sleep(0.1)
        assert ref.id.hex() not in head.object_location_counts()

    def test_resolution_orders_least_loaded(self, ray_start):
        rt = _ws.get_runtime()
        head = node_mod._node.head
        oid = ObjectID.generate()
        a1, a2 = "tcp://127.0.0.1:1111", "tcp://127.0.0.1:2222"
        for addr in (a1, a2):
            head._h_object_location_add(
                None, {"object_id": oid, "addr": addr, "node_id": "nX"})
        firsts = []
        for _ in range(2):
            reply = rt.head.request(
                {"kind": "object_locations", "object_id": oid},
                timeout=5)
            assert len(reply["locations"]) == 2
            firsts.append(reply["locations"][0]["addr"])
        # Grant accounting rotates the preferred replica.
        assert set(firsts) == {a1, a2}

    def test_dead_process_registrations_dropped(self, ray_start):
        head = node_mod._node.head
        oid = ObjectID.generate()
        addr = "tcp://127.0.0.1:3333"
        head._h_object_location_add(
            None, {"object_id": oid, "addr": addr, "node_id": "nY"})
        assert head.object_location_counts().get(oid.hex()) == 1

        class _DeadConn:
            peer_addr = addr
        head._on_conn_close(_DeadConn())
        assert oid.hex() not in head.object_location_counts()

    def test_cluster_info_exposes_location_counts(self, ray_start):
        head = node_mod._node.head
        oid = ObjectID.generate()
        head._h_object_location_add(
            None, {"object_id": oid, "addr": "tcp://127.0.0.1:4",
                   "node_id": "nZ"})
        info = ray_tpu.cluster_info()
        locs = info["object_locations"]
        assert locs["objects"] >= 1 and locs["replicas"] >= 1
        assert any(h == oid.hex() for h, _ in locs["top"])


# ======================================================================
# local-shm short-circuit (satellite fix): sealed-on-this-node objects
# must never cost an owner RPC
# ======================================================================
class TestLocalShortCircuit:
    def _sealed_foreign_ref(self, rt, value):
        oid = ObjectID.generate()
        blob = serialization.dumps(value)
        rt.shm.put_blob(oid, blob)
        # Owner deliberately unreachable: any RPC would fail/hang.
        return ObjectRef(oid, "tcp://127.0.0.1:9", len(blob))

    def test_get_never_dials_owner(self, ray_start):
        rt = _ws.get_runtime()
        value = np.arange(50_000, dtype=np.int64)  # ~400 KB
        ref = self._sealed_foreign_ref(rt, value)
        before = _counter("object_fetch_source.local_shm")
        t0 = time.monotonic()
        out = ray_tpu.get(ref, timeout=5)
        assert time.monotonic() - t0 < 2.0
        np.testing.assert_array_equal(out, value)
        assert _counter("object_fetch_source.local_shm") > before
        assert "tcp://127.0.0.1:9" not in rt._conns

    def test_wait_is_ready_without_owner_rpc(self, ray_start):
        rt = _ws.get_runtime()
        ref = self._sealed_foreign_ref(
            rt, np.arange(40_000, dtype=np.int64))
        ready, not_ready = ray_tpu.wait([ref], num_returns=1, timeout=2)
        assert ready == [ref] and not not_ready
        assert "tcp://127.0.0.1:9" not in rt._conns

    def test_request_from_owner_probe_short_circuits(self, ray_start):
        # Even the fetch worker itself (race window: sealed between
        # prefetch check and pool execution) must not dial out.
        rt = _ws.get_runtime()
        ref = self._sealed_foreign_ref(
            rt, np.arange(30_000, dtype=np.int64))
        rt._request_from_owner(ref, timeout=2)
        cell = rt.memory.get_if_exists(ref.id)
        assert cell is not None and cell.value.kind == "shm"
        assert "tcp://127.0.0.1:9" not in rt._conns


# ======================================================================
# per-node single-flight fetch claims
# ======================================================================
class TestSingleFlight:
    def test_claim_primitives(self, tmp_path):
        store = SharedObjectStore("claims")
        store.prefix = os.path.join(str(tmp_path), "raytpu_claims_")
        oid = ObjectID.generate()
        assert store.try_claim_fetch(oid)
        assert not store.try_claim_fetch(oid)  # single flight
        assert store.fetch_claim_holder(oid) == os.getpid()
        store.release_fetch_claim(oid)
        assert store.fetch_claim_holder(oid) is None
        assert store.try_claim_fetch(oid)  # reusable after release
        store.release_fetch_claim(oid)

    def test_stale_claim_of_dead_process_is_broken(self, ray_start):
        rt = _ws.get_runtime()
        oid = ObjectID.generate()
        ref = ObjectRef(oid, "tcp://127.0.0.1:9", 200_000)
        proc = subprocess.Popen([sys.executable, "-c", "pass"])
        proc.wait()
        with open(rt.shm._claim_path(oid), "w") as f:
            f.write(str(proc.pid))  # dead claimer
        out = rt._await_node_fetch(ref, time.monotonic() + 5)
        assert out == "retry"
        assert rt.shm.fetch_claim_holder(oid) is None  # claim broken

    def test_waiter_wakes_on_sibling_seal(self, ray_start):
        rt = _ws.get_runtime()
        value = np.arange(40_000, dtype=np.int64)
        blob = serialization.dumps(value)
        oid = ObjectID.generate()
        ref = ObjectRef(oid, "tcp://127.0.0.1:9", len(blob))
        assert rt.shm.try_claim_fetch(oid)  # "sibling" holds the claim

        def seal_later():
            time.sleep(0.2)
            rt.shm.put_blob(oid, blob)
        t = threading.Thread(target=seal_later)
        t.start()
        before = _counter("object_fetch_dedup_waits")
        out = rt._await_node_fetch(ref, time.monotonic() + 10)
        t.join()
        assert out == "done"
        assert _counter("object_fetch_dedup_waits") > before
        rt.shm.release_fetch_claim(oid)


# ======================================================================
# redirect tree (owner fan-out cap) + stale-replica fallback
# ======================================================================
class TestRedirectTree:
    def test_owner_at_cap_redirects_then_no_redirect_serves(
            self, ray_start):
        rt = _ws.get_runtime()
        ref = ray_tpu.put(np.zeros(1_000_000, dtype=np.uint8))  # > stripe_min
        oid = ref.id
        with rt._uploads_lock:
            rt._object_uploads[oid] = rt._max_uploads_per_object
            rt._object_sent_to[oid] = [("tcp://127.0.0.1:7777", "nodeZ")]
        replies = []

        class _Conn:
            peer_addr = "tcp://127.0.0.1:8888"

            def reply(self, msg, **fields):
                replies.append(fields)
        rt._on_get_object(_Conn(), {"object_id": oid,
                                    "node_id": "other", "seq": 1})
        assert replies[0]["status"] == "redirect"
        assert replies[0]["addr"] == "tcp://127.0.0.1:7777"
        # no_redirect (a borrower that already bounced off a stale
        # replica) forces the owner to serve past the cap.
        replies.clear()
        rt._on_get_object(_Conn(), {"object_id": oid, "node_id": "other",
                                    "seq": 2, "no_redirect": True})
        assert replies[0]["status"] == "chunked"
        with rt._uploads_lock:  # forced upload took a slot
            assert rt._object_uploads.get(oid, 0) \
                >= rt._max_uploads_per_object

    def test_redirect_not_issued_below_cap(self, ray_start):
        rt = _ws.get_runtime()
        ref = ray_tpu.put(np.zeros(1_000_000, dtype=np.uint8))
        replies = []

        class _Conn:
            peer_addr = "tcp://127.0.0.1:8888"

            def reply(self, msg, **fields):
                replies.append(fields)
        rt._on_get_object(_Conn(), {"object_id": ref.id,
                                    "node_id": "other", "seq": 1})
        assert replies[0]["status"] == "chunked"

    def test_redirect_then_stale_replica_falls_back_to_owner(
            self, ray_start):
        """Full fetcher-side chain: owner redirects -> replica evicted
        its copy (stale) -> fetcher retries the owner with no_redirect
        and the owner serves. The eviction-under-redirect case of the
        tree."""
        rt = _ws.get_runtime()
        value = np.arange(60_000, dtype=np.int64)
        blob = serialization.dumps(value)
        oid = ObjectID.generate()
        events = []
        servers = []

        def replica_handler(conn, msg):
            if msg.get("kind") != "get_object":
                return
            events.append("replica")
            conn.reply(msg, status="lost")  # evicted: stale entry

        replica_srv = protocol.Server("tcp://127.0.0.1:0",
                                      replica_handler)
        servers.append(replica_srv)

        def owner_handler(conn, msg):
            if msg.get("kind") != "get_object":
                return
            if msg.get("no_redirect"):
                events.append("owner-forced")
                conn.reply(msg, status="blob", data=blob)
            else:
                events.append("owner-redirect")
                conn.reply(msg, status="redirect",
                           addr=replica_srv.path, node="nodeR")

        owner_srv = protocol.Server("tcp://127.0.0.1:0", owner_handler)
        servers.append(owner_srv)
        try:
            ref = ObjectRef(oid, owner_srv.path, len(blob))
            before = _counter("object_fetch_replica_fallbacks")
            rt._request_from_owner(ref, timeout=15)
            assert events == ["owner-redirect", "replica",
                              "owner-forced"]
            cell = rt.memory.get_if_exists(oid)
            assert cell is not None
            np.testing.assert_array_equal(
                rt._decode_cell(oid, cell.value), value)
            assert _counter("object_fetch_replica_fallbacks") > before
            assert _counter("object_fetch_redirects_followed") >= 1
        finally:
            for s in servers:
                s.close()

    def test_stale_directory_entry_falls_back(self, ray_start):
        """The head names a replica that is gone: the fetch must fall
        back to the owner transparently."""
        rt = _ws.get_runtime()
        head = node_mod._node.head
        value = np.arange(60_000, dtype=np.int64)
        blob = serialization.dumps(value)
        oid = ObjectID.generate()

        def owner_handler(conn, msg):
            if msg.get("kind") == "get_object":
                conn.reply(msg, status="blob", data=blob)

        owner_srv = protocol.Server("tcp://127.0.0.1:0", owner_handler)
        try:
            # Dead replica in the directory (nothing listens there).
            head._h_object_location_add(
                None, {"object_id": oid,
                       "addr": "tcp://127.0.0.1:1", "node_id": "gone"})
            ref = ObjectRef(oid, owner_srv.path, len(blob))
            before = _counter("object_fetch_replica_fallbacks")
            rt._request_from_owner(ref, timeout=15)
            cell = rt.memory.get_if_exists(oid)
            assert cell is not None
            np.testing.assert_array_equal(
                rt._decode_cell(oid, cell.value), value)
            assert _counter("object_fetch_replica_fallbacks") > before
        finally:
            owner_srv.close()


# ======================================================================
# config / catalog surface
# ======================================================================
class TestDistributionConfig:
    def test_knobs_registered(self):
        from ray_tpu._private import config
        for knob in ("RAY_TPU_LOCATION_FETCH",
                     "RAY_TPU_MAX_UPLOADS_PER_OBJECT"):
            assert knob in config.defs(), knob

    def test_chaos_catalog_has_replica_fetch(self):
        assert "replica.fetch" in chaos.SITES
        assert {"die", "stale"} <= set(chaos.SITES["replica.fetch"])

    def test_off_switch_disables_routing(self, monkeypatch, ray_start):
        rt = _ws.get_runtime()
        monkeypatch.setattr(rt, "_location_fetch", False)
        ref = ObjectRef(ObjectID.generate(), "tcp://127.0.0.1:9",
                        10 << 20)
        assert not rt._routed_fetch_eligible(ref)
        assert rt._pick_fetch_source(ref) is None


# ======================================================================
# multi-node integration: broadcast egress stays flat, same-node zero
# wire bytes, replica registration
# ======================================================================
@pytest.fixture(scope="class")
def bcast_cluster():
    saved = {k: os.environ.get(k)
             for k in ("RAY_TPU_WIRE_COMPRESSION",
                       "RAY_TPU_LOCATION_FETCH")}
    os.environ["RAY_TPU_WIRE_COMPRESSION"] = "off"
    os.environ["RAY_TPU_LOCATION_FETCH"] = "1"
    from ray_tpu.cluster_utils import Cluster
    cluster = Cluster(head_resources={"CPU": 3})
    cluster.add_node(resources={"CPU": 2, "B": 8})
    try:
        yield cluster
    finally:
        cluster.shutdown()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


class _BorrowerImpl:
    def ping(self):
        return os.getpid()

    def fetch(self, value):
        # Ref args auto-resolve before the body runs (the RLlib
        # set_weights shape): the fetch already happened in THIS
        # process through the routed path — snapshot its counters.
        from ray_tpu._private import metrics as metrics_mod
        snap = metrics_mod.snapshot()["counters"]
        return {"sum": int(value.sum()), "pid": os.getpid(),
                "counters": {k: v for k, v in snap.items()
                             if k.startswith(("object_fetch",
                                              "wire_bytes"))}}


Borrower = ray_tpu.remote(resources={"B": 1})(_BorrowerImpl)
LocalBorrower = ray_tpu.remote(resources={"CPU": 1})(_BorrowerImpl)


class TestClusterBroadcast:
    BLOB = 2 << 20  # 2 MB, incompressible

    def _blob(self, seed):
        return np.random.default_rng(seed).integers(
            0, 256, self.BLOB, dtype=np.uint8)

    def _bcast(self, borrowers, blob):
        before = _counter("wire_bytes_on_wire")
        ref = ray_tpu.put(blob)
        out = ray_tpu.get([b.fetch.remote(ref) for b in borrowers],
                          timeout=120)
        expected = int(blob.sum())
        assert all(r["sum"] == expected for r in out)
        del ref
        return _counter("wire_bytes_on_wire") - before, out

    def test_broadcast_egress_flat_as_borrowers_double(
            self, bcast_cluster):
        """4 distinct worker processes on one remote node concurrently
        fetching one owner object must coalesce into ~one wire
        transfer: owner egress per broadcast stays ~flat as the
        borrower count doubles (the >=2x win over owner-only, where
        egress would be N blobs)."""
        borrowers = [Borrower.remote() for _ in range(4)]
        pids = ray_tpu.get([b.ping.remote() for b in borrowers],
                           timeout=60)
        assert len(set(pids)) == 4  # distinct processes, one node
        e2, _ = self._bcast(borrowers[:2], self._blob(1))
        e4, out4 = self._bcast(borrowers, self._blob(2))
        # Each broadcast costs about ONE blob of owner egress (dedup),
        # not N: >=2x reduction at N=4 versus per-borrower fetches.
        assert e4 < 2.0 * self.BLOB, (e2, e4)
        assert e4 < 1.6 * max(e2, 1), (e2, e4)
        # At least one borrower was served by the node store rather
        # than its own wire transfer.
        dedup_or_local = sum(
            r["counters"].get("object_fetch_source.local_shm", 0)
            + r["counters"].get("object_fetch_dedup_waits", 0)
            for r in out4)
        assert dedup_or_local >= 1

    def test_replica_registered_in_directory(self, bcast_cluster):
        head = bcast_cluster.node.head
        borrowers = [Borrower.remote()]
        blob = self._blob(3)
        ref = ray_tpu.put(blob)
        out = ray_tpu.get(borrowers[0].fetch.remote(ref), timeout=90)
        assert out["sum"] == int(blob.sum())
        wait_until(
            lambda: head.object_location_counts().get(ref.id.hex(), 0)
            >= 1, timeout=10, msg="replica registration from remote node")

    def test_same_node_borrower_zero_wire_bytes(self, bcast_cluster):
        """A borrower process on the owner's node serves the fetch
        straight from the shared store: object_fetch_source.local_shm
        counts it and its wire-receive counter stays zero."""
        b = LocalBorrower.remote()
        ray_tpu.get(b.ping.remote(), timeout=60)
        blob = self._blob(4)
        ref = ray_tpu.put(blob)
        out = ray_tpu.get(b.fetch.remote(ref), timeout=60)
        assert out["sum"] == int(blob.sum())
        assert out["counters"].get("object_fetch_source.local_shm",
                                   0) >= 1
        assert out["counters"].get("wire_bytes_recv", 0) == 0


# ======================================================================
# chaos: replica.fetch site, deterministic replay
# ======================================================================
class TestChaosReplicaFetch:
    def test_replica_die_falls_back_and_replays(self, tmp_path):
        """A kill schedule takes out the replica chosen for a routed
        fetch: the borrower falls back to the owner transparently (no
        partial seal — the fault fires before any byte lands) and the
        injection trace replays byte-identical from its seed."""
        spec = "seed=11;replica.fetch:die:n1"
        trace_path = str(tmp_path / "chaos.jsonl")
        saved = {k: os.environ.get(k)
                 for k in ("RAY_TPU_CHAOS", "RAY_TPU_CHAOS_TRACE",
                           "RAY_TPU_WIRE_COMPRESSION")}
        os.environ["RAY_TPU_CHAOS"] = spec
        os.environ["RAY_TPU_CHAOS_TRACE"] = trace_path
        os.environ["RAY_TPU_WIRE_COMPRESSION"] = "off"
        from ray_tpu.cluster_utils import Cluster
        cluster = None
        try:
            cluster = Cluster(head_resources={"CPU": 2})
            cluster.add_node(resources={"CPU": 2, "A": 1})
            cluster.add_node(resources={"CPU": 2, "C": 1})

            @ray_tpu.remote(resources={"A": 1})
            class FirstBorrower:
                def fetch(self, value):  # ref arg auto-resolves
                    return int(value.sum())

            @ray_tpu.remote(resources={"C": 1})
            class SecondBorrower:
                def fetch(self, value):
                    return int(value.sum())

            blob = np.random.default_rng(9).integers(
                0, 256, 1 << 20, dtype=np.uint8)
            ref = ray_tpu.put(blob)
            expected = int(blob.sum())
            # First borrower seals a replica on its node + registers.
            a = FirstBorrower.remote()
            assert ray_tpu.get(a.fetch.remote(ref), timeout=90) \
                == expected
            head = cluster.node.head
            wait_until(
                lambda: head.object_location_counts().get(
                    ref.id.hex(), 0) >= 1,
                timeout=10, msg="replica registration")
            # Second borrower routes at the replica; chaos kills that
            # fetch; the owner fallback must still deliver the value.
            c = SecondBorrower.remote()
            assert ray_tpu.get(c.fetch.remote(ref), timeout=90) \
                == expected
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                m = ray_tpu.cluster_metrics()["counters"]
                if m.get("object_fetch_replica_fallbacks", 0) >= 1 \
                        and m.get("chaos_injections_total", 0) >= 1:
                    break
                time.sleep(0.5)
            else:
                pytest.fail(f"fallback/injection counters missing: {m}")
        finally:
            if cluster is not None:
                cluster.shutdown()
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            chaos.uninstall()
        entries = chaos.load_trace(trace_path)
        assert any(e["site"] == "replica.fetch" and e["kind"] == "die"
                   for e in entries)
        replayed = chaos.replay(spec, entries)
        assert chaos.trace_bytes(replayed) == chaos.trace_bytes(entries)
