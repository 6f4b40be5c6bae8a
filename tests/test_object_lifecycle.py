"""Object store lifecycle: refcounting, capacity eviction, borrows.

Parity: `src/ray/core_worker/reference_count.h` (local refs + borrows
gate eviction) + plasma capacity eviction +
`python/ray/tests/test_reference_counting.py`.
"""

import gc
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import wait_until


@pytest.fixture
def small_store_ray():
    """A session whose object store caps at ~10 MB."""
    os.environ["RAY_TPU_OBJECT_STORE_CAPACITY"] = str(10 * 1024 * 1024)
    import ray_tpu
    ray_tpu.init(num_cpus=2)
    try:
        yield ray_tpu
    finally:
        ray_tpu.shutdown()
        del os.environ["RAY_TPU_OBJECT_STORE_CAPACITY"]


class TestEviction:
    def test_unreferenced_objects_evict(self, small_store_ray):
        ray = small_store_ray
        rt = ray._private.worker_state.get_runtime()
        # 8 x 2 MB puts against a 10 MB cap: dropping each ref as we go
        # lets earlier objects evict.
        for _ in range(8):
            ref = ray.put(np.zeros(1 << 18))  # 2 MB
            del ref
            gc.collect()
        assert rt.shm.used_bytes() <= 10 * 1024 * 1024

    def test_referenced_objects_survive(self, small_store_ray):
        ray = small_store_ray
        held = [ray.put(np.zeros(1 << 18)) for _ in range(3)]  # 6 MB
        for _ in range(5):
            ref = ray.put(np.zeros(1 << 18))
            del ref
            gc.collect()
        # every held ref still resolves
        for r in held:
            assert ray.get(r).shape == (1 << 18,)

    def test_store_full_raises_when_all_referenced(self, small_store_ray):
        ray = small_store_ray
        from ray_tpu.exceptions import ObjectStoreFullError
        held = []
        with pytest.raises(ObjectStoreFullError):
            for _ in range(8):
                held.append(ray.put(np.zeros(1 << 18)))

    def test_evicted_object_raises_lost(self, small_store_ray):
        ray = small_store_ray
        from ray_tpu._private.object_ref import ObjectRef
        ref = ray.put(np.zeros(1 << 18))
        # Keep only the raw id; the live-ref count drops to zero.
        oid, addr = ref.id, ref.owner_addr
        del ref
        gc.collect()
        for _ in range(6):
            r = ray.put(np.zeros(1 << 18))
            del r
            gc.collect()
        resurrected = ObjectRef(oid, addr)
        rt = ray._private.worker_state.get_runtime()
        assert not rt.shm.contains(oid)


class TestExportPins:
    """Acknowledged-borrow protocol (r4, replaces the r3 wall-clock
    grace): an owned ref exported through a protocol send stays pinned
    until the recipient's add_borrow arrives — no matter how delayed —
    or the recipient's connection dies."""

    def _export_via_protocol(self, rt, ref, peer="fake-peer-addr"):
        """Simulate pickling `ref` inside a protocol send to `peer`."""
        from ray_tpu._private import object_ref as oref
        oref.begin_export_collection()
        import pickle
        pickle.dumps(ref)
        rt._finish_export_collection(peer)

    def test_pin_survives_beyond_old_grace(self, small_store_ray,
                                           monkeypatch):
        ray = small_store_ray
        rt = ray._private.worker_state.get_runtime()
        # Old-grace regression setup: a borrower whose add_borrow lands
        # after the grace window. With pins, eviction must still wait.
        monkeypatch.setattr(rt, "_eviction_grace", 0.05)
        ref = ray.put(np.zeros(1 << 18))  # 2 MB
        oid = ref.id
        self._export_via_protocol(rt, ref)
        del ref
        gc.collect()
        import time
        time.sleep(0.2)  # well past the (shrunk) wall-clock grace
        # Pressure the store: pinned object must survive eviction.
        for _ in range(5):
            r = ray.put(np.zeros(1 << 18))
            del r
            gc.collect()
        assert rt.shm.contains(oid), \
            "exported object evicted before its borrow was acknowledged"
        # The (delayed) acknowledgement arrives; borrow registered.
        with rt._owned_lock:
            rt._borrows.setdefault(oid, {})["fake-peer-addr"] = 1
            rt._consume_export_pin_locked(oid, "fake-peer-addr")
        assert oid not in rt._export_pins
        # Borrow released -> object becomes evictable again.
        with rt._owned_lock:
            rt._borrows.pop(oid, None)
        for _ in range(5):
            r = ray.put(np.zeros(1 << 18))
            del r
            gc.collect()
        assert not rt.shm.contains(oid)

    def test_peer_death_releases_pin(self, small_store_ray, monkeypatch):
        ray = small_store_ray
        rt = ray._private.worker_state.get_runtime()
        monkeypatch.setattr(rt, "_eviction_grace", 0.05)
        ref = ray.put(np.zeros(1 << 18))
        oid = ref.id
        self._export_via_protocol(rt, ref, peer="dead-peer")
        del ref
        gc.collect()
        import time
        time.sleep(0.1)
        rt._drop_peer_pins("dead-peer")
        for _ in range(5):
            r = ray.put(np.zeros(1 << 18))
            del r
            gc.collect()
        assert not rt.shm.contains(oid)

    def test_real_task_arg_pins_and_releases(self, small_store_ray):
        """End to end: a ref passed as a task arg is pinned at send and
        released once the worker's borrow registers + drops."""
        ray = small_store_ray
        rt = ray._private.worker_state.get_runtime()

        @ray.remote
        def consume(x):
            return float(np.sum(x[:4]))

        ref = ray.put(np.ones(1 << 18))
        out = ray.get(consume.remote(ref))
        assert out == 4.0
        # After completion the worker's remove_borrow eventually lands;
        # pins must not accumulate indefinitely.
        import time
        deadline = time.time() + 10
        while time.time() < deadline:
            with rt._owned_lock:
                if ref.id not in rt._export_pins:
                    break
            time.sleep(0.1)
        with rt._owned_lock:
            assert ref.id not in rt._export_pins


class TestBorrows:
    def test_worker_borrow_blocks_eviction(self, small_store_ray):
        """An object borrowed by a live actor must not evict even after
        the driver drops its refs."""
        ray = small_store_ray

        @ray.remote
        class Holder:
            def __init__(self):
                self.ref = None

            def hold(self, ref):
                self.ref = ref  # keeps a live ObjectRef in the worker
                return "held"

            def read(self):
                import ray_tpu
                return float(ray_tpu.get(self.ref[0])[0])

        h = Holder.remote()
        big = ray.put(np.full(1 << 18, 7.0))  # 2 MB
        # Pass as a nested structure so the worker receives the REF
        # (top-level args are resolved to values before execution).
        assert ray.get(h.hold.remote([big])) == "held"
        # Borrow registration is async: the actor's add_borrow has to
        # reach this (the owning) process before the driver's ref goes.
        rt = ray._private.worker_state.get_runtime()
        wait_until(lambda: big.id in rt._borrows, timeout=30)
        del big
        gc.collect()
        for _ in range(6):
            r = ray.put(np.zeros(1 << 18))
            del r
            gc.collect()
        # The held object must still be readable through the borrow.
        assert ray.get(h.read.remote()) == 7.0

    def test_refcounts_drop_to_zero(self, small_store_ray):
        ray = small_store_ray
        rt = ray._private.worker_state.get_runtime()
        ref = ray.put(np.zeros(128))
        oid = ref.id
        assert rt.ref_tracker.count(oid) >= 1
        del ref
        gc.collect()
        assert rt.ref_tracker.count(oid) == 0
