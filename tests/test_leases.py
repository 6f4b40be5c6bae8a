"""Worker-lease dispatch tests (VERDICT r2 weak #3 / next-round #6).

Reference model: `src/ray/core_worker/transport/direct_task_transport.h`
— callers lease workers from the scheduler, then push normal tasks
caller->worker directly (pipelined); the head leaves the per-task hot
path. Throughput gate lives in `ray_tpu/ray_perf.py`; these tests cover
the correctness properties: reuse, linger return, death retry, adaptive
depth leaving slow-task demand spillable, and the opt-out.
"""

import os
import time

import pytest

import ray_tpu


@pytest.fixture
def ray_session(monkeypatch):
    monkeypatch.setenv("RAY_TPU_LEASE_LINGER_S", "0.4")
    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()


def _head():
    from ray_tpu._private import node as node_mod
    return node_mod._node.head


class TestLeases:
    def test_sequential_tasks_reuse_leased_worker(self, ray_session):
        @ray_tpu.remote
        def whoami():
            return os.getpid()

        pids = {ray_tpu.get(whoami.remote(), timeout=30)
                for _ in range(10)}
        # One lease serves the whole sequential stream.
        assert len(pids) == 1

    def test_lease_returns_to_pool_after_linger(self, ray_session):
        @ray_tpu.remote
        def one():
            return 1

        assert ray_tpu.get(one.remote(), timeout=30) == 1
        head = _head()

        def leased_count():
            with head._lock:
                return sum(1 for w in head._workers.values()
                           if w.leased_to is not None)

        assert leased_count() >= 1
        deadline = time.monotonic() + 10
        while leased_count() > 0 and time.monotonic() < deadline:
            time.sleep(0.1)
        assert leased_count() == 0, "lease never returned after linger"
        # Returned worker is idle-pool visible again.
        with head._lock:
            assert any(len(n.idle) > 0 for n in head._nodes.values())

    def test_leased_worker_death_retries(self, ray_session):
        marker = f"/tmp/lease-retry-{os.getpid()}"
        open(marker, "w").write("")

        @ray_tpu.remote(max_retries=3)
        def die_once():
            with open(marker, "a") as f:
                f.write("x")
            if len(open(marker).read()) == 1:
                os._exit(1)  # simulate worker crash mid-lease
            return "recovered"

        assert ray_tpu.get(die_once.remote(), timeout=60) == "recovered"
        assert len(open(marker).read()) == 2
        os.unlink(marker)

    def test_max_retries_zero_fails_cleanly(self, ray_session):
        @ray_tpu.remote(max_retries=0)
        def die():
            os._exit(1)

        with pytest.raises(Exception):
            ray_tpu.get(die.remote(), timeout=60)

    def test_slow_tasks_keep_shallow_pipelines(self, ray_session):
        """Slow tasks must not pile onto one lease (adaptive depth):
        with 2 CPUs, 6 x 0.5s tasks should run 2-wide, well under the
        6 x 0.5s serial floor. (Six tasks, not four: the wider gap
        between the 1.5s overlapped and 3.0s serial floors tolerates
        this 1-core CI box's load-induced wakeup delays without the
        threshold creeping past the serial floor.)"""
        @ray_tpu.remote
        def slow():
            time.sleep(0.5)
            return os.getpid()

        t0 = time.monotonic()
        pids = ray_tpu.get([slow.remote() for _ in range(6)], timeout=60)
        took = time.monotonic() - t0
        assert len(set(pids)) >= 2, "no parallelism across leases"
        # Overlapped 2-wide: ~1.5-1.9s. Serial floor: 3.0s.
        assert took < 2.7, f"serialized onto one lease: {took:.1f}s"

    def test_disable_leases_env(self, monkeypatch):
        monkeypatch.setenv("RAY_TPU_DISABLE_LEASES", "1")
        ray_tpu.init(num_cpus=2)
        try:
            @ray_tpu.remote
            def f(x):
                return x + 1

            assert ray_tpu.get([f.remote(i) for i in range(4)],
                               timeout=30) == [1, 2, 3, 4]
            import ray_tpu._private.worker_state as ws
            assert not ws.get_runtime()._lease_groups
        finally:
            ray_tpu.shutdown()


class TestLeasedResultDelivery:
    def test_large_results_of_whole_node_tasks_all_arrive(self):
        """Three tasks that each take a node's whole resources and return
        a result too large to inline: one lease, depth two, the third
        task queued behind it. The results' bytes reach the caller's
        store (striped) before or beside the push_result that announces
        them; that push must still free the lease slot, or the third task
        is never dispatched (PR 30: a cell already in the memory store
        made the push a 'duplicate')."""
        import numpy as np
        from ray_tpu.cluster_utils import Cluster
        cluster = Cluster(head_resources={"CPU": 1})
        try:
            cluster.add_node(resources={"CPU": 2})

            @ray_tpu.remote(resources={"CPU": 2})
            def big():
                return np.zeros(2_000_000, np.uint8)

            out = ray_tpu.get([big.remote() for _ in range(3)], timeout=15)
            assert [o.nbytes for o in out] == [2_000_000] * 3
            from ray_tpu._private import metrics
            assert not metrics.snapshot()["counters"].get(
                "leased_tasks_recovered")
        finally:
            cluster.shutdown()

    def test_recovered_tasks_free_their_slots_and_lease(self, monkeypatch):
        """Result pushes that ARE lost (chaos drops the first two) while
        one lease holds all the CPUs and a third task is queued behind it:
        the probe's recovery must hand a freed slot to the queued task,
        and let the emptied lease linger out so that the head can place
        the two resubmissions."""
        monkeypatch.setenv("RAY_TPU_LEASED_PROBE_S", "1.0")
        monkeypatch.setenv("RAY_TPU_LEASE_LINGER_S", "0.4")
        ray_tpu.init(num_cpus=2, chaos="seed=5;exec.after:drop_result:once1;"
                                       "exec.after:drop_result:once2")
        try:
            @ray_tpu.remote(resources={"CPU": 2})
            def f(x):
                return x + 1

            out = ray_tpu.get([f.remote(i) for i in range(3)], timeout=30)
            assert out == [1, 2, 3]
            from ray_tpu._private import metrics
            assert metrics.snapshot()["counters"].get(
                "leased_tasks_recovered", 0) == 2
        finally:
            ray_tpu.shutdown()
