"""Multi-node cluster: cross-node scheduling, object transfer, failure.

Parity: `python/ray/tests/test_multi_node.py` + `test_object_manager.py` +
`test_multinode_failures.py`, using the in-process cluster harness
(`python/ray/cluster_utils.py:12`, SURVEY.md §4.2). Nodes here are agent
subprocesses with distinct node ids and node-scoped object stores, so
cross-node gets exercise the real chunked wire transfer.
"""

import os

import numpy as np
import pytest
from conftest import wait_until

import ray_tpu
from ray_tpu.cluster_utils import Cluster


@pytest.fixture
def cluster():
    c = Cluster(head_resources={"CPU": 1})
    yield c
    c.shutdown()


def _node_of_worker():
    # reads the worker's node id from its environment
    return os.environ.get("RAY_TPU_NODE_ID", "node0")


class TestMultiNodeScheduling:
    def test_tasks_spill_to_remote_node(self, cluster):
        cluster.add_node(resources={"CPU": 4})

        @ray_tpu.remote
        def where():
            import os
            import time
            time.sleep(1.0)  # long enough that node0 alone can't drain all
            return os.environ.get("RAY_TPU_NODE_ID", "node0")

        # Saturate: 8 one-second tasks but node0 only has 1 CPU slot.
        refs = [where.options(num_cpus=1).remote() for _ in range(8)]
        nodes = set(ray_tpu.get(refs, timeout=60))
        assert "node1" in nodes, f"no task spilled to node1: {nodes}"

    def test_actor_placement_by_resources(self, cluster):
        cluster.add_node(resources={"CPU": 1, "GPUX": 2})

        @ray_tpu.remote
        class Where:
            def node(self):
                import os
                return os.environ.get("RAY_TPU_NODE_ID", "node0")

        a = Where.options(resources={"GPUX": 1}).remote()
        assert ray_tpu.get(a.node.remote()) == "node1"

    def test_cluster_info_lists_nodes(self, cluster):
        cluster.add_node(resources={"CPU": 2})
        cinfo = cluster.node.runtime.cluster_info()
        assert set(cinfo["nodes"]) == {"node0", "node1"}
        assert cinfo["nodes"]["node1"]["total_resources"]["CPU"] == 2


class TestCrossNodeObjects:
    def test_small_result_crosses_nodes(self, cluster):
        cluster.add_node(resources={"CPU": 2})

        @ray_tpu.remote(resources={"CPU": 2})
        def produce():
            return {"x": 42}

        assert ray_tpu.get(produce.remote())["x"] == 42

    def test_large_result_crosses_nodes(self, cluster):
        """> INLINE_OBJECT_MAX results stream chunk-wise into the
        caller's node-local store."""
        cluster.add_node(resources={"CPU": 2})

        @ray_tpu.remote(resources={"CPU": 2})
        def produce():
            return np.arange(3_000_000, dtype=np.int64)  # 24 MB

        arr = ray_tpu.get(produce.remote())
        assert arr.shape == (3_000_000,)
        assert int(arr[12345]) == 12345

    def test_large_arg_crosses_nodes(self, cluster):
        cluster.add_node(resources={"CPU": 2})
        big = np.ones(2_000_000, dtype=np.float64)  # 16 MB
        ref = ray_tpu.put(big)

        @ray_tpu.remote(resources={"CPU": 2})
        def total(x):
            return float(x.sum())

        assert ray_tpu.get(total.remote(ref)) == 2_000_000.0

    def test_worker_to_worker_cross_node(self, cluster):
        """An object produced on node1 is consumed by a task on node0
        via owner-mediated transfer."""
        cluster.add_node(resources={"CPU": 2})

        @ray_tpu.remote(resources={"CPU": 2})
        def produce():
            return np.full(200_000, 7.0)  # 1.6 MB -> shm path

        @ray_tpu.remote(resources={"CPU": 1})
        def consume(x):
            return float(x[0])

        ref = produce.remote()
        assert ray_tpu.get(consume.remote(ref)) == 7.0


class TestNodeFailure:
    def test_node_death_fails_actor(self, cluster):
        handle = cluster.add_node(resources={"CPU": 2})

        @ray_tpu.remote(resources={"CPU": 2})
        class Pinned:
            def ping(self):
                return "ok"

        a = Pinned.remote()
        assert ray_tpu.get(a.ping.remote()) == "ok"
        cluster.remove_node(handle)
        with pytest.raises(ray_tpu.RayActorError):
            ray_tpu.get(a.ping.remote(), timeout=30)

    def test_task_retry_after_node_death(self, cluster):
        """In-flight tasks on a dying node retry elsewhere."""
        handle = cluster.add_node(resources={"CPU": 4})

        @ray_tpu.remote
        def slow():
            import time
            time.sleep(3)
            return _node_of_worker()

        refs = [slow.options(num_cpus=1, max_retries=3).remote()
                for _ in range(4)]
        # They are scheduled, some of them on node1.
        wait_until(lambda: any(t["node"] == "node1" for t in
                               ray_tpu.tasks(state="RUNNING")), timeout=60)
        cluster.remove_node(handle)
        results = ray_tpu.get(refs, timeout=120)
        assert all(r in ("node0",) for r in results)
