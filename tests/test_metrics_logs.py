"""Observability plane tests (VERDICT r2 item #5).

- Worker log streaming: print() inside tasks/actors lands on the
  driver's console (parity: `python/ray/log_monitor.py:36` ->
  `worker.py:910`).
- Metrics: per-process counters/gauges aggregate at the head, readable
  via `ray_tpu.cluster_metrics()`, the `stat --metrics` CLI, and the
  Prometheus HTTP endpoint.
"""

import os
import sys
import time
import urllib.request

import pytest
from conftest import wait_until

import ray_tpu


class TestLogStreaming:
    def test_worker_prints_reach_driver(self, capfd):
        ray_tpu.init(num_cpus=2)
        try:
            @ray_tpu.remote
            def chatty():
                print("MARKER-from-worker-task")
                sys.stdout.flush()
                return 1

            assert ray_tpu.get(chatty.remote(), timeout=30) == 1
            deadline = time.monotonic() + 10
            seen = ""
            while time.monotonic() < deadline:
                seen += capfd.readouterr().out
                if "MARKER-from-worker-task" in seen:
                    break
                time.sleep(0.2)
            assert "MARKER-from-worker-task" in seen
            # Origin prefix present (node/file).
            line = next(l for l in seen.splitlines()
                        if "MARKER-from-worker-task" in l)
            assert line.startswith("(node0/")
        finally:
            ray_tpu.shutdown()

    def test_log_streaming_can_be_disabled(self, monkeypatch, capfd):
        monkeypatch.setenv("RAY_TPU_LOG_TO_DRIVER", "0")
        ray_tpu.init(num_cpus=2)
        try:
            @ray_tpu.remote
            def chatty():
                print("MARKER-silenced")
                sys.stdout.flush()
                return 1

            assert ray_tpu.get(chatty.remote(), timeout=30) == 1
            time.sleep(1.5)
            assert "MARKER-silenced" not in capfd.readouterr().out
        finally:
            ray_tpu.shutdown()


class TestMetrics:
    def test_cluster_metrics_aggregate(self, monkeypatch):
        monkeypatch.setenv("RAY_TPU_METRICS_INTERVAL_S", "0.3")
        ray_tpu.init(num_cpus=2)
        try:
            @ray_tpu.remote
            def f(x):
                return x

            ray_tpu.get([f.remote(i) for i in range(10)], timeout=30)
            deadline = time.monotonic() + 10
            agg = {}
            while time.monotonic() < deadline:
                agg = ray_tpu.cluster_metrics()
                if agg["counters"].get("tasks_executed", 0) >= 10:
                    break
                time.sleep(0.3)
            assert agg["counters"]["tasks_submitted"] >= 10
            assert agg["counters"]["tasks_executed"] >= 10
            assert "workers_registered" in agg["gauges"]
            assert "store_used_bytes" in agg["gauges"]
        finally:
            ray_tpu.shutdown()

    def test_prometheus_endpoint(self, monkeypatch):
        import socket
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        monkeypatch.setenv("RAY_TPU_METRICS_PORT", str(port))
        monkeypatch.setenv("RAY_TPU_METRICS_INTERVAL_S", "0.3")
        ray_tpu.init(num_cpus=2)
        try:
            @ray_tpu.remote
            def f():
                return 0

            ray_tpu.get([f.remote() for _ in range(4)], timeout=30)

            def scrape():
                return urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) \
                    .read().decode()

            # The processes push their counters on their own cadence.
            wait_until(lambda: "# TYPE ray_tpu_tasks_submitted counter"
                       in scrape(), timeout=30)
            assert "ray_tpu_workers_registered" in scrape()
            js = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics.json", timeout=10) \
                .read().decode()
            import json
            agg = json.loads(js)
            assert agg["counters"]["tasks_submitted"] >= 4
        finally:
            ray_tpu.shutdown()

    def test_dashboard_page(self, monkeypatch):
        """Dashboard-lite at `/` (parity: dashboard.py:91): nodes,
        actors, store gauges, error + log tails, server-rendered."""
        import socket
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        monkeypatch.setenv("RAY_TPU_METRICS_PORT", str(port))
        monkeypatch.setenv("RAY_TPU_METRICS_INTERVAL_S", "0.3")
        ray_tpu.init(num_cpus=2)
        try:
            @ray_tpu.remote
            class Dash:
                def ping(self):
                    return "ok"

            a = Dash.options(name="dash_actor").remote()
            assert ray_tpu.get(a.ping.remote(), timeout=30) == "ok"

            @ray_tpu.remote
            def boom():
                raise RuntimeError("dashboard-test-error")

            with pytest.raises(Exception):
                ray_tpu.get(boom.remote(), timeout=30)
            def fetch():
                return urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/", timeout=10).read().decode()

            # The worker reports the error to the head asynchronously.
            wait_until(lambda: "dashboard-test-error" in fetch(), timeout=30)
            page = fetch()
            assert "<h1>ray_tpu" in page
            assert "node0" in page
            assert "dash_actor" in page       # named actor row
            assert "ALIVE" in page
            assert "dashboard-test-error" in page  # error tail
        finally:
            ray_tpu.shutdown()

    def test_prometheus_name_sanitization(self):
        """Dots/dashes/spaces in metric names must not emit invalid
        exposition lines (Prometheus names are [a-zA-Z0-9_:] only)."""
        import re

        from ray_tpu._private import metrics
        text = metrics.prometheus_text({
            "counters": {"store.used-bytes": 1.0, "9lives": 2.0},
            "gauges": {"a b/c": 3.0}})
        assert "ray_tpu_store_used_bytes 1" in text
        assert "ray_tpu__9lives 2" in text
        assert "ray_tpu_a_b_c 3" in text
        name_re = re.compile(
            r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [-+0-9.einf]+$")
        for line in text.strip().splitlines():
            if line.startswith("#"):
                continue
            assert name_re.match(line), f"invalid exposition line {line!r}"

    def test_aggregate_per_node_breakdown(self):
        from ray_tpu._private import metrics
        agg = metrics.aggregate({
            "addr1": {"node": "node0", "counters": {"c": 1.0},
                      "gauges": {"g": 10.0}},
            "addr2": {"node": "node0", "gauges": {"g": 5.0}},
            "addr3": {"node": "node1", "gauges": {"g": 2.0}},
        })
        assert agg["counters"]["c"] == 1.0
        assert agg["gauges"]["g"] == 17.0  # cluster total preserved
        assert agg["per_node"]["node0"]["gauges"]["g"] == 15.0
        assert agg["per_node"]["node1"]["gauges"]["g"] == 2.0
        text = metrics.prometheus_text(agg)
        assert 'ray_tpu_g{node="node0"} 15' in text
        assert 'ray_tpu_g{node="node1"} 2' in text

    def test_trainer_iteration_gauges(self, monkeypatch):
        """A training iteration pushes its timing breakdown into the
        metrics plane: the Prometheus endpoint exposes ray_tpu_train_*
        gauges during a (short) PPO run."""
        import socket
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        monkeypatch.setenv("RAY_TPU_METRICS_PORT", str(port))
        monkeypatch.setenv("RAY_TPU_METRICS_INTERVAL_S", "0.3")
        ray_tpu.init(num_cpus=2)
        t = None
        try:
            from ray_tpu.rllib.agents.ppo import PPOTrainer
            t = PPOTrainer(config={
                "env": "CartPole-v0", "num_workers": 0,
                "train_batch_size": 128, "sgd_minibatch_size": 32,
                "num_sgd_iter": 2, "rollout_fragment_length": 64,
                "num_envs_per_worker": 1,
                "model": {"fcnet_hiddens": [16]}, "seed": 0})
            t.train()
            deadline = time.monotonic() + 15
            text = ""
            while time.monotonic() < deadline:
                text = urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) \
                    .read().decode()
                if "ray_tpu_train_iter_time_s" in text:
                    break
                time.sleep(0.3)
            for gauge in ("ray_tpu_train_iter_time_s",
                          "ray_tpu_train_sample_time_s",
                          "ray_tpu_train_learn_time_s",
                          "ray_tpu_train_env_throughput",
                          "ray_tpu_train_learner_throughput"):
                assert gauge in text, f"{gauge} missing from exposition"
            agg = ray_tpu.cluster_metrics()
            assert agg["counters"]["train_iterations"] >= 1
            assert agg["gauges"]["train_iter_time_s"] > 0
        finally:
            if t is not None:
                t.stop()
            ray_tpu.shutdown()

    def test_stat_metrics_cli(self, monkeypatch):
        monkeypatch.setenv("RAY_TPU_METRICS_INTERVAL_S", "0.3")
        ray_tpu.init(num_cpus=2)
        try:
            @ray_tpu.remote
            def f():
                return 0

            ray_tpu.get(f.remote(), timeout=30)
            from ray_tpu._private import node as node_mod
            addr = node_mod._node.head.sock_path
            import io
            from contextlib import redirect_stdout
            from ray_tpu.scripts.scripts import main as cli_main

            def stat():
                buf = io.StringIO()
                with redirect_stdout(buf):
                    cli_main(["stat", "--metrics", "--address", addr])
                return buf.getvalue()

            # One metrics interval (0.3 s here) has to pass first.
            wait_until(lambda: "tasks_submitted" in stat(), timeout=30)
            out = stat()
            assert "gauges:" in out
        finally:
            ray_tpu.shutdown()
