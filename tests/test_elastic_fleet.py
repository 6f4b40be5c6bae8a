"""Elastic fleets: grow/shrink/evict/preempt the sampler fleet mid-run.

Covers the fleet controller policy plane (`_private/fleet.py`), the
chaos `window:<start>:<period>` trigger + `agent.preempt` site, the
weight-plane churn regressions (version pruning, warm-rejoin
bootstrap, encoder checkpoint/resume), the rate-driven autoscaler
feed, the `scripts fleet` view, and the acceptance run: an IMPALA
fleet halved then doubled mid-run under seeded rolling preemption
matching a static control within noise.
"""

import argparse
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import chaos
from ray_tpu._private.fleet import (EvictionThrottle, FleetController,
                                    FLEET_EVENTS_KV_KEY, MAX_EVENTS)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Thread-name prefixes owned by the runtime/head/agent service planes
# (mirrors test_chaos.py's PR-3 zero-leak gate).
SERVICE_THREAD_PREFIXES = (
    "conn-recv-", "server-", "stripe-send", "send-batcher",
    "borrow-notify", "metrics-push", "lease-sweeper", "task-exec",
    "agent-monitor", "head-monitor", "task-events-flush", "obj-fetch",
    "object-stripe-send",
)


def _leaked_service_threads():
    return sorted(
        t.name for t in threading.enumerate()
        if t.name.startswith(SERVICE_THREAD_PREFIXES))


# ---------------------------------------------------------------------
# chaos: window trigger + agent.preempt site (pure, no cluster)
# ---------------------------------------------------------------------
class TestWindowTrigger:
    SPEC = "seed=3;agent.preempt:kill:window:5:3"

    def test_parse(self):
        seed, rules = chaos.parse_spec(self.SPEC)
        assert seed == 3
        (r,) = rules
        assert (r.site, r.kind, r.trigger) == \
            ("agent.preempt", "kill", "window")
        assert (r.value, r.period) == (5, 3)

    def test_parse_with_param(self):
        _, rules = chaos.parse_spec(
            "seed=1;actor.sample:delay:window:2:4:0.01")
        (r,) = rules
        assert r.trigger == "window" and r.delay == 0.01

    def test_fires_on_start_then_every_period(self):
        ctl = chaos.ChaosController(self.SPEC)
        fired = [occ for occ in range(1, 13)
                 if ctl.fire("agent.preempt", f"w{occ % 2}")]
        assert fired == [5, 8, 11]

    def test_targeted_window_respects_detail(self):
        # '@'-params scope the rule to one tag; the rng/occurrence
        # streams still advance for every occurrence.
        ctl = chaos.ChaosController(
            "seed=1;agent.preempt:kill:window:2:2:w1@0")
        fired = [(occ, f"w{occ % 2}") for occ in range(1, 9)
                 if ctl.fire("agent.preempt", f"w{occ % 2}")]
        # window matches occs 2,4,6,8; detail w1 only on odd occs — so
        # only the even-occ matches with detail w0 are filtered out and
        # nothing fires at all.
        assert fired == []

    @pytest.mark.parametrize("bad", [
        "agent.preempt:kill:window:0:3",   # start < 1
        "agent.preempt:kill:window:5:0",   # period < 1
        "agent.preempt:kill:window:x:3",   # non-integer start
        "agent.preempt:kill:window:5",     # missing period
        "agent.preempt:zap:window:5:3",    # unknown kind
    ])
    def test_bad_window_specs_raise(self, bad):
        with pytest.raises(chaos.ChaosSpecError):
            chaos.parse_spec(bad)

    def test_catalog_has_preempt_site(self):
        assert "kill" in chaos.SITES["agent.preempt"]

    def test_same_seed_byte_identical_and_replays(self):
        def drive(ctl):
            for occ in range(1, 20):
                ctl.fire("agent.preempt", f"w{occ % 3}")
            return ctl.trace
        a = drive(chaos.ChaosController(self.SPEC))
        b = drive(chaos.ChaosController(self.SPEC))
        assert len(a) >= 4
        assert chaos.trace_bytes(a) == chaos.trace_bytes(b)
        replayed = chaos.replay(self.SPEC, a)
        assert chaos.trace_bytes(replayed) == chaos.trace_bytes(a)

    def test_cli_pretty_print_and_catalog(self):
        proc = subprocess.run(
            [sys.executable, "-m", "ray_tpu.scripts", "chaos",
             "--spec", self.SPEC], cwd=REPO, capture_output=True,
            text=True, timeout=60)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "window:5:3" in proc.stdout
        proc = subprocess.run(
            [sys.executable, "-m", "ray_tpu.scripts", "chaos",
             "--catalog"], cwd=REPO, capture_output=True, text=True,
            timeout=60)
        assert proc.returncode == 0
        assert "agent.preempt" in proc.stdout


# ---------------------------------------------------------------------
# eviction throttle + fleet controller policy (pure, fake mechanics)
# ---------------------------------------------------------------------
class TestEvictionThrottle:
    def test_per_tag_min_interval(self):
        th = EvictionThrottle(min_interval_s=30.0, window_s=1000.0,
                              max_per_window=100)
        assert th.allow("w1", now=0.0)
        assert not th.allow("w1", now=10.0)   # same tag too soon
        assert th.allow("w2", now=10.0)       # other tags unaffected
        assert th.allow("w1", now=31.0)

    def test_windowed_global_cap(self):
        th = EvictionThrottle(min_interval_s=0.0, window_s=60.0,
                              max_per_window=2)
        assert th.allow("a", now=0.0)
        assert th.allow("b", now=1.0)
        assert not th.allow("c", now=2.0)     # fleet-wide budget spent
        assert th.allow("c", now=62.0)        # window rolled past


class _FakeFleet:
    """Pure mechanics double: tags in a list, fresh monotonic ids."""

    def __init__(self, n=2):
        self.seq = n
        self.tags = [f"w{i}" for i in range(n)]

    def spawn(self):
        tag = f"w{self.seq}"
        self.seq += 1
        self.tags.append(tag)
        return object(), tag

    def retire(self, worker):
        # The controller passes None for "newest" (shrink) or the live
        # worker handle (evict/preempt); this double retires the oldest
        # member for any handle.
        if not self.tags:
            return None
        if worker is None:
            return self.tags.pop()
        return self.tags.pop(0)

    def controller(self, **kw):
        return FleetController(
            spawn=self.spawn, retire=self.retire,
            size=lambda: len(self.tags), **kw)


class TestFleetControllerUnit:
    def test_grow_bounded_by_max(self):
        f = _FakeFleet(2)
        c = f.controller(min_size=1, max_size=3)
        assert c.grow(5) == ["w2"]       # one slot to max_size
        assert c.size == 3
        assert c.joins_total == 1

    def test_shrink_bounded_by_min(self):
        f = _FakeFleet(3)
        c = f.controller(min_size=2, max_size=8)
        assert c.shrink(5) == ["w2"]     # newest first, stops at min
        assert c.size == 2

    def test_evict_replaces_with_fresh_tag(self):
        f = _FakeFleet(2)
        c = f.controller(min_size=1, max_size=8,
                         throttle=EvictionThrottle(
                             min_interval_s=0.0, window_s=60.0,
                             max_per_window=100))
        new_tag = c.evict(object(), "w0")
        # evict is retire+join in one step: size constant, fresh id.
        assert new_tag == "w2" and c.size == 2
        assert "w0" not in f.tags
        assert c.evictions_total == 1 and c.joins_total == 1

    def test_throttled_eviction_is_denied(self):
        f = _FakeFleet(2)
        c = f.controller(min_size=1, max_size=8,
                         throttle=EvictionThrottle(
                             min_interval_s=1e9, window_s=60.0,
                             max_per_window=0))
        assert c.evict(object(), "w0") is None
        assert c.size == 2 and c.throttled_evictions == 1
        assert c.evictions_total == 0

    def test_preempt_never_throttled(self):
        f = _FakeFleet(3)
        c = f.controller(min_size=1, max_size=8,
                         throttle=EvictionThrottle(
                             min_interval_s=1e9, window_s=60.0,
                             max_per_window=0))
        for tag in ("w0", "w1", "w2"):
            assert c.preempt(object(), tag) is not None
        assert c.evictions_total == 3 and c.size == 3

    def test_recovery_clock_closes_on_first_sample(self):
        f = _FakeFleet(2)
        c = f.controller(min_size=1, max_size=8)
        new_tag = c.preempt(object(), "w1")
        assert c.stats()["recoveries"] == 0
        c.note_sample(new_tag)
        s = c.stats()
        assert s["recoveries"] == 1
        assert s["recovery_s_p50"] >= 0.0
        # Steady-state samples from non-replacements are a no-op.
        c.note_sample("w0")
        assert c.stats()["recoveries"] == 1

    def test_event_ledger_is_bounded(self):
        f = _FakeFleet(2)
        c = f.controller(min_size=1, max_size=8)
        for _ in range(MAX_EVENTS):
            tag = f.tags[-1]
            c.preempt(object(), tag)     # 2 events per cycle
        assert len(c.events) == MAX_EVENTS
        assert all(e["event"] in ("evict", "join", "recovered")
                   for e in c.events)

    def test_stats_shape(self):
        c = _FakeFleet(2).controller(min_size=1, max_size=4)
        s = c.stats()
        assert s["fleet_size"] == 2
        assert s["fleet_min"] == 1 and s["fleet_max"] == 4
        assert {"joins_total", "evictions_total",
                "throttled_evictions", "recoveries"} <= set(s)

    def test_publish_without_runtime_is_safe(self):
        # No ray runtime: the gauge write works, the KV push degrades
        # silently (a controller must never throw from bookkeeping).
        _FakeFleet(2).controller(min_size=1, max_size=4).publish()


# ---------------------------------------------------------------------
# weight plane: churn pruning + warm-rejoin bootstrap + resume
# ---------------------------------------------------------------------
class _FakeMethod:
    def __init__(self, log):
        self.log = log

    def remote(self, ref):
        self.log.append(ref)
        return object()


class _FakeWorker:
    def __init__(self):
        self.received = []
        self.set_weights = _FakeMethod(self.received)


class TestWeightPlaneChurn:
    def _broadcaster(self, monkeypatch, codec="q8_delta"):
        from ray_tpu.rllib.utils.weight_broadcast import WeightBroadcaster
        # Pure-unit put: payloads stand in for their own refs.
        monkeypatch.setattr(ray_tpu, "put", lambda x: x)
        weights = {"w": np.zeros(64, np.float32)}

        def get_weights():
            return {k: v.copy() for k, v in weights.items()}
        b = WeightBroadcaster(get_weights, codec=codec, shard_count=1)
        return b, weights

    def test_remove_worker_prunes_versions_and_acks(self):
        """Regression: churn used to grow _worker_versions (and the ack
        pool) one dead handle per evicted worker, forever."""
        from ray_tpu.rllib.utils.weight_broadcast import WeightBroadcaster
        b = WeightBroadcaster(lambda: {}, codec="full")
        w1, w2 = object(), object()
        b._worker_versions[w1] = 3
        b._worker_versions[w2] = 3
        b._acks.add(w1, "ref1")
        b._acks.add(w2, "ref2")
        b.remove_worker(w1)
        assert list(b._worker_versions) == [w2]
        assert list(b._acks._tasks.values()) == [w2]
        assert b.stats()["num_weight_sync_tracked_workers"] == 1

    def test_taskpool_remove_worker_returns_dropped_refs(self):
        from ray_tpu.rllib.utils.actors import TaskPool
        p = TaskPool()
        w1, w2 = object(), object()
        p.add(w1, "a")
        p.add(w1, "b")
        p.add(w2, "c")
        assert sorted(p.remove_worker(w1)) == ["a", "b"]
        assert p.count == 1

    def test_taskpool_survives_removal_between_yields(self, monkeypatch):
        """Preempting a worker while draining `completed()` drops its
        other ready refs; the drain must skip them, not KeyError."""
        from ray_tpu.rllib.utils import actors
        monkeypatch.setattr(actors.ray_tpu, "wait",
                            lambda refs, **kw: (list(refs), []))
        p = actors.TaskPool()
        w1, w2 = object(), object()
        p.add(w1, "a")
        p.add(w1, "b")
        p.add(w2, "c")
        seen = []
        for worker, ref in p.completed():
            seen.append(ref)
            if worker is w1:
                p.remove_worker(w1)
        assert seen == ["a", "c"] and p.count == 0

    def test_bootstrap_routes_delta_for_warm_rejoin(self, monkeypatch):
        b, weights = self._broadcaster(monkeypatch)
        b.broadcast()                       # v1: full (no base yet)
        weights["w"] += 1.0
        b.broadcast()                       # v2: delta against base v1
        warm = _FakeWorker()
        assert b.bootstrap(warm, held_version=1)
        assert [p.codec for p in warm.received] == ["q8_delta"]
        cold = _FakeWorker()
        assert b.bootstrap(cold, held_version=None)
        assert [p.codec for p in cold.received] == ["full"]
        # A wrong claim is downgraded to the full blob, not trusted.
        liar = _FakeWorker()
        assert b.bootstrap(liar, held_version=99)
        assert [p.codec for p in liar.received] == ["full"]

    def test_encoder_state_resumes_delta_stream(self):
        """A restored encoder continues the exact versioned stream: a
        decoder that tracked the old incarnation keeps applying deltas
        (no stale handshake, bit-identical reconstruction)."""
        from ray_tpu._private.weight_sync import (WeightSyncDecoder,
                                                  WeightSyncEncoder)
        rng = np.random.default_rng(0)
        enc = WeightSyncEncoder(codec="q8_delta", shard_count=1)
        dec = WeightSyncDecoder()
        w = {"a": rng.standard_normal(128).astype(np.float32)}
        for _ in range(2):
            for p in enc.encode(w):
                tree, status = dec.apply(p)
                assert status == "ok"
            w = {"a": w["a"] + rng.standard_normal(128)
                 .astype(np.float32) * 0.01}
        state = enc.get_state()

        enc2 = WeightSyncEncoder(codec="full")     # fresh process
        enc2.set_state(state)
        assert enc2.version == 2 and enc2.codec == "q8_delta"
        payloads = enc2.encode(w)                  # v3
        assert payloads[0].codec == "q8_delta"
        assert payloads[0].base_version == 2       # stream continued
        tree, status = dec.apply(payloads[0])
        assert status == "ok"                      # no stale fallback
        np.testing.assert_array_equal(tree["a"], enc2._base)


# ---------------------------------------------------------------------
# config knobs
# ---------------------------------------------------------------------
class TestFleetConfig:
    def test_knobs_registered_with_defaults(self):
        from ray_tpu._private import config as config_mod
        assert config_mod.get("RAY_TPU_STRAGGLER_EVICT") is False
        assert config_mod.get("RAY_TPU_FLEET_MIN") == 1
        assert config_mod.get("RAY_TPU_FLEET_MAX") == 64
        assert config_mod.get("RAY_TPU_FLEET_EVICT_INTERVAL_S") == 30.0
        assert config_mod.get("RAY_TPU_FLEET_EVICTIONS_PER_WINDOW") == 2
        names = {row["name"] for row in config_mod.dump()}
        assert {"RAY_TPU_STRAGGLER_EVICT", "RAY_TPU_FLEET_MIN",
                "RAY_TPU_FLEET_MAX", "RAY_TPU_FLEET_EVICT_WINDOW_S"} \
            <= names


# ---------------------------------------------------------------------
# autoscaler: live cluster_rates() demand feed
# ---------------------------------------------------------------------
class _FakeProvider:
    def __init__(self):
        self.nodes = []
        self._counter = 0

    def non_terminated_nodes(self):
        return list(self.nodes)

    def create_node(self, count=1, node_type=None):
        out = []
        for _ in range(count):
            self._counter += 1
            nid = f"fake-{self._counter}"
            self.nodes.append(nid)
            out.append(nid)
        return out

    def terminate_node(self, node_id):
        self.nodes.remove(node_id)


class TestRateDrivenAutoscaler:
    def _mk(self, **cfg):
        from ray_tpu.autoscaler import LoadMetrics, StandardAutoscaler
        p, lm = _FakeProvider(), LoadMetrics()
        return p, lm, StandardAutoscaler(p, lm, cfg)

    def test_backlog_growth_from_counter_rates(self):
        from ray_tpu.autoscaler import LoadMetrics
        lm = LoadMetrics()
        assert lm.backlog_growth_per_s() == 0.0   # ring not warm
        lm.update_rates({"tasks_submitted": 12.0,
                         "tasks_executed": 4.0})
        assert lm.backlog_growth_per_s() == 8.0

    def test_growth_suppresses_idle_scale_down(self):
        p, lm, a = self._mk(min_workers=0, max_workers=2,
                            idle_timeout_s=0.05)
        lm.queued_demand = 3
        a.update()
        assert len(p.nodes) == 2
        for nid in p.nodes:
            lm.update(nid, {"CPU": 2.0}, {"CPU": 2.0})  # fully idle
        lm.queued_demand = 0
        time.sleep(0.1)
        lm.update_rates({"tasks_submitted": 10.0,
                         "tasks_executed": 2.0})
        a.update()
        assert len(p.nodes) == 2          # growing: keep idle capacity
        lm.update_rates({})               # growth gone
        a.update()
        assert len(p.nodes) == 0          # normal idle scale-down

    def test_legacy_scalar_path_launches_on_growth(self):
        p, lm, a = self._mk(min_workers=0, max_workers=4,
                            max_launch_batch=2)
        lm.queued_demand = 0              # snapshot queue reads empty
        lm.update_rates({"tasks_submitted": 6.0,
                         "tasks_executed": 1.0})
        a.update()
        assert len(p.nodes) == 2          # burst caught between polls

    def test_projected_demand_vectors_scale_ahead(self):
        p, lm, a = self._mk(min_workers=0, max_workers=10,
                            max_launch_batch=8, demand_horizon_s=10.0)
        lm.pending_demand = [{"CPU": 1.0}]
        lm.update_rates({"tasks_submitted": 3.0,
                         "tasks_executed": 1.0})
        a.update()
        # 1 snapshot vector + 2/s x 10s projected = 21 wanted; batch 8.
        assert len(p.nodes) == 8
        # Without the rate feed the same snapshot launches one node.
        p2, lm2, a2 = self._mk(min_workers=0, max_workers=10,
                               max_launch_batch=8)
        lm2.pending_demand = [{"CPU": 1.0}]
        a2.update()
        assert len(p2.nodes) == 1

    def test_projection_with_empty_snapshot_uses_cpu_shape(self):
        p, lm, a = self._mk(min_workers=0, max_workers=4,
                            max_launch_batch=2, demand_horizon_s=5.0)
        lm.pending_demand = []            # vectors known, none pending
        lm.update_rates({"tasks_submitted": 4.0,
                         "tasks_executed": 2.0})
        a.update()
        assert len(p.nodes) == 2

    def test_zero_horizon_disables_projection(self):
        p, lm, a = self._mk(min_workers=0, max_workers=4,
                            demand_horizon_s=0.0)
        lm.pending_demand = []
        lm.update_rates({"tasks_submitted": 9.0,
                         "tasks_executed": 0.0})
        a.update()
        assert len(p.nodes) == 0

    def test_cluster_config_accepts_horizon(self):
        from ray_tpu.autoscaler.autoscaler import validate_cluster_config
        validate_cluster_config({"demand_horizon_s": 15.0})
        with pytest.raises(ValueError):
            validate_cluster_config({"demand_horizon_s": "soon"})


# ---------------------------------------------------------------------
# scripts fleet view (faked connection: rendering only)
# ---------------------------------------------------------------------
class TestFleetCLI:
    def test_cmd_fleet_renders_metrics_and_events(self, monkeypatch,
                                                  capsys):
        from ray_tpu.scripts import scripts
        metrics = {
            "counters": {"fleet_joins_total": 3.0,
                         "fleet_evictions_total": 2.0},
            "gauges": {"fleet_size": 4.0},
            "quantiles": {"actor_recovery_s": {
                "count": 2.0, "p50": 0.8, "p95": 1.2, "p99": 1.2,
                "max": 1.3}},
        }
        events = [{"ts": 1700000000.0, "event": "evict", "tag": "w1",
                   "reason": "straggler"},
                  {"ts": 1700000001.0, "event": "join", "tag": "w5",
                   "reason": "replace:w1"},
                  {"ts": 1700000002.0, "event": "recovered",
                   "tag": "w5", "recovery_s": 0.8}]

        class FakeConn:
            def request(self, msg, timeout=None):
                if msg["kind"] == "get_metrics":
                    return {"metrics": metrics}
                assert msg == {"kind": "kv_get",
                               "key": "ikv:" + FLEET_EVENTS_KV_KEY}
                return {"value": json.dumps(events)}

            def close(self):
                pass

        monkeypatch.setattr(scripts, "_resolve_address", lambda a: "x")
        monkeypatch.setattr(scripts, "_connect", lambda a: FakeConn())
        scripts.cmd_fleet(argparse.Namespace(address=None))
        out = capsys.readouterr().out
        assert "fleet size: 4" in out
        assert "joins: 3" in out and "evictions: 2" in out
        assert "p50=0.8s" in out
        assert "replace:w1" in out and "recovery_s=0.8" in out

    def test_cmd_fleet_no_fleet_yet(self, monkeypatch, capsys):
        from ray_tpu.scripts import scripts

        class FakeConn:
            def request(self, msg, timeout=None):
                if msg["kind"] == "get_metrics":
                    return {"metrics": {"counters": {}, "gauges": {}}}
                return {"value": None}

            def close(self):
                pass

        monkeypatch.setattr(scripts, "_resolve_address", lambda a: "x")
        monkeypatch.setattr(scripts, "_connect", lambda a: FakeConn())
        scripts.cmd_fleet(argparse.Namespace(address=None))
        assert "no fleet controller" in capsys.readouterr().out


# ---------------------------------------------------------------------
# live fleet ops over a real runtime
# ---------------------------------------------------------------------
def _impala_config(**over):
    cfg = {
        "env": "CartPole-v0",
        "num_workers": 2,
        "rollout_fragment_length": 20,
        "train_batch_size": 80,
        "num_envs_per_worker": 2,
        "model": {"fcnet_hiddens": [32, 32]},
        "lr": 0.001,
        "min_iter_time_s": 0,
        "seed": 0,
    }
    cfg.update(over)
    return cfg


class TestFleetIntegration:
    def test_grow_shrink_evict_preempt(self, ray_start):
        from ray_tpu.rllib.agents.impala import IMPALATrainer
        t = IMPALATrainer(config=_impala_config(num_workers=2))
        try:
            opt = t.optimizer
            fleet = opt.fleet
            assert fleet is not None and fleet.size == 2
            tags0 = set(opt._worker_tags.values())
            assert tags0 == {"w0", "w1"}

            grown = fleet.grow(1)
            assert grown == ["w2"] and fleet.size == 3
            assert len(opt.workers.remote_workers) == 3
            assert fleet.shrink(1) == ["w2"] and fleet.size == 2

            # Preempt a live member: replaced in one step, fresh tag.
            w = opt.workers.remote_workers[0]
            tag = opt._worker_tags[w]
            new_tag = fleet.preempt(w, tag)
            assert new_tag is not None and new_tag not in tags0
            assert fleet.size == 2
            assert w not in opt.workers.remote_workers
            assert tag not in opt._worker_tags.values()

            # Training proceeds and the replacement's first harvested
            # sample closes the recovery clock.
            for _ in range(5):
                r = t.train()
                assert r["num_steps_trained"] > 0
                if fleet.stats()["recoveries"] >= 1:
                    break
            assert fleet.stats()["recoveries"] >= 1

            # Weight-plane pruning held through the churn: exactly the
            # live members are tracked.
            stats = opt.stats()
            assert stats["num_weight_sync_tracked_workers"] \
                == fleet.size
            assert stats["fleet"]["joins_total"] >= 2
            assert stats["fleet"]["evictions_total"] >= 1

            # Straggler-evict path is throttle-gated: default budget is
            # 2 per window, so a third rapid eviction is denied.
            throttled_before = fleet.throttled_evictions
            for _ in range(3):
                w = opt.workers.remote_workers[0]
                fleet.evict(w, opt._worker_tags[w], reason="straggler")
            assert fleet.throttled_evictions > throttled_before
            assert fleet.size == 2
        finally:
            t._stop()

    def test_learner_checkpoint_resume(self, ray_start):
        from ray_tpu.rllib.agents.impala import IMPALATrainer
        t = IMPALATrainer(config=_impala_config(num_workers=0))
        try:
            opt = t.optimizer
            t.train()
            ref = opt.save_learner_state()
            saved_version = opt._broadcaster.version
            saved_trained = opt.num_steps_trained
            saved_weights = t.workers.local_worker.policy.get_weights()

            t.train()                       # state moves on
            assert opt.num_steps_trained > saved_trained

            opt.restore_learner_state(ref)
            assert opt._broadcaster.version == saved_version
            assert opt.num_steps_trained == saved_trained
            restored = t.workers.local_worker.policy.get_weights()
            import jax
            for a, b in zip(jax.tree.leaves(saved_weights),
                            jax.tree.leaves(restored)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))
            # Restored learner keeps training.
            r = t.train()
            assert np.isfinite(r["info"]["learner"]["total_loss"])
        finally:
            t._stop()


# ---------------------------------------------------------------------
# acceptance: halved-then-doubled under rolling preemption vs static
# ---------------------------------------------------------------------
REWARD_BAR = 30.0
MAX_ITERS = 25


class TestChurnVsStatic:
    def _run(self, churn=False):
        from ray_tpu.rllib.agents.impala import IMPALATrainer
        t = IMPALATrainer(config=_impala_config(lr=0.005))
        best = -np.inf
        fleet_sizes = []
        try:
            opt = t.optimizer
            for i in range(MAX_ITERS):
                result = t.train()
                rew = result.get("episode_reward_mean")
                if rew is not None and np.isfinite(rew):
                    best = max(best, rew)
                if churn and i == 1:
                    opt.fleet.shrink(1)          # halve: 2 -> 1
                if churn and i == 3:
                    opt.fleet.grow(1)            # double back: 1 -> 2
                fleet_sizes.append(opt.fleet.size)
                if best > REWARD_BAR and (not churn or i >= 4):
                    break
            stats = opt.stats()
        finally:
            t._stop()
        return best, stats, fleet_sizes

    def test_halved_doubled_preempted_matches_static(self, monkeypatch,
                                                     tmp_path):
        spec = "seed=11;agent.preempt:kill:window:10:40"
        trace_path = str(tmp_path / "preempt.jsonl")
        base_threads = set(_leaked_service_threads())

        # -- static control ---------------------------------------
        ray_tpu.init(num_cpus=4)
        try:
            static_best, static_stats, _ = self._run(churn=False)
        finally:
            ray_tpu.shutdown()
        assert static_best > REWARD_BAR, static_best

        # -- churn run: halved, doubled, rolling preemption -------
        monkeypatch.setenv("RAY_TPU_CHAOS_TRACE", trace_path)
        ray_tpu.init(num_cpus=4, chaos=spec)
        try:
            churn_best, churn_stats, sizes = self._run(churn=True)
            # Recovery histogram populated and visible cluster-wide.
            assert churn_stats["fleet"]["recoveries"] >= 1
            deadline = time.monotonic() + 15
            q = None
            while time.monotonic() < deadline:
                agg = ray_tpu.cluster_metrics()
                q = (agg.get("quantiles") or {}).get("actor_recovery_s")
                if q and q.get("count"):
                    break
                time.sleep(0.5)
            assert q and q["count"] >= 1, "actor_recovery_s never " \
                "reached the aggregated metrics plane"
            assert agg["counters"].get("fleet_evictions_total", 0) >= 1
            # Event ledger landed in the head KV for `scripts fleet`.
            from ray_tpu.experimental import internal_kv
            events = json.loads(internal_kv.kv_get(FLEET_EVENTS_KV_KEY))
            assert any(e["event"] == "join" for e in events)
            assert any(e["event"] == "recovered" for e in events)
        finally:
            ray_tpu.shutdown()

        # Within noise: the elastic run clears the same learning bar.
        assert churn_best > REWARD_BAR, \
            f"churned run stalled: {churn_best} vs {static_best}"
        # The fleet really was halved and doubled.
        assert 1 in sizes and sizes[-1] == 2
        # Static control saw no fleet churn.
        assert static_stats["fleet"]["joins_total"] == 0

        # Rolling preemption fired and replays byte-identical.
        entries = chaos.load_trace(trace_path)
        preempts = [e for e in entries if e["site"] == "agent.preempt"]
        assert preempts, "window schedule never fired"
        replayed = chaos.replay(spec, entries)
        assert chaos.trace_bytes(replayed) == chaos.trace_bytes(entries)

        # Zero NEW leaked service threads (the PR-3 gate).
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            leaked = [n for n in _leaked_service_threads()
                      if n not in base_threads]
            if not leaked:
                break
            time.sleep(0.3)
        assert not leaked, leaked


# ---------------------------------------------------------------------
# slow: rolling-preemption soak over a 2-node PPO cluster
# ---------------------------------------------------------------------
@pytest.mark.slow
class TestPreemptionSoak:
    def test_rolling_worker_kills_ppo(self, monkeypatch, tmp_path):
        """A steady cadence of worker-process kills
        (exec.before:kill:window) marching through a 2-node PPO run:
        every iteration completes, the trainer recreates workers, and
        the fault schedule replays from its seed."""
        # Each worker-process incarnation dies on its 6th task
        # execution (~2 training iterations), then its replacement does
        # the same — a rolling schedule that keeps marching without
        # ever starving the node (a denser cadence, e.g. window:3:5,
        # kills replacements faster than recovery can re-place them).
        spec = "seed=21;exec.before:kill:window:6:80"
        trace_path = str(tmp_path / "soak.jsonl")
        monkeypatch.setenv("RAY_TPU_CHAOS", spec)
        monkeypatch.setenv("RAY_TPU_CHAOS_TRACE", trace_path)
        monkeypatch.setenv("RAY_TPU_LEASED_PROBE_S", "1.5")
        from ray_tpu.cluster_utils import Cluster
        c = Cluster(head_resources={"CPU": 4})
        try:
            c.add_node(resources={"CPU": 2})
            from ray_tpu.rllib.agents.ppo import PPOTrainer
            t = PPOTrainer(config={
                "env": "CartPole-v0",
                "num_workers": 1,
                "train_batch_size": 128,
                "sgd_minibatch_size": 64,
                "num_sgd_iter": 2,
                "rollout_fragment_length": 64,
                "num_envs_per_worker": 2,
                "model": {"fcnet_hiddens": [16, 16]},
                "ignore_worker_failures": True,
                "seed": 0,
            })
            for _ in range(8):
                r = t.train()
                assert r["timesteps_this_iter"] >= 128
            t.stop()
        finally:
            c.shutdown()
        entries = chaos.load_trace(trace_path)
        kills = [e for e in entries
                 if (e["site"], e["kind"]) == ("exec.before", "kill")]
        assert len(kills) >= 2, entries
        replayed = chaos.replay(spec, entries)
        assert chaos.trace_bytes(replayed) == chaos.trace_bytes(entries)
