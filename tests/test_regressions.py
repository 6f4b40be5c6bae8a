"""Regression tests for reviewed defects (config aliasing, Discrete obs,
mesh validation, horizon plumbing)."""

import numpy as np
import pytest

from ray_tpu.rllib.utils.config import deep_merge


class TestDeepMerge:
    def test_no_aliasing_of_nested_dicts(self):
        defaults = {"model": {"fcnet_hiddens": [256, 256]}, "lr": 1.0}
        merged = deep_merge(deep_merge({}, defaults), {
            "model": {"fcnet_hiddens": [32]}})
        assert merged["model"]["fcnet_hiddens"] == [32]
        assert defaults["model"]["fcnet_hiddens"] == [256, 256]

    def test_shared_defaults_not_polluted_by_trainer(self):
        from ray_tpu.rllib.agents.trainer import COMMON_CONFIG
        from ray_tpu.rllib.agents.ppo.ppo import PPOTrainer
        before = dict(COMMON_CONFIG["model"])
        t = PPOTrainer(config={
            "env": "CartPole-v0",
            "model": {"fcnet_hiddens": [8]},
            "train_batch_size": 32,
            "sgd_minibatch_size": 16,
            "num_sgd_iter": 1,
            "rollout_fragment_length": 16,
        })
        t._stop()
        assert dict(COMMON_CONFIG["model"]) == before


class DiscreteObsEnv:
    """16-state chain with Discrete observations."""

    def __init__(self):
        from ray_tpu.rllib.env.spaces import Discrete
        self.observation_space = Discrete(16)
        self.action_space = Discrete(2)
        self.state = 0

    def reset(self):
        self.state = 0
        return self.state

    def step(self, action):
        self.state = min(15, self.state + (1 if action == 1 else 0))
        done = self.state == 15
        return self.state, float(self.state) / 15.0, done, {}

    def seed(self, seed=None):
        pass

    def close(self):
        pass


class TestDiscreteObs:
    def test_ppo_trains_on_discrete_obs(self):
        from ray_tpu.rllib.agents.ppo.ppo import PPOTrainer
        t = PPOTrainer(config={
            "env": lambda cfg: DiscreteObsEnv(),
            "train_batch_size": 64,
            "sgd_minibatch_size": 32,
            "num_sgd_iter": 2,
            "rollout_fragment_length": 32,
        })
        result = t.train()
        assert np.isfinite(result["info"]["learner"]["total_loss"])
        # One-hot preprocessing happened: obs column is (B, 16) floats.
        a = t.compute_action(3)
        assert a in (0, 1)
        t._stop()


class TestMeshValidation:
    def test_too_many_devices_raises(self):
        from ray_tpu.rllib.agents.ppo.ppo import PPOTrainer
        with pytest.raises(ValueError, match="num_tpus_for_learner"):
            PPOTrainer(config={
                "env": "CartPole-v0",
                "num_tpus_for_learner": 4096,
            })


class TestHorizonPlumbing:
    def test_horizon_truncates_episodes(self):
        from ray_tpu.rllib.evaluation.rollout_worker import RolloutWorker
        from ray_tpu.rllib.agents.pg.pg import PGJaxPolicy
        from ray_tpu.rllib.env.registry import make_env
        w = RolloutWorker(
            env_creator=lambda cfg: make_env("CartPole-v0", cfg),
            policy_cls=PGJaxPolicy,
            policy_config={"model": {"fcnet_hiddens": [8]}},
            rollout_fragment_length=64,
            horizon=5)
        batch = w.sample()
        metrics = w.get_metrics()
        assert metrics, "expected completed episodes under horizon=5"
        assert all(m.episode_length <= 5 for m in metrics)
        # Horizon-truncated rows are terminal in the emitted batch.
        import ray_tpu.rllib.sample_batch as sb
        for ep in batch.split_by_episode():
            if ep.count == 5:
                assert bool(ep[sb.DONES][-1])

    def test_use_lstm_builds_recurrent_model(self):
        # use_lstm now resolves to the recurrent trunk (the recurrent
        # policy path drives it; see tests/test_recurrent.py).
        from ray_tpu.models import catalog
        from ray_tpu.rllib.env.spaces import Box
        model = catalog.get_model(
            Box(low=-1, high=1, shape=(4,), dtype=np.float32), 2,
            {"use_lstm": True})
        assert hasattr(model, "initial_state")


class TestAdvisoryFixes:
    """Round-2 advisor findings."""

    def test_mapping_fn_registry_resolves_and_rejects(self):
        from ray_tpu.rllib.utils.registry import (
            register_policy_mapping_fn, resolve_policy_mapping_fn)
        fn = resolve_policy_mapping_fn("round_robin", ["p0", "p1"])
        assert fn(0) == "p0" and fn(1) == "p1" and fn(2) == "p0"
        # String agent ids map deterministically.
        assert fn("agent_7") in ("p0", "p1")
        with pytest.raises(ValueError):
            resolve_policy_mapping_fn("lambda aid: __import__('os')", ["p"])
        register_policy_mapping_fn(
            "all_to_first", lambda pids: (lambda aid: pids[0]))
        fn2 = resolve_policy_mapping_fn("all_to_first", ["a", "b"])
        assert fn2(99) == "a"

    def test_ope_gain_sign_correct_for_negative_returns(self):
        # V_gain_est must divide by the true v_old even when returns are
        # negative (Pendulum-style), not clamp the denominator to 1e-8.
        import types
        from ray_tpu.rllib.offline.off_policy_estimator import (
            ImportanceSamplingEstimator)
        from ray_tpu.rllib.sample_batch import SampleBatch
        est = ImportanceSamplingEstimator.__new__(
            ImportanceSamplingEstimator)
        est.gamma = 1.0
        est._rewards_and_rho = types.MethodType(
            lambda self, ep: (np.array([-1.0, -1.0]),
                              np.array([1.0, 1.0])), est)
        out = est.estimate(SampleBatch({"rewards": np.array([-1., -1.])}))
        # rho == 1 everywhere -> gain must be exactly 1.0, not huge.
        assert abs(out.metrics["V_gain_est"] - 1.0) < 1e-6

    def test_syncer_sync_down_falls_back_to_old(self, tmp_path):
        from ray_tpu.tune.syncer import Syncer
        import os
        up = tmp_path / "up"
        local = tmp_path / "local"
        local.mkdir()
        (local / "ckpt").write_text("v1")
        s = Syncer(str(up))
        s.sync_up(str(local), "trial-1")
        # Simulate a crash between the two sync_up renames: primary gone,
        # aside copy present.
        os.rename(up / "trial-1", up / "trial-1.old")
        out = tmp_path / "restored"
        s.sync_down("trial-1", str(out))
        assert (out / "ckpt").read_text() == "v1"

    def test_exported_refs_survive_eviction_grace(self, tmp_path):
        """An owned object whose ref was pickled for a peer must not be
        LRU-evicted inside the grace window even with zero local refs."""
        import os
        # 9 MiB: 5 x 2 MiB puts overshoot unconditionally, so the
        # eviction path always runs (10 MiB would be a knife-edge).
        os.environ["RAY_TPU_OBJECT_STORE_CAPACITY"] = str(9 * 1024 * 1024)
        import pickle
        import ray_tpu
        ray_tpu.init(num_cpus=1)
        try:
            rt = ray_tpu._private.worker_state.get_runtime()
            ref = ray_tpu.put(np.zeros(1 << 18))  # 2 MB
            pickle.dumps(ref)   # simulates shipping the ref to a peer
            oid = ref.id
            del ref
            # Pressure the store: without the grace window the exported
            # object would be the LRU victim. With it, the store refuses
            # to evict (raising full is the CORRECT outcome here).
            from ray_tpu.exceptions import ObjectStoreFullError
            held = []
            try:
                for _ in range(4):
                    held.append(ray_tpu.put(np.zeros(1 << 18)))
            except ObjectStoreFullError:
                pass
            assert oid in rt._exported_at
            assert rt.shm.contains(oid)
        finally:
            ray_tpu.shutdown()
            del os.environ["RAY_TPU_OBJECT_STORE_CAPACITY"]


class TestBenchMedianWindows:
    def test_even_window_count_uses_median_low(self):
        """Advisor round 5: statistics.median of an even count averages the
        middle two — a rate belonging to NO window, so the extra lookup
        crashed. median_low always names a real window."""
        import bench
        calls = iter([(10.0, "w0"), (30.0, "w1"), (20.0, "w2"),
                      (40.0, "w3")])
        med, stddev_pct, extra, rates = bench.median_windows(
            lambda: next(calls), n=4)
        assert med == 20.0          # lower of the middle pair {20, 30}
        assert extra == "w2"        # the extra of THAT window
        assert rates == [10.0, 30.0, 20.0, 40.0]

    def test_odd_window_count_unchanged(self):
        import bench
        calls = iter([(10.0, "a"), (30.0, "b"), (20.0, "c")])
        med, _, extra, _ = bench.median_windows(lambda: next(calls),
                                                n=3)
        assert med == 20.0 and extra == "c"
