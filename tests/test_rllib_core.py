"""RL stack unit tests: sample batch, GAE, distributions, models, sampler
(parity: reference `rllib/tests/` unit coverage)."""

import numpy as np
import pytest

from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.sample_batch import MultiAgentBatch, SampleBatch


def make_batch(n, eps_id=0):
    return SampleBatch({
        sb.OBS: np.random.rand(n, 4).astype(np.float32),
        sb.ACTIONS: np.random.randint(0, 2, n),
        sb.REWARDS: np.ones(n, np.float32),
        sb.DONES: np.zeros(n, bool),
        sb.EPS_ID: np.full(n, eps_id, np.int64),
    })


class TestSampleBatch:
    def test_count_and_concat(self):
        b = SampleBatch.concat_samples([make_batch(3), make_batch(5)])
        assert b.count == 8

    def test_mismatched_lengths_rejected(self):
        with pytest.raises(ValueError):
            SampleBatch({"a": np.zeros(3), "b": np.zeros(4)})

    def test_rows_and_slice(self):
        b = make_batch(5)
        rows = list(b.rows())
        assert len(rows) == 5
        s = b.slice(1, 3)
        assert s.count == 2

    def test_shuffle_preserves_alignment(self):
        n = 100
        b = SampleBatch({
            "x": np.arange(n, dtype=np.float32),
            "y": np.arange(n, dtype=np.float32) * 2,
        })
        s = b.shuffle(np.random.default_rng(0))
        np.testing.assert_array_equal(s["y"], s["x"] * 2)
        assert not np.array_equal(s["x"], b["x"])

    def test_split_by_episode(self):
        b = SampleBatch.concat_samples(
            [make_batch(3, 1), make_batch(4, 2), make_batch(2, 3)])
        parts = b.split_by_episode()
        assert [p.count for p in parts] == [3, 4, 2]

    def test_multi_agent(self):
        mb = MultiAgentBatch({"p1": make_batch(3), "p2": make_batch(3)}, 3)
        mb2 = MultiAgentBatch.concat_samples([mb, mb])
        assert mb2.count == 6
        assert mb2.policy_batches["p1"].count == 6


class TestGAE:
    def test_gae_matches_reference_formula(self):
        from ray_tpu.rllib.evaluation.postprocessing import compute_advantages
        T = 5
        gamma, lam = 0.9, 0.8
        rewards = np.array([1, 0, 2, 0, 1], np.float32)
        vf = np.array([0.5, 0.4, 0.3, 0.2, 0.1], np.float32)
        batch = SampleBatch({
            sb.REWARDS: rewards, sb.VF_PREDS: vf,
            sb.OBS: np.zeros((T, 2), np.float32),
        })
        last_r = 0.7
        out = compute_advantages(batch, last_r, gamma, lam, use_gae=True)
        # brute force
        v_ext = np.concatenate([vf, [last_r]])
        deltas = rewards + gamma * v_ext[1:] - v_ext[:-1]
        adv = np.zeros(T)
        acc = 0.0
        for t in reversed(range(T)):
            acc = deltas[t] + gamma * lam * acc
            adv[t] = acc
        np.testing.assert_allclose(out[sb.ADVANTAGES], adv, rtol=1e-5)
        np.testing.assert_allclose(out[sb.VALUE_TARGETS], adv + vf, rtol=1e-5)

    def test_discounted_returns(self):
        from ray_tpu.rllib.evaluation.postprocessing import compute_advantages
        rewards = np.array([1, 1, 1], np.float32)
        batch = SampleBatch({
            sb.REWARDS: rewards, sb.OBS: np.zeros((3, 2), np.float32)})
        out = compute_advantages(batch, 0.0, gamma=0.5, use_gae=False,
                                 use_critic=False)
        np.testing.assert_allclose(
            out[sb.VALUE_TARGETS], [1.75, 1.5, 1.0], rtol=1e-5)


class TestDistributions:
    def test_categorical(self):
        import jax
        from ray_tpu.models.distributions import Categorical
        logits = np.log(np.array([[0.7, 0.2, 0.1]], np.float32))
        d = Categorical(logits)
        np.testing.assert_allclose(
            float(d.logp(np.array([0]))[0]), np.log(0.7), rtol=1e-5)
        ent = -np.sum([0.7, 0.2, 0.1] * np.log([0.7, 0.2, 0.1]))
        np.testing.assert_allclose(float(d.entropy()[0]), ent, rtol=1e-5)
        samples = [int(d.sample(jax.random.PRNGKey(i))[0]) for i in range(50)]
        assert samples.count(0) > 20  # mode dominates

    def test_categorical_kl_zero_self(self):
        from ray_tpu.models.distributions import Categorical
        logits = np.random.randn(4, 6).astype(np.float32)
        d = Categorical(logits)
        np.testing.assert_allclose(np.asarray(d.kl(Categorical(logits))),
                                   np.zeros(4), atol=1e-6)

    def test_diag_gaussian(self):
        import jax
        from ray_tpu.models.distributions import DiagGaussian
        inputs = np.concatenate([
            np.zeros((1, 2), np.float32),  # mean 0
            np.zeros((1, 2), np.float32),  # log_std 0 -> std 1
        ], axis=-1)
        d = DiagGaussian(inputs)
        # logp of mean = -0.5*d*log(2pi)
        np.testing.assert_allclose(
            float(d.logp(np.zeros((1, 2), np.float32))[0]),
            -np.log(2 * np.pi), rtol=1e-5)
        s = d.sample(jax.random.PRNGKey(0))
        assert s.shape == (1, 2)

    def test_squashed_gaussian_bounds(self):
        import jax
        from ray_tpu.models.distributions import SquashedGaussian
        inputs = np.random.randn(10, 4).astype(np.float32) * 3
        d = SquashedGaussian(inputs, low=-2.0, high=2.0)
        s = np.asarray(d.sample(jax.random.PRNGKey(0)))
        assert np.all(s >= -2.0) and np.all(s <= 2.0)


class TestModels:
    def test_fcnet_shapes(self):
        import jax
        from ray_tpu.models.networks import FullyConnectedNetwork
        net = FullyConnectedNetwork(num_outputs=6, hiddens=(32, 32))
        params = net.init(jax.random.PRNGKey(0), np.zeros((1, 4), np.float32))
        logits, value = net.apply(params, np.zeros((7, 4), np.float32))
        assert logits.shape == (7, 6)
        assert value.shape == (7,)

    def test_visionnet_shapes(self):
        import jax
        from ray_tpu.models.networks import VisionNetwork
        net = VisionNetwork(num_outputs=6)
        obs = np.zeros((2, 84, 84, 4), np.uint8)
        params = net.init(jax.random.PRNGKey(0), obs)
        logits, value = net.apply(params, obs)
        assert logits.shape == (2, 6)
        assert value.shape == (2,)
        assert logits.dtype == np.float32  # heads in f32 despite bf16 trunk

    def test_catalog_picks_network(self):
        from ray_tpu.models import catalog
        from ray_tpu.models.networks import (FullyConnectedNetwork,
                                             VisionNetwork)
        from ray_tpu.rllib.env.spaces import Box
        m = catalog.get_model(Box(-1, 1, (4,)), 2, {})
        assert isinstance(m, FullyConnectedNetwork)
        m = catalog.get_model(Box(0, 255, (84, 84, 4), np.uint8), 6, {})
        assert isinstance(m, VisionNetwork)


class TestEnvs:
    def test_cartpole_contract(self):
        from ray_tpu.rllib.env import make_env
        env = make_env("CartPole-v0")
        obs = env.reset()
        assert obs.shape == (4,)
        total = 0
        done = False
        while not done:
            obs, r, done, info = env.step(env.action_space.sample())
            total += r
        assert 1 <= total <= 200

    def test_pendulum_contract(self):
        from ray_tpu.rllib.env import make_env
        env = make_env("Pendulum-v0")
        obs = env.reset()
        assert obs.shape == (3,)
        obs, r, done, _ = env.step(np.array([0.5]))
        assert r <= 0

    def test_vector_env(self):
        from ray_tpu.rllib.env import CartPole, VectorEnv
        venv = VectorEnv(lambda: CartPole(), 3)
        obs = venv.reset()
        assert obs.shape == (3, 4)
        obs, rew, dones, infos = venv.step([0, 1, 0])
        assert obs.shape == (3, 4) and rew.shape == (3,)


class TestSampler:
    def test_fragment_length_and_metrics(self):
        from ray_tpu.rllib.env import CartPole, VectorEnv
        from ray_tpu.rllib.evaluation.sampler import SyncSampler
        from ray_tpu.rllib.policy.policy import RandomPolicy

        venv = VectorEnv(lambda: CartPole(), 2)
        policy = RandomPolicy(venv.observation_space, venv.action_space, {})
        sampler = SyncSampler(venv, policy, rollout_fragment_length=50)
        batch = sampler.sample()
        assert batch.count == 100  # 2 envs x 50 steps
        # Random policy on cartpole finishes episodes within ~25 steps.
        metrics = sampler.get_metrics()
        assert len(metrics) >= 2
        assert all(m.episode_reward == m.episode_length for m in metrics)

    def test_episode_ids_distinct(self):
        from ray_tpu.rllib.env import CartPole, VectorEnv
        from ray_tpu.rllib.evaluation.sampler import SyncSampler
        from ray_tpu.rllib.policy.policy import RandomPolicy

        venv = VectorEnv(lambda: CartPole(), 1)
        policy = RandomPolicy(venv.observation_space, venv.action_space, {})
        # An episode ends after 200 steps at the latest, so a fragment of
        # 201 holds two whatever the unseeded policy draws (one of 100 was
        # a single episode once in ~20 whole runs).
        sampler = SyncSampler(venv, policy, rollout_fragment_length=201)
        batch = sampler.sample()
        # Multiple episodes in the fragment → multiple eps ids.
        assert len(np.unique(batch[sb.EPS_ID])) >= 2


class TestFilters:
    def test_mean_std_filter(self):
        from ray_tpu.rllib.utils.filter import MeanStdFilter
        f = MeanStdFilter((3,))
        xs = np.random.randn(500, 3) * 5 + 2
        for x in xs:
            f(x)
        out = f(np.array([2.0, 2.0, 2.0]), update=False)
        assert np.all(np.abs(out) < 1.0)  # near the running mean

    def test_filter_merge(self):
        from ray_tpu.rllib.utils.filter import MeanStdFilter
        a, b = MeanStdFilter((1,)), MeanStdFilter((1,))
        data = np.random.randn(200, 1)
        for x in data[:100]:
            a(x)
        for x in data[100:]:
            b(x)
        a.apply_changes(b)
        np.testing.assert_allclose(a.rs.mean, data.mean(axis=0), atol=1e-6)
