"""Tests for the TPU-native rollout architectures added in round 3:

- BatchedEnv (vectorized host envs) — the Sebulba actor's unit of work
- VectorSampler — packed O(1)-python-per-step sampling
- Inline actors (Sebulba) — batched learner-device inference
- JaxEnv + AnakinOptimizer — fully device-resident IMPALA

Reference test model (SURVEY.md §4): regression-by-learning for the
end-to-end paths, numeric parity for env dynamics.
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.env.batched_env import (BatchedCartPole,
                                           BatchedEnvFromSingle,
                                           BatchedSyntheticAtari)
from ray_tpu.rllib.env.env import CartPole, Pendulum
from ray_tpu.rllib.env.registry import make_batched_env
from ray_tpu.rllib.sample_batch import SampleBatch


@pytest.fixture
def ray_session():
    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()


# ---------------------------------------------------------------------
# BatchedEnv
# ---------------------------------------------------------------------
class TestBatchedEnvs:
    def test_batched_cartpole_matches_single_dynamics(self):
        single = CartPole()
        single.seed(0)
        single.reset()
        batched = BatchedCartPole(3, seed=0)
        batched.vector_reset()
        # Inject identical state, step with identical actions, compare.
        state = np.array([0.01, -0.02, 0.03, 0.04])
        single._state = state.copy()
        single._t = 0
        batched._state = np.tile(state, (3, 1))
        batched._t[:] = 0
        for action in [0, 1, 1, 0, 1]:
            obs_s, r_s, d_s, _ = single.step(action)
            obs_b, r_b, d_b = batched.vector_step(np.full(3, action))
            np.testing.assert_allclose(obs_b[1], obs_s, rtol=1e-6)
            assert r_b[1] == r_s
            assert bool(d_b[1]) == d_s
            if d_s:
                break

    def test_batched_cartpole_auto_resets(self):
        env = BatchedCartPole(4, max_steps=5, seed=0)
        env.vector_reset()
        done_seen = False
        for _ in range(6):
            obs, rew, dones = env.vector_step(np.ones(4, np.int64))
            done_seen = done_seen or dones.any()
        assert done_seen
        # After auto-reset the step counters restarted.
        assert (env._t < 5).all()

    def test_batched_synthetic_atari_signal(self):
        env = BatchedSyntheticAtari(8, episode_len=50, seed=0)
        obs = env.vector_reset()
        assert obs.shape == (8, 84, 84, 4) and obs.dtype == np.uint8
        # Playing the target action yields reward 1 for every slot.
        obs, rew, dones = env.vector_step(env._target.copy())
        np.testing.assert_array_equal(rew, np.ones(8, np.float32))
        # The bright band encodes the (new) target: band rows are brighter.
        band = 84 // env.num_actions
        for i in range(8):
            t = int(env._target[i])
            band_mean = obs[i, t * band:(t + 1) * band].mean()
            rest = np.concatenate(
                [obs[i, :t * band], obs[i, (t + 1) * band:]])
            assert band_mean > rest.mean() + 64

    def test_batched_synthetic_atari_episode_len(self):
        env = BatchedSyntheticAtari(2, episode_len=3, seed=0)
        env.vector_reset()
        dones = [env.vector_step(np.zeros(2, np.int64))[2] for _ in range(3)]
        assert not dones[0].any() and not dones[1].any()
        assert dones[2].all()

    def test_fallback_adapter_and_registry(self):
        env = make_batched_env("Pendulum-v0", 3, seed=0)
        assert isinstance(env, BatchedEnvFromSingle)
        obs = env.vector_reset()
        assert obs.shape == (3, 3)
        obs, rew, dones = env.vector_step(np.zeros((3, 1), np.float32))
        assert obs.shape == (3, 3) and rew.shape == (3,)
        # Natively-vectorized registration wins for CartPole.
        env2 = make_batched_env("CartPole-v0", 2, seed=0)
        assert isinstance(env2, BatchedCartPole)


# ---------------------------------------------------------------------
# SampleBatch BOOTSTRAP_OBS semantics
# ---------------------------------------------------------------------
class TestBootstrapObsColumn:
    def test_count_ignores_fragment_columns(self):
        b = SampleBatch({
            sb.BOOTSTRAP_OBS: np.zeros((2, 4)),
            sb.OBS: np.zeros((10, 4)),
            sb.REWARDS: np.zeros(10),
        })
        assert b.count == 10

    def test_concat_concatenates_bootstrap(self):
        mk = lambda: SampleBatch({sb.OBS: np.zeros((6, 2)),
                                  sb.BOOTSTRAP_OBS: np.zeros((2, 2))})
        out = SampleBatch.concat_samples([mk(), mk()])
        assert out[sb.OBS].shape == (12, 2)
        assert out[sb.BOOTSTRAP_OBS].shape == (4, 2)

    def test_slice_drops_bootstrap(self):
        b = SampleBatch({sb.OBS: np.arange(12).reshape(6, 2),
                         sb.BOOTSTRAP_OBS: np.zeros((2, 2))})
        s = b.slice(0, 3)
        assert sb.BOOTSTRAP_OBS not in s and s.count == 3


# ---------------------------------------------------------------------
# VectorSampler packing
# ---------------------------------------------------------------------
class _ScriptedPolicy:
    """Deterministic policy: action = (step index) % 2, records calls."""

    def __init__(self):
        self.calls = 0

    def compute_actions(self, obs, state_batches=None, explore=True):
        n = len(obs)
        actions = np.full(n, self.calls % 2, np.int64)
        self.calls += 1
        extra = {sb.ACTION_LOGP: np.zeros(n, np.float32),
                 sb.ACTION_DIST_INPUTS: np.zeros((n, 2), np.float32),
                 sb.VF_PREDS: np.zeros(n, np.float32)}
        return actions, [], extra


class TestVectorSampler:
    def test_packing_layout(self):
        from ray_tpu.rllib.evaluation.vector_sampler import VectorSampler
        env = BatchedCartPole(4, seed=0)
        pol = _ScriptedPolicy()
        sampler = VectorSampler(env, pol, rollout_fragment_length=10)
        batch = sampler.sample()
        assert batch.count == 40
        assert batch[sb.OBS].shape == (40, 4)
        assert batch[sb.BOOTSTRAP_OBS].shape == (4, 4)
        # Env-major: each env's 10 rows are contiguous, t restarts per
        # env (no dones expected in 10 steps from near-zero init).
        t = batch[sb.T].reshape(4, 10)
        for i in range(4):
            deltas = np.diff(t[i])
            assert ((deltas == 1) | (t[i][1:] == 0)).all()
        # One compute_actions per step, not per env.
        assert pol.calls == 10
        # Bootstrap obs is the env's current obs after the fragment.
        np.testing.assert_array_equal(batch[sb.BOOTSTRAP_OBS],
                                      sampler._obs)

    def test_eps_ids_change_at_dones(self):
        from ray_tpu.rllib.evaluation.vector_sampler import VectorSampler
        env = BatchedSyntheticAtari(2, episode_len=4, seed=0)
        pol = _ScriptedPolicy()
        sampler = VectorSampler(env, pol, rollout_fragment_length=10)
        batch = sampler.sample()
        eps = batch[sb.EPS_ID].reshape(2, 10)
        dones = batch[sb.DONES].reshape(2, 10)
        for i in range(2):
            for step in range(9):
                if dones[i, step]:
                    assert eps[i, step + 1] != eps[i, step]
                else:
                    assert eps[i, step + 1] == eps[i, step]
        assert len(sampler.metrics) == 4  # 2 envs x 2 completed episodes


# ---------------------------------------------------------------------
# DeviceSebulbaSampler (round 4): device-resident rollouts
# ---------------------------------------------------------------------
class _CountingFrameEnv:
    """BatchedEnv emitting [N, 4, 4, 1] uint8 frames whose value is the
    global step counter; episodes end every `episode_len` steps."""

    def __init__(self, num_envs, episode_len=3):
        from ray_tpu.rllib.env.spaces import Box, Discrete
        self.num_envs = num_envs
        self.episode_len = episode_len
        self.observation_space = Box(0, 255, shape=(4, 4, 1),
                                     dtype=np.uint8)
        self.action_space = Discrete(2)
        self._count = 0
        self._t = np.zeros(num_envs, np.int64)

    def _frames(self):
        return np.full((self.num_envs, 4, 4, 1), self._count % 256,
                       np.uint8)

    def vector_reset(self):
        self._count = 0
        self._t[:] = 0
        return self._frames()

    def vector_step(self, actions):
        self._count += 1
        self._t += 1
        dones = self._t >= self.episode_len
        self._t[dones] = 0
        return self._frames(), np.zeros(self.num_envs, np.float32), dones

    def seed(self, seed=None):
        pass


class TestDeviceSampler:
    def _make_policy(self, env):
        from ray_tpu.rllib.agents.pg.pg import DEFAULT_CONFIG, PGJaxPolicy
        cfg = dict(DEFAULT_CONFIG)
        # Tiny conv for the 4x4 test frames (nature CNN needs >= 84x84).
        cfg.update({"model": {"fcnet_hiddens": [8],
                              "conv_filters": ((4, 2, 1),)},
                    "seed": 0})
        return PGJaxPolicy(env.observation_space, env.action_space, cfg)

    def test_frame_stack_matches_host_semantics(self):
        """On-device stacking must reproduce host FrameStack exactly:
        rolling window within an episode, reset-filled at boundaries."""
        from ray_tpu.rllib.env.device_frame_stack import DeviceFrameStack
        from ray_tpu.rllib.evaluation.device_sampler import (
            DeviceSebulbaSampler)
        K, T, N = 4, 8, 2
        env = DeviceFrameStack(_CountingFrameEnv(N, episode_len=3), K)
        policy = self._make_policy(env)
        sampler = DeviceSebulbaSampler(env, policy,
                                       rollout_fragment_length=T)
        batch = sampler.sample()
        obs = np.asarray(batch[sb.OBS]).reshape(N, T, 4, 4, K)
        # Host reference: frame value at global step t is t; episodes
        # are 3 steps long, so stacks reset-fill at t in {0, 3, 6, ...}.
        def host_stack(t):
            ep_start = (t // 3) * 3
            frames = [max(ep_start, t - (K - 1) + i) for i in range(K)]
            return np.array(frames, np.uint8)
        for t in range(T):
            expect = host_stack(t)
            for i in range(N):
                np.testing.assert_array_equal(
                    obs[i, t, 0, 0, :], expect,
                    err_msg=f"stack mismatch at t={t}")
        # Bootstrap obs = stack for step T (post-fragment).
        boot = np.asarray(batch[sb.BOOTSTRAP_OBS])
        np.testing.assert_array_equal(boot[0, 0, 0, :], host_stack(T))
        # Accounting: only single frames went up, only actions came back.
        stats = sampler.transfer_stats()
        assert stats["steps"] == N * T
        # Per step: N frames of 16 bytes + N done bytes (+ initial).
        assert stats["bytes_h2d"] <= (T + 2) * N * (4 * 4 + 1)

    def test_device_batch_columns_stay_on_device(self):
        """OBS/BOOTSTRAP/dist-inputs columns come back as jax arrays (no
        host round-trip); host columns stay numpy."""
        import jax
        from ray_tpu.rllib.evaluation.device_sampler import (
            DeviceSebulbaSampler)
        env = BatchedCartPole(4, seed=0)
        policy = self._make_policy(env)
        sampler = DeviceSebulbaSampler(env, policy,
                                       rollout_fragment_length=5)
        batch = sampler.sample()
        assert isinstance(batch[sb.OBS], jax.Array)
        assert isinstance(batch[sb.BOOTSTRAP_OBS], jax.Array)
        assert isinstance(batch[sb.ACTION_DIST_INPUTS], jax.Array)
        assert isinstance(batch[sb.ACTIONS], np.ndarray)
        assert batch[sb.OBS].shape == (20, 4)
        assert batch.count == 20
        # eps ids advance at dones, mirroring VectorSampler bookkeeping.
        assert batch[sb.EPS_ID].shape == (20,)

    def test_device_rollouts_false_uses_host_sampler(self, ray_session):
        from ray_tpu.rllib.agents.registry import get_trainer_class
        from ray_tpu.rllib.evaluation.vector_sampler import VectorSampler
        t = get_trainer_class("IMPALA")(config={
            "env": "CartPole-v0",
            "num_workers": 0,
            "num_inline_actors": 1,
            "num_envs_per_worker": 8,
            "rollout_fragment_length": 10,
            "train_batch_size": 80,
            "device_rollouts": False,
            "min_iter_time_s": 0,
            "seed": 0,
        })
        assert isinstance(
            t.optimizer._inline_actors[0].sampler, VectorSampler)
        t.train()
        t.stop()

    def test_impala_frames_env_trains(self, ray_session):
        """IMPALA over the single-frame env + on-device stacking: the
        full device-resident pipeline end to end."""
        from ray_tpu.rllib.agents.registry import get_trainer_class
        t = get_trainer_class("IMPALA")(config={
            "env": "SyntheticAtariFrames-v0",
            "env_config": {"episode_len": 50},
            "num_workers": 0,
            "num_inline_actors": 1,
            "num_envs_per_worker": 8,
            "rollout_fragment_length": 10,
            "train_batch_size": 80,
            "device_frame_stack": 4,
            "min_iter_time_s": 0,
            "seed": 0,
        })
        r = t.train()
        assert r["timesteps_this_iter"] >= 80
        # The policy was built for the STACKED space.
        pol = t.workers.local_worker.policy
        assert pol.observation_space.shape == (84, 84, 4)
        t.stop()


# ---------------------------------------------------------------------
# End-to-end learning (regression-by-learning, SURVEY §4.2 lesson 2)
# ---------------------------------------------------------------------
class TestEndToEnd:
    def test_inline_sebulba_impala_learns_cartpole(self, ray_session):
        from ray_tpu.rllib.agents.registry import get_trainer_class
        t = get_trainer_class("IMPALA")(config={
            "env": "CartPole-v0",
            "num_workers": 0,
            "num_inline_actors": 1,
            "num_envs_per_worker": 16,
            "rollout_fragment_length": 20,
            "train_batch_size": 320,
            "lr": 3e-3,
            "min_iter_time_s": 0,
            "seed": 0,
        })
        # The budget is experience, 320 steps an iteration. The actor
        # runs ahead of the learner by what the host's scheduler gives
        # it, so the iterations it takes vary: 15-22 on an idle box, 35
        # seen beside twelve busy processes. The loop ends as soon as
        # it has learned.
        best = 0.0
        for _ in range(100):
            r = t.train()
            rew = r.get("episode_reward_mean")
            if rew == rew:  # not nan
                best = max(best, rew)
            if best > 60:
                break
        t.stop()
        assert best > 60, f"inline IMPALA failed to learn: best={best}"

    def test_anakin_impala_learns_cartpole(self, ray_session):
        from ray_tpu.rllib.agents.registry import get_trainer_class
        t = get_trainer_class("IMPALA")(config={
            "env": "CartPole-v0",
            "anakin": True,
            "num_workers": 0,
            "num_envs_per_worker": 32,
            "rollout_fragment_length": 20,
            "train_batch_size": 640,
            "num_tpus_for_learner": 4,
            "lr": 3e-3,
            "min_iter_time_s": 0,
            "seed": 0,
        })
        best = 0.0
        for _ in range(10):
            r = t.train()
            rew = r.get("episode_reward_mean", float("nan"))
            if rew == rew:
                best = max(best, rew)
            if best > 150:
                break
        t.stop()
        assert best > 150, f"anakin IMPALA failed to learn: best={best}"
        # Throughput accounting matches the fused shape.
        assert r["timesteps_this_iter"] == 32 * 20 * 10

    def test_inline_appo_trains(self, ray_session):
        """APPO shares the optimizer factory; its loss must accept
        BOOTSTRAP_OBS fragment batches too (round-3 review finding)."""
        from ray_tpu.rllib.agents.registry import get_trainer_class
        t = get_trainer_class("APPO")(config={
            "env": "CartPole-v0",
            "num_workers": 0,
            "num_inline_actors": 1,
            "num_envs_per_worker": 8,
            "rollout_fragment_length": 10,
            "train_batch_size": 80,
            "min_iter_time_s": 0,
            "seed": 0,
        })
        r = t.train()
        assert r["timesteps_this_iter"] > 0
        t.stop()

    def test_inline_impala_with_sgd_minibatches(self, ray_session):
        """Minibatch SGD over fragment batches: BOOTSTRAP_OBS must follow
        the sequence permutation inside the fused program."""
        from ray_tpu.rllib.agents.registry import get_trainer_class
        t = get_trainer_class("IMPALA")(config={
            "env": "CartPole-v0",
            "num_workers": 0,
            "num_inline_actors": 1,
            "num_envs_per_worker": 8,
            "rollout_fragment_length": 10,
            "train_batch_size": 80,
            "num_sgd_iter": 2,
            "sgd_minibatch_size": 40,
            "min_iter_time_s": 0,
            "seed": 0,
        })
        r = t.train()
        assert r["timesteps_this_iter"] > 0
        t.stop()

    def test_learner_death_fails_fast(self, ray_session):
        """A dead learner thread surfaces its real error immediately,
        not a 600s stall (round-3 review finding)."""
        import time
        from ray_tpu.rllib.agents.registry import get_trainer_class
        t = get_trainer_class("IMPALA")(config={
            "env": "CartPole-v0",
            "num_workers": 0,
            "num_inline_actors": 1,
            "num_envs_per_worker": 8,
            "rollout_fragment_length": 10,
            "train_batch_size": 80,
            "min_iter_time_s": 0,
            "seed": 0,
        })
        t.train()  # healthy first step
        # Sabotage the next learner step.
        def boom(*a, **k):
            raise RuntimeError("injected learner failure")
        t.optimizer.learner.local_worker.policy.learn_on_batch = boom
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="learner thread died"):
            for _ in range(50):
                t.optimizer.step()
        assert time.monotonic() - t0 < 120
        t.stop()

    def test_inline_rejects_remote_workers(self, ray_session):
        from ray_tpu.rllib.agents.registry import get_trainer_class
        with pytest.raises(ValueError, match="alternative sampling"):
            get_trainer_class("IMPALA")(config={
                "env": "CartPole-v0",
                "num_workers": 2,
                "num_inline_actors": 1,
                "rollout_fragment_length": 10,
                "train_batch_size": 80,
            })

    def test_anakin_rejects_host_only_env(self, ray_session):
        from ray_tpu.rllib.agents.registry import get_trainer_class
        with pytest.raises(ValueError, match="no JAX"):
            get_trainer_class("IMPALA")(config={
                "env": "Pendulum-v0",
                "anakin": True,
                "num_workers": 0,
                "num_envs_per_worker": 4,
                "rollout_fragment_length": 5,
                "train_batch_size": 20,
                "seed": 0,
            })

    def test_anakin_rejects_workers(self, ray_session):
        from ray_tpu.rllib.agents.registry import get_trainer_class
        with pytest.raises(ValueError, match="num_workers"):
            get_trainer_class("IMPALA")(config={
                "env": "CartPole-v0",
                "anakin": True,
                "num_workers": 2,
                "rollout_fragment_length": 5,
                "train_batch_size": 20,
            })


# ---------------------------------------------------------------------
# The fused program's packing against stack-then-transpose
# ---------------------------------------------------------------------
def _stack_then_em(opt):
    """The fused program in its plain form, kept here as the reference:
    every column, frames included, stacked `[T, N, ..]` by the rollout's
    scan and transposed env-major (`em`), then the optimizer's own
    `learn`. Feed-forward policies that keep their logits. Returns
    `run(params, opt_state, env_state, obs, rng)` -> (params, the
    batches of the call's updates stacked, the call's stats)."""
    import functools

    import jax
    import jax.numpy as jnp
    policy, env = opt.policy, opt.env
    N, T, M = opt.num_envs, opt.T, opt.updates_per_call
    vstep = jax.vmap(env.step)

    def em(x):
        return jnp.swapaxes(x, 0, 1).reshape((N * T,) + x.shape[2:])

    def step(params, carry, _):
        env_state, obs, rng = carry
        rng, akey, ekey = jax.random.split(rng, 3)
        dist_inputs, _ = policy.apply(params, obs)
        action = policy.dist_class(dist_inputs).sample(akey)
        env_state, next_obs, reward, done = vstep(
            env_state, action, jax.random.split(ekey, N))
        return (env_state, next_obs, rng), (
            obs, action, reward, done, dist_inputs)

    def update(carry, _):
        params, opt_state, env_state, obs, rng = carry
        (env_state, obs, rng), traj = jax.lax.scan(
            functools.partial(step, params), (env_state, obs, rng), None,
            length=T)
        obs_t, act_t, rew_t, done_t, logits_t = traj
        batch = {
            sb.OBS: em(obs_t), sb.ACTIONS: em(act_t),
            sb.REWARDS: em(rew_t),
            sb.DONES: em(done_t).astype(jnp.float32),
            sb.BOOTSTRAP_OBS: obs, sb.ACTION_DIST_INPUTS: em(logits_t)}
        rng, lkey = jax.random.split(rng)
        params, opt_state, stats = opt.learn(params, opt_state, batch, lkey)
        return (params, opt_state, env_state, obs, rng), (batch, stats)

    @jax.jit
    def run(params, opt_state, env_state, obs, rng):
        carry, (batches, stats) = jax.lax.scan(
            update, (params, opt_state, env_state, obs, rng), None,
            length=M)
        return carry[0], batches, {
            k: jnp.max(v) if k.endswith("_max") else jnp.mean(v)
            for k, v in stats.items()}
    return run


def _frames_match(batch, T):
    """The share of a `JaxSyntheticAtari` batch's rows whose frame shows
    the target their reward was paid against (a bright band of 14 rows
    names it; a frame of another env slot or step agrees once in six), in
    `sb.OBS` and, where it is there, in `sb.OBS_TIME_MAJOR` read as its
    comment says."""
    import jax.numpy as jnp

    def share(frames):
        target = jnp.argmax(frames[:, ::14, 0, 0][:, :6] >= 128, axis=1)
        return jnp.mean((batch[sb.REWARDS] > 0) == (
            batch[sb.ACTIONS] == target))
    out = share(batch[sb.OBS])
    view = batch.get(sb.OBS_TIME_MAJOR)
    if view is not None:
        g, t, b = view.shape[:3]
        assert t == T
        out = jnp.minimum(out, share(jnp.swapaxes(view, 1, 2).reshape(
            (g * b * t,) + view.shape[3:])))
    return out


# (env, env slots, steps, learner devices, frames written in place)
PACK_CASES = {
    "frames": ("SyntheticAtari-v0", 128, 4, 1, True),
    # a lane tile part-filled: the frames are stacked like any column
    "frames_8_envs": ("SyntheticAtari-v0", 8, 4, 1, False),
    "cartpole": ("CartPole-v0", 8, 4, 1, False),
    "frames_dp_mesh": ("SyntheticAtari-v0", 256, 2, 2, True),
}


@pytest.mark.parametrize("case", list(PACK_CASES))
def test_fused_packing_is_stack_then_em(case):
    """(a) The batch the fused program hands its learner is `em` of the
    stacked trajectory in every column, the frames bit for bit and
    `sb.OBS` in the env's dtype, and where the frames were written in
    place `sb.OBS_TIME_MAJOR` is the same rows as the rollout wrote them. (b) One call leaves the
    params and stats of the stack-then-`em` form. (c) Over a `dp` mesh
    the buffer is sharded by env slot: the compiled program moves no
    observation between devices."""
    import jax
    from ray_tpu.rllib.agents.registry import get_trainer_class
    env, N, T, devices, in_place = PACK_CASES[case]
    # The frames and the episode clock are integer functions of the seed:
    # equal to the bit. Logits are float32 of two programs XLA compiled
    # apart, and a sampled action (so a reward, and CartPole's next
    # observation) may fall the other way on a tie within their rounding.
    frames = env == "SyntheticAtari-v0"
    exact = {sb.OBS, sb.BOOTSTRAP_OBS, sb.DONES} if frames else set()
    trainer = get_trainer_class("IMPALA")(config=dict(
        env=env, anakin=True, num_workers=0, num_envs_per_worker=N,
        rollout_fragment_length=T, train_batch_size=N * T,
        anakin_updates_per_call=2, num_tpus_for_learner=devices,
        min_iter_time_s=0, seed=5))
    try:
        opt, policy = trainer.optimizer, trainer.get_policy()
        state = (policy.params, policy.opt_state, opt._env_state, opt._obs,
                 opt._rng)
        want_params, want_batches, want_stats = jax.tree.map(
            np.asarray, _stack_then_em(opt)(*state))

        seen = []
        loss_fn = policy._loss_fn

        def spy(pol, params, batch, rng, loss_state):
            """The loss, handing its batch out (one device: a mesh would
            hand out shards) and saying, over a mesh too, how many rows'
            frame is the one their reward was paid for."""
            if devices == 1:
                jax.debug.callback(lambda b: seen.append(b), batch)
            loss, stats = loss_fn(pol, params, batch, rng, loss_state)
            if frames:
                stats = dict(stats, rows_with_their_frame=_frames_match(
                    batch, T))
            return loss, stats

        policy._loss_fn = spy
        opt._anakin_fn = opt._build_fn()
        args = state + (opt._ep_rew, opt._ep_len, opt._pstate)
        if devices > 1:
            text = opt._anakin_fn.lower(*args).compile().as_text()
            for moved in ("all-gather", "all-to-all", "collective-permute"):
                assert moved not in text, moved
            assert "all-reduce" in text  # the gradients', the only one
        got_params, *_, got_stats = opt._anakin_fn(*args)
        jax.block_until_ready(got_params)

        for i, batch in enumerate(seen):
            view = batch.pop(sb.OBS_TIME_MAJOR, None)
            assert (view is not None) == in_place
            assert set(batch) == set(want_batches)
            for key, column in batch.items():
                column, want = np.asarray(column), want_batches[key][i]
                assert column.dtype == want.dtype, key
                if key in exact:
                    np.testing.assert_array_equal(column, want, err_msg=key)
                else:
                    off = ~np.isclose(column, want, atol=1e-4)
                    assert off.mean() <= 0.02, (key, off.mean())
            if in_place:
                # [1, T, N, ..] here: row n * T + t of OBS at [0, t, n].
                want = want_batches[sb.OBS][i].reshape(
                    (N, T) + view.shape[3:]).swapaxes(0, 1)[None]
                np.testing.assert_array_equal(np.asarray(view), want)
        assert len(seen) == (2 if devices == 1 else 0)
        if frames:
            assert want_batches[sb.OBS].dtype == np.uint8

        # The model saw the same rows in another order and summed their
        # gradients in that order: equal up to float32's rounding, which
        # the optimizer's normalised step (about `lr` an element and
        # update) passes on where a gradient is near zero: a few elements
        # in a hundred may move the other way (over a mesh, where the
        # bfloat16 trunk is tiled by another batch), none by more than two
        # updates can.
        for key, want in want_stats.items():
            np.testing.assert_allclose(
                float(got_stats[key]), want, rtol=1e-3, err_msg=key)
        if frames:
            assert float(got_stats["rows_with_their_frame"]) == 1.0
        lr = trainer.config["lr"]
        for got, want in zip(jax.tree.leaves(got_params),
                             jax.tree.leaves(want_params)):
            off = np.abs(np.asarray(got) - want)
            assert off.max() <= 2.5 * lr and (off > 0.25 * lr).mean() <= 0.05
    finally:
        trainer.stop()


# ---------------------------------------------------------------------
# JaxEnv parity
# ---------------------------------------------------------------------
class TestJaxEnvs:
    def test_jax_cartpole_matches_host_dynamics(self):
        import jax
        from ray_tpu.rllib.env.jax_env import JaxCartPole
        env = JaxCartPole()
        host = CartPole()
        host.seed(0)
        host.reset()
        state0 = np.array([0.01, -0.02, 0.03, 0.04], np.float32)
        host._state = state0.copy().astype(np.float64)
        host._t = 0
        jstate = {"s": state0, "t": np.int32(0)}
        rng = jax.random.PRNGKey(0)
        for action in [1, 0, 1, 1]:
            obs_h, r_h, d_h, _ = host.step(action)
            jstate, obs_j, r_j, d_j = env.step(jstate, action, rng)
            np.testing.assert_allclose(np.asarray(obs_j), obs_h, rtol=1e-5)
            assert float(r_j) == r_h and bool(d_j) == d_h

    def test_jax_synthetic_atari_contract(self):
        import jax
        from ray_tpu.rllib.env.jax_env import JaxSyntheticAtari
        env = JaxSyntheticAtari(episode_len=3)
        state, obs = env.reset(jax.random.PRNGKey(0))
        obs = np.asarray(obs)
        assert obs.shape == (84, 84, 4) and obs.dtype == np.uint8
        # Correct action is rewarded.
        state2, _, r, d = env.step(state, int(state["target"]),
                                   jax.random.PRNGKey(1))
        assert float(r) == 1.0 and not bool(d)
        # Episode terminates after episode_len steps.
        s = state
        for k in range(3):
            s, _, _, d = env.step(s, 0, jax.random.PRNGKey(k + 2))
        assert bool(d)
