"""Tests for the round-6 Sebulba pipeline gears (ISSUE 6):

- Double-buffered env groups (`sebulba_env_groups`): lag-0 equivalence —
  the grouped sampler's trajectories are byte-identical to the serial
  sampler's under fixed seeds and deterministic actions.
- k-step on-device action selection (`sebulba_onchip_steps`): lag-k
  correctness — the behavior logits stored in the SampleBatch are the
  ones that actually selected each action (V-trace sees true ratios),
  the recorded observations are the TRUE per-step observations, and the
  POLICY_LAG column records each transition's selection lag.
- Tier-1 smoke: the transfer-accounting dict carries the lag fields and
  per-actor action-fetch time never exceeds wall-clock, so the
  accounting can't silently rot.
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.env.batched_env import BatchedCartPole
from ray_tpu.rllib.evaluation.device_sampler import DeviceSebulbaSampler


@pytest.fixture
def ray_session():
    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()


def _make_policy(env, seed=0):
    from ray_tpu.rllib.agents.pg.pg import DEFAULT_CONFIG, PGJaxPolicy
    cfg = dict(DEFAULT_CONFIG)
    cfg.update({"model": {"fcnet_hiddens": [8],
                          "conv_filters": ((4, 2, 1),)},
                "seed": seed})
    return PGJaxPolicy(env.observation_space, env.action_space, cfg)


class _FixedCartPole(BatchedCartPole):
    """CartPole whose row i always resets to a caller-given state —
    fully deterministic dynamics for byte-identity comparisons (resets
    included: serial row i and its group-split twin reset identically).
    """

    def __init__(self, states, max_steps: int = 200):
        states = np.asarray(states, np.float64)
        super().__init__(len(states), max_steps=max_steps, seed=0)
        self._init = states

    def _reset_rows(self, mask):
        self._state[mask] = self._init[mask]
        self._t[mask] = 0


class _CountingFrameEnv:
    """BatchedEnv emitting [N, 4, 4, 1] uint8 frames whose value is the
    global step counter — the recorded OBS column can be checked against
    ground truth exactly."""

    def __init__(self, num_envs, episode_len=1000):
        from ray_tpu.rllib.env.spaces import Box, Discrete
        self.num_envs = num_envs
        self.episode_len = episode_len
        self.observation_space = Box(0, 255, shape=(4, 4, 1),
                                     dtype=np.uint8)
        self.action_space = Discrete(3)
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


# ---------------------------------------------------------------------
# Lag-0 equivalence: groups are a pure pipelining change
# ---------------------------------------------------------------------
class TestGroupedByteIdentity:
    # Two rows that survive the fragment, two that tip over mid-fragment
    # (exercises per-row deterministic resets and eps-id reallocation).
    STATES = np.array([
        [0.01, -0.02, 0.03, 0.04],
        [-0.02, 0.01, -0.04, 0.02],
        [0.05, 0.9, 0.20, 1.5],
        [-0.05, -0.9, -0.20, -1.5],
    ])

    def _sample_rounds(self, sampler, rounds=3):
        cols = (sb.OBS, sb.ACTION_LOGP, sb.ACTION_DIST_INPUTS,
                sb.VF_PREDS, sb.BOOTSTRAP_OBS, sb.ACTIONS, sb.REWARDS,
                sb.DONES, sb.EPS_ID, sb.T, sb.POLICY_LAG)
        out = []
        for _ in range(rounds):
            b = sampler.sample()
            out.append({k: np.asarray(b[k]) for k in cols})
        return out

    def test_groups2_byte_identical_to_serial(self):
        env_serial = _FixedCartPole(self.STATES)
        policy = _make_policy(env_serial)
        serial = DeviceSebulbaSampler(
            env_serial, policy, rollout_fragment_length=10,
            explore=False)
        grouped = DeviceSebulbaSampler(
            [_FixedCartPole(self.STATES[:2]),
             _FixedCartPole(self.STATES[2:])],
            policy, rollout_fragment_length=10, explore=False)
        assert len(grouped.groups) == 2
        for r, (bs, bg) in enumerate(zip(self._sample_rounds(serial),
                                         self._sample_rounds(grouped))):
            for col in bs:
                # Integer and boolean columns (actions, dones, eps ids,
                # t, lag) are exact. Float columns agree to rounding: a
                # 2-row and a 4-row forward of the same rows differ in
                # the last bits (2.3e-9 absolute on the CPU), which is
                # batch-size-dependent summation order, not the sampler.
                if bs[col].dtype.kind in "iub":
                    np.testing.assert_array_equal(
                        bs[col], bg[col],
                        err_msg=f"column {col} diverged at round {r}")
                else:
                    np.testing.assert_allclose(
                        bs[col], bg[col], rtol=1e-5, atol=0,
                        err_msg=f"column {col} diverged at round {r}")
                assert bs[col].dtype == bg[col].dtype, col
        # Both runs crossed episode boundaries (the comparison above
        # covered reset handling, not just steady-state stepping).
        assert sum(m.episode_length for m in serial.metrics) > 0

    def test_groups_require_equal_sizes(self):
        env_a = _FixedCartPole(self.STATES[:3])
        env_b = _FixedCartPole(self.STATES[3:])
        policy = _make_policy(env_a)
        with pytest.raises(ValueError, match="same number of env slots"):
            DeviceSebulbaSampler([env_a, env_b], policy,
                                 rollout_fragment_length=5)


# ---------------------------------------------------------------------
# Lag-k correctness: V-trace must see the true behavior policy
# ---------------------------------------------------------------------
class TestOnChipSelection:
    def test_fragment_must_tile_windows(self):
        env = _CountingFrameEnv(2)
        policy = _make_policy(env)
        with pytest.raises(ValueError, match="multiple"):
            DeviceSebulbaSampler(env, policy, rollout_fragment_length=5,
                                 onchip_steps=2)

    def test_lagk_logits_obs_and_lag_column(self):
        import jax.numpy as jnp
        N, T, k = 3, 6, 2
        env = _CountingFrameEnv(N)
        policy = _make_policy(env)
        sampler = DeviceSebulbaSampler(
            env, policy, rollout_fragment_length=T, explore=False,
            onchip_steps=k)
        batch = sampler.sample()
        obs = np.asarray(batch[sb.OBS]).reshape(N, T, 4, 4, 1)
        di = np.asarray(batch[sb.ACTION_DIST_INPUTS]).reshape(N, T, -1)
        logp = np.asarray(batch[sb.ACTION_LOGP]).reshape(N, T)
        vf = np.asarray(batch[sb.VF_PREDS]).reshape(N, T)
        acts = np.asarray(batch[sb.ACTIONS]).reshape(N, T)
        lag = np.asarray(batch[sb.POLICY_LAG]).reshape(N, T)

        # The lag column records each transition's selection staleness.
        np.testing.assert_array_equal(
            lag, np.tile(np.arange(T) % k, (N, 1)))

        # Recorded observations are the TRUE per-step observations
        # (counting env: frame value at step t is t), even though
        # actions were selected from the window-head obs.
        for t in range(T):
            np.testing.assert_array_equal(
                obs[:, t], np.full((N, 4, 4, 1), t, np.uint8))

        for w in range(T // k):
            head = w * k
            # Behavior logits/value are shared across the window — they
            # are the distribution that ACTUALLY selected every action
            # of the window (computed at the window-head obs).
            for j in range(1, k):
                np.testing.assert_array_equal(di[:, head + j],
                                              di[:, head])
                np.testing.assert_array_equal(vf[:, head + j],
                                              vf[:, head])
            # ... and they match a fresh forward at the head obs.
            want_di, want_vf = policy.apply(
                policy.params, jnp.asarray(obs[:, head]))
            np.testing.assert_allclose(di[:, head], np.asarray(want_di),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(vf[:, head], np.asarray(want_vf),
                                       rtol=1e-5, atol=1e-6)
            # Deterministic selection: every sub-step takes the head
            # distribution's argmax.
            np.testing.assert_array_equal(
                acts[:, head:head + k],
                np.tile(np.argmax(di[:, head], axis=-1)[:, None],
                        (1, k)))
            # Stored logp is the behavior logp of the stored action
            # under the stored behavior logits: exp-normalized check.
            for j in range(k):
                z = di[:, head + j]
                ref = (z[np.arange(N), acts[:, head + j]]
                       - np.log(np.exp(z).sum(-1)))
                np.testing.assert_allclose(logp[:, head + j], ref,
                                           rtol=1e-4, atol=1e-5)

        # One blocking fetch per window, not per step.
        st = sampler.transfer_stats()
        assert st["fetch_waits"] == T // k
        assert st["policy_lag_sum"] == int(
            (np.arange(T) % k).sum()) * N

    def test_onchip_composes_with_groups_delta_and_stack(self):
        """The full gauntlet: delta env + device frame stack + 2 groups
        + k=2 windows still reconstructs true observations."""
        from ray_tpu.rllib.env.delta_obs import BatchedSpriteAtari
        from ray_tpu.rllib.env.device_frame_stack import DeviceFrameStack
        N_PER, T, k = 2, 6, 2
        mk = lambda seed: DeviceFrameStack(
            BatchedSpriteAtari(N_PER, episode_len=8, seed=seed), 4)
        env_a, env_b = mk(3), mk(5)
        policy = _make_policy(env_a)
        sampler = DeviceSebulbaSampler(
            [env_a, env_b], policy, rollout_fragment_length=T,
            explore=False, onchip_steps=k)
        assert sampler.delta and len(sampler.groups) == 2
        batch = sampler.sample()
        # After T env steps the envs' canonical frames are the
        # POST-fragment observation — the bootstrap rows. Their newest
        # stacked channel must be the device-reconstructed frame.
        boot = np.asarray(batch[sb.BOOTSTRAP_OBS])
        canon = np.concatenate(
            [env_a.inner._frames[:, :-1], env_b.inner._frames[:, :-1]])
        np.testing.assert_array_equal(
            boot[:, :, :, -1].reshape(2 * N_PER, -1), canon)
        assert batch.count == 2 * N_PER * T


# ---------------------------------------------------------------------
# Tier-1 smoke: accounting + config plumbing through the trainer
# ---------------------------------------------------------------------
class TestPipelineSmoke:
    def test_trainer_rejects_untiled_onchip_steps(self, ray_session):
        from ray_tpu.rllib.agents.registry import get_trainer_class
        with pytest.raises(ValueError, match="sebulba_onchip_steps"):
            get_trainer_class("IMPALA")(config={
                "env": "CartPole-v0",
                "num_workers": 0,
                "num_inline_actors": 1,
                "num_envs_per_worker": 4,
                "rollout_fragment_length": 5,
                "train_batch_size": 20,
                "sebulba_onchip_steps": 2,
                "min_iter_time_s": 0,
            })

    def test_sebulba_smoke_accounting_and_gauges(self, ray_session):
        """2 windows on the CPU backend: the accounting dict carries the
        lag fields, per-actor action-fetch never exceeds wall-clock, and
        the pipeline gauges reach the metrics plane."""
        from ray_tpu._private import metrics as metrics_mod
        from ray_tpu.rllib.agents.registry import get_trainer_class
        # Earlier trainers in this process leave their aK gauges behind
        # (the registry is process-global); start from a clean slate so
        # the wait loop below observes THIS trainer's publish, not a
        # stale k=1 lag of 0.
        metrics_mod.reset()
        t0 = time.perf_counter()
        t = get_trainer_class("IMPALA")(config={
            "env": "SpriteAtari-v0",
            "env_config": {"episode_len": 30},
            "num_workers": 0,
            "num_inline_actors": 1,
            "num_envs_per_worker": 4,
            "rollout_fragment_length": 10,
            "train_batch_size": 40,
            "device_frame_stack": 4,
            "sebulba_env_groups": 2,
            "sebulba_onchip_steps": 5,
            "min_iter_time_s": 0,
            "seed": 0,
        })
        opt = t.optimizer
        sampler = opt._inline_actors[0].sampler
        assert len(sampler.groups) == 2 and sampler.k == 5
        deadline = time.monotonic() + 60
        gauges = {}
        while time.monotonic() < deadline:
            t.train()
            gauges = metrics_mod.snapshot()["gauges"]
            if "sebulba_action_fetch_pct.a0" in gauges:
                break
        assert "sebulba_action_fetch_pct.a0" in gauges
        assert "sebulba_env_step_pct.a0" in gauges
        assert "sebulba_policy_lag_steps.a0" in gauges
        # Mean selection lag of k=5 windows is (k-1)/2 = 2.
        assert abs(gauges["sebulba_policy_lag_steps.a0"] - 2.0) < 1e-6

        stats = opt.stats()
        transfer = stats["transfer"]
        for field in ("policy_lag_sum", "fetch_waits", "t_fetch_s",
                      "t_env_s", "steps"):
            assert field in transfer, field
        assert transfer["policy_lag_sum"] > 0
        # Accounting sanity: a single actor thread cannot spend more
        # time blocked on fetches (or stepping envs) than wall-clock.
        elapsed = time.perf_counter() - t0
        st = sampler.transfer_stats()
        assert st["t_fetch_s"] <= elapsed
        assert st["t_env_s"] <= elapsed
        # Mean recorded lag is bounded by the configured gear ((k-1)/2;
        # `steps` may include a fragment still in flight on the actor
        # thread, so the ratio can undershoot but never overshoot).
        assert 0 < st["policy_lag_sum"] / st["steps"] <= 2.0
        t.stop()
