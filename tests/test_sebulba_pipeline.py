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
- Compiled programs only (ISSUE 26): the fragment's one `pack` program
  is byte-identical to the numpy expression the host columns use;
  `select_fn` derives the key `_next_rng()` would have drawn from
  `(base, counter)`; counters taken by concurrent threads never collide.
"""

import sys
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import profiling
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
# Compiled programs only: the pack, the key, the counter
# ---------------------------------------------------------------------
def _sprite_policy(env, seed=0, mesh=None):
    from ray_tpu.rllib.agents.pg.pg import DEFAULT_CONFIG, PGJaxPolicy
    cfg = dict(DEFAULT_CONFIG)
    cfg.update({"model": {"fcnet_hiddens": [8],
                          "conv_filters": ((4, 8, 4), (8, 4, 2))},
                "seed": seed})
    if mesh is not None:
        cfg["_mesh"] = mesh
    return PGJaxPolicy(env.observation_space, env.action_space, cfg)


def _sprite_envs(groups: int, stack: int, n: int = 2):
    """Episodes shorter than a fragment, so reset rows are packed too."""
    from ray_tpu.rllib.env.delta_obs import BatchedSpriteAtari
    from ray_tpu.rllib.env.device_frame_stack import DeviceFrameStack
    envs = [BatchedSpriteAtari(n, episode_len=3, seed=7 + g)
            for g in range(groups)]
    return [DeviceFrameStack(e, stack) for e in envs] if stack else envs


def _numpy_pack(gbufs):
    """The expression `sample()` uses for its host columns: per group
    `np.stack` of T per-step arrays [n, ...] -> env-major [n*T, ...]
    rows, groups concatenated in order."""
    parts = []
    for bufs in gbufs:
        a = np.stack(bufs)
        parts.append(np.swapaxes(a, 0, 1).reshape(
            (a.shape[0] * a.shape[1],) + a.shape[2:]))
    return parts[0] if len(parts) == 1 else np.concatenate(parts, axis=0)


class TestCompiledPack:
    @pytest.mark.parametrize("stack", [0, 4], ids=["stack0", "stack4"])
    @pytest.mark.parametrize("delta", [False, True],
                             ids=["frames", "delta"])
    @pytest.mark.parametrize("k", [1, 2], ids=["k1", "k2"])
    @pytest.mark.parametrize("groups", [1, 2], ids=["G1", "G2"])
    def test_pack_is_byte_identical_to_numpy(self, groups, k, delta,
                                             stack):
        self._check(groups, k, delta, stack, mesh=None)

    def test_pack_over_a_mesh_is_byte_identical_to_numpy(self):
        """Two devices: a group's rows are sharded over the mesh going
        in, the fragment's rows (group 0 first) coming out."""
        from ray_tpu.parallel import mesh as mesh_lib
        self._check(2, 2, True, 4, mesh=mesh_lib.make_mesh(2))

    def _check(self, groups, k, delta, stack, mesh):
        T = 4
        envs = _sprite_envs(groups, stack)
        sampler = DeviceSebulbaSampler(
            envs, _sprite_policy(envs[0], mesh=mesh),
            rollout_fragment_length=T, use_delta=delta, onchip_steps=k)
        assert sampler.delta == delta and sampler.frame_stack == stack
        taken = []
        pack_fn = sampler._pack_fn
        sampler._pack_fn = lambda *a: taken.append(a) or pack_fn(*a)
        batch = sampler.sample()
        (obs, logp, di, val, boot), = taken
        host = lambda handles: [np.asarray(h) for h in handles]
        # Per-step lists, as the parent's appends built them: step t of
        # window w has logp[w][t % k] and the window's one di / value.
        want = {
            sb.OBS: _numpy_pack([host(g) for g in obs]),
            sb.ACTION_LOGP: _numpy_pack(
                [[w[j] for w in host(g) for j in range(k)] for g in logp]),
            sb.ACTION_DIST_INPUTS: _numpy_pack(
                [[w for w in host(g) for _ in range(k)] for g in di]),
            sb.VF_PREDS: _numpy_pack(
                [[w for w in host(g) for _ in range(k)] for g in val]),
            sb.BOOTSTRAP_OBS: np.concatenate(host(boot), axis=0),
        }
        n = envs[0].num_envs
        assert want[sb.OBS].shape == (
            groups * n * T,) + envs[0].observation_space.shape
        for col, ref in want.items():
            got = np.asarray(batch[col])
            assert got.dtype == ref.dtype and got.shape == ref.shape, col
            assert got.tobytes() == ref.tobytes(), col
            assert batch[col].sharding == sampler.policy._bsharded, col
        if mesh is not None:
            assert len(batch[sb.OBS].sharding.device_set) == mesh.size


class TestKeyFromCounter:
    @pytest.mark.parametrize("k", [1, 2], ids=["k1", "k2"])
    def test_select_draws_the_key_next_rng_would(self, k):
        """`select_fn(.., base, counter, explore=True)` against the
        policy's own action program given `fold_in(base, counter)`, the
        key the parent passed in: same actions, logp, dist inputs and
        value, bit for bit."""
        import jax
        envs = _sprite_envs(1, 0)
        policy = _sprite_policy(envs[0], seed=3)
        sampler = DeviceSebulbaSampler(
            envs, policy, rollout_fragment_length=2 * k, explore=True,
            onchip_steps=k)
        obs = sampler.groups[0].obs_next
        base = policy._host_rng
        seen = set()
        for counter in (5, 6, 2 ** 31 + 11):
            acts, logp, di, val = sampler._select_fn(
                policy.params, obs, base, np.uint32(counter), True)
            key = jax.random.fold_in(base, counter)
            keys = [key] if k == 1 else list(jax.random.split(key, k))
            assert acts.shape == (k, envs[0].num_envs)
            for j, kj in enumerate(keys):
                a, lp, d, v = policy._action_fn(
                    policy.params, obs, kj, True)
                for got, ref in ((acts[j], a), (logp[j], lp), (di, d),
                                 (val, v)):
                    got, ref = np.asarray(got), np.asarray(ref)
                    assert got.dtype == ref.dtype
                    assert got.tobytes() == ref.tobytes()
            seen.add(np.asarray(acts).tobytes())
        assert len(seen) > 1, "every counter sampled the same actions"

    def test_next_rng_folds_the_same_counter(self):
        import jax
        policy = _sprite_policy(_sprite_envs(1, 0)[0])
        at = int(policy._next_rng_counter())
        np.testing.assert_array_equal(
            np.asarray(policy._next_rng()),
            np.asarray(jax.random.fold_in(policy._host_rng, at + 1)))
        assert int(policy._next_rng_counter()) == at + 2

    def test_concurrent_counters_are_distinct_and_gap_free(self):
        policy = _sprite_policy(_sprite_envs(1, 0)[0])
        first = int(policy._next_rng_counter()) + 1
        threads, each = 4, 5000
        got = [[] for _ in range(threads)]
        start = threading.Barrier(threads)

        def take(out):
            start.wait(timeout=60)
            for _ in range(each):
                out.append(int(policy._next_rng_counter()))

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            workers = [threading.Thread(target=take, args=(g,), daemon=True)
                       for g in got]
            for w in workers:
                w.start()
            for w in workers:
                w.join(60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(w.is_alive() for w in workers)
        assert all(len(g) == each for g in got)
        assert sorted(c for g in got for c in g) == list(
            range(first, first + threads * each))


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
        # The phases read their CPU while somebody asks, as a capture does.
        with profiling.phase_cpu_reads():
            while time.monotonic() < deadline:
                t.train()
                gauges = metrics_mod.snapshot()["gauges"]
                if "sebulba_action_fetch_pct.a0" in gauges \
                        and "sebulba_gil_wait_pct.a0" in gauges:
                    break
        assert "sebulba_action_fetch_pct.a0" in gauges
        assert "sebulba_env_step_pct.a0" in gauges
        assert "sebulba_policy_lag_steps.a0" in gauges
        # Mean selection lag of k=5 windows is (k-1)/2 = 2.
        assert abs(gauges["sebulba_policy_lag_steps.a0"] - 2.0) < 1e-6
        # Wall less CPU of the env-step and record phases, over the
        # actor's wall: a share of it (a CPU clock may pass the wall's by
        # what reading them costs).
        assert -1.0 <= gauges["sebulba_gil_wait_pct.a0"] <= 100.0

        # Every Python thread of the inline path has a clock, and the
        # optimizer hands them out under one handle.
        account = opt.host_account()
        assert set(account["threads"]) == {"inline-actor-0", "learner",
                                           "driver"}
        assert account["threads"]["driver"]["counts"]["driver.collect"] > 0
        for snap in account["threads"].values():
            assert snap["cpu_s"] > 0 and set(snap["cpu_seconds"]) == set(
                snap["seconds"])
        assert account["process"]["cpu_s"] >= sum(
            snap["cpu_s"] for snap in account["threads"].values())

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

    def test_the_frames_feed_trains_over_a_mesh_of_four(self, ray_session):
        """`impala_sebulba_frames_x4` at a tiny size: inline actor threads
        shard full-frame uploads and fragments over a `dp` mesh of four
        devices while the learner's update all-reduces on it; params and
        batch are reported on all four, and the learner's clock is
        partitioned by the four phases its metrics read."""
        import math
        from ray_tpu.rllib.agents.registry import get_trainer_class
        t = get_trainer_class("IMPALA")(config={
            "env": "SyntheticAtariFrames-v0",
            "num_workers": 0,
            "num_inline_actors": 2,
            "num_envs_per_worker": 8,
            "device_frame_stack": 4,
            "obs_delta": False,
            "rollout_fragment_length": 4,
            "train_batch_size": 32,
            "learner_queue_size": 2,
            "num_tpus_for_learner": 4,
            "min_iter_time_s": 0,
            "seed": 0,
        })
        try:
            opt = t.optimizer
            deadline = time.monotonic() + 90
            result = {}
            while opt.num_steps_trained < 3 * 32:
                assert time.monotonic() < deadline, "trained too few steps"
                result = t.train()
            assert result["device"]["count"] == 4
            assert result["device"]["params_on"] == 4
            assert result["device"]["batch_on"] == 4
            assert math.isfinite(result["info"]["learner"]["total_loss"])
            learner = opt.learner.clock.snapshot()
            for name in ("learner.dequeue", "learner.h2d",
                         "learner.lock_wait", "learner.train",
                         "learner.readback"):
                assert learner["counts"].get(name, 0) >= 3, name
            sampler = opt._inline_actors[0].sampler
            assert len(sampler.policy._bsharded.device_set) == 4
            phases = sampler.transfer_stats()["phases"]["counts"]
            assert phases["sebulba.upload"] > 0 and phases["sebulba.select"] > 0
        finally:
            t.stop()
        # The learner's queue timer (`learner_wait_pct`,
        # `learner_queue_wait_ms`) is a view of the stopped thread's clock.
        learner = opt.learner.clock.snapshot()
        timer = opt.learner.queue_timer
        assert timer.total == learner["seconds"]["learner.dequeue"]
        assert timer.count == learner["counts"]["learner.dequeue"]
        assert opt.stats()["timing"]["learner_queue_wait_ms"] == round(
            1000 * timer.total / timer.count, 3)
