"""Single-agent environment API + built-in environments.

The reference uses OpenAI gym environments (CartPole-v0, Pendulum-v0, Atari)
throughout its tuned examples and tests. gym is not available here, so the
classic-control environments are implemented natively with the same
dynamics, observation/action spaces, and episode-termination rules, plus a
synthetic Atari-shaped environment for throughput benchmarking.

API: `reset() -> obs`, `step(action) -> (obs, reward, done, info)` —
the same contract RLlib's samplers expect.
"""

from __future__ import annotations

import numpy as np

from .spaces import Box, Discrete


class Env:
    observation_space = None
    action_space = None

    def reset(self):
        raise NotImplementedError

    def step(self, action):
        raise NotImplementedError

    def seed(self, seed=None):
        self._rng = np.random.default_rng(seed)

    def close(self):
        pass


def init_cartpole_constants(obj, max_steps: int):
    """Shared CartPole parameters + spaces (Barto-Sutton-Anderson '83,
    gym CartPole-v0 values). One definition serves the single-env,
    batched-numpy, and (by numeric parity test) JAX implementations."""
    obj.gravity = 9.8
    obj.masscart, obj.masspole = 1.0, 0.1
    obj.total_mass = obj.masscart + obj.masspole
    obj.length = 0.5  # half pole length
    obj.polemass_length = obj.masspole * obj.length
    obj.force_mag = 10.0
    obj.tau = 0.02
    obj.theta_threshold = 12 * 2 * np.pi / 360
    obj.x_threshold = 2.4
    obj.max_steps = max_steps
    high = np.array([obj.x_threshold * 2, np.finfo(np.float32).max,
                     obj.theta_threshold * 2, np.finfo(np.float32).max],
                    dtype=np.float32)
    obj.observation_space = Box(-high, high)
    obj.action_space = Discrete(2)


def cartpole_step(p, state: np.ndarray, actions) -> tuple:
    """Euler-integrate one step for a [N, 4] state batch. Returns
    (new_state [N, 4], threshold_violation [N] bool). `p` carries the
    constants from `init_cartpole_constants`."""
    x, x_dot, theta, theta_dot = state.T
    force = np.where(np.asarray(actions) == 1, p.force_mag, -p.force_mag)
    costheta, sintheta = np.cos(theta), np.sin(theta)
    temp = (force + p.polemass_length * theta_dot ** 2 * sintheta) \
        / p.total_mass
    thetaacc = (p.gravity * sintheta - costheta * temp) / (
        p.length * (4.0 / 3.0
                    - p.masspole * costheta ** 2 / p.total_mass))
    xacc = temp - p.polemass_length * thetaacc * costheta / p.total_mass
    x = x + p.tau * x_dot
    x_dot = x_dot + p.tau * xacc
    theta = theta + p.tau * theta_dot
    theta_dot = theta_dot + p.tau * thetaacc
    new_state = np.stack([x, x_dot, theta, theta_dot], axis=1)
    violation = (np.abs(x) > p.x_threshold) \
        | (np.abs(theta) > p.theta_threshold)
    return new_state, violation


class CartPole(Env):
    """Cart-pole balancing (200-step limit, +1 reward per step, terminate
    at |x|>2.4 or |theta|>12deg); dynamics shared with BatchedCartPole."""

    def __init__(self, max_steps: int = 200):
        init_cartpole_constants(self, max_steps)
        self._rng = np.random.default_rng()
        self._state = None
        self._t = 0

    def reset(self):
        self._state = self._rng.uniform(-0.05, 0.05, size=4)
        self._t = 0
        return self._state.astype(np.float32)

    def step(self, action):
        new_state, violation = cartpole_step(
            self, self._state[None, :], np.array([action]))
        self._state = new_state[0]
        self._t += 1
        done = bool(violation[0]) or self._t >= self.max_steps
        return self._state.astype(np.float32), 1.0, done, {}


class Pendulum(Env):
    """Torque-controlled pendulum swing-up (matching gym Pendulum-v0:
    200-step episodes, continuous action in [-2, 2])."""

    def __init__(self, max_steps: int = 200):
        self.max_speed = 8.0
        self.max_torque = 2.0
        self.dt = 0.05
        self.g, self.m, self.l = 10.0, 1.0, 1.0
        self.max_steps = max_steps
        high = np.array([1.0, 1.0, self.max_speed], dtype=np.float32)
        self.observation_space = Box(-high, high)
        self.action_space = Box(-self.max_torque, self.max_torque, shape=(1,))
        self._rng = np.random.default_rng()

    def reset(self):
        self._theta = self._rng.uniform(-np.pi, np.pi)
        self._thetadot = self._rng.uniform(-1.0, 1.0)
        self._t = 0
        return self._obs()

    def _obs(self):
        return np.array([np.cos(self._theta), np.sin(self._theta),
                         self._thetadot], dtype=np.float32)

    def step(self, action):
        u = float(np.clip(np.asarray(action).reshape(-1)[0],
                          -self.max_torque, self.max_torque))
        th, thdot = self._theta, self._thetadot
        norm_th = ((th + np.pi) % (2 * np.pi)) - np.pi
        cost = norm_th ** 2 + 0.1 * thdot ** 2 + 0.001 * u ** 2
        thdot = thdot + (3 * self.g / (2 * self.l) * np.sin(th)
                         + 3.0 / (self.m * self.l ** 2) * u) * self.dt
        thdot = np.clip(thdot, -self.max_speed, self.max_speed)
        th = th + thdot * self.dt
        self._theta, self._thetadot = th, thdot
        self._t += 1
        return self._obs(), -float(cost), self._t >= self.max_steps, {}


class SyntheticAtari(Env):
    """Atari-shaped throughput environment: 84x84x4 uint8 frames, 6 actions.

    Stands in for ALE (not available in this image) when measuring
    sampler/learner throughput at the reference's Atari configuration
    (reference preprocessing: `rllib/env/atari_wrappers.py` produces
    84x84x4 stacked frames). Observations carry a learnable signal (frame
    intensity encodes the best action) so policies must do real work.
    """

    def __init__(self, episode_len: int = 1000, num_actions: int = 6,
                 channels: int = 4):
        self.observation_space = Box(0, 255, shape=(84, 84, channels),
                                     dtype=np.uint8)
        self.action_space = Discrete(num_actions)
        self.episode_len = episode_len
        self.num_actions = num_actions
        self.channels = channels
        self._rng = np.random.default_rng()

    def reset(self):
        self._t = 0
        self._target = int(self._rng.integers(self.num_actions))
        return self._frame()

    def _frame(self):
        frame = self._rng.integers(
            0, 64, size=(84, 84, self.channels), dtype=np.uint8)
        # Embed the target action as a bright band.
        band = 84 // self.num_actions
        frame[self._target * band:(self._target + 1) * band, :, :] += 128
        return frame

    def step(self, action):
        reward = 1.0 if int(action) == self._target else 0.0
        self._t += 1
        self._target = int(self._rng.integers(self.num_actions))
        return self._frame(), reward, self._t >= self.episode_len, {}


def init_token_bigram(obj, vocab_size: int, episode_len: int, seed: int):
    """Shared TokenBigram parameters + spaces (host and JAX variants): the
    seeded rule `(a * token + b) mod vocab_size`, a scalar int32 token
    observation, one action a token of the vocabulary."""
    obj.vocab_size = vocab_size
    obj.episode_len = episode_len
    rng = np.random.default_rng(seed)
    obj.a = int(rng.integers(1, vocab_size))
    obj.b = int(rng.integers(0, vocab_size))
    obj.observation_space = Box(0, vocab_size - 1, shape=(), dtype=np.int32)
    obj.action_space = Discrete(vocab_size)


class TokenBigram(Env):
    """Token env for language-model policies: the observation is the
    current token id (a scalar int32 Box: token ids are embedded by the
    policy, not one-hot encoded), the action the next token, and the next
    observation is the action taken. Reward 1 where the action is
    `(a * token + b) mod vocab_size` for the env's seeded `a`, `b`;
    an episode is `episode_len` tokens. `jax_env.py:JaxTokenBigram` is the
    same env on the device."""

    def __init__(self, vocab_size: int = 50304, episode_len: int = 1024,
                 seed: int = 0):
        init_token_bigram(self, vocab_size, episode_len, seed)
        self._rng = np.random.default_rng()

    def reset(self):
        self._t = 0
        self._token = int(self._rng.integers(self.vocab_size))
        return np.int32(self._token)

    def step(self, action):
        target = (self.a * self._token + self.b) % self.vocab_size
        reward = 1.0 if int(action) == target else 0.0
        self._t += 1
        self._token = int(action)
        return np.int32(self._token), reward, self._t >= self.episode_len, {}


class RepeatInitialObs(Env):
    """Cue-recall memory task (parity: the reference's
    `RepeatInitialObsEnv` LSTM example env): a one-hot cue appears only at
    t=0; the agent is rewarded for emitting the cue's index at every
    step. Feedforward policies are capped at chance (1/num_cues); any
    working recurrent policy solves it quickly — a sharp regression test
    for state threading + BPTT."""

    def __init__(self, num_cues: int = 3, episode_len: int = 6):
        self.num_cues = num_cues
        self.episode_len = episode_len
        self.observation_space = Box(
            0.0, 1.0, shape=(num_cues,))
        self.action_space = Discrete(num_cues)
        self._rng = np.random.default_rng()

    def reset(self):
        self._cue = int(self._rng.integers(self.num_cues))
        self._t = 0
        obs = np.zeros(self.num_cues, np.float32)
        obs[self._cue] = 1.0
        return obs

    def step(self, action):
        self._t += 1
        reward = 1.0 if int(action) == self._cue else 0.0
        return (np.zeros(self.num_cues, np.float32), reward,
                self._t >= self.episode_len, {})


class StatelessCartPole(CartPole):
    """CartPole with velocity components hidden — requires memory (used to
    exercise recurrent policies, parity: RLlib's stateless cartpole
    example)."""

    def __init__(self, max_steps: int = 200):
        super().__init__(max_steps)
        high = np.array([self.x_threshold * 2, self.theta_threshold * 2],
                        dtype=np.float32)
        self.observation_space = Box(-high, high)

    def _mask(self, obs):
        return obs[[0, 2]]

    def reset(self):
        return self._mask(super().reset())

    def step(self, action):
        obs, r, d, i = super().step(action)
        return self._mask(obs), r, d, i
