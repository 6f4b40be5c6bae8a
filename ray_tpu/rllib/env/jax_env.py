"""Device-resident (JAX) environments: the Anakin-side env API.

The reference's envs are host-side Python objects stepped one process at
a time — its throughput scaling knob is more CPU workers
(`rllib/env/base_env.py`, `doc/source/rllib-env.rst:114`). The TPU-native
framework adds a second env tier with no reference equivalent: envs
written as pure JAX functions run ON the accelerator, letting the rollout
loop, policy inference, and the learner update fuse into one XLA program
(the Podracer "Anakin" architecture; see
`optimizers/anakin_optimizer.py`). Observations never cross the
host↔device boundary — on hosts where that boundary is the bottleneck,
this is the difference between starving the chip and saturating it.

API (pure functions over explicit state, gymnax-style):
  - `reset(rng) -> (state, obs)` for ONE env; runners `vmap` it.
  - `step(state, action, rng) -> (state, obs, reward, done)` for ONE
    env, auto-resetting: when the episode ends the returned state/obs
    are the next episode's initial state/obs and done=True marks the
    boundary. All branches must be `lax.select`-style (traceable).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .spaces import Box, Discrete


class JaxEnv:
    """Base class: a pure-function env. Subclasses define `reset`/`step`
    as traceable functions of (state, action, rng)."""

    observation_space = None
    action_space = None

    def reset(self, rng):
        raise NotImplementedError

    def step(self, state, action, rng):
        raise NotImplementedError


class JaxSyntheticAtari(JaxEnv):
    """On-device SyntheticAtari (same dynamics as
    `env.py:SyntheticAtari`): 84x84x4 uint8 frames, `num_actions`
    actions, reward 1 when the action matches the target encoded as a
    bright horizontal band, target re-randomized every step, fixed
    episode length."""

    def __init__(self, episode_len: int = 1000, num_actions: int = 6):
        self.episode_len = episode_len
        self.num_actions = num_actions
        self.observation_space = Box(0, 255, shape=(84, 84, 4),
                                     dtype=np.uint8)
        self.action_space = Discrete(num_actions)
        self._band = 84 // num_actions

    def _obs(self, target, rng):
        noise = jax.random.randint(rng, (84, 84, 4), 0, 64, jnp.uint8)
        rows = jnp.arange(84)[:, None, None]
        band = ((rows >= target * self._band)
                & (rows < (target + 1) * self._band))
        return noise + band.astype(jnp.uint8) * 128

    def reset(self, rng):
        tkey, okey = jax.random.split(rng)
        target = jax.random.randint(tkey, (), 0, self.num_actions)
        state = {"t": jnp.zeros((), jnp.int32), "target": target}
        return state, self._obs(target, okey)

    def step(self, state, action, rng):
        tkey, okey = jax.random.split(rng)
        reward = (action == state["target"]).astype(jnp.float32)
        t = state["t"] + 1
        done = t >= self.episode_len
        t = jnp.where(done, 0, t)
        target = jax.random.randint(tkey, (), 0, self.num_actions)
        state = {"t": t, "target": target}
        return state, self._obs(target, okey), reward, done


class JaxCartPole(JaxEnv):
    """On-device CartPole with the same dynamics/termination as
    `env.py:CartPole` (gym CartPole-v0 semantics)."""

    def __init__(self, max_steps: int = 200):
        self.max_steps = max_steps
        self.gravity = 9.8
        self.masscart, self.masspole = 1.0, 0.1
        self.total_mass = self.masscart + self.masspole
        self.length = 0.5
        self.polemass_length = self.masspole * self.length
        self.force_mag = 10.0
        self.tau = 0.02
        self.theta_threshold = 12 * 2 * np.pi / 360
        self.x_threshold = 2.4
        high = np.array([self.x_threshold * 2, np.finfo(np.float32).max,
                         self.theta_threshold * 2, np.finfo(np.float32).max],
                        dtype=np.float32)
        self.observation_space = Box(-high, high)
        self.action_space = Discrete(2)

    def reset(self, rng):
        s = jax.random.uniform(rng, (4,), jnp.float32, -0.05, 0.05)
        return {"s": s, "t": jnp.zeros((), jnp.int32)}, s

    def step(self, state, action, rng):
        x, x_dot, theta, theta_dot = state["s"]
        force = jnp.where(action == 1, self.force_mag, -self.force_mag)
        costheta, sintheta = jnp.cos(theta), jnp.sin(theta)
        temp = (force + self.polemass_length * theta_dot ** 2 * sintheta) \
            / self.total_mass
        thetaacc = (self.gravity * sintheta - costheta * temp) / (
            self.length * (4.0 / 3.0
                           - self.masspole * costheta ** 2 / self.total_mass))
        xacc = temp - self.polemass_length * thetaacc * costheta \
            / self.total_mass
        x = x + self.tau * x_dot
        x_dot = x_dot + self.tau * xacc
        theta = theta + self.tau * theta_dot
        theta_dot = theta_dot + self.tau * thetaacc
        t = state["t"] + 1
        done = ((jnp.abs(x) > self.x_threshold)
                | (jnp.abs(theta) > self.theta_threshold)
                | (t >= self.max_steps))
        s = jnp.stack([x, x_dot, theta, theta_dot]).astype(jnp.float32)
        # Auto-reset: done slots restart with a fresh initial state.
        s0 = jax.random.uniform(rng, (4,), jnp.float32, -0.05, 0.05)
        s = jnp.where(done, s0, s)
        t = jnp.where(done, 0, t)
        return {"s": s, "t": t}, s, jnp.float32(1.0), done


class JaxTokenBigram(JaxEnv):
    """On-device token env (same dynamics as `env.py:TokenBigram`): the
    observation is the current token id, the action the next token, and
    the next observation is the action taken. Reward 1 where the action is
    `(a * token + b) mod vocab_size` for the env's seeded `a`, `b`; an
    episode is `episode_len` tokens and restarts from a random first
    token. It costs next to nothing by design: what a token policy's
    rollout takes is the policy's."""

    def __init__(self, vocab_size: int = 50304, episode_len: int = 1024,
                 seed: int = 0):
        from .env import init_token_bigram
        init_token_bigram(self, vocab_size, episode_len, seed)

    def reset(self, rng):
        token = jax.random.randint(rng, (), 0, self.vocab_size, jnp.int32)
        return {"t": jnp.zeros((), jnp.int32), "token": token}, token

    def step(self, state, action, rng):
        token = state["token"]
        # (a * token + b) mod V without leaving int32: a * token can reach
        # 2.5e9 at a 50k vocabulary.
        target = (jnp.uint32(self.a) * token.astype(jnp.uint32)
                  + jnp.uint32(self.b)) % jnp.uint32(self.vocab_size)
        action = action.astype(jnp.int32)
        reward = (action == target.astype(jnp.int32)).astype(jnp.float32)
        t = state["t"] + 1
        done = t >= self.episode_len
        first = jax.random.randint(rng, (), 0, self.vocab_size, jnp.int32)
        token = jnp.where(done, first, action)
        return {"t": jnp.where(done, 0, t), "token": token}, token, \
            reward, done


# -- registry ------------------------------------------------------------
_JAX_REGISTRY = {}


def register_jax_env(name: str, creator) -> None:
    """Register `creator(env_config) -> JaxEnv`."""
    _JAX_REGISTRY[name] = creator


def make_jax_env(name: str, env_config: dict = None) -> JaxEnv:
    env_config = env_config or {}
    if name not in _JAX_REGISTRY:
        raise ValueError(
            f"no JAX (device-resident) env registered under {name!r}; "
            f"registered: {sorted(_JAX_REGISTRY)}. Anakin mode needs a "
            "JaxEnv — host envs can only run in the Sebulba "
            "(inline-actor) or remote-worker paths.")
    return _JAX_REGISTRY[name](env_config)


def has_jax_env(name) -> bool:
    return isinstance(name, str) and name in _JAX_REGISTRY


register_jax_env("SyntheticAtari-v0",
                 lambda cfg: JaxSyntheticAtari(
                     episode_len=cfg.get("episode_len", 1000),
                     num_actions=cfg.get("num_actions", 6)))
register_jax_env("TokenBigram-v0",
                 lambda cfg: JaxTokenBigram(
                     vocab_size=cfg.get("vocab_size", 50304),
                     episode_len=cfg.get("episode_len", 1024),
                     seed=cfg.get("seed", 0)))
register_jax_env("CartPole-v0", lambda cfg: JaxCartPole(max_steps=200))
register_jax_env("CartPole-v1", lambda cfg: JaxCartPole(max_steps=500))
