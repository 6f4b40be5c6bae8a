"""Environment registry.

Parity: the reference resolves env names via gym + `tune.registry`'s
`register_env` (`rllib/agents/trainer.py` `_setup`). Built-in names mirror
the gym ids used by the reference's tuned examples; unknown ids fall
through to gymnasium when it is installed (`gym_adapter.py`), with
Atari-looking envs automatically wrapped DeepMind-style
(`atari_wrappers.py`), matching the reference's `gym.make` +
`wrap_deepmind` resolution.
"""

from __future__ import annotations

from typing import Callable, Dict

from .env import (CartPole, Pendulum, RepeatInitialObs, StatelessCartPole,
                  SyntheticAtari, TokenBigram)

_REGISTRY: Dict[str, Callable] = {}


def register_env(name: str, creator: Callable) -> None:
    """Register `creator(env_config) -> Env` under `name`."""
    _REGISTRY[name] = creator


def _make_gym_env(name: str, env_config: dict):
    from .atari_wrappers import is_atari, wrap_deepmind
    from .gym_adapter import GymEnv
    env = GymEnv.make(name, env_config)
    if is_atari(env):
        env = wrap_deepmind(
            env, dim=env_config.get("dim", 84),
            framestack=env_config.get("framestack", True))
    return env


def make_env(name: str, env_config: dict = None):
    env_config = env_config or {}
    if name in _REGISTRY:
        return _REGISTRY[name](env_config)
    from .gym_adapter import have_gymnasium
    if have_gymnasium():
        import gymnasium
        # Only NAME-RESOLUTION failures fall through to "unknown env";
        # a real construction failure (missing ale-py, deprecated id)
        # must surface its own actionable message.
        err = gymnasium.error
        not_found = tuple(
            e for e in (getattr(err, "NameNotFound", None),
                        getattr(err, "NamespaceNotFound", None),
                        getattr(err, "VersionNotFound", None),
                        getattr(err, "UnregisteredEnv", None))
            if e is not None)
        try:
            return _make_gym_env(name, env_config)
        except not_found:
            pass
    raise ValueError(
        f"unknown env {name!r}; registered: {sorted(_REGISTRY)} "
        "(gymnasium ids also resolve when gymnasium is installed)")


def registered_envs():
    return sorted(_REGISTRY)


# -- batched (vectorized) envs ------------------------------------------
# The Sebulba inline-actor path steps envs as a batch (see
# `batched_env.py`). Envs with a natively-vectorized implementation
# register it here; everything else falls back to a per-env loop adapter.
_BATCHED_REGISTRY: Dict[str, Callable] = {}


def register_batched_env(name: str, creator: Callable) -> None:
    """Register `creator(num_envs, env_config) -> BatchedEnv`."""
    _BATCHED_REGISTRY[name] = creator


def make_batched_env(name, num_envs: int, env_config: dict = None,
                     seed=None, device_frame_stack: int = 0,
                     obs_delta=False, obs_delta_budget: int = 256):
    """Build a BatchedEnv for `name` (string id or env creator callable).

    Uses the natively-vectorized implementation when one is registered;
    otherwise wraps N single-env instances (`BatchedEnvFromSingle`).
    With `device_frame_stack=k` the env must emit single-channel frames;
    they are wrapped for on-device stacking (`device_frame_stack.py`).
    With `obs_delta=True`, envs without native delta support gain the
    generic host-side `DeltaEncoder` (`delta_obs.py`); "auto" keeps
    native support only.
    """
    from .batched_env import BatchedEnvFromSingle
    env_config = env_config or {}
    if isinstance(name, str) and name in _BATCHED_REGISTRY:
        env = _BATCHED_REGISTRY[name](num_envs, env_config)
    elif isinstance(name, str):
        env = BatchedEnvFromSingle(
            lambda: make_env(name, env_config), num_envs)
    else:  # creator callable
        env = BatchedEnvFromSingle(lambda: name(env_config), num_envs)
    if obs_delta is True and not hasattr(env, "delta_budget"):
        from .delta_obs import DeltaEncoder
        env = DeltaEncoder(env, budget=obs_delta_budget)
    if device_frame_stack:
        from .device_frame_stack import DeviceFrameStack
        env = DeviceFrameStack(env, device_frame_stack)
    if seed is not None:
        env.seed(seed)
    return env


def _batched_synthetic_atari(channels=4):
    def creator(n, cfg):
        from .batched_env import BatchedSyntheticAtari
        return BatchedSyntheticAtari(
            n, episode_len=cfg.get("episode_len", 1000),
            num_actions=cfg.get("num_actions", 6),
            pool_size=cfg.get("pool_size", 32),
            channels=cfg.get("channels", channels))
    return creator


def _batched_cartpole(max_steps):
    def creator(n, cfg):
        from .batched_env import BatchedCartPole
        return BatchedCartPole(n, max_steps=max_steps)
    return creator


register_batched_env("SyntheticAtari-v0", _batched_synthetic_atari(4))
# Single-frame emission variant for on-device frame stacking (pair with
# config device_frame_stack=4; see env/device_frame_stack.py).
register_batched_env("SyntheticAtariFrames-v0", _batched_synthetic_atari(1))


def _batched_sprite_atari(n, cfg):
    from .delta_obs import BatchedSpriteAtari
    return BatchedSpriteAtari(
        n, episode_len=cfg.get("episode_len", 1000),
        num_actions=cfg.get("num_actions", 6),
        pool_size=cfg.get("pool_size", 16),
        speed=cfg.get("speed", 3))


# Temporally-coherent Atari-shaped frames with native delta emission
# (env/delta_obs.py): single-channel, pair with device_frame_stack=4 and
# obs_delta="auto" on the inline-actor path.
register_batched_env("SpriteAtari-v0", _batched_sprite_atari)
register_batched_env("CartPole-v0", _batched_cartpole(200))
register_batched_env("CartPole-v1", _batched_cartpole(500))


# Built-ins (same ids the reference's yamls use).
register_env("CartPole-v0", lambda cfg: CartPole(max_steps=200))
register_env("CartPole-v1", lambda cfg: CartPole(max_steps=500))
register_env("Pendulum-v0", lambda cfg: Pendulum())
register_env("StatelessCartPole-v0", lambda cfg: StatelessCartPole())
register_env("RepeatInitialObs-v0",
             lambda cfg: RepeatInitialObs(
                 num_cues=cfg.get("num_cues", 3),
                 episode_len=cfg.get("episode_len", 6)))
register_env("SyntheticAtari-v0",
             lambda cfg: SyntheticAtari(
                 episode_len=cfg.get("episode_len", 1000),
                 num_actions=cfg.get("num_actions", 6)))
register_env("TokenBigram-v0",
             lambda cfg: TokenBigram(
                 vocab_size=cfg.get("vocab_size", 50304),
                 episode_len=cfg.get("episode_len", 1024),
                 seed=cfg.get("seed", 0)))
register_env("SyntheticAtariFrames-v0",
             lambda cfg: SyntheticAtari(
                 episode_len=cfg.get("episode_len", 1000),
                 num_actions=cfg.get("num_actions", 6),
                 channels=1))


def _sprite_atari(cfg):
    from .delta_obs import SpriteAtari
    return SpriteAtari(
        episode_len=cfg.get("episode_len", 1000),
        num_actions=cfg.get("num_actions", 6),
        pool_size=cfg.get("pool_size", 16),
        speed=cfg.get("speed", 3))


register_env("SpriteAtari-v0", _sprite_atari)


def _multiagent_cartpole(cfg):
    from .multi_agent_env import MultiAgentCartPole
    return MultiAgentCartPole(num_agents=cfg.get("num_agents", 2),
                              max_steps=cfg.get("max_steps", 200))


register_env("MultiAgentCartPole-v0", _multiagent_cartpole)


def _two_step_game_grouped(cfg):
    from .group_agents_wrapper import GroupedMultiAgentEnv, TwoStepGame
    return GroupedMultiAgentEnv(TwoStepGame(), n_agents=2)


register_env("GroupedTwoStepGame-v0", _two_step_game_grouped)


def _spread_grouped(cfg):
    from .group_agents_wrapper import GroupedMultiAgentEnv, SpreadGame
    n = cfg.get("n_agents", 2)
    return GroupedMultiAgentEnv(
        SpreadGame(n_agents=n, episode_len=cfg.get("episode_len", 5),
                   seed=cfg.get("seed")), n_agents=n)


register_env("GroupedSpread-v0", _spread_grouped)


# ALE-shaped Catch (env/ale_catch.py): the ROM-free env that exercises
# the full DeepMind preprocessing stack (atari_wrappers.py).
def _ale_catch(framestack):
    def creator(cfg):
        from .ale_catch import CatchALE
        from .atari_wrappers import wrap_deepmind
        env = CatchALE(
            lives=cfg.get("lives", 3),
            flicker=cfg.get("flicker", True))
        if (seed := cfg.get("seed")) is not None:
            env.seed(seed)
        return wrap_deepmind(env, dim=cfg.get("dim", 84),
                             framestack=framestack)
    return creator


# Host-side 4-frame stack ([84, 84, 4] obs) — any sampler.
register_env("ALECatch-v0", _ale_catch(True))
# Single-frame emission ([84, 84, 1]) for ON-DEVICE stacking — pair
# with trainer config device_frame_stack: 4 (inline-actor path).
register_env("ALECatchFrames-v0", _ale_catch("device"))
