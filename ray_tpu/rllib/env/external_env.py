"""ExternalEnv: environments that drive the policy (not vice versa).

Parity: `rllib/env/external_env.py` — for simulators/services that call
INTO the agent: the user's `run()` loop calls `start_episode` /
`get_action(obs)` / `log_returns(reward)` / `end_episode(obs)`, while
the framework polls completed steps out. The reference runs `run()` on a
thread and bridges through queues; this implementation does the same and
adapts it to the standard Env interface so any trainer can consume an
ExternalEnv unchanged (the sampler steps the adapter, the adapter
exchanges obs/actions with the user loop).
"""

from __future__ import annotations

import queue
import threading
import uuid
from typing import Optional

import numpy as np


class ExternalEnvClosed(RuntimeError):
    """The sampler that consumed this env has stopped (`close()`)."""


class ExternalEnv(threading.Thread):
    def __init__(self, observation_space, action_space):
        super().__init__(daemon=True, name="external-env-run")
        self.observation_space = observation_space
        self.action_space = action_space
        # user loop -> framework: (kind, payload)
        self._obs_q: "queue.Queue" = queue.Queue(1)
        # framework -> user loop: actions
        self._action_q: "queue.Queue" = queue.Queue(1)
        self._episode_reward = 0.0
        self._loop_started = False
        # Action actually executed for the in-flight step when the user
        # loop chose it via log_action (off-policy). Carried on the NEXT
        # obs event so the sampler can relabel the recorded transition.
        self._pending_logged_action = None
        self._awaiting_action = False
        self._pending_obs = None
        self._closed = threading.Event()

    # -- user-side API (called from run()) -------------------------------
    def run(self):
        raise NotImplementedError

    def start_episode(self, episode_id: Optional[str] = None) -> str:
        self._episode_reward = 0.0
        return episode_id or uuid.uuid4().hex

    def get_action(self, episode_id: str, observation):
        """Block until the policy provides an action for `observation`."""
        self._put_event("obs", observation)
        action = self._take_action()
        self._pending_logged_action = None
        return action

    def log_action(self, episode_id: str, observation, action):
        """Record an off-policy step: the external actor chose `action`
        itself. The logged action is threaded back to the sampler via the
        next obs event (`info["off_policy_action"]`), which substitutes it
        into the recorded batch and recomputes logp under the current
        policy (parity: the reference's ExternalEnv stores the logged
        action in the trajectory, `rllib/env/external_env.py`)."""
        self._put_event("obs", observation)
        self._take_action()  # discard the policy's choice
        self._pending_logged_action = action

    def log_returns(self, episode_id: str, reward: float):
        self._episode_reward += float(reward)

    def end_episode(self, episode_id: str, observation):
        self._put_event("done", observation)
        self._pending_logged_action = None

    def _put_event(self, kind: str, observation):
        event = (kind, observation, self._take_reward(),
                 self._pending_logged_action)
        while True:
            self._check_open()
            try:
                return self._obs_q.put(event, timeout=0.1)
            except queue.Full:
                pass

    def _take_action(self):
        while True:
            self._check_open()
            try:
                return self._action_q.get(timeout=0.1)
            except queue.Empty:
                pass

    def _check_open(self):
        if self._closed.is_set():
            raise ExternalEnvClosed(
                "the sampler of this ExternalEnv has stopped: no policy "
                "will answer this episode")

    def _take_reward(self) -> float:
        r = self._episode_reward
        self._episode_reward = 0.0
        return r

    # -- framework-side adapter (standard Env interface) -----------------
    def reset(self):
        if not self._loop_started:
            self._loop_started = True
            self.start()
        if getattr(self, "_awaiting_action", False):
            # Mid-episode reset (e.g. sampler horizon truncation): the
            # external world can't be forced to reset — the user loop is
            # parked waiting for an action for `_pending_obs`. Treat it
            # as a soft episode boundary: hand back the current obs and
            # let the episode continue (blocking on the queue here would
            # deadlock both threads).
            return self._pending_obs
        kind, obs, _, _ = self._obs_q.get()
        # an immediate 'done' (empty episode) is skipped
        while kind == "done":
            kind, obs, _, _ = self._obs_q.get()
        self._pending_obs = obs
        self._awaiting_action = True
        return obs

    def step(self, action):
        self._action_q.put(action)
        kind, obs, reward, logged = self._obs_q.get()
        done = kind == "done"
        self._pending_obs = obs
        self._awaiting_action = not done
        info = {} if logged is None else {"off_policy_action": logged}
        return obs, reward, done, info

    def close(self):
        """The sampler has stopped (RolloutWorker.stop): the user
        loop's request in flight, and every later one, raises
        ExternalEnvClosed instead of waiting for an action that nobody
        will choose. Behind a PolicyServer the client sees it as an
        HTTP 500 at once, not as its own request timeout."""
        self._closed.set()

    def seed(self, seed=None):
        pass
