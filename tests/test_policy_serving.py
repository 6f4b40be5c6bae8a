"""REST policy serving + RemoteVectorEnv (VERDICT r2 item #7).

Loopback test per the reference's serving example
(`rllib/utils/policy_server.py` docstring): a trainer learns CartPole
where the env lives OUTSIDE the trainer process boundary, driven
entirely through PolicyClient REST calls; plus env-per-actor stepping
through RemoteVectorEnv.
"""

import socket
import threading
import time
import urllib.error

import numpy as np
import pytest
from conftest import wait_until

import ray_tpu
from ray_tpu.rllib.env.env import CartPole
from ray_tpu.rllib.env.external_env import ExternalEnv
from ray_tpu.rllib.env.registry import register_env
from ray_tpu.rllib.env.spaces import Box, Discrete
from ray_tpu.rllib.utils.policy_client import PolicyClient
from ray_tpu.rllib.utils.policy_server import PolicyServer


@pytest.fixture
def ray_session():
    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class TestPolicyServing:
    def test_train_cartpole_through_rest_boundary(self, ray_session):
        port = _free_port()
        high = np.array([4.8, np.finfo(np.float32).max,
                         0.42, np.finfo(np.float32).max], np.float32)

        class Serving(ExternalEnv):
            def __init__(self, cfg=None):
                super().__init__(Box(-high, high), Discrete(2))

            def run(self):
                PolicyServer(self, "127.0.0.1", port).serve_forever()

        results = []
        errors = []
        holder = {}
        trained = threading.Event()

        register_env("CartPoleServing-v0", lambda cfg: Serving())

        def train_loop():
            # Constructed here: the first env reset blocks until the
            # REST client supplies an observation (the serving env is
            # driven from outside).
            try:
                from ray_tpu.rllib.agents.registry import \
                    get_trainer_class
                trainer = get_trainer_class("PG")(config={
                    "env": "CartPoleServing-v0",
                    "num_workers": 0,
                    "rollout_fragment_length": 100,
                    "train_batch_size": 200,
                    "lr": 5e-3,
                    "min_iter_time_s": 0,
                    "seed": 0,
                })
                holder["trainer"] = trainer
                for _ in range(3):
                    results.append(trainer.train())
            except Exception as e:  # pragma: no cover
                errors.append(e)
            finally:
                trained.set()
                # Stopping the trainer closes the serving env: the
                # client's request in flight fails at once (an HTTP
                # 500 carrying ExternalEnvClosed) where it used to
                # wait out its whole timeout.
                if "trainer" in holder:
                    holder["trainer"].stop()

        t = threading.Thread(target=train_loop, daemon=True)
        t.start()

        # Client side: a REAL CartPole stepped outside the trainer,
        # asking the server for on-policy actions. The server binds
        # once the trainer's policy finishes building (jit init takes
        # seconds), so connect with retries.
        # Generous request timeout: while the trainer compiles its first
        # update the sampler pauses and in-flight get_action calls wait.
        client = PolicyClient(f"127.0.0.1:{port}", timeout=120)
        deadline = time.monotonic() + 60
        eid = None
        while time.monotonic() < deadline:
            try:
                eid = client.start_episode()
                break
            except OSError:
                time.sleep(0.5)
        assert eid is not None, "policy server never came up"
        env = CartPole()
        env.seed(0)
        steps = 0
        first = True
        try:
            while not trained.is_set() and steps < 5000:
                if not first:
                    eid = client.start_episode()
                first = False
                obs = env.reset()
                done = False
                while not done and not trained.is_set():
                    action = client.get_action(eid, obs)
                    obs, reward, done, _ = env.step(int(action))
                    client.log_returns(eid, reward)
                    steps += 1
                if done:
                    client.end_episode(eid, obs)
        except urllib.error.HTTPError as e:
            # The train loop finished while our request was in flight.
            assert trained.is_set() and e.code == 500, e
            assert "ExternalEnvClosed" in str(e), e
        t.join(timeout=120)
        assert not t.is_alive()
        assert not errors, errors
        assert len(results) == 3
        assert results[-1]["episode_reward_mean"] > 0
        assert results[-1]["timesteps_this_iter"] >= 200

    def test_log_action_roundtrip(self, ray_session):
        """Off-policy logging commands reach the env adapter."""
        port = _free_port()

        class Serving(ExternalEnv):
            def __init__(self):
                super().__init__(Box(-np.ones(2, np.float32),
                                     np.ones(2, np.float32)), Discrete(2))

            def run(self):
                PolicyServer(self, "127.0.0.1", port).serve_forever()

        env = Serving()
        env._loop_started = True
        env.start()
        client = PolicyClient(f"127.0.0.1:{port}")

        # refused until the server thread listens
        eid = wait_until(lambda: _try(client.start_episode), timeout=30)

        # Drain framework side on a thread (acts as the sampler).
        consumed = []

        def fake_sampler():
            obs = env.reset()
            consumed.append(obs)
            obs, reward, done, _ = env.step(0)
            consumed.append((obs, reward, done))

        t = threading.Thread(target=fake_sampler, daemon=True)
        t.start()
        client.log_action(eid, np.zeros(2, np.float32), 1)
        client.log_returns(eid, 0.5)
        client.end_episode(eid, np.ones(2, np.float32))
        t.join(timeout=30)
        assert len(consumed) == 2

    def test_close_fails_the_request_in_flight(self):
        """When the sampler stops (`close()`), the client's get_action
        in flight and every later request fail at once, naming the
        cause: they do not wait out the client's request timeout."""
        port = _free_port()

        class Serving(ExternalEnv):
            def __init__(self):
                super().__init__(Box(-np.ones(2, np.float32),
                                     np.ones(2, np.float32)), Discrete(2))

            def run(self):
                PolicyServer(self, "127.0.0.1", port).serve_forever()

        env = Serving()
        client = PolicyClient(f"127.0.0.1:{port}", timeout=60)
        raised = []

        def drive():
            eid = wait_until(lambda: _try(client.start_episode),
                             timeout=30)
            for _ in range(2):  # the one in flight, then a later one
                try:
                    client.get_action(eid, np.zeros(2, np.float32))
                except urllib.error.HTTPError as e:
                    raised.append((e.code, str(e)))

        t = threading.Thread(target=drive, daemon=True)
        t.start()
        env.reset()  # the sampler holds the first observation
        t0 = time.monotonic()
        env.close()
        t.join(timeout=30)
        assert not t.is_alive() and time.monotonic() - t0 < 10
        assert [c for c, _ in raised] == [500, 500], raised
        assert all("ExternalEnvClosed" in m for _, m in raised), raised


def _try(call):
    try:
        return call()
    except OSError:
        return None


class TestRemoteVectorEnv:
    def test_remote_envs_step_and_train(self, ray_session):
        from ray_tpu.rllib.agents.registry import get_trainer_class
        trainer = get_trainer_class("PG")(config={
            "env": "CartPole-v0",
            "num_workers": 0,
            "num_envs_per_worker": 3,
            "remote_worker_envs": True,
            "rollout_fragment_length": 50,
            "train_batch_size": 100,
            "min_iter_time_s": 0,
            "seed": 0,
        })
        r = trainer.train()
        assert r["timesteps_this_iter"] >= 100
        # The local worker's env really is actor-backed.
        from ray_tpu.rllib.env.remote_vector_env import RemoteVectorEnv
        assert isinstance(trainer.workers.local_worker.env,
                          RemoteVectorEnv)
        assert len(trainer.workers.local_worker.env.actors) == 3
        trainer.stop()
