"""Multi-device learner tests on the virtual 8-CPU-device mesh.

Parity: the reference exercises its multi-GPU learner via
`rllib/tests/test_optimizers.py` (LocalMultiGPUOptimizer with num_gpus>1 on
fake devices). Here the learner program is jitted over a
`jax.sharding.Mesh` of num_tpus_for_learner devices (conftest.py forces 8
virtual CPU devices), so XLA inserts the gradient all-reduce.
"""

import numpy as np
import pytest

from conftest import cpu_mesh, ppo_batch, ppo_policy, toy_spaces


class TestMultiDeviceLearner:
    def test_ppo_mesh4_trains(self):
        from ray_tpu.rllib.agents.ppo import PPOTrainer
        t = PPOTrainer(config={
            "env": "CartPole-v0",
            "num_workers": 0,
            "num_tpus_for_learner": 4,
            "train_batch_size": 256,
            "sgd_minibatch_size": 64,
            "num_sgd_iter": 3,
            "rollout_fragment_length": 64,
            "num_envs_per_worker": 2,
            "model": {"fcnet_hiddens": [32, 32]},
            "seed": 0,
        })
        r1 = t.train()
        r2 = t.train()
        assert np.isfinite(r2["info"]["learner"]["total_loss"])
        # Params stay replicated across the mesh: a fresh single-device
        # policy loaded with the trained weights must act identically.
        from ray_tpu.rllib.agents.ppo import PPOTrainer as P2
        w = t.get_policy().get_weights()
        t1 = P2(config={
            "env": "CartPole-v0", "num_workers": 0,
            "train_batch_size": 256, "sgd_minibatch_size": 64,
            "rollout_fragment_length": 64,
            "model": {"fcnet_hiddens": [32, 32]}, "seed": 0,
        })
        t1.get_policy().set_weights(w)
        obs = np.array([[0.01, 0.0, 0.02, 0.0]] * 4, np.float32)
        a_mesh, _, _ = t.get_policy().compute_actions(obs, explore=False)
        a_one, _, _ = t1.get_policy().compute_actions(obs, explore=False)
        np.testing.assert_array_equal(np.asarray(a_mesh), np.asarray(a_one))
        t1.stop()
        t.stop()

    def test_impala_mesh4_trains(self, ray_start):
        from ray_tpu.rllib.agents.registry import get_trainer_class
        cls = get_trainer_class("IMPALA")
        t = cls(config={
            "env": "CartPole-v0",
            "num_workers": 1,
            "num_tpus_for_learner": 4,
            "rollout_fragment_length": 64,
            "train_batch_size": 128,
            "model": {"fcnet_hiddens": [32, 32]},
            "seed": 0,
        })
        for _ in range(3):
            r = t.train()
        assert r["timesteps_total"] > 0
        learner = r["info"]["learner"]
        assert np.isfinite(learner["total_loss"])
        t.stop()


# ---------------------------------------------------------------------
# XLA's psum, inserted from the batch's sharding, is the one gradient
# exchange: from equal weights, one update on a mesh leaves the loss and
# every parameter where the one-device program leaves them.
# ---------------------------------------------------------------------
def _assert_same_update(one, mesh, loss_one, loss_mesh):
    """`one` and `mesh` are parameter trees after the same update."""
    import jax
    np.testing.assert_allclose(loss_mesh, loss_one, rtol=2e-4)
    flat_one = jax.tree_util.tree_leaves_with_path(one)
    flat_mesh = jax.tree.leaves(mesh)
    assert len(flat_one) == len(flat_mesh)
    for (path, a), b in zip(flat_one, flat_mesh):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=2e-4, atol=1e-6,
            err_msg=jax.tree_util.keystr(path))


UPDATES = {
    "learn_on_batch": lambda p, batch: p.learn_on_batch(batch),
    # Two minibatches an epoch: the second step starts from the first's
    # parameters, and both policies draw the same permutation.
    "sgd_learn": lambda p, batch: p.sgd_learn(
        batch, num_sgd_iter=1, minibatch_size=32),
}


@pytest.mark.parametrize("n_dev", [2, 4, 8])
@pytest.mark.parametrize("update", sorted(UPDATES))
def test_mesh_update_matches_one_device(update, n_dev):
    one, mesh = ppo_policy(cpu_mesh(1)), ppo_policy(cpu_mesh(n_dev))
    mesh.set_weights(one.get_weights())
    before = one.get_weights()
    batch = ppo_batch(64)
    s_one, s_mesh = UPDATES[update](one, batch), UPDATES[update](mesh, batch)
    assert mesh.devices_in_use() == {"params_on": n_dev, "batch_on": n_dev}
    _assert_same_update(one.get_weights(), mesh.get_weights(),
                        s_one["total_loss"], s_mesh["total_loss"])
    # ... and the update moved them: equal trees are not two untouched ones.
    import jax
    assert any(np.abs(a - b).max() > 1e-6 for a, b in zip(
        jax.tree.leaves(before), jax.tree.leaves(one.get_weights())))


def test_dqn_mesh_update_matches_one_device():
    from ray_tpu.rllib import sample_batch as sb
    from ray_tpu.rllib.agents.dqn.dqn import DEFAULT_CONFIG
    from ray_tpu.rllib.agents.dqn.dqn_policy import DQNPolicy

    def policy(n_dev):
        cfg = dict(DEFAULT_CONFIG)
        cfg.update({"model": {"fcnet_hiddens": [16]}, "hiddens": [16],
                    "_mesh": cpu_mesh(n_dev)})
        return DQNPolicy(*toy_spaces(), cfg)

    one, mesh = policy(1), policy(2)
    mesh.set_weights(one.get_weights())
    batch = ppo_batch(64)
    batch[sb.NEW_OBS] = np.roll(batch[sb.OBS], 1, axis=0)
    (s_one, td_one), (s_mesh, td_mesh) = (
        p.learn_with_td(batch) for p in (one, mesh))
    _assert_same_update(one.get_weights()["online"],
                        mesh.get_weights()["online"],
                        s_one["loss"], s_mesh["loss"])
    np.testing.assert_allclose(td_mesh, td_one, rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("n_dev", [2, 4])
def test_sgd_trainer_mesh_step_matches_one_device(n_dev):
    import flax.linen as nn
    import optax
    from ray_tpu.sgd.jax_trainer import JaxTrainer

    def data_creator(config):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((64, 4)).astype(np.float32)
        y = x @ np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32) + 0.1
        return (x, y), (x, y)

    def trainer(num_devices):
        # One batch an epoch: one train step.
        return JaxTrainer(
            model_creator=lambda config: nn.Dense(1),
            data_creator=data_creator,
            optimizer_creator=lambda config: optax.adam(1e-2),
            loss_creator=lambda config: (
                lambda out, target: ((out - target) ** 2).mean()),
            num_replicas=0, batch_size=64,
            num_devices_per_replica=num_devices, config={"seed": 0})

    one, mesh = trainer(1), trainer(n_dev)
    assert mesh.local_runner.mesh.devices.size == n_dev
    s_one, s_mesh = one.train(), mesh.train()
    assert s_one["num_samples"] == s_mesh["num_samples"] == 64
    _assert_same_update(
        one.get_model_weights(), mesh.get_model_weights(),
        s_one["train_loss"], s_mesh["train_loss"])
    one.shutdown()
    mesh.shutdown()


def test_xla_makes_the_exchange():
    """The compiled `train_fn` of a 2-device policy holds an all-reduce
    that nothing in the program wrote; a 1-device one holds none."""
    def compiled(n_dev):
        p = ppo_policy(cpu_mesh(n_dev))
        return p._train_fn.lower(
            p.params, p.opt_state, p._device_batch(ppo_batch(64)),
            p._next_rng(), p.loss_state).compile().as_text()

    assert "all-reduce" in compiled(2)
    assert "all-reduce" not in compiled(1)
