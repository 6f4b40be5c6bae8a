"""The `sdar_moe` token policy's loss and loop at a tiny size on the CPU (the
model against its reference: `tests/test_sdar_policy.py`, whose row and
rollout this file shares): the trainer on the fused Anakin path from the tuned
example; the block-level V-trace's loss and its gradient against the
reference's; a given row weighs nothing; the first minibatch of a rollout is
on-policy; what the optimizer refuses.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml
from test_sdar_policy import NET, N, reference, rollout, unmask_steps
from token_families import BENCH, ROOT

from ray_tpu.rllib import sample_batch as sb


@pytest.fixture(scope="module")
def trained():
    """The trainer from the tuned example at the cell's rehearsal sizes."""
    from ray_tpu.rllib.agents.registry import get_trainer_class
    with open(os.path.join(
            ROOT, "ray_tpu/rllib/tuned_examples/sdar-token-impala.yaml")) as f:
        (example,) = yaml.safe_load(f).values()
    with open(os.path.join(
            BENCH, "workloads/sdar_block_token_anakin_2k.json")) as f:
        workload = json.load(f)
    from drivers.rllib_trainer import merge
    config = merge(dict(example["config"], env=example["env"], seed=11),
                   workload["rehearse_trainer_config"])
    config.pop("num_tpus_for_learner")
    trainer = get_trainer_class(example["run"])(config=config)
    yield trainer, example, workload
    trainer.stop()


def test_the_trainer_runs_from_the_tuned_example(trained):
    trainer, example, workload = trained
    assert example["config"]["model"]["custom_model"] == "sdar_moe"
    cfg = trainer.config
    envs, T_, episode = (trainer.optimizer.num_envs,
                         cfg["rollout_fragment_length"],
                         cfg["env_config"]["episode_len"])
    result = trainer.train()
    stats = result["info"]["learner"]
    # Steps are actions: an episode's positions less its given first.
    assert result["timesteps_total"] == envs * T_ // (episode + 1) * episode
    assert np.isfinite(stats["total_loss"])
    assert stats["block_len"] == 4 and stats["denoise_steps"] == 2
    assert stats["decode_passes_per_token"] == 0.75
    # Two layers: the last one's clean stream stops at its keys and values.
    assert stats["learner_rows_per_token"] == 3 - 1 / 2
    minibatches = envs * T_ // cfg["sgd_minibatch_size"]
    assert stats["given_rows"] * minibatches == envs * T_ // (episode + 1)
    assert 0 < stats["unmask_top_prob_mean"] <= 1
    assert result["episodes_total"] == envs * T_ // (episode + 1)
    assert result["episode_len_mean"] == episode


def test_the_tuned_example_holds_the_configuration_s_trainer(trained):
    _, example, workload = trained
    with open(os.path.join(
            BENCH, "configs", workload["config"] + ".json")) as f:
        config = json.load(f)
    for key in ("lr", "grad_clip", "min_iter_time_s"):
        assert example["config"][key] == config["trainer_config"][key]


def minibatch(policy, seed=0):
    """A seeded minibatch of the trainer's shape, as the rollout packs it,
    and the same for the reference."""
    cfg = policy.config
    T_, L = cfg["rollout_fragment_length"], policy.block_len
    frags = cfg["sgd_minibatch_size"] // T_
    net = dict(NET, **cfg["model"]["custom_model_config"])
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, net["vocab_size"] - 1, (frags, T_))
    steps = unmask_steps(rng, frags, T_, L, net["denoise_steps"])
    episode = cfg["env_config"]["episode_len"] + 1
    steps[:, ::episode] = -1
    dones = np.zeros((frags, T_), np.float32)
    dones[:, episode - 1::episode] = 1.0
    ref = {"tokens": tokens, "steps": steps,
           "rewards": rng.integers(0, 2, (frags, T_)).astype(np.float32),
           "behaviour_logp": (-np.log(net["vocab_size"]) + rng.uniform(
               -0.5, 0.5, (frags, T_))).astype(np.float32)}
    batch = {
        sb.OBS: jnp.asarray(tokens.reshape(-1), jnp.int32),
        sb.ACTIONS: jnp.asarray(tokens.reshape(-1), jnp.int32),
        sb.UNMASK_STEPS: jnp.asarray(steps.reshape(-1), jnp.int32),
        sb.REWARDS: jnp.asarray(ref["rewards"].reshape(-1)),
        sb.DONES: jnp.asarray(dones.reshape(-1)),
        sb.ACTION_LOGP: jnp.asarray(ref["behaviour_logp"].reshape(-1)),
        sb.VF_PREDS: jnp.zeros(frags * T_, jnp.float32),
        sb.BOOTSTRAP_OBS: jnp.zeros(frags, jnp.int32)}
    return batch, ref, net


def loss_and_grad(policy, batch):
    return jax.jit(jax.value_and_grad(
        lambda p: policy._loss_fn(policy, p, batch, jax.random.PRNGKey(0),
                                  policy.loss_state)[0]))(policy.params)


def test_the_loss_and_its_gradient_are_the_reference_s(trained):
    policy = trained[0].get_policy()
    batch, ref, net = minibatch(policy)
    assert ref["tokens"].shape[1] == net["max_position_embeddings"]
    loss, grads = loss_and_grad(policy, batch)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.vtrace_loss(
            {"params": p}, ref, net, policy.config)[0]))(
                policy.params["params"])
    assert abs(float(loss) - float(want)) <= 1e-3 * abs(float(want))
    flat = lambda tree: {  # noqa: E731
        jax.tree_util.keystr(path): leaf for path, leaf in
        jax.tree_util.tree_flatten_with_path(tree)[0]}
    got, want_grads = flat(grads["params"]), flat(want_grads)
    for name, g in want_grads.items():
        scale = float(jnp.max(jnp.abs(g))) or 1.0
        assert float(jnp.max(jnp.abs(got[name] - g))) <= 2e-3 * scale, name
    # The MASK id's column of the head takes no gradient.
    assert float(jnp.max(jnp.abs(grads["params"]["head"][:, -1]))) == 0.0


def test_a_given_row_weighs_nothing(trained):
    """Whatever stands in a given row's reward, behaviour log-probability
    or action, the loss and its gradient are what they were."""
    policy = trained[0].get_policy()
    batch, _, _ = minibatch(policy, seed=1)
    given = batch[sb.UNMASK_STEPS] < 0
    assert int(jnp.sum(given)) > 0
    loss, grads = loss_and_grad(policy, batch)
    other = dict(
        batch,
        **{sb.REWARDS: jnp.where(given, 100.0, batch[sb.REWARDS]),
           sb.ACTION_LOGP: jnp.where(given, -7.0, batch[sb.ACTION_LOGP]),
           sb.ACTIONS: jnp.where(given, 3, batch[sb.ACTIONS])})
    loss2, grads2 = loss_and_grad(policy, other)
    assert float(loss) == float(loss2)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads2)):
        np.testing.assert_array_equal(a, b)
    # A generated row's does move it.
    moved = dict(batch, **{sb.REWARDS: batch[sb.REWARDS] + 1.0})
    assert float(loss_and_grad(policy, moved)[0]) != float(loss)


def test_the_first_minibatch_of_a_rollout_is_on_policy(trained):
    """The optimizer's own rollout, learned from at the parameters that
    sampled it: every block's importance ratio is 1."""
    trainer = trained[0]
    opt, policy = trainer.optimizer, trainer.get_policy()
    cfg = policy.config
    frags = N
    model = policy.model
    net = dict(NET, **cfg["model"]["custom_model_config"])
    trace = rollout(model, policy.params, net, seed=9,
                    positions=cfg["rollout_fragment_length"])
    rows = lambda x: x[:frags].reshape(-1)  # noqa: E731
    dones = np.zeros(trace["tokens"].shape, np.float32)
    dones[:, -1] = 1.0
    batch = {
        sb.OBS: rows(trace["tokens"]), sb.ACTIONS: rows(trace["tokens"]),
        sb.UNMASK_STEPS: rows(trace["steps"]),
        sb.REWARDS: jnp.ones(frags * dones.shape[1]),
        sb.DONES: jnp.asarray(rows(dones)),
        sb.ACTION_LOGP: rows(trace["logp"]),
        sb.BOOTSTRAP_OBS: jnp.zeros(frags, jnp.int32)}
    _, stats = jax.jit(lambda p: policy._loss_fn(
        policy, p, batch, jax.random.PRNGKey(0), policy.loss_state))(
            policy.params)
    assert abs(float(stats["is_ratio_mean"]) - 1.0) <= 1e-3
    assert abs(float(stats["is_ratio_max"]) - 1.0) <= 5e-3
    assert float(stats["given_rows"]) == frags
    assert opt.num_envs >= frags


@pytest.mark.parametrize("episode_len,fragment", [(30, 32), (31, 48),
                                                  (15, 32)])
def test_the_optimizer_refuses_fragments_that_are_not_whole_episodes(
        episode_len, fragment):
    """An episode is the env's steps and its given first position, in whole
    blocks, and a fragment whole episodes."""
    from ray_tpu.rllib.agents.registry import get_trainer_class
    config = dict(
        env="TokenBigram-v0",
        env_config={"vocab_size": 95, "episode_len": episode_len},
        anakin=True, num_workers=0, num_envs_per_worker=4,
        rollout_fragment_length=fragment, train_batch_size=4 * fragment,
        min_iter_time_s=0,
        model={"custom_model": "sdar_moe", "compute_dtype": "f32",
               "custom_model_config": dict(NET, max_position_embeddings=64)})
    if (episode_len + 1) % 4 == 0 and fragment % (episode_len + 1) == 0:
        get_trainer_class("IMPALA")(config=config).stop()
        return
    with pytest.raises(ValueError, match="whole episodes"):
        get_trainer_class("IMPALA")(config=config)
