"""The OLMoE token policy's loss and loop at a tiny size on the CPU (the
model against its reference: `tests/test_olmoe_policy.py`, whose row this file
shares): the family's shared check of V-trace's loss and its gradients
(`tests/token_families.py`), and what is its own: V-trace from ACTION_LOGP,
the trainer on the fused Anakin path, what its learner's stats say of the
decode's cache, a wide action space, whole episodes, an LSTM's carried state,
and the Nature-CNN Anakin program's outputs as they were before any of it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_olmoe_policy import FAMILY, NET, S, blocks_of_4  # noqa: F401
from token_families import (  # noqa: F401: pytest collects what is named
    test_vtrace_minibatch_loss_and_gradients_match_reference,
    token_trainer_config, two_iterations)

from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.agents.impala import IMPALATrainer
from ray_tpu.rllib.agents.impala.vtrace_policy import vtrace_loss


def by_config_alone(**over):
    """The shared config with the blocks in the policy's own default
    dtype, as a trainer by config alone has them."""
    return token_trainer_config(FAMILY, **{"model": {
        "custom_model": "olmoe", "custom_model_config": NET}, **over})


def test_vtrace_from_logp_equals_vtrace_from_dist_inputs():
    """A 6-action batch: behaviour log-probabilities read from ACTION_LOGP
    give the loss, the stats and the gradients that the stored behaviour
    logits give."""
    trainer = IMPALATrainer(config=dict(
        env="SyntheticAtari-v0", num_workers=0, rollout_fragment_length=5,
        train_batch_size=20, min_iter_time_s=0, seed=2))
    try:
        policy = trainer.get_policy()
        assert policy.dist_dim == 6
        rng = np.random.default_rng(1)
        n, T = 20, 5
        logits = rng.normal(size=(n, 6)).astype(np.float32)
        actions = rng.integers(0, 6, size=n)
        logp = jax.nn.log_softmax(logits)[np.arange(n), actions]
        batch = {
            sb.OBS: jnp.asarray(rng.integers(
                0, 256, size=(n, 84, 84, 4)), jnp.uint8),
            sb.ACTIONS: jnp.asarray(actions),
            sb.REWARDS: jnp.asarray(rng.normal(size=n), jnp.float32),
            sb.DONES: jnp.asarray(rng.integers(0, 2, size=n), jnp.float32),
            sb.BOOTSTRAP_OBS: jnp.asarray(rng.integers(
                0, 256, size=(n // T, 84, 84, 4)), jnp.uint8),
        }
        with_logits = dict(batch, **{sb.ACTION_DIST_INPUTS: logits})
        with_logp = dict(batch, **{sb.ACTION_LOGP: logp})
        params = policy.params
        out = [jax.jit(jax.value_and_grad(
            lambda p, b=b: vtrace_loss(policy, p, b, None, {}),
            has_aux=True))(params) for b in (with_logits, with_logp)]
        ((loss_a, stats_a), grads_a), ((loss_b, stats_b), grads_b) = out
        np.testing.assert_allclose(loss_a, loss_b, rtol=1e-6)
        for key in stats_a:
            np.testing.assert_allclose(stats_a[key], stats_b[key],
                                       rtol=1e-5, atol=1e-7)
        for a, b in zip(jax.tree.leaves(grads_a), jax.tree.leaves(grads_b)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
        assert float(stats_a["is_ratio_max"]) != 1.0  # off-policy batch
    finally:
        trainer.stop()


# -- the loop -------------------------------------------------------------
def test_token_trainer_trains_on_the_fused_path(token_trainer):
    """Two iterations by config alone (`token_families.two_iterations`),
    and the counters in `learner_stats`."""
    stats, kept = two_iterations(FAMILY, token_trainer)
    # Four minibatches a rollout: the later ones are off-policy, so the
    # importance ratios have left 1.
    assert stats["is_ratio_max"] > 1.0
    # The rollout's decode step, from its static shape: 8 rows to 2 of 8
    # experts, multiplied in the batched form.
    assert kept["decode_rows_per_expert"] == 2.0
    assert kept["decode_experts_batched"] == 1.0
    assert kept["experts_grouped_kernel"] == 0.0  # this is no TPU
    # Every expert is here: no share of the pairs to count.
    assert "experts_held_row_share" not in kept
    assert "dispatch_rows_share" not in kept
    # Its attention: the window of 16 is one block, read whole every step.
    assert kept["decode_cache_block"] == S
    assert kept["decode_cache_read_share"] == 1.0
    # The learner's attention: 16 tokens are no two tiles, and this is
    # no TPU.

@pytest.mark.parametrize("episode_len,share", [(S, 0.5 + 4 / (2 * S)),
                                               (1, 4 / S)])
def test_decode_cache_counters_in_learner_stats(episode_len, share,
                                                blocks_of_4):
    """`decode_cache_read_share` is reduced on the device from the value
    that selects the blocks: a window that fills from empty reads
    1/2 + b/(2S) of itself over a rollout, one held at position 0 (every
    step ends an episode) one block of four; `decode_cache_block` is the
    host's constant."""
    trainer = IMPALATrainer(config=by_config_alone(
        env_config={"vocab_size": NET["vocab_size"],
                    "episode_len": episode_len}))
    try:
        trainer.train()
        kept = trainer.optimizer.learner_stats
        assert kept["decode_cache_block"] == blocks_of_4
        assert kept["decode_cache_read_share"] == pytest.approx(share)
    finally:
        trainer.stop()


def test_wide_action_space_keeps_logp_not_logits():
    """Decided from the action space's size: a 50,304-way policy's
    trajectory carries ACTION_LOGP and VF_PREDS, a 6-way one its logits."""
    import ray_tpu.rllib.policy.jax_policy as jp
    wide = dict(NET, vocab_size=jp.MAX_KEPT_DIST_INPUTS + 8)
    trainer = IMPALATrainer(config=by_config_alone(
        env_config={"vocab_size": wide["vocab_size"], "episode_len": S},
        num_envs_per_worker=2, train_batch_size=2 * S,
        sgd_minibatch_size=S,
        model={"custom_model": "olmoe", "custom_model_config": wide}))
    try:
        policy = trainer.get_policy()
        assert not policy.keeps_dist_inputs
        seen = {}
        loss_fn = policy._loss_fn

        def spy(pol, params, batch, rng, loss_state):
            seen.update({k: v.shape for k, v in batch.items()
                         if hasattr(v, "shape")})
            return loss_fn(pol, params, batch, rng, loss_state)

        policy._loss_fn = spy
        trainer.optimizer._anakin_fn = trainer.optimizer._build_fn()
        result = trainer.train()
        assert np.isfinite(result["info"]["learner"]["total_loss"])
        assert sb.ACTION_DIST_INPUTS not in seen
        assert seen[sb.ACTION_LOGP] == (S,) and seen[sb.VF_PREDS] == (S,)
    finally:
        trainer.stop()


def test_context_window_policy_needs_whole_episodes():
    with pytest.raises(ValueError, match="whole episodes"):
        IMPALATrainer(config=by_config_alone(
            env_config={"vocab_size": NET["vocab_size"], "episode_len": 12}))


@pytest.mark.parametrize("minibatch", [0, 40])
def test_lstm_policy_trains_on_the_fused_path(minibatch):
    """The LSTM's (c, h) is a case of the carried policy state: replayed
    from `state_in`, the one-update rollout is exactly on-policy."""
    trainer = IMPALATrainer(config=dict(
        env="CartPole-v0", anakin=True, num_workers=0,
        num_envs_per_worker=8, rollout_fragment_length=10,
        train_batch_size=80, sgd_minibatch_size=minibatch,
        anakin_updates_per_call=2, min_iter_time_s=0, seed=1,
        model={"use_lstm": True, "lstm_cell_size": 16,
               "fcnet_hiddens": [16]}))
    try:
        stats = trainer.train()["info"]["learner"]
        assert np.isfinite(stats["total_loss"])
        if minibatch == 0:
            assert stats["is_ratio_max"] == pytest.approx(1.0, abs=1e-5)
        else:
            assert stats["is_ratio_max"] > 1.0
    finally:
        trainer.stop()


def test_nature_cnn_anakin_outputs_unchanged():
    """The Nature-CNN path through the same functions is the program it
    was: for a fixed seed the stats of two calls are those of the parent
    commit (24c7a04, recorded from its tree on this CPU)."""
    trainer = IMPALATrainer(config=dict(
        env="SyntheticAtari-v0", env_config={"episode_len": 8},
        anakin=True, num_workers=0, num_envs_per_worker=4,
        rollout_fragment_length=4, train_batch_size=16,
        anakin_updates_per_call=2, min_iter_time_s=0, lr=6e-4,
        grad_clip=40.0, seed=7))
    want = [
        {"entropy": 1.79152250289917, "mean_kl_behaviour": 0.0,
         "policy_loss": -0.16051942110061646,
         "total_loss": -0.646298885345459, "vf_loss": 0.27608194947242737,
         "vtrace_mean_vs": 0.3140600919723511},
        {"entropy": 1.791407823562622, "mean_kl_behaviour": 0.0,
         "policy_loss": -0.019576922059059143,
         "total_loss": 0.47002220153808594, "vf_loss": 0.1337347775697708,
         "vtrace_mean_vs": 0.37526535987854004},
    ]
    try:
        assert trainer.get_policy().keeps_dist_inputs
        for expected in want:
            stats = trainer.train()["info"]["learner"]
            for key, value in expected.items():
                assert stats[key] == pytest.approx(value, rel=1e-4,
                                                   abs=1e-6), key
            assert stats["is_ratio_max"] == 1.0  # one update: on-policy
            assert not [k for k in stats if k.startswith("decode_")]
    finally:
        trainer.stop()
