"""The `qwen3_next` token policy's loss and loop at a tiny size on the CPU
(the model against its reference: `tests/test_qwen3_next_policy.py`, whose
sizes and helpers these tests share): V-trace's loss and its gradients
through the learner's pass (the scan over chunks under one decay a head, the
gated attention, the bootstrap step through every kind of state) against
`jax.grad` of the plain reference; one update of the optimizer's own step
against the reference's gradients through the reference's Adam, and wrong
updates refused; the trainer on the fused Anakin path, a float32 matrix state
in the scan's carry.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_qwen3_next_policy import (  # noqa: F401
    CHUNK, NET, STATE_SHAPES, B, S, reference, solve_in_blocks_of_four,
    state_shapes)

from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.agents.impala import IMPALATrainer
from ray_tpu.rllib.agents.impala.vtrace_policy import vtrace_loss


def token_trainer_config(**over):
    cfg = dict(
        env="TokenBigram-v0",
        env_config={"vocab_size": NET["vocab_size"], "episode_len": S},
        anakin=True, num_workers=0, num_envs_per_worker=4,
        rollout_fragment_length=S, train_batch_size=4 * S,
        sgd_minibatch_size=2 * S, num_sgd_iter=1,
        anakin_updates_per_call=1, min_iter_time_s=0, lr=6e-4, seed=3,
        model={"custom_model": "qwen3_next", "custom_model_config": NET,
               "compute_dtype": "f32"})
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def token_trainer():
    trainer = IMPALATrainer(config=token_trainer_config())
    yield trainer
    trainer.stop()


def seeded_batch(frags, seed):
    """`frags` whole episodes of a walk (`TokenBigram-v0`: the action
    taken is the next observation), as the learner's packed batch and as
    the reference's."""
    rng = np.random.default_rng(seed)
    walk = rng.integers(0, NET["vocab_size"], size=(frags, S + 1))
    ref_batch = {
        "tokens": walk[:, :S], "actions": walk[:, 1:],
        "rewards": rng.integers(0, 2, size=(frags, S)).astype(np.float32),
        "behaviour_logp": rng.uniform(-5.0, -4.0, size=(frags, S)).astype(
            np.float32)}
    dones = np.zeros((frags, S), np.float32)
    dones[:, -1] = 1.0
    batch = {
        sb.OBS: jnp.asarray(ref_batch["tokens"].reshape(-1), jnp.int32),
        sb.ACTIONS: jnp.asarray(ref_batch["actions"].reshape(-1), jnp.int32),
        sb.REWARDS: jnp.asarray(ref_batch["rewards"].reshape(-1)),
        sb.DONES: jnp.asarray(dones.reshape(-1)),
        sb.ACTION_LOGP: jnp.asarray(ref_batch["behaviour_logp"].reshape(-1)),
        sb.VF_PREDS: jnp.zeros(frags * S, jnp.float32),
        sb.BOOTSTRAP_OBS: jnp.asarray(walk[:, S], jnp.int32)}
    return batch, ref_batch


def test_vtrace_minibatch_loss_and_gradients_match_reference(token_trainer):
    """One minibatch of whole episodes through the system's loss (packed
    rows, ACTION_LOGP, the bootstrap step differentiated through every
    kind of state) and through `jax.grad` of the plain reference, every
    parameter to 2e-3 of its gradient's largest entry (float32: two orders
    of the same sums through four blocks and a scan of S positions; a
    bfloat16 block anywhere reads 1e-1); the model has no constants, and
    every parameter has its two moments."""
    policy = token_trainer.get_policy()
    batch, ref_batch = seeded_batch(B, 5)
    variables = jax.tree.map(jnp.asarray, policy.get_weights())
    assert set(variables) == {"params"}
    (total, stats), grads = jax.value_and_grad(
        lambda v: vtrace_loss(policy, v, batch, None, {}),
        has_aux=True)(variables)
    (want_total, _), want_grads = jax.value_and_grad(
        lambda v: reference.vtrace_loss(v, ref_batch, NET, policy.config),
        has_aux=True)(variables)
    np.testing.assert_allclose(total, want_total, rtol=1e-4)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads["params"])
    want_flat = jax.tree.leaves(want_grads["params"])
    assert len(flat) == len(want_flat)
    for (path, got), want in zip(flat, want_flat):
        scale = float(jnp.max(jnp.abs(want))) + 1e-8
        assert float(jnp.max(jnp.abs(got - want))) <= 2e-3 * scale, path
    moments = [leaf for leaf in jax.tree.leaves(policy.opt_state)
               if leaf.dtype == jnp.float32]
    assert len(moments) == 2 * len(jax.tree.leaves(variables["params"]))
    assert stats["expert_load_mean"] > 0
    assert 0.0 < stats["experts_held_row_share"] < 1.0


# Compiled once a module: the optimizer's step, and the reference's loss
# and gradient by what its loss depends on (the planted error, the value
# loss's weight; a clip or a learning rate changes Adam's side alone).
_COMPILED = {}


def one_update(trainer, seed=7, **wrong):
    """One update of seeded whole episodes by the optimizer's own step
    (`AnakinOptimizer.learn`) from the trainer's parameters and optimizer
    state, against the reference's loss, gradients and Adam: what the
    benchmark's driver does at the cell's minibatch. `wrong` plants a
    fault in the reference's side."""
    policy, opt = trainer.get_policy(), trainer.optimizer
    cfg = dict(policy.config, **wrong.get("cfg", {}))
    batch, ref_batch = seeded_batch(opt.minibatch // opt.T, seed)

    def flat(tree):
        return {jax.tree_util.keystr(path): np.asarray(leaf)
                for path, leaf in
                jax.tree_util.tree_flatten_with_path(tree)[0]}
    before = policy.params
    (adam,) = [s for s in jax.tree.leaves(
        policy.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
        if hasattr(s, "mu")]
    if "learn" not in _COMPILED:
        _COMPILED["learn"] = jax.jit(opt.learn)
    after, _, stats = _COMPILED["learn"](
        before, policy.opt_state, batch, jax.random.PRNGKey(0))
    key = (wrong.get("mutate"), cfg["vf_loss_coeff"])
    if key not in _COMPILED:
        _COMPILED[key] = jax.jit(jax.value_and_grad(
            lambda p, ref_batch: reference.vtrace_loss(
                {"params": p}, ref_batch, NET, cfg,
                mutate=wrong.get("mutate")), has_aux=True))
    (want_loss, _), grads = _COMPILED[key](before["params"], ref_batch)
    count = int(adam.count)
    assert count > 0
    want_change, norm = reference.adam_update(
        flat(grads), flat(adam.mu["params"]), flat(adam.nu["params"]),
        count, cfg)
    assert norm > 0
    old, new = flat(before["params"]), flat(after["params"])
    return reference.compare_update(stats["total_loss"], want_loss, {
        name: float(reference.change_error(old[name], new[name], want))
        for name, want in want_change.items()})


def test_one_update_by_the_optimizer_s_own_step_matches_reference(
        token_trainer):
    token_trainer.train()  # Adam's moments are not zero
    found = one_update(token_trainer)
    assert found["ok"], found
    assert found["loss_error"] < 1e-5 and found["update_error"] < 1e-2, found


WRONG_UPDATES = {
    "taps_reversed_in_the_gradient": dict(mutate="taps_reversed"),
    "a_decay_a_key_head": dict(mutate="decay_a_key_head"),
    "beta_out_of_the_subtraction": dict(mutate="beta_out_of_subtraction"),
    "the_whole_head_rotated": dict(mutate="rope_whole_head"),
    "no_attention_gate": dict(mutate="no_attention_gate"),
    "norms_not_zero_centred": dict(mutate="norms_not_zero_centred"),
    "vf_coeff_doubled": dict(cfg={"vf_loss_coeff": 1.0}, by="loss_error"),
    "no_clip": dict(cfg={"grad_clip": None}, by="update_error"),
    "ten_times_the_lr": dict(cfg={"lr": 6e-3}, by="update_error"),
}


@pytest.mark.parametrize("wrong", WRONG_UPDATES)
def test_update_limits_refuse_a_wrong_update(wrong, token_trainer):
    """The comparison of one update fails each named error, planted in
    the reference's side: by the loss, by the worst parameter's change, or
    by either."""
    token_trainer.train()
    fault = dict(WRONG_UPDATES[wrong])
    by = fault.pop("by", None)
    found = one_update(token_trainer, **fault)
    assert not found["ok"], found
    if by:
        limits = {"loss_error": reference.UPDATE_LOSS_TOLERANCE,
                  "update_error": reference.UPDATE_TOLERANCE}
        assert found[by] > limits[by], found


def test_qwen3_next_token_trainer_trains_on_the_fused_path(token_trainer):
    """`IMPALATrainer(anakin, TokenBigram-v0, qwen3_next)` by config alone:
    two iterations, a finite loss, a rising count, a policy state of three
    kinds of leaf (a float32 matrix state beside the blocks' own) carried
    by the optimizer as one pytree, the new counters in `learner_stats`."""
    counts = []
    for _ in range(2):
        result = token_trainer.train()
        stats = result["info"]["learner"]
        assert np.isfinite(stats["total_loss"])
        counts.append(result["timesteps_total"])
    assert counts[1] - counts[0] == 4 * S and counts[0] > 0
    kept = token_trainer.optimizer.learner_stats
    assert kept["expert_load_max"] >= kept["expert_load_mean"] > 0
    # 4 of 16 experts held: about a quarter of the (row, expert) pairs.
    assert 0.05 < kept["experts_held_row_share"] < 0.6
    # What the learner's product gathered: all, in the batched form these
    # sizes take.
    assert kept["dispatch_rows_share"] == 1.0
    assert kept["experts_grouped_kernel"] == 0.0  # this is no TPU
    assert kept["decode_rows_per_expert"] == 4 * 3 / 16
    assert kept["decode_cache_read_share"] == 1.0
    assert kept["causal_attention_fused"] == 0.0
    assert kept["state_step_kernel"] == 0.0
    # float32 here: one layer's K and V of 2 heads of 16 a position; three
    # layers' 3 x 128 inputs and 4 x 16 x 16 matrices a sequence.
    assert (kept["kv_cache_bytes_per_token"], kept["kv_groups"]) == (
        2 * 2 * 16 * 4, 2)
    assert (kept["conv_layers"], kept["conv_state_bytes_per_row"]) == (
        3, 3 * 3 * 128 * 4)
    assert (kept["gdn_layers"], kept["gdn_state_bytes_per_row"],
            kept["gdn_chunk"]) == (3, 3 * 4 * 16 * 16 * 4, CHUNK)
    state, _ = token_trainer.optimizer._pstate
    assert set(state) == {"kv", "conv", "gdn", "pos"}
    assert state_shapes(state) == STATE_SHAPES
    assert any(np.any(np.asarray(a)) for a in jax.tree.leaves(state["gdn"]))
    # What the benchmark's three readers of the state make of it.
    caches = jax.tree.leaves(state["kv"])
    assert sum(c.nbytes for c in caches) / (4 * S) == 256
    assert sum(c.nbytes for c in jax.tree.leaves(state["conv"])) / 4 == 4608
    assert sum(c.nbytes for c in jax.tree.leaves(state["gdn"])) / 4 == 12288
