"""The `glm4_moe_lite` token policy's loss and loop at a tiny size on the CPU
(the model against its reference: `tests/test_glm4_moe_lite_policy.py`, whose
row this file shares): the family's shared checks of one update by the
optimizer's own step and of the wrong updates its limits refuse
(`tests/token_families.py`), and what is its own: V-trace with the model's own
term, the reference's Adam against the optimizer's chain, what the update's
comparison allows, and the trainer on the fused Anakin path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_glm4_moe_lite_policy import FAMILY, LATENT, B, S, reference
from token_families import (  # noqa: F401: pytest collects what is named
    reference_loss_and_gradient, seeded_batch,
    test_one_update_by_the_optimizer_s_own_step_matches_reference,
    test_update_limits_refuse_a_wrong_update, two_iterations)

from ray_tpu.rllib.agents.impala.vtrace_policy import vtrace_loss


def test_vtrace_minibatch_loss_with_the_model_s_term_matches_reference(
        token_trainer):
    """One minibatch of whole episodes through the system's loss (packed
    rows, ACTION_LOGP, the bootstrap step through the latent cache, the
    model's "losses" added) and through `jax.grad` of the plain reference;
    the router bias has no gradient and no optimizer state."""
    policy = token_trainer.get_policy()
    batch, ref_batch = seeded_batch(FAMILY, B, 5)
    variables = jax.tree.map(jnp.asarray, policy.get_weights())
    assert set(variables) == {"params", "constants"}
    (total, stats), grads = jax.jit(jax.value_and_grad(
        lambda v: vtrace_loss(policy, v, batch, None, {}),
        has_aux=True))(variables)
    (want_total, parts), want_grads = reference_loss_and_gradient(
        FAMILY, policy.config)(
            variables["params"], {"constants": variables["constants"]},
            ref_batch)
    np.testing.assert_allclose(total, want_total, rtol=1e-4)
    np.testing.assert_allclose(
        stats["mtp_loss"] * B * (S - 2), parts["nextn_nll"], rtol=1e-4)
    # The model's term is in the total: without it the two differ.
    assert abs(float(total) - float(
        want_total - reference.NEXTN_LOSS_WEIGHT * parts["nextn_nll"])) \
        > 1e-2 * abs(float(total))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads["params"])
    want_flat = jax.tree.leaves(want_grads)
    assert len(flat) == len(want_flat)
    for (path, got), want in zip(flat, want_flat):
        scale = float(jnp.max(jnp.abs(want))) + 1e-8
        assert float(jnp.max(jnp.abs(got - want))) <= 2e-3 * scale, path
    assert not any(bool(jnp.any(g != 0))
                   for g in jax.tree.leaves(grads["constants"]))
    # Adam's moments exist for the parameters alone.
    moments = [leaf for leaf in jax.tree.leaves(policy.opt_state)
               if leaf.dtype == jnp.float32]
    assert len(moments) == 2 * len(jax.tree.leaves(variables["params"]))
    assert stats["expert_load_mean"] > 0
    assert 0.0 < stats["experts_held_row_share"] < 1.0

@pytest.mark.parametrize("steps_before", [0, 3])
@pytest.mark.parametrize("clip", [None, 0.5])
def test_reference_adam_update_is_the_optimizer_s(clip, steps_before):
    """`reference.adam_update` (plain numpy) against the chain the policy
    builds (`default_optimizer`: clip by global norm, Adam), from fresh
    moments and from moments three updates old."""
    from ray_tpu.rllib.policy.jax_policy import default_optimizer
    cfg = {"lr": 3e-3, "grad_clip": clip}
    tx = default_optimizer(cfg)
    keys = jax.random.split(jax.random.PRNGKey(4), 2 * (steps_before + 1))
    params = {"a": jax.random.normal(keys[0], (5, 7)),
              "b": jax.random.normal(keys[1], (3,))}

    def grads_of(i):
        return {"a": jax.random.normal(keys[2 * i], (5, 7)),
                "b": 3.0 * jax.random.normal(keys[2 * i + 1], (3,))}
    state = tx.init(params)
    for i in range(steps_before):
        _, state = tx.update(grads_of(i), state, params)
    (adam,) = [s for s in jax.tree.leaves(
        state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    grads = grads_of(steps_before)
    want, _ = tx.update(grads, state, params)
    got, norm = reference.adam_update(
        grads, adam.mu, adam.nu, int(adam.count), cfg)
    assert int(adam.count) == steps_before and norm > (clip or 0)
    for name in params:
        np.testing.assert_allclose(got[name], want[name], rtol=2e-5)


@pytest.mark.parametrize("moved_by", [0.4, 3.0])
def test_update_comparison_allows_float32_storage_and_nothing_more(
        moved_by):
    """A scale of 1.0 that an update moves by less than half its float32
    spacing (6e-8) stays where it is, and that is not held against the
    update; a change that is wrong by more than the spacing is."""
    spacing = float(np.spacing(np.float32(1.0)))
    before = np.ones(64, np.float32)
    want = np.full(64, moved_by * spacing, np.float32)
    after = (before.astype(np.float64) + want).astype(np.float32)
    assert float(reference.change_error(before, after, want)) == 0.0
    if moved_by > 0.5:  # the same change with its sign wrong
        wrong = (before.astype(np.float64) - want).astype(np.float32)
        error = float(reference.change_error(before, wrong, want))
        assert error > 1.0
        assert not reference.compare_update(1.0, 1.0, {"scale": error})["ok"]
    else:  # the parameter has not moved at all
        assert np.array_equal(after, before)


def test_glm_token_trainer_trains_on_the_fused_path(token_trainer):
    """Two iterations by config alone (`token_families.two_iterations`),
    the counters in `learner_stats`, and a selection bias that no update
    has moved."""
    policy = token_trainer.get_policy()
    bias_before = jax.tree.map(np.asarray, policy.get_weights()["constants"])
    head_before = np.asarray(policy.get_weights()["params"]["head"])
    _, kept = two_iterations(FAMILY, token_trainer)
    # 2 of 8 experts held: about a quarter of the (row, expert) pairs.
    assert 0.05 < kept["experts_held_row_share"] < 0.6
    # What the learner's product gathered: all, in the batched form these
    # sizes take.
    assert kept["dispatch_rows_share"] == 1.0
    assert kept["experts_grouped_kernel"] == 0.0  # this is no TPU
    assert 3.0 < kept["mtp_loss"] < 6.0  # ln 96 = 4.56 at random weights
    assert kept["decode_rows_per_expert"] == 8 * 2 / 8
    assert kept["decode_experts_batched"] == 1.0
    assert kept["decode_cache_block"] == S
    assert kept["decode_cache_read_share"] == 1.0
    assert kept["latent_cache_bytes_per_token"] == 3 * LATENT * 4
    # The rollout's state: the latents, [rows, window, 24] a layer.
    state, _ = token_trainer.optimizer._pstate
    assert [c.shape for c in jax.tree.leaves(state["kv"])] == [
        (8, S, LATENT)] * 3
    after = policy.get_weights()
    for a, b in zip(jax.tree.leaves(bias_before),
                    jax.tree.leaves(after["constants"])):
        assert np.array_equal(a, np.asarray(b))
    assert not np.array_equal(head_before, np.asarray(after["params"]["head"]))
