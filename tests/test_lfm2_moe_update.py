"""The `lfm2_moe` token policy's loss and loop at a tiny size on the CPU (the
model against its reference: `tests/test_lfm2_moe_policy.py`, whose row this
file shares): the family's shared checks of V-trace's loss and its gradients
(the bootstrap step differentiated through both kinds of state; the tied
embedding's gradient is the lookup's and the head's together; the router bias
has no gradient and no optimizer state), of one update by the optimizer's own
step and of the wrong updates its limits refuse (`tests/token_families.py`),
and what is its own: the trainer on the fused Anakin path, a policy state of
two kinds of leaf, and what its learner's stats say the grouped kernel read.
"""

import jax
import numpy as np
import pytest
from test_lfm2_moe_policy import FAMILY, S
from token_families import (  # noqa: F401: pytest collects what is named
    test_vtrace_minibatch_loss_and_gradients_match_reference,
    test_one_update_by_the_optimizer_s_own_step_matches_reference,
    test_update_limits_refuse_a_wrong_update,
    state_shapes, token_trainer_config, two_iterations)

from ray_tpu.rllib.agents.impala import IMPALATrainer


def test_lfm2_token_trainer_trains_on_the_fused_path(token_trainer):
    """Two iterations by config alone (`token_families.two_iterations`), a
    policy state of two kinds of leaf carried by the optimizer as one
    pytree, the counters in `learner_stats`."""
    _, kept = two_iterations(FAMILY, token_trainer)
    # 2 of 8 experts held: about a quarter of the (row, expert) pairs.
    assert 0.05 < kept["experts_held_row_share"] < 0.6
    # What the learner's product gathered: all, in the batched form these
    # sizes take.
    assert kept["dispatch_rows_share"] == 1.0
    assert kept["experts_grouped_kernel"] == 0.0  # this is no TPU
    assert kept["decode_rows_per_expert"] == 4 * 2 / 8
    assert kept["decode_cache_read_share"] == 1.0
    # float32 here: one layer's 2 x 2 heads x 8 x 4 B a position; four
    # layers' two rows of 64 x 4 B a sequence.
    assert kept["kv_cache_bytes_per_token"] == 128
    assert (kept["conv_layers"], kept["conv_state_bytes_per_row"]) == (
        4, 4 * 2 * 64 * 4)
    state, _ = token_trainer.optimizer._pstate
    assert set(state) == {"kv", "conv", "pos"}
    assert state_shapes(FAMILY, state) == FAMILY.state_shapes(S)
    # What the benchmark's two readers of the state make of it.
    caches = jax.tree.leaves(state["kv"])
    assert sum(c.nbytes for c in caches) / (4 * S) == 128
    assert sum(c.nbytes for c in jax.tree.leaves(state["conv"])) / 4 == 2048


def test_learner_stats_report_what_the_grouped_kernel_read(kernel_here):
    """The trainer on the fused Anakin path with the kernel form in its
    rollout and under its learner's bootstrap step: the one cache, three
    blocks of 8, fills from empty every rollout and is read 1/2 + block /
    2S of."""
    trainer = IMPALATrainer(config=token_trainer_config(FAMILY))
    try:
        result = trainer.train()
        assert np.isfinite(result["info"]["learner"]["total_loss"])
        kept = trainer.optimizer.learner_stats
        assert kept["decode_cache_read_share"] == pytest.approx(
            0.5 + 8 / (2 * S))
        # The host's counters are of the platform the trainer runs on.
        assert kept["decode_attention_kernel"] == 0.0
        assert kept["decode_cache_block"] == S
    finally:
        trainer.stop()
