"""The `smallthinker` token policy's loss and loop at a tiny size on the CPU
(the model against its reference: `tests/test_smallthinker_policy.py`, whose
row this file shares): the family's shared checks of V-trace's loss and its
gradients, of one update by the optimizer's own step and of the wrong updates
its limits refuse (`tests/token_families.py`), and what is its own: the
trainer on the fused Anakin path, caches that differ in length by layer, and
what its learner's stats say the grouped kernel read.
"""

import jax
import numpy as np
import pytest
from test_smallthinker_policy import CACHES, FAMILY, WINDOW, S  # noqa: F401
from token_families import (  # noqa: F401: pytest collects what is named
    test_vtrace_minibatch_loss_and_gradients_match_reference,
    test_one_update_by_the_optimizer_s_own_step_matches_reference,
    test_update_limits_refuse_a_wrong_update,
    token_trainer_config, two_iterations)

from ray_tpu.rllib.agents.impala import IMPALATrainer


def test_smallthinker_token_trainer_trains_on_the_fused_path(token_trainer):
    """Two iterations by config alone (`token_families.two_iterations`), a
    policy state whose caches differ in length by layer, the counters in
    `learner_stats`."""
    _, kept = two_iterations(FAMILY, token_trainer)
    # 2 of 8 experts held: about a quarter of the (row, expert) pairs.
    assert 0.05 < kept["experts_held_row_share"] < 0.6
    # What the learner's product gathered: all, in the batched form these
    # sizes take.
    assert kept["dispatch_rows_share"] == 1.0
    assert kept["experts_grouped_kernel"] == 0.0  # this is no TPU
    assert kept["decode_rows_per_expert"] == 4 * 2 / 8
    assert kept["decode_cache_block"] == S
    # One block a cache: the full layer's 24 positions, a ring's 8 of 24.
    assert kept["decode_cache_read_share_full"] == 1.0
    assert kept["decode_cache_read_share_window"] == pytest.approx(1 / 3)
    assert kept["decode_cache_read_share"] == pytest.approx(0.5)
    assert (kept["window_layers"], kept["kv_groups"]) == (3, 4)
    # float32 here: 2 x 2 heads x 16 x 4 B a position a layer.
    assert kept["kv_cache_bytes_per_token"] == 256 * (S + 3 * WINDOW) / S
    state, _ = token_trainer.optimizer._pstate
    assert [c.shape for c in jax.tree.leaves(state["kv"])] == [
        (4,) + shape for shape in CACHES for _ in range(2)]


def test_learner_stats_report_what_the_grouped_kernel_read(kernel_here):
    """The trainer on the fused Anakin path with the kernel form in its
    rollout and under its learner's bootstrap step, a layer at a time: the
    full cache, three blocks of 8, fills from empty and is read 1/2 +
    block / 2S of; a ring of one block takes no kernel and is read
    whole."""
    trainer = IMPALATrainer(config=token_trainer_config(FAMILY))
    try:
        result = trainer.train()
        assert np.isfinite(result["info"]["learner"]["total_loss"])
        kept = trainer.optimizer.learner_stats
        assert kept["decode_cache_read_share_full"] == pytest.approx(
            0.5 + 8 / (2 * S))
        assert kept["decode_cache_read_share_window"] == pytest.approx(
            WINDOW / S)
        # The host's counters are of the platform the trainer runs on.
        assert kept["decode_attention_kernel"] == 0.0
        assert kept["decode_cache_block"] == S
    finally:
        trainer.stop()
