"""The `laguna` token policy's loss and loop at a tiny size on the CPU (the
model against its reference: `tests/test_laguna.py`, whose row this file
shares): the family's shared checks of V-trace's loss and its gradients
(every parameter's, the gates', both kinds' W_q and the routers' among them;
the bootstrap step differentiated through the full caches and the rings), of
one update by the optimizer's own step and of the wrong updates its limits
refuse (`tests/token_families.py`), and the trainer on the fused Anakin path.
"""

import jax
import pytest
from test_laguna import CACHES, FAMILY, WINDOW, S  # noqa: F401
from token_families import (  # noqa: F401: pytest collects what is named
    test_vtrace_minibatch_loss_and_gradients_match_reference,
    test_one_update_by_the_optimizer_s_own_step_matches_reference,
    test_update_limits_refuse_a_wrong_update,
    two_iterations)


def test_laguna_token_trainer_trains_on_the_fused_path(token_trainer):
    """Two iterations by config alone (`token_families.two_iterations`), a
    policy state whose caches differ in length by layer, the counters in
    `learner_stats`."""
    _, kept = two_iterations(FAMILY, token_trainer)
    # 4 of 16 experts held: about a quarter of the (row, expert) pairs.
    assert 0.05 < kept["experts_held_row_share"] < 0.6
    assert kept["dispatch_rows_share"] == 1.0
    assert kept["decode_rows_per_expert"] == 4 * 3 / 16
    assert kept["decode_cache_block"] == S
    # One block a cache: a full layer's 32 positions, a ring's 8 of 32.
    assert kept["decode_cache_read_share_full"] == 1.0
    assert kept["decode_cache_read_share_window"] == pytest.approx(1 / 4)
    assert kept["decode_cache_read_share"] == pytest.approx(
        (2 + 3 / 4) / 5)
    assert kept["decode_attention_kernel"] == 0.0  # this is no TPU
    assert (kept["window_layers"], kept["kv_groups"]) == (3, 2)
    # float32 here: 2 x 2 heads x 16 x 4 B a position a layer.
    assert kept["kv_cache_bytes_per_token"] == 256 * (
        2 * S + 3 * WINDOW) / S
    state, _ = token_trainer.optimizer._pstate
    assert [c.shape for c in jax.tree.leaves(state["kv"])] == [
        (4,) + shape for shape in CACHES for _ in range(2)]
