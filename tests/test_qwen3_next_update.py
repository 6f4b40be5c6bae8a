"""The `qwen3_next` token policy's loss and loop at a tiny size on the CPU
(the model against its reference: `tests/test_qwen3_next_policy.py`, whose
row this file shares): the family's shared checks of V-trace's loss and its
gradients through the learner's pass (the scan over chunks under one decay a
head, the gated attention, the bootstrap step through every kind of state;
the model has no constants, and every parameter has its two moments), of one
update by the optimizer's own step and of the wrong updates its limits refuse
(`tests/token_families.py`), and the trainer on the fused Anakin path, a
float32 matrix state in the scan's carry.
"""

import jax
import numpy as np
from test_qwen3_next_policy import (  # noqa: F401
    CHUNK, FAMILY, S, solve_in_blocks_of_four)
from token_families import (  # noqa: F401: pytest collects what is named
    test_vtrace_minibatch_loss_and_gradients_match_reference,
    test_one_update_by_the_optimizer_s_own_step_matches_reference,
    test_update_limits_refuse_a_wrong_update,
    state_shapes, two_iterations)


def test_qwen3_next_token_trainer_trains_on_the_fused_path(token_trainer):
    """Two iterations by config alone (`token_families.two_iterations`), a
    policy state of three kinds of leaf (a float32 matrix state beside the
    blocks' own) carried by the optimizer as one pytree, the counters in
    `learner_stats`."""
    _, kept = two_iterations(FAMILY, token_trainer)
    # 4 of 16 experts held: about a quarter of the (row, expert) pairs.
    assert 0.05 < kept["experts_held_row_share"] < 0.6
    # What the learner's product gathered: all, in the batched form these
    # sizes take.
    assert kept["dispatch_rows_share"] == 1.0
    assert kept["experts_grouped_kernel"] == 0.0  # this is no TPU
    assert kept["decode_rows_per_expert"] == 4 * 3 / 16
    assert kept["decode_cache_read_share"] == 1.0
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
    assert state_shapes(FAMILY, state) == FAMILY.state_shapes(S)
    assert any(np.any(np.asarray(a)) for a in jax.tree.leaves(state["gdn"]))
    # What the benchmark's three readers of the state make of it.
    caches = jax.tree.leaves(state["kv"])
    assert sum(c.nbytes for c in caches) / (4 * S) == 256
    assert sum(c.nbytes for c in jax.tree.leaves(state["conv"])) / 4 == 4608
    assert sum(c.nbytes for c in jax.tree.leaves(state["gdn"])) / 4 == 12288
