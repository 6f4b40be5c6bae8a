"""The `kimi_linear` token policy's loss and loop at a tiny size on the CPU
(the model against its reference: `tests/test_kimi_linear_policy.py`, whose
row this file shares): the family's shared checks of V-trace's loss and its
gradients through the learner's pass (the scan over chunks, the latent layer,
the bootstrap step through every kind of state), of one update by the
optimizer's own step and of the wrong updates its limits refuse
(`tests/token_families.py`), and the trainer on the fused Anakin path, a
float32 matrix state in the scan's carry.
"""

import jax
import numpy as np
from test_kimi_linear_policy import (  # noqa: F401
    CHUNK, FAMILY, S, sub_blocks_of_four)
from token_families import (  # noqa: F401: pytest collects what is named
    test_vtrace_minibatch_loss_and_gradients_match_reference,
    test_one_update_by_the_optimizer_s_own_step_matches_reference,
    test_update_limits_refuse_a_wrong_update,
    state_shapes, two_iterations)


def test_kimi_linear_token_trainer_trains_on_the_fused_path(token_trainer):
    """Two iterations by config alone (`token_families.two_iterations`), a
    policy state of three kinds of leaf (a float32 matrix state beside the
    blocks' own) carried by the optimizer as one pytree, the counters in
    `learner_stats`."""
    _, kept = two_iterations(FAMILY, token_trainer)
    # 2 of 8 experts held: about a quarter of the (row, expert) pairs.
    assert 0.05 < kept["experts_held_row_share"] < 0.6
    # What the learner's product gathered: all, in the batched form these
    # sizes take.
    assert kept["dispatch_rows_share"] == 1.0
    assert kept["experts_grouped_kernel"] == 0.0  # this is no TPU
    assert kept["decode_rows_per_expert"] == 4 * 2 / 8
    assert kept["decode_cache_read_share"] == 1.0
    # float32 here: one latent layer's 24 values a position; four layers'
    # 3 x 192 inputs and 4 x 16 x 16 matrices a sequence.
    assert kept["latent_cache_bytes_per_token"] == 24 * 4
    assert (kept["conv_layers"], kept["conv_state_bytes_per_row"]) == (
        4, 4 * 3 * 192 * 4)
    assert (kept["kda_layers"], kept["kda_state_bytes_per_row"],
            kept["kda_chunk"]) == (4, 4 * 4 * 16 * 16 * 4, CHUNK)
    state, _ = token_trainer.optimizer._pstate
    assert set(state) == {"kv", "conv", "kda", "pos"}
    assert state_shapes(FAMILY, state) == FAMILY.state_shapes(S)
    assert any(np.any(np.asarray(a)) for a in jax.tree.leaves(state["kda"]))
    # What the benchmark's three readers of the state make of it.
    caches = jax.tree.leaves(state["kv"])
    assert sum(c.nbytes for c in caches) / (4 * S) == 24 * 4
    assert sum(c.nbytes for c in jax.tree.leaves(state["conv"])) / 4 == 9216
    assert sum(c.nbytes for c in jax.tree.leaves(state["kda"])) / 4 == 16384
