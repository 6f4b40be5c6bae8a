"""Bytes of matrix state the rollout's policy state holds for one sequence,
all Gated DeltaNet layers together: the bytes of the leaves under the
state's "gdn" key (`[rows, value heads, d_k, d_v]` float32 each, as the
Anakin optimizer keeps them on the device between calls) over the rows.
Three layers of 32 value heads of 128 x 128 float32 values read 6,291,456,
whatever the sequences' length; the same state in bfloat16 would read half,
and one with a positions axis 4,096 times as much. It guards the state
against either. A policy state without the key (every model without such a
layer, and every program before PR 52) reads nothing. Layer: policy_state."""

UNIT = "bytes"
LAYER = "policy_state"
SOURCE = "program_counter"
BETTER = "lower"


def read(ctx, state):
    pstate = getattr(ctx.session.optimizer, "_pstate", None)
    if not pstate or not isinstance(pstate[0], dict) \
            or "gdn" not in pstate[0]:
        return None
    import jax
    held = jax.tree.leaves(pstate[0]["gdn"])
    if not held:
        return None
    return sum(a.nbytes for a in held) / float(held[0].shape[0])
