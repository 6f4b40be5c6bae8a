"""Bytes of convolution state the rollout's policy state holds for one
sequence, all convolution layers together: the bytes of the leaves under the
state's "conv" key (`[rows, taps - 1, hidden]` each, as the Anakin optimizer
keeps them on the device between calls) over the rows. Four layers of two
gated inputs of 2,048 bfloat16 values read 32,768, whatever the sequences'
length; a state that grew with the length (a cache of gated inputs, 4,096
positions) would read 67,108,864. It guards the state against one with a
positions axis. A policy state without the key (every model whose layers are
all attention, and every program before PR 38) reads nothing.
Layer: policy_state."""

UNIT = "bytes"
LAYER = "policy_state"
SOURCE = "program_counter"
BETTER = "lower"


def read(ctx, state):
    pstate = getattr(ctx.session.optimizer, "_pstate", None)
    if not pstate or not isinstance(pstate[0], dict) \
            or "conv" not in pstate[0]:
        return None
    import jax
    held = jax.tree.leaves(pstate[0]["conv"])
    if not held:
        return None
    return sum(a.nbytes for a in held) / float(held[0].shape[0])
