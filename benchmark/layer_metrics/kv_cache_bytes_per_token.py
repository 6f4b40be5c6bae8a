"""Bytes of key/value cache the rollout's policy state holds for one
position of one sequence's context, all layers together: the bytes of the
state's cache arrays (`[rows, positions, ...]` each, as the Anakin optimizer
keeps them on the device between calls) over rows x the LONGEST cache's
positions, which is the context. A layer that keeps every position of the
context counts its whole width; a window layer's ring counts for its own
length alone. One full layer and three rings of half the context, 4 heads
of 128 in bfloat16, read 2,048 + 3 x 1,024 = 5,120; with every layer keeping
every position they would read 8,192. It guards the rings against caches as
long as the context. Layer: policy_state."""

UNIT = "bytes"
LAYER = "policy_state"
SOURCE = "program_counter"
BETTER = "lower"


def read(ctx, state):
    pstate = getattr(ctx.session.optimizer, "_pstate", None)
    if not pstate or not isinstance(pstate[0], dict) or "kv" not in pstate[0]:
        return None
    import jax
    caches = jax.tree.leaves(pstate[0]["kv"])
    if not caches:
        return None
    rows, context = caches[0].shape[0], max(c.shape[1] for c in caches)
    return sum(c.nbytes for c in caches) / float(rows * context)
