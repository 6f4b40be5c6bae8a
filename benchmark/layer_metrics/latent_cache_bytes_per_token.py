"""Bytes of cache the rollout's policy state holds for one position of one
sequence, all layers together: the bytes of the state's cache arrays
(`[rows, window, ...]` each, as the Anakin optimizer keeps them on the
device between calls) over rows x window. A latent cache of 5 layers x 576
bfloat16 values reads 5,760; the same layers' keys and values decompressed
would read 102,400. It guards the latent cache against a decompressed one.
Layer: policy_state."""

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
    rows, window = caches[0].shape[:2]
    return sum(c.nbytes for c in caches) / float(rows * window)
