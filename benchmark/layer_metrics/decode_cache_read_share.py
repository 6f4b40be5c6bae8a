"""Share of the window's positions that a decode step's attention read,
over the newest call's rollout (`decode_cache_read_share` of the
optimizer's `learner_stats`: the model counts it inside the step, from the
value that selects the blocks). A window that fills from empty in blocks of
b of S positions reads 1/2 + b/(2S) of itself; 1 is the window read whole
every step. Layer: programs."""

UNIT = "ratio"
LAYER = "programs"
SOURCE = "program_counter"
BETTER = "lower"


def read(ctx, state):
    stats = getattr(ctx.session.optimizer, "learner_stats", None) or {}
    share = stats.get("decode_cache_read_share")
    return None if share is None else float(share)
