"""Share of a minibatch's (row, expert) pairs that landed on the experts
this chip holds, in the learner's pass of the newest call
(`experts_held_row_share` of the optimizer's `learner_stats`: the model
counts it where a layer holds a share of its experts; the mean over the
expert layers and the call's minibatches). held / experts under a uniform
router; the grouped products' rows, and so their time, follow it. Layer:
moe_dispatch."""

UNIT = "ratio"
LAYER = "moe_dispatch"
SOURCE = "program_counter"
BETTER = "lower"


def read(ctx, state):
    stats = getattr(ctx.session.optimizer, "learner_stats", None) or {}
    share = stats.get("experts_held_row_share")
    return None if share is None else float(share)
