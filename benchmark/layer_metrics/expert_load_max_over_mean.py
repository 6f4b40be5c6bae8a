"""Rows of the fullest expert group over the mean group, in the learner's
grouped products of the newest call (the largest over the call's
minibatches and layers; `expert_load_max` / `expert_load_mean` of the
optimizer's `learner_stats`, counted by the model). 1 is a balanced router;
the grouped product's time follows the rows, not the balance, but an expert
that takes everything starves the others of gradient. Layer: moe_dispatch."""

UNIT = "ratio"
LAYER = "moe_dispatch"
SOURCE = "program_counter"
BETTER = "lower"


def read(ctx, state):
    stats = getattr(ctx.session.optimizer, "learner_stats", None) or {}
    top, mean = stats.get("expert_load_max"), stats.get("expert_load_mean")
    if not top or not mean:
        return None
    return float(top) / float(mean)
