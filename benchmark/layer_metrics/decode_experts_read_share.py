"""Share of a layer's held experts whose matrices a rollout step's expert
products read, over the newest call's rollout (`decode_experts_read_share`
of the optimizer's `learner_stats`: the model counts, an expert layer a
step, the held experts that some row of the step chose, where the products
are `expert_step.chosen_kernel`'s; the mean over steps and expert layers).
The kernel's time is a straight line in it, so it is what a cell's rate
follows when the routers' weights change; 1 is every held expert read
every step, which is what the batched form does and what the program
states where the step is not the kernel's. Layer: moe_dispatch."""

UNIT = "ratio"
LAYER = "moe_dispatch"
SOURCE = "program_counter"
BETTER = "lower"


def read(ctx, state):
    stats = getattr(ctx.session.optimizer, "learner_stats", None) or {}
    share = stats.get("decode_experts_read_share")
    return None if share is None else float(share)
