"""Passes through the layers that the rollout pays a generated token, of a
policy that generates a block of positions a step (`decode_passes_per_token`
of the optimizer's `learner_stats`, which the model states from its static
shapes: (denoising passes + 1 commit pass) / block length). 2 denoising
passes and a commit over a block of 4 read 0.75; a commit fused into the next
block's first pass would read 0.5; an autoregressive decode, which states
nothing, pays 1 and is left out. Layer: the programs."""

UNIT = "passes/token"
LAYER = "programs"
SOURCE = "program_counter"
BETTER = "lower"


def read(ctx, state):
    stats = getattr(ctx.session.optimizer, "learner_stats", None) or {}
    passes = stats.get("decode_passes_per_token")
    return None if passes is None else float(passes)
