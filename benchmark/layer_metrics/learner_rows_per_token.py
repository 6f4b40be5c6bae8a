"""Rows through the layers that the learner pays a position of its
minibatch, of a policy that generates a block of positions a step
(`learner_rows_per_token` of the optimizer's `learner_stats`, which the
model states from its static shapes: the clean stream and one noisy stream a
denoising pass, less the clean stream's last layer, of which only the keys
and values are made: streams - 1 / layers). 2 passes over 5 layers read 2.8:
the experts see 2.8 times the minibatch's rows. A policy that learns one row
a token states nothing and is left out. Layer: the programs."""

UNIT = "rows/token"
LAYER = "programs"
SOURCE = "program_counter"
BETTER = "lower"


def read(ctx, state):
    stats = getattr(ctx.session.optimizer, "learner_stats", None) or {}
    rows = stats.get("learner_rows_per_token")
    return None if rows is None else float(rows)
