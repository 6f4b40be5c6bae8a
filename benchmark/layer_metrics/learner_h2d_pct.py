"""Share of the window the learner thread spent putting a train batch on
the device(s): phase `learner.h2d` of its `PhaseClock`. Layer: the async
learner."""

from layer_metrics import program_account
from lib.counters import share_pct

UNIT = "%"
LAYER = "async_learner"
SOURCE = "program_counter"
BETTER = "lower"
PHASES = ("learner.h2d",)


def begin(ctx):
    return program_account.learner_seconds(ctx, PHASES)


def read(ctx, state):
    return share_pct(state, begin(ctx), ctx.window_s)
