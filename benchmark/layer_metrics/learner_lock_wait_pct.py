"""Share of the window the learner thread waited for the policy's update
lock (an actor thread holds it while it dispatches a selection): phase
`learner.lock_wait` of its `PhaseClock`. Layer: the async learner."""

from layer_metrics import program_account
from lib.counters import share_pct

UNIT = "%"
LAYER = "async_learner"
SOURCE = "program_counter"
BETTER = "lower"
PHASES = ("learner.lock_wait",)


def begin(ctx):
    return program_account.learner_seconds(ctx, PHASES)


def read(ctx, state):
    return share_pct(state, begin(ctx), ctx.window_s)
