"""Share of the window the learner thread spent waiting for a train batch
(`LearnerThread.queue_timer`, a blocking `get` on the learner queue).
Layer: the async learner."""

from lib.counters import share_pct

UNIT = "%"
LAYER = "async_learner"
SOURCE = "program_counter"
BETTER = "lower"


def begin(ctx):
    learner = getattr(ctx.session.optimizer, "learner", None)
    timer = getattr(learner, "queue_timer", None)
    return None if timer is None else timer.total


def read(ctx, state):
    return share_pct(state, begin(ctx), ctx.window_s)
