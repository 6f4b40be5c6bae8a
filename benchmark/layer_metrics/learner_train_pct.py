"""Share of the window the learner thread spent dispatching an update and
reading its stats back: phases `learner.train` + `learner.readback` of the
thread's `PhaseClock` (`optimizer.learner.clock`). With `learner_wait_pct`
(= `learner.dequeue`), `learner_h2d_pct` and `learner_lock_wait_pct` it
partitions the thread. Layer: the async learner."""

from layer_metrics import program_account
from lib.counters import share_pct

UNIT = "%"
LAYER = "async_learner"
SOURCE = "program_counter"
BETTER = "higher"
PHASES = ("learner.train", "learner.readback")


def begin(ctx):
    return program_account.learner_seconds(ctx, PHASES)


def read(ctx, state):
    return share_pct(state, begin(ctx), ctx.window_s)
