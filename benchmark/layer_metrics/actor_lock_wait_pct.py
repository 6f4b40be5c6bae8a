"""Share of the window the inline actor threads waited for the policy's
update lock before dispatching a selection (the learner holds it while it
dispatches an update): phase `sebulba.lock_wait`, over window x actors.
Layer: the Sebulba sampler."""

from lib import phases

UNIT = "%"
LAYER = "sebulba_sampler"
SOURCE = "program_counter"
BETTER = "lower"
PHASES = ("sebulba.lock_wait",)


def begin(ctx):
    return phases.begin(ctx, PHASES)


def read(ctx, state):
    return phases.share(ctx, state, PHASES)
