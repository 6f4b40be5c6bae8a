"""Share of the window the inline actor threads were blocked putting a
fragment on a full learner queue: phase `sebulba.enqueue`, over window x
actors. The counterpart of `learner_wait_pct`: both near zero means the
host loop itself sets the pace.
Layer: the Sebulba sampler."""

from lib import phases

UNIT = "%"
LAYER = "sebulba_sampler"
SOURCE = "program_counter"
BETTER = "lower"
PHASES = ("sebulba.enqueue",)


def begin(ctx):
    return phases.begin(ctx, PHASES)


def read(ctx, state):
    return phases.share(ctx, state, PHASES)
