"""Share of the window the inline actor threads spent dispatching their two
programs (the call itself: launch and output allocation, not the device's
time): phases `sebulba.apply` + `sebulba.select`, over window x actors.
Layer: the Sebulba sampler."""

from lib import phases

UNIT = "%"
LAYER = "sebulba_sampler"
SOURCE = "program_counter"
BETTER = "lower"
PHASES = ("sebulba.apply", "sebulba.select")


def begin(ctx):
    return phases.begin(ctx, PHASES)


def read(ctx, state):
    return phases.share(ctx, state, PHASES)
