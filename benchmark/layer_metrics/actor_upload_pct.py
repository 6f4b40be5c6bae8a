"""Share of the window the inline actor threads spent in `jax.device_put` of
host arrays (frame, done flags, packed delta, full rows): phase
`sebulba.upload` of each thread's clock, over window x actors.
Layer: the Sebulba sampler."""

from lib import phases

UNIT = "%"
LAYER = "sebulba_sampler"
SOURCE = "program_counter"
BETTER = "lower"
PHASES = ("sebulba.upload",)


def begin(ctx):
    return phases.begin(ctx, PHASES)


def read(ctx, state):
    return phases.share(ctx, state, PHASES)
