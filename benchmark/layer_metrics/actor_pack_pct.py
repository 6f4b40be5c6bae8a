"""Share of the window the inline actor threads spent assembling train
batches: retaining each step's columns (phase `sebulba.record`: list
appends, the per-step slice of the window's log-probs, episode
bookkeeping) and stacking them at fragment ends (phase `sebulba.pack`), over
window x actors.
Layer: the Sebulba sampler."""

from lib import phases

UNIT = "%"
LAYER = "sebulba_sampler"
SOURCE = "program_counter"
BETTER = "lower"
PHASES = ("sebulba.record", "sebulba.pack")


def begin(ctx):
    return phases.begin(ctx, PHASES)


def read(ctx, state):
    return phases.share(ctx, state, PHASES)
