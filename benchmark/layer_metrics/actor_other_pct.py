"""Share of the window the inline actor threads spent in no phase at all
(100 less fetch, record, env step, upload, dispatch, lock wait, pack and
enqueue): `other_s` of each thread's clock, over window x actors. What no
span covers. Layer: the Sebulba sampler."""

from lib import phases

UNIT = "%"
LAYER = "sebulba_sampler"
SOURCE = "program_counter"
BETTER = "lower"
PHASES = ("other",)


def begin(ctx):
    return phases.begin(ctx, PHASES)


def read(ctx, state):
    return phases.share(ctx, state, PHASES)
