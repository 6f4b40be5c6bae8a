"""Share of the window the inline actor threads spent blocked fetching
actions from the device: `t_fetch_s` from `sampler.transfer_stats()` summed
over actors, over window x actors. Layer: the Sebulba sampler."""

from lib.counters import share_pct

UNIT = "%"
LAYER = "sebulba_sampler"
SOURCE = "program_counter"
BETTER = "lower"
KEY = "t_fetch_s"


def _actors(ctx):
    return getattr(ctx.session.optimizer, "_inline_actors", None) or []


def begin(ctx):
    actors = _actors(ctx)
    if not actors:
        return None
    return sum(a.sampler.transfer_stats()[KEY] for a in actors)


def read(ctx, state):
    return share_pct(state, begin(ctx), ctx.window_s, len(_actors(ctx)))
