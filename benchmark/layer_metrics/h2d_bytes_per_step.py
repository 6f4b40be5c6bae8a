"""Bytes the inline actors uploaded for each env step sampled: delta
`bytes_h2d` / delta `steps` of `sampler.transfer_stats()` summed over
actors. The delta encoder's own number. Layer: the Sebulba sampler."""

UNIT = "bytes/step"
LAYER = "sebulba_sampler"
SOURCE = "program_counter"
BETTER = "lower"


def begin(ctx):
    actors = getattr(ctx.session.optimizer, "_inline_actors", None) or []
    if not actors:
        return None
    stats = [a.sampler.transfer_stats() for a in actors]
    return (sum(s["bytes_h2d"] for s in stats),
            sum(s["steps"] for s in stats))


def read(ctx, state):
    now = begin(ctx)
    if state is None or now is None or now[1] <= state[1]:
        return None
    return (now[0] - state[0]) / (now[1] - state[1])
