"""Arithmetic shared by the readers of the inline actor threads' phase
clocks: `sampler.transfer_stats()["phases"]` is a cumulative snapshot
(`seconds` per phase, `other_s` for what no phase covers), one per actor
thread. A program that keeps no such table gives nothing to read."""

from lib.counters import share_pct


def _actors(ctx):
    return getattr(ctx.session.optimizer, "_inline_actors", None) or []


def begin(ctx, names):
    """Seconds the actor threads have spent in `names` so far, summed over
    threads and names ("other" is the threads' uncovered remainder); None
    without actors or without a phase table."""
    actors = _actors(ctx)
    total = 0.0
    for actor in actors:
        snapshot = actor.sampler.transfer_stats().get("phases")
        if snapshot is None:
            return None
        for name in names:
            total += (snapshot["other_s"] if name == "other"
                      else snapshot["seconds"].get(name, 0.0))
    return total if actors else None


def share(ctx, state, names):
    """Share of window x actors spent in `names` since `state`."""
    return share_pct(state, begin(ctx, names), ctx.window_s,
                     len(_actors(ctx)))
