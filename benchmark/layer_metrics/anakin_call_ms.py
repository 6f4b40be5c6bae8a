"""Mean host time of one fused Anakin call over the window: a host clock
around a dispatch that ends in a stats readback, so it is the program's
run time plus dispatch. Layer: the Anakin loop."""

UNIT = "ms"
LAYER = "anakin_loop"
SOURCE = "program_counter"
BETTER = "lower"


def _totals(ctx):
    opt = ctx.session.optimizer
    if not hasattr(opt, "_grad_calls"):
        return None
    return opt._grad_time_total, opt._grad_calls


def begin(ctx):
    return _totals(ctx)


def read(ctx, state):
    now = _totals(ctx)
    if state is None or now is None or now[1] <= state[1]:
        return None
    return 1000.0 * (now[0] - state[0]) / (now[1] - state[1])
