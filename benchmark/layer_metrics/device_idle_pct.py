"""Share of the traced slice in which no XLA op ran on the device, mean
over the cell's chips: 100 * (1 - busy / window). Layer: the device."""

UNIT = "%"
LAYER = "device"
SOURCE = "device_trace"
BETTER = "lower"


def read(ctx, state):
    if not ctx.trace:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
