"""Share of the device's busy seconds in the traced slice that the
program's own names leave dark: self time of the ops whose `tf_op` holds no
`anakin/*`, `train/*`, `sebulba/*` or `policy/*` scope (`unscoped_s` of the
program's account; its `unscoped|<op kind>` rows say which ops). Layer: the
programs."""

from layer_metrics import program_account

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"
BETTER = "lower"


begin = program_account.begin


def read(ctx, state):
    return program_account.share_of_busy(ctx, lambda a: a["unscoped_s"])
