"""Share of the device's busy seconds in the traced slice spent in
collectives (all-reduce, all-gather, reduce-scatter, collective-permute,
their `-start` / `-done` halves), mean over the chips: `collective_s` of
the program's own account of the harness's trace. Layer: the programs."""

from layer_metrics import program_account

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"
BETTER = "lower"


begin = program_account.begin


def read(ctx, state):
    return program_account.share_of_busy(ctx, lambda a: a["collective_s"])
