"""Share of the device's busy seconds in the traced slice that went to the
gated short convolutions: self time of the ops whose innermost `policy/*`
scope is `policy/short_conv` (the operator's norm, its two projections, the
gates and the taps, in the rollout's decode steps and in the learner's
passes alike), from the program's own account of the harness's trace. What
the convolution layers' feed-forwards cost stands under other scopes. A
program without the scope (every model whose layers are all attention, and
every program before PR 38) reads nothing. Layer: the programs."""

from layer_metrics import program_account

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"
BETTER = "lower"

SCOPE = "policy/short_conv"

begin = program_account.begin


def short_conv_seconds(acct):
    return sum(s for row, s in acct["scopes"].items()
               if row.split("|")[-1] == SCOPE)


def read(ctx, state):
    # No op under the scope: the metric is left out, not read as 0.
    return program_account.share_of_busy(ctx, short_conv_seconds) or None
