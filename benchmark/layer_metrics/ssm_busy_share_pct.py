"""Share of the device's busy seconds in the traced slice that went to the
Mamba-2 layers: self time of the ops whose innermost `policy/*` scope is
`policy/mamba2` (the operator's norm, projections, gate, group norm and
output) or `policy/ssm_state` (what touches the matrix states: a decode
step's decay, outer product and read; the learner's chunks and the scan
between them, with their transposes), in the rollout's decode steps and in
the learner's passes alike, from the program's own account of the harness's
trace. The layer's convolution stands under `policy/short_conv`. A program
without the scopes (every model without such a layer, and every program
before PR 45) reads nothing. Layer: the programs."""

from layer_metrics import program_account

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"
BETTER = "lower"

SCOPES = ("policy/mamba2", "policy/ssm_state")

begin = program_account.begin


def ssm_seconds(acct):
    return sum(s for row, s in acct["scopes"].items()
               if row.split("|")[-1] in SCOPES)


def read(ctx, state):
    # No op under the scopes: the metric is left out, not read as 0.
    return program_account.share_of_busy(ctx, ssm_seconds) or None
