"""Share of the device's busy seconds in the traced slice that went to Gated
DeltaNet: self time of the ops whose innermost `policy/*` scope is
`policy/gdn` (the operator's norm, projections, convolution, gates, output
norm and output projection) or `policy/gdn_state` (what touches the matrix
states: a decode step's decay, rank-one update and read; the learner's
chunks, solves and the scan between them, with their transposes), in the
rollout's decode steps and in the learner's passes alike, from the program's
own account of the harness's trace. What the Gated DeltaNet layers'
feed-forwards cost stands under other scopes. A program without the scopes
(every model without such a layer, and every program before PR 52) reads
nothing. Layer: the programs."""

from layer_metrics import program_account

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"
BETTER = "lower"

SCOPES = ("policy/gdn", "policy/gdn_state")

begin = program_account.begin


def gdn_seconds(acct):
    return sum(s for row, s in acct["scopes"].items()
               if row.split("|")[-1] in SCOPES)


def read(ctx, state):
    # No op under the scopes: the metric is left out, not read as 0.
    return program_account.share_of_busy(ctx, gdn_seconds) or None
