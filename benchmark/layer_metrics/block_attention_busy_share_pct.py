"""Share of the device's busy seconds in the traced slice that went to the
attention of a policy that generates a block of positions a step: self time
of the ops whose innermost `policy/*` scope is `policy/block_attention` (a
block step's norm, projections, QK-norm, RoPE, the write of the block's keys
and values, the read of the cache by the block's folded queries and the
output projection; the learner's same over its clean and noisy streams, the
fused kernel under the stream mask and its backward kernel), in the rollout
and in the learner alike, from the program's own account of the harness's
trace. A program without the scope (every policy that yields one token a
step, and every program before PR 48) reads nothing. Layer: the programs."""

from layer_metrics import program_account

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"
BETTER = "lower"

SCOPES = ("policy/block_attention",)

begin = program_account.begin


def attention_seconds(acct):
    return sum(s for row, s in acct["scopes"].items()
               if row.split("|")[-1] in SCOPES)


def read(ctx, state):
    # No op under the scope: the metric is left out, not read as 0.
    return program_account.share_of_busy(ctx, attention_seconds) or None
