"""Share of the device's busy seconds in the traced slice that went to the
rollout's block steps, the S denoising passes and the commit pass of every
block (`policy/block_denoise`, `policy/block_commit`), from the program's own
account of the harness's trace. The account names an op by its INNERMOST
`policy/*` scope, and a pass's layers stand under scopes of their own inside
the two (`policy/block_attention`, `policy/router`, `policy/experts_batched`,
`policy/head`, ...); so the passes' time is read as the decode half's
(`anakin/decode|*`: the block steps, the sampler and the env's steps between
them) of a program that HAS an op whose innermost scope is one of the two
(the embedding lookups, the sampler, the residual sums). The rest of the
busy time is the learner's. A program without the scopes (every policy that
yields one token a step, and every program before PR 48) reads nothing.
Layer: the programs."""

from layer_metrics import program_account

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"
BETTER = "lower"

SCOPES = ("policy/block_denoise", "policy/block_commit")
DECODE = "anakin/decode"

begin = program_account.begin


def block_step_seconds(acct):
    rows = acct["scopes"]
    if not any(row.split("|")[-1] in SCOPES for row in rows):
        return 0.0
    return sum(s for row, s in rows.items() if row.split("|")[0] == DECODE)


def read(ctx, state):
    # No op under the scopes: the metric is left out, not read as 0.
    return program_account.share_of_busy(ctx, block_step_seconds) or None
