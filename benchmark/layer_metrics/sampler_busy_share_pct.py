"""Share of the device's busy seconds in the traced slice that went to the
sampler's programs: self time of the ops under a `sebulba/*` scope (apply,
select, pack) or under `policy/action` alone, from the program's own
account of the harness's trace; the rest is the learner's `train/*` and
what has no scope. Layer: the programs."""

from layer_metrics import program_account

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"
BETTER = "lower"


begin = program_account.begin


def sampler_seconds(acct):
    return sum(s for row, s in acct["scopes"].items()
               if row.startswith("sebulba/") or row == "policy/action")


def read(ctx, state):
    return program_account.share_of_busy(ctx, sampler_seconds)
