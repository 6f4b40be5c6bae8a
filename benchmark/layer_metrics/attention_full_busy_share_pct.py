"""Share of the device's busy seconds in the traced slice that went to the
FULL attention layers: self time of the ops whose innermost `policy/*` scope
is `policy/attention_full` (the layer's norm, its projections at that kind's
heads, the cache's write and the attention over every position so far in a
decode step, the fused causal kernel and its backward in the learner, W_o),
in the rollout's decode steps and in the learner's passes alike, from the
program's own account of the harness's trace. What a layer's rotation and
gate cost stands under `policy/rope` and `policy/attention_gate` where XLA
keeps them apart, its feed-forward under other scopes. A program without the
scope (every model whose layers are of one kind, and every program without
window layers) reads nothing. Layer: the programs."""

from layer_metrics import program_account

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"
BETTER = "lower"

SCOPE = "policy/attention_full"

begin = program_account.begin


def scope_seconds(acct, scope):
    return sum(s for row, s in acct["scopes"].items()
               if row.split("|")[-1] == scope)


def read(ctx, state):
    # No op under the scope: the metric is left out, not read as 0.
    return program_account.share_of_busy(
        ctx, lambda acct: scope_seconds(acct, SCOPE)) or None
