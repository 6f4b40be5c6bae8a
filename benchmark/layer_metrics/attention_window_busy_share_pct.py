"""Share of the device's busy seconds in the traced slice that went to the
WINDOW attention layers: self time of the ops whose innermost `policy/*`
scope is `policy/attention_window` (as `attention_full_busy_share_pct` reads
`policy/attention_full`: a decode step's attention over a ring of the
window, the learner's fused kernel over the tiles a window keeps), in the
rollout's decode steps and in the learner's passes alike, from the program's
own account of the harness's trace. A program without the scope reads
nothing. Layer: the programs."""

from layer_metrics import attention_full_busy_share_pct as full
from layer_metrics import program_account

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"
BETTER = "lower"

SCOPE = "policy/attention_window"

begin = program_account.begin


def read(ctx, state):
    return program_account.share_of_busy(
        ctx, lambda acct: full.scope_seconds(acct, SCOPE)) or None
