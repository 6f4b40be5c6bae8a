"""Share of its memory roofline at which the rollout's decode steps moved the
Gated DeltaNet layers' matrix states, in the traced slice: the bytes a step
OWES for them (every layer's S read once and written once a row:
`lib/flops_qwen3_next.gdn_step_bytes`) times the slice's decode steps, over
the chip's HBM bandwidth (`lib/peaks.py`), over the self time of the ops
under `anakin/decode` whose innermost `policy/*` scope is `policy/gdn_state`
(from the program's own account of the harness's trace). The step is bound by
bytes, not operations (3 x 2 FLOPs an element of S against 8 bytes). The owed
bytes do not depend on what computes the step (XLA's fusions, or a kernel),
so the share cannot pass 100 % unless the program leaves work out, and a step
that passes over S three times reads a third. A slice's decode steps are its
trained steps over the rows (each row of the rollout is one env step a decode
step). A program without the scope (every model without such a layer, and
every program before PR 52) reads nothing. Layer: the programs."""

import importlib

from layer_metrics import program_account
from lib import peaks

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"
BETTER = "higher"

ROW = "anakin/decode|policy/gdn_state"

begin = program_account.begin


def read(ctx, state):
    acct = program_account.account(ctx)
    network = getattr(ctx.session, "network", None)
    module = ctx.workload.get("flops_module")
    if not acct or network is None or not module or ctx.slice_steps <= 0:
        return None
    seconds = acct["scopes"].get(ROW)
    if not seconds:
        return None
    flops = importlib.import_module("lib." + module)
    if not hasattr(flops, "gdn_step_bytes"):
        return None
    rows = ctx.session.optimizer.num_envs
    # A chip's share of the rows, every decode step of the slice.
    owed = (ctx.slice_steps / float(rows)) * flops.gdn_step_bytes(
        network, rows // ctx.chips)
    least = owed / peaks.peak_hbm_bytes_per_s(ctx.device_kind)
    return 100.0 * least / seconds
