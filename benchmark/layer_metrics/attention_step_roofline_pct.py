"""Share of its memory roofline at which the rollout's decode steps read the
key/value caches, in the traced slice: the bytes a step OWES for them (every
position a row holds of every layer's K and V once, a full cache the
positions so far and a ring at most its window, on the mean over an episode:
`lib/flops_<family>.attention_step_bytes`) times the slice's decode steps,
over the chip's HBM bandwidth (`lib/peaks.py`), over the self time of the ops
under `anakin/decode` whose innermost `policy/*` scope is
`policy/attention_full` or `policy/attention_window` (from the program's own
account of the harness's trace: the layers' norms, projections and W_o are
in that time too, so the kernel alone runs nearer its roofline than this
reads). The step's attention is bound by bytes, not operations (6 or 8
query heads a cached head: 12-16 FLOPs a cached byte). The owed bytes do not
depend on what computes the step (XLA's two products over the whole cache,
or a kernel over the blocks held) nor on how many blocks it fetches, so the
share cannot pass 100 % unless the program leaves work out. A slice's decode
steps are its trained steps over the rows (each row of the rollout is one
env step a decode step). A program without the scopes, or a cell whose
module has no `attention_step_bytes`, reads nothing. Layer: the programs."""

import importlib

from layer_metrics import program_account
from lib import peaks

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"
BETTER = "higher"

ROWS = ("anakin/decode|policy/attention_full",
        "anakin/decode|policy/attention_window")

begin = program_account.begin


def read(ctx, state):
    acct = program_account.account(ctx)
    network = getattr(ctx.session, "network", None)
    module = ctx.workload.get("flops_module")
    if not acct or network is None or not module or ctx.slice_steps <= 0:
        return None
    seconds = sum(acct["scopes"].get(row, 0.0) for row in ROWS)
    if not seconds:
        return None
    flops = importlib.import_module("lib." + module)
    if not hasattr(flops, "attention_step_bytes"):
        return None
    rows = ctx.session.optimizer.num_envs
    # A chip's share of the rows, every decode step of the slice.
    owed = (ctx.slice_steps / float(rows)) * flops.attention_step_bytes(
        network, rows // ctx.chips)
    least = owed / peaks.peak_hbm_bytes_per_s(ctx.device_kind)
    return 100.0 * least / seconds
