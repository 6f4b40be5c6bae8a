"""Matrix FLOPs the device owed for the tokens generated and learned in the
traced slice over the device's BUSY time times the chip's bf16 peak
(`lib/peaks.py`). The count comes from shapes, by the module of `lib/` that
the cell's file names under `flops_module` (its
`device_flops_per_step(network, device_passes)`: one decode forward and one
learner forward + backward a token, as the cell's `device_passes` say), so
one reader serves every decoder that has such a module. The blocks run in
bf16 and the float32 heads run as bf16 passes on the MXU at default
precision, so one peak serves. What the program computes beyond the
algorithm's need (masked cache positions, masked parts of a tile, each
block's recomputation in the backward pass) is not owed, so the share cannot
be raised by computing more. A cell whose file names no module, or a session
without a `network`, reads nothing."""

import importlib

from lib import peaks

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"
BETTER = "higher"


def read(ctx, state):
    network = getattr(ctx.session, "network", None)
    module = ctx.workload.get("flops_module")
    if not ctx.trace or ctx.slice_steps <= 0 or network is None or not module:
        return None
    flops = importlib.import_module("lib." + module)
    owed = ctx.slice_steps * flops.device_flops_per_step(
        network, ctx.workload["device_passes"])
    chip_seconds = ctx.trace["busy_s"] * ctx.chips
    return 100.0 * owed / (chip_seconds * peaks.peak_flops(ctx.device_kind))
