"""Matrix FLOPs the device owed for the tokens generated and learned in the
traced slice (from shapes, `lib/flops_glm4_moe_lite.py`: one decode forward
of the trunk and one learner forward + backward of the trunk and the
next-next-token module a token, as the cell's `device_passes` say; the held
experts at their expected share, attention in the cheaper of its two forms)
over the device's BUSY time times the chip's bf16 peak (`lib/peaks.py`). The
blocks run in bf16 and the float32 heads run as bf16 passes on the MXU at
default precision, so one peak serves. What the program computes beyond the
algorithm's need (masked cache positions, the causal pass's upper triangle,
the absorbed form's wider rows, each block's recomputation in the backward
pass) is not owed, so the share cannot be raised by computing more."""

from lib import flops_glm4_moe_lite, peaks

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"
BETTER = "higher"


def read(ctx, state):
    network = getattr(ctx.session, "network", None)
    if not ctx.trace or ctx.slice_steps <= 0 or network is None:
        return None
    owed = ctx.slice_steps * flops_glm4_moe_lite.device_flops_per_step(
        network, ctx.workload["device_passes"])
    chip_seconds = ctx.trace["busy_s"] * ctx.chips
    return 100.0 * owed / (chip_seconds * peaks.peak_flops(ctx.device_kind))
