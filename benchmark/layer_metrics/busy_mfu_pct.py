"""Matrix FLOPs the device owed for the steps trained in the traced slice
(from shapes, `lib/flops.py`; passes per step from the cell's
`device_passes`) over the device's BUSY time times the chip's bf16 peak
(`lib/peaks.py`). It is the programs' efficiency while they run; idle time
is `device_idle_pct`'s. The trunk is bf16 and the f32 heads run as bf16
passes on the MXU at default precision, so one peak serves."""

from lib import flops, peaks

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"
BETTER = "higher"


def read(ctx, state):
    if not ctx.trace or ctx.slice_steps <= 0:
        return None
    owed = ctx.slice_steps * flops.device_flops_per_step(
        ctx.config["network"], ctx.workload["device_passes"])
    chip_seconds = ctx.trace["busy_s"] * ctx.chips
    return 100.0 * owed / (chip_seconds * peaks.peak_flops(ctx.device_kind))
