"""Share of the chip's idle seconds in the traced slice during which at
least one loop thread waited for the policy's update lock
(`sebulba.lock_wait` on an actor thread, `learner.lock_wait` on the
learner): the `*.lock_wait` row of the program's `idle_any` account, each
idle second counted once however many threads waited. Layer: the device."""

from layer_metrics import program_account

UNIT = "%"
LAYER = "device"
SOURCE = "device_trace"
BETTER = "lower"
STEP = "lock_wait"


begin = program_account.begin


def contended_seconds(acct):
    if not acct["threads"]:
        return None
    rows = acct["idle_any"]
    if "*." + STEP in rows:  # several families wait for it: their union
        return rows["*." + STEP]
    return sum(s for name, s in rows.items() if name.endswith("." + STEP))


def read(ctx, state):
    return program_account.share_of_idle(ctx, contended_seconds)
