"""Share of the chip's idle seconds in the traced slice during which the
learner thread sat in `learner.dequeue` (no train batch was ready): the
learner's row of the program's idle-by-phase account, by overlap of the
idle gaps with the thread's phases on the trace's clock. `learner_wait_pct`
times the same wait from outside, against the whole window. Layer: the
device."""

from layer_metrics import program_account

UNIT = "%"
LAYER = "device"
SOURCE = "device_trace"
BETTER = "lower"


begin = program_account.begin


def starved_seconds(acct):
    learner = acct["idle"].get("learner")
    return learner["seconds"].get("learner.dequeue", 0.0) if learner else None


def read(ctx, state):
    return program_account.share_of_idle(ctx, starved_seconds)
