"""What the readers of the program's own account share. No metric is named
after this file.

The program reduces a device trace by its own names
(`ray_tpu/_private/device_account.py`: device seconds by `named_scope`,
idle seconds by the phase each loop thread had open). These readers hand
it the harness's OWN trace, so that the numbers `lib/trace.py` reduces and
the numbers the program reduces come from the same events on one clock:
`begin` makes the harness keep the slice's trace by the documented
`BENCH_TRACE_DIR` (a fresh temporary directory, unless the builder already
keeps traces somewhere), `account` reduces it once inside `bench.slice`,
remembers the result on `ctx` and removes the directory `begin` made.

A program without the module (every commit before PR 36) has no account:
`account` is then None and each reader leaves its metric out.
"""

import os
import shutil
import sys
import tempfile

WINDOW_SPAN = "bench.slice"


def begin(ctx):
    """Called from a reader's `begin`, before the slice starts; any number
    of readers may call it."""
    if getattr(ctx, "account_trace_dir", None) is None:
        ctx.account_made_dir = None
        if not os.environ.get("BENCH_TRACE_DIR"):
            ctx.account_made_dir = tempfile.mkdtemp(prefix="bench_trace_")
            os.environ["BENCH_TRACE_DIR"] = ctx.account_made_dir
        ctx.account_trace_dir = os.environ["BENCH_TRACE_DIR"]


def account(ctx):
    """The program's account of the traced slice, for a reader that is
    read after it (`SOURCE = "device_trace"`). None where `begin` was not
    called, the harness found no device op (every rehearsal), the program
    has no `device_account`, or it cannot read the trace (said on stderr;
    a reader never fails the run)."""
    if hasattr(ctx, "program_account"):
        return ctx.program_account
    if getattr(ctx, "account_trace_dir", None) is None:
        return None
    ctx.program_account = None
    try:
        if ctx.trace:
            from ray_tpu._private import device_account
            ctx.program_account = device_account.account(
                ctx.account_trace_dir, window=WINDOW_SPAN)
    except ImportError:
        pass
    except Exception as e:  # noqa: BLE001 - the run goes on without it
        print(f"# program_account: {type(e).__name__}: {e}", file=sys.stderr)
    finally:
        if ctx.account_made_dir:
            shutil.rmtree(ctx.account_made_dir, ignore_errors=True)
            if os.environ.get("BENCH_TRACE_DIR") == ctx.account_made_dir:
                del os.environ["BENCH_TRACE_DIR"]
    return ctx.program_account


def share_of_busy(ctx, seconds_of):
    """100 * seconds_of(account) / busy seconds; None without an account."""
    acct = account(ctx)
    if not acct or acct["busy_s"] <= 0:
        return None
    return 100.0 * seconds_of(acct) / acct["busy_s"]


def share_of_idle(ctx, seconds_of):
    """100 * seconds_of(account) / idle seconds; None without an account,
    without idle time, or where `seconds_of` finds nothing (None)."""
    acct = account(ctx)
    if not acct or acct["idle_s"] <= 0:
        return None
    seconds = seconds_of(acct)
    return None if seconds is None else 100.0 * seconds / acct["idle_s"]


def learner_seconds(ctx, names):
    """Seconds the learner thread has spent in `names` so far, from its
    `PhaseClock`; None where the optimizer has no learner thread or the
    thread keeps no clock."""
    learner = getattr(ctx.session.optimizer, "learner", None)
    clock = getattr(learner, "clock", None)
    if clock is None:
        return None
    seconds = clock.snapshot()["seconds"]
    return sum(seconds.get(name, 0.0) for name in names)
