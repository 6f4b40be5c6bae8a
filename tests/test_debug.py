"""Stall watchdog + signal stack dumps (_private/debug.py) — the
runtime's analog of the reference's TSAN/valgrind harnesses for its
failure mode (wedged Python threads, not memory corruption)."""

import io
import os
import signal
import subprocess
import sys
import time

from conftest import wait_until

from ray_tpu._private.debug import StallWatchdog


class TestStallWatchdog:
    def test_detects_stall_and_dumps_once(self):
        out = io.StringIO()
        w = StallWatchdog("test-loop", timeout_s=0.3, out=out)
        try:
            for _ in range(3):
                w.beat()
                time.sleep(0.05)
            assert not w.stalled
            # No more beats: the dump comes ...
            wait_until(lambda: "STALL" in out.getvalue(), timeout=30)
            assert w.stalled
            time.sleep(0.7)  # ... and two more timeouts bring no second
            text = out.getvalue()
            assert "STALL" in text and "test-loop" in text
            # Exactly one dump per stall.
            assert text.count("STALL") == 1
            # A new beat re-arms it.
            w.beat()
            assert not w.stalled
        finally:
            w.stop()

    def test_healthy_loop_stays_quiet(self):
        out = io.StringIO()
        w = StallWatchdog("quiet", timeout_s=0.5, out=out)
        try:
            for _ in range(8):
                w.beat()
                time.sleep(0.1)
            assert out.getvalue() == ""
        finally:
            w.stop()


def test_sigusr1_dumps_all_thread_stacks():
    """A booted head process dumps thread stacks on SIGUSR1 and keeps
    running (the wedge-inspection path)."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import ray_tpu, os, time, threading\n"
        "ray_tpu.init(num_cpus=1)\n"
        "print('PID', os.getpid(), flush=True)\n"
        "time.sleep(30)\n" % os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    proc = subprocess.Popen(
        [sys.executable, "-c", code], stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    try:
        line = proc.stdout.readline()
        assert line.startswith("PID")
        pid = int(line.split()[1])
        # init() installed the handler before the PID line was printed.
        os.kill(pid, signal.SIGUSR1)
        # The dump's first line arrives on stderr; then the process
        # must still be alive.
        err = proc.stderr.readline()
        assert proc.poll() is None, "process must survive the dump"
        proc.terminate()
        err += proc.communicate(timeout=20)[1]
        assert "Current thread" in err or "Thread" in err
    finally:
        if proc.poll() is None:
            proc.kill()
