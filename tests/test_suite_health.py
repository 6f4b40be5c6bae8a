"""The suite's own watchdog (`conftest.py`): a test that blocks for ever
fails alone, within its limit, with every thread's stack in the report;
the tests behind it run; no process of its runtime survives."""

import os
import shutil
import subprocess
import sys
import textwrap

import psutil

_TESTS = os.path.dirname(os.path.abspath(__file__))


def _run_pytest(tmp_path, body):
    """Run `body` as a test file beside a copy of this suite's conftest,
    in a pytest of its own."""
    shutil.copy(os.path.join(_TESTS, "conftest.py"), tmp_path)
    (tmp_path / "test_blocking.py").write_text(textwrap.dedent(body))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(_TESTS), env.get("PYTHONPATH", "")])
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-q", "test_blocking.py"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=150)


def test_blocked_test_fails_alone_with_stacks(tmp_path):
    run = _run_pytest(tmp_path, """
        import threading
        import pytest

        @pytest.mark.time_limit(1)
        def test_blocks():
            threading.Event().wait()  # BLOCKED-HERE

        def test_behind_it():
            pass
        """)
    out = run.stdout
    assert run.returncode == 1, out + run.stderr
    assert "1 failed, 1 passed" in out, out
    assert "test_blocks passed its time limit of 1 s" in out, out
    # The dump names the line the main thread was blocked on.
    assert 'test_blocking.py", line 7 in test_blocks' in out, out


def test_blocked_test_leaves_no_runtime_process(tmp_path):
    pid_file = tmp_path / "pids"
    run = _run_pytest(tmp_path, f"""
        import os
        import pytest
        import ray_tpu

        @pytest.mark.time_limit(5)
        def test_blocks_with_a_runtime_up():
            ray_tpu.init(num_cpus=2)

            @ray_tpu.remote
            def pid_then_block():
                import time
                with open({str(pid_file)!r}, "a") as f:
                    f.write(f"{{os.getpid()}}\\n")
                time.sleep(1000)

            ray_tpu.get([pid_then_block.remote() for _ in range(2)])

        def test_behind_it():
            assert not ray_tpu.is_initialized()
            ray_tpu.init(num_cpus=1)
            ray_tpu.shutdown()
        """)
    assert run.returncode == 1, run.stdout + run.stderr
    assert "1 failed, 1 passed" in run.stdout, run.stdout
    pids = [int(line) for line in pid_file.read_text().split()]
    assert pids, "the blocked test's workers never started"
    assert not [p for p in pids if psutil.pid_exists(p)], pids
