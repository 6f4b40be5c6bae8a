"""Tracing/profiling + CLI introspection.

Parity: `src/ray/core_worker/profiling.h:14` (span batching),
`python/ray/profiling.py:17` (`ray.profile`), `state.py:672`
(chrome trace dump), `scripts.py:234/426/832/852` (`ray
start/stop/timeline/stat`).
"""

import json
import os
import subprocess
import sys
import time

import pytest
from conftest import wait_until

import ray_tpu


class TestTimeline:
    def test_task_and_user_spans_in_trace(self, ray_start, tmp_path):
        @ray_tpu.remote
        def work(x):
            with ray_tpu.profile("inner-span", {"x": x}):
                return x

        assert ray_tpu.get([work.remote(i) for i in range(3)]) == [0, 1, 2]
        with ray_tpu.profile("driver-span"):
            pass
        # The workers' profilers flush on their own interval.
        wait_until(lambda: {"work", "inner-span", "driver-span"}
                   <= {e["name"] for e in ray_tpu.timeline()}, timeout=30)
        path = str(tmp_path / "trace.json")
        ray_tpu.timeline(path)
        events = json.load(open(path))
        names = {e["name"] for e in events}
        assert "work" in names        # task execution span
        assert "inner-span" in names  # worker-side user span
        assert "driver-span" in names
        ev = next(e for e in events if e["name"] == "work")
        assert ev["ph"] == "X" and ev["dur"] >= 0

    def test_timeline_returns_events(self, ray_start):
        @ray_tpu.remote
        def f():
            return 1

        ray_tpu.get(f.remote())
        events = ray_tpu.timeline()
        assert isinstance(events, list)


class TestCLI:
    def test_head_attach_stat_stop(self, tmp_path):
        """`start --head` + driver attach + `stat` + `stop` (parity:
        ray start/ray.init(redis_address)/ray stat/ray stop)."""
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [p for p in sys.path if p])
        # NOTE: keep the default short tmp root — AF_UNIX socket paths
        # cap at ~108 chars, and pytest tmp_path nests deeply.
        import tempfile
        addr_file = os.path.join(tempfile.gettempdir(), "ray_tpu_cli",
                                 "head_address")
        if os.path.exists(addr_file):
            os.unlink(addr_file)
        head = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.scripts", "start", "--head",
             "--num-cpus", "2"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            cwd=str(tmp_path))
        try:
            deadline = time.time() + 30
            while not os.path.exists(addr_file):
                assert time.time() < deadline, "head never wrote address"
                assert head.poll() is None, head.stdout.read().decode()
                time.sleep(0.2)
            address = open(addr_file).read().strip()

            out = subprocess.run(
                [sys.executable, "-m", "ray_tpu.scripts", "stat",
                 "--address", address],
                env=env, capture_output=True, text=True, timeout=60)
            assert "total resources" in out.stdout, out.stderr

            driver = subprocess.run(
                [sys.executable, "-c", (
                    "import ray_tpu\n"
                    f"ray_tpu.init(address={address!r})\n"
                    "@ray_tpu.remote\n"
                    "def f(x): return x * 2\n"
                    "print('R=', ray_tpu.get(f.remote(21)))\n"
                    "ray_tpu.shutdown()\n")],
                env=env, capture_output=True, text=True, timeout=90)
            assert "R= 42" in driver.stdout, (driver.stdout,
                                              driver.stderr)
        finally:
            head.terminate()
            head.wait(timeout=15)


def test_xla_profile_captures_device_trace(tmp_path):
    """SURVEY §5.1: device-side XLA traces complement the host span
    timeline; the context manager must produce a loadable profile."""
    import glob
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    import ray_tpu
    d = str(tmp_path / "prof")
    with ray_tpu.xla_profile(d):
        jax.jit(lambda x: jnp.tanh(x) @ x.T)(
            np.ones((64, 64), np.float32)).block_until_ready()
    found = glob.glob(os.path.join(d, "**", "*"), recursive=True)
    assert any(os.path.isfile(f) for f in found), found


class TestTaskStateAPI:
    """Task-lifecycle state API (parity: the reference state API's
    `ray list tasks` / `ray summary tasks`): transitions recorded by
    driver, head, and workers land in the head's bounded ring."""

    def test_finished_task_records_per_state_durations(self, ray_start):
        @ray_tpu.remote
        def ok(x):
            return x

        assert ray_tpu.get(ok.remote(1), timeout=30) == 1
        rec = _poll_task_record("ok", "FINISHED")
        assert rec["node"] == "node0"
        assert rec["worker_pid"] is not None
        assert rec["caller"]  # submitting driver's addr
        # Per-state durations: the task passed through SUBMITTED and
        # RUNNING at minimum, each with a non-negative residence time.
        assert rec["durations"].get("SUBMITTED", -1) >= 0
        assert rec["durations"].get("RUNNING", -1) >= 0
        assert rec["end"] >= rec["start"]
        summary = ray_tpu.task_summary()
        assert summary["ok"]["FINISHED"] >= 1

    def test_failed_task_lands_in_failed_with_error(self, ray_start):
        @ray_tpu.remote
        def boom():
            raise ValueError("task-state-boom")

        with pytest.raises(Exception):
            ray_tpu.get(boom.remote(), timeout=30)
        rec = _poll_task_record("boom", "FAILED")
        assert "task-state-boom" in (rec["error"] or "")
        assert ray_tpu.task_summary()["boom"]["FAILED"] >= 1
        # Filters select by state.
        failed = ray_tpu.tasks(state="FAILED")
        assert all(r["state"] == "FAILED" for r in failed)
        assert any(r["name"] == "boom" for r in failed)

    def test_actor_method_calls_recorded(self, ray_start):
        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def inc(self):
                self.n += 1
                return self.n

        c = Counter.remote()
        assert ray_tpu.get(c.inc.remote(), timeout=30) == 1
        rec = _poll_task_record("Counter.inc", "FINISHED")
        assert rec["kind"] == "actor_task"


def _poll_task_record(name, state, timeout=10):
    """Worker-side transitions flush on a short cadence; poll."""
    deadline = time.monotonic() + timeout
    last = []
    while time.monotonic() < deadline:
        last = ray_tpu.tasks(name=name)
        if last and last[0]["state"] == state:
            return last[0]
        time.sleep(0.2)
    raise AssertionError(
        f"no task {name!r} reached {state}; saw {last}")


def test_flow_events_link_submit_to_exec_across_nodes():
    """The Chrome trace carries flow events (`ph:"s"` at the driver's
    submit span, `ph:"f"` at the worker's exec span, keyed by task id)
    so Perfetto draws causality arrows across process/node lanes."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    cluster = Cluster(head_resources={"CPU": 1})
    cluster.add_node(resources={"CPU": 2})
    try:
        # The resource shape only fits the second node: the submit
        # side (driver) and exec side (remote worker) are guaranteed
        # to be different processes on different nodes.
        @ray_tpu.remote(resources={"CPU": 2})
        def remote_work():
            return os.getpid()

        worker_pid = ray_tpu.get(remote_work.remote(), timeout=60)
        assert worker_pid != os.getpid()
        deadline = time.time() + 15
        cross = []
        while time.time() < deadline and not cross:
            events = ray_tpu.timeline()
            starts = {e["id"]: e for e in events if e.get("ph") == "s"}
            ends = {e["id"]: e for e in events if e.get("ph") == "f"}
            cross = [fid for fid in starts.keys() & ends.keys()
                     if starts[fid]["pid"] != ends[fid]["pid"]]
            if not cross:
                time.sleep(0.5)
        assert cross, "no cross-process flow s->f pair in the trace"
        fid = cross[0]
        events = ray_tpu.timeline()
        # The flow binds a driver-side submit span to the worker-side
        # exec span carrying the same task id.
        sub = [e for e in events if e.get("ph") == "X"
               and (e.get("args") or {}).get("task_id") == fid
               and e["name"].startswith("submit ")]
        ex = [e for e in events if e.get("ph") == "X"
              and (e.get("args") or {}).get("task_id") == fid
              and not e["name"].startswith("submit ")]
        assert sub and ex
        assert str(worker_pid) in str(ex[0]["pid"])
    finally:
        cluster.shutdown()


def test_profiler_drop_accounting_and_joined_stop(ray_start):
    """Span-buffer truncation is counted (not silent) and surfaces in
    the timeline dump's metadata; Profiler.stop() joins the flush
    thread so the final batch can't be lost."""
    from ray_tpu._private import metrics as metrics_mod
    from ray_tpu._private import profiling, worker_state
    rt = worker_state.get_runtime()
    # Overflow the local buffer; the 1 s background flush could steal
    # one batch mid-loop, so retry until a drop registers.
    for _ in range(3):
        for i in range(profiling.MAX_BUFFER + 500):
            rt.profiler.record("user", f"spam-{i % 7}", 0.0, 0.0)
        if metrics_mod.snapshot()["counters"].get(
                "profile_events_dropped", 0) > 0:
            break
    assert metrics_mod.snapshot()["counters"].get(
        "profile_events_dropped", 0) > 0
    rt.profiler.flush()
    events = ray_tpu.timeline()
    meta = [e for e in events
            if e.get("ph") == "M"
            and e.get("name") == "ray_tpu_profile_events_dropped"]
    assert meta and meta[0]["args"]["count"] > 0
    # stop() must terminate AND join the flush thread.
    rt.profiler.stop()
    assert not rt.profiler._thread.is_alive()


def test_object_transfer_spans_in_timeline():
    """Cross-node object pulls appear in the cluster timeline as sized
    'transfer' spans (parity: the reference's object-transfer timeline,
    state.py:744) — both the chunked path (>8 MiB) and the
    single-message blob path."""
    import numpy as np

    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    cluster = Cluster(head_resources={"CPU": 1})
    cluster.add_node(resources={"CPU": 2})
    try:
        @ray_tpu.remote(resources={"CPU": 2})
        def make(n):
            return np.zeros(n, np.uint8)

        # > chunk size (8 MiB): the result streams back CHUNKED.
        big = ray_tpu.get(make.remote(12 << 20), timeout=120)
        assert big.nbytes == 12 << 20

        # Borrowed driver-owned 1 MiB ref pulled by the remote worker:
        # the owner replies with one 'blob' message (the second span
        # source, runtime._request_from_owner).
        borrowed = ray_tpu.put(np.ones(1 << 20, np.uint8))

        @ray_tpu.remote(resources={"CPU": 2})
        def consume(arr):
            return int(arr[0])

        assert ray_tpu.get(consume.remote(borrowed), timeout=120) == 1
        # Remote workers' spans flush to the head on a 1 s cadence.
        import time
        deadline = time.time() + 15
        sizes = []
        while time.time() < deadline:
            events = ray_tpu.timeline()
            sizes = [(e.get("args") or {}).get("bytes", 0)
                     for e in events if e.get("cat") == "transfer"]
            if any(b >= 12 << 20 for b in sizes) and \
                    any(0 < b <= 2 << 20 for b in sizes):
                break
            time.sleep(0.5)
        assert any(b >= 12 << 20 for b in sizes), sizes  # chunked pull
        assert any(0 < b <= 2 << 20 for b in sizes), sizes  # blob pull
    finally:
        cluster.shutdown()
