"""`_private/device_account`: a device trace reduced by the program's own
names. Hand-built `XSpace` bytes for the arithmetic (times in
microseconds below, picoseconds on the wire), the recorded v5e trace and a
CPU `jax.profiler` session for the reading."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu._private import device_account as da
from ray_tpu._private import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "benchmark", "tests", "data",
                        "small_tpu_trace.xplane.pb")
US = 1_000_000  # picoseconds


class Space:
    """An `XSpace` by hand: planes, lines and events with named metadata."""

    def __init__(self):
        self.space = da._schema()()

    def plane(self, name):
        plane = self.space.planes.add(name=name)
        for i, stat in enumerate(("tf_op", "hlo_category"), 1):
            entry = plane.stat_metadata.add(key=i)
            entry.value.id, entry.value.name = i, stat
        return _Plane(plane)

    def write(self, tmp_path, name="t.xplane.pb"):
        path = tmp_path / name
        path.write_bytes(self.space.SerializeToString())
        return str(path)


class _Plane:
    def __init__(self, plane):
        self.plane, self.ids = plane, {}

    def metadata(self, name, tf_op="", category=""):
        key = (name, tf_op, category)
        if key not in self.ids:
            entry = self.plane.event_metadata.add(key=len(self.ids) + 1)
            entry.value.id, entry.value.name = entry.key, name
            if tf_op:
                entry.value.stats.add(metadata_id=1, str_value=tf_op)
            if category:
                entry.value.stats.add(metadata_id=2, str_value=category)
            self.ids[key] = entry.key
        return self.ids[key]

    def line(self, name, line_id, events, timestamp_ns=0):
        """events: (name, start_us, duration_us[, tf_op[, category]])."""
        line = self.plane.lines.add(id=line_id, name=name,
                                    timestamp_ns=timestamp_ns)
        for name, start, dur, *rest in events:
            line.events.add(metadata_id=self.metadata(name, *rest),
                            offset_ps=int(start * US),
                            duration_ps=int(dur * US))
        return self


def device(space, n, ops, modules=()):
    return (space.plane(f"/device:TPU:{n}")
            .line(da.MODULE_LINE, 1, modules).line(da.OP_LINE, 2, ops))


def approx(x):
    return pytest.approx(x, abs=1e-12)


def test_a_while_keeps_its_own_time_and_the_rows_add_up_to_busy(tmp_path):
    s = Space()
    device(s, 0, [
        ("%while.3 = (s32[]) while(..)", 0, 100, "jit(f)/anakin/loss/while"),
        ("%fusion.7 = f32[8] fusion(..)", 10, 30,
         "jit(f)/anakin/loss/while/body/policy/head/dot_general:"),
        ("%fusion.8 = f32[8] fusion(..)", 50, 40,
         "jit(f)/anakin/update/while/body/mul:"),
        ("%fusion.9 = f32[8] fusion(..)", 200, 50, "jit(f)/anakin/update/add:"),
    ], modules=[("jit_f(123)", 0, 100), ("jit_f(123)", 200, 50)])
    a = da.account(s.write(tmp_path))
    assert a["window_s"] == approx(250e-6) and a["chips"] == 1
    assert a["busy_s"] == approx(150e-6) and a["idle_s"] == approx(100e-6)
    assert a["scopes"] == {"anakin/loss": approx(30e-6),
                           "anakin/loss|policy/head": approx(30e-6),
                           "anakin/update": approx(90e-6)}
    assert sum(a["scopes"].values()) == approx(a["busy_s"])
    assert a["unscoped_s"] == 0 and a["collective_s"] == 0
    assert a["modules"] == {"jit_f": {
        "seconds": approx(150e-6), "launches": 2,
        "seconds_per_chip": [approx(150e-6)]}}
    assert a["events"] == 6 and a["threads"] == [] and a["idle"] == {}


@pytest.mark.parametrize("tf_op, kind, row", [
    # decode / learn enclose the other anakin scopes and name the op first
    ("jit(a)/anakin/decode/while/body/anakin/inference/policy/mla_attend/dot",
     "fusion", "anakin/decode|policy/mla_attend"),
    ("jit(a)/anakin/loss/anakin/learn/transpose(jvp(policy/dispatch))/mul",
     "fusion", "anakin/learn|policy/dispatch"),
    # otherwise the innermost of each kind
    ("jit(a)/anakin/env_step/anakin/inference/policy/attention/policy/router/x",
     "fusion", "anakin/inference|policy/router"),
    # the in-place write of a step's frames, inside the rollout's scan
    ("jit(a)/anakin/update/while/body/closed_call/anakin/env_step/while/body/"
     "closed_call/anakin/pack/dynamic_update_slice", "fusion", "anakin/pack"),
    ("jit(s)/sebulba/select/policy/action/conv", "convolution",
     "sebulba/select|policy/action"),
    ("jit(t)/train/loss/train/allreduce/psum", "all-reduce", "train/allreduce"),
    ("jit(p)/policy/action/dot_general:", "fusion", "policy/action"),
    # a name that only ends like a scope is none
    ("jit(f)/my_anakin/loss/apolicy/head/dot", "fusion", "unscoped|fusion"),
    ("", "ragged-dot-none", "unscoped|ragged-dot-none"),
])
def test_scope_precedence(tf_op, kind, row):
    assert da.scope_key(tf_op, kind) == row


def test_unscoped_ops_go_by_kind_and_collectives_are_counted_apart(tmp_path):
    s = Space()
    device(s, 0, [
        ("%ragged-dot-none.3 = bf16[8] custom-call(..)", 0, 10),
        ("%ragged-dot-none.11 = bf16[8] custom-call(..)", 10, 10),
        ("%copy-done.105 = bf16[4] copy-done(..)", 20, 5, "", "copy-done"),
        ("%all-reduce.2 = f32[4] all-reduce(..)", 30, 20,
         "jit(t)/train/allreduce/psum", "all-reduce"),
        # the category says it where the op's name does not
        ("%fusion.5 = f32[4] fusion(..)", 50, 4, "", "all-gather fusion"),
        ("%all-reduce-start.1 = f32[4] all-reduce-start(..)", 60, 1),
        ("%all-reduce-done.1 = f32[4] all-reduce-done(..)", 70, 2),
        ("%reduce-scatter.4 = f32[4] reduce-scatter(..)", 80, 3),
        ("%collective-permute.6 = f32[4] collective-permute(..)", 90, 5),
        ("%fusion.6 = f32[4] fusion(..)", 95, 5, "jit(t)/train/update/mul"),
    ])
    a = da.account(s.write(tmp_path))
    assert a["scopes"]["unscoped|ragged-dot-none"] == approx(20e-6)
    assert a["scopes"]["unscoped|copy-done"] == approx(5e-6)
    assert a["scopes"]["train/allreduce"] == approx(20e-6)
    assert a["collective_s"] == approx(35e-6)
    assert a["unscoped_s"] == approx(40e-6)
    assert a["unscoped_s"] == approx(sum(
        v for k, v in a["scopes"].items() if k.startswith("unscoped|")))
    assert sum(a["scopes"].values()) == approx(a["busy_s"])


def two_threads_of_one_name(s):
    """One chip busy in [0, 10) and [40, 50) of a window [0, 100): idle 80.
    Two host lines, both `python3`: an actor and the learner."""
    device(s, 0, [("%fusion.1 = f32[] fusion()", 0, 10, "jit(t)/train/loss/x"),
                  ("%fusion.2 = f32[] fusion()", 40, 10, "jit(s)/sebulba/apply/x")])
    host = s.plane("/host:CPU")
    host.line("python3", 111, [
        ("bench.slice", 0, 100),
        ("ray_tpu.sebulba.lock_wait", 5, 25),    # idle overlap 20
        ("ray_tpu.sebulba.env_step", 30, 30),    # 10 + 10 = 20
        ("PjitFunction(f)", 31, 2),              # not the program's: ignored
    ])
    host.line("python3", 222, [
        ("ray_tpu.learner.dequeue", 0, 45),      # 30
        ("ray_tpu.learner.lock_wait", 45, 15),   # 10
        ("ray_tpu.learner.train", 60, 20),       # 20
    ])
    host.line("python3", 333, [("ray_tpu.sebulba.lock_wait", 20, 30)])  # 20


def test_idle_rows_a_thread_by_overlap_and_idle_any_once(tmp_path):
    s = Space()
    two_threads_of_one_name(s)
    a = da.account(s.write(tmp_path), window="bench.slice")
    assert a["idle_s"] == approx(80e-6)
    rows = {t["line_id"]: t for t in a["threads"]}
    assert set(rows) == {111, 222, 333}
    assert all(t["name"] == "python3" for t in a["threads"])
    assert rows[111]["family"] == rows[333]["family"] == "sebulba"
    assert rows[222]["family"] == "learner"
    assert rows[111]["idle"] == {"sebulba.lock_wait": approx(20e-6),
                                 "sebulba.env_step": approx(20e-6),
                                 "other": approx(40e-6)}
    assert rows[222]["idle"] == {"learner.dequeue": approx(30e-6),
                                 "learner.lock_wait": approx(10e-6),
                                 "learner.train": approx(20e-6),
                                 "other": approx(20e-6)}
    for t in a["threads"]:
        assert sum(t["idle"].values()) == approx(a["idle_s"])
    # the mean over a family's threads, and each family adds up too
    actors = a["idle"]["sebulba"]
    assert actors["threads"] == 2
    assert actors["seconds"] == {"sebulba.lock_wait": approx(20e-6),
                                 "sebulba.env_step": approx(10e-6),
                                 "other": approx(50e-6)}
    assert sum(actors["seconds"].values()) == approx(a["idle_s"])
    # [10, 30) on one thread and [20, 40) on another: 30 idle us, once
    assert a["idle_any"]["sebulba.lock_wait"] == approx(30e-6)
    assert a["idle_any"]["learner.lock_wait"] == approx(10e-6)
    # somebody waited for the one lock: [10, 40) and [50, 60)
    assert a["idle_any"]["*.lock_wait"] == approx(40e-6)
    assert "*.dequeue" not in a["idle_any"]  # one family has that step
    assert a["phases"]["sebulba.lock_wait"] == {
        "host_s": approx(55e-6), "count": 2, "threads": 2}
    assert a["phases"]["learner.dequeue"] == {
        "host_s": approx(45e-6), "count": 1, "threads": 1}


def test_four_chips_means_and_per_chip_lists(tmp_path):
    s = Space()
    for n in range(4):
        device(s, n, [
            ("%fusion.1 = f32[] fusion()", 0, 10 * (n + 1), "j/train/loss/x"),
            ("%all-reduce.1 = f32[] all-reduce()", 50, 10, "j/train/allreduce/p"),
        ], modules=[("jit_train_fn(9)", 0, 60)])
    s.plane("/host:CPU").line("python3", 1, [
        ("bench.slice", 0, 100), ("ray_tpu.learner.train", 0, 50)])
    s.plane("/device:CUSTOM:Megascale Trace")  # no chip: not counted
    a = da.account(s.write(tmp_path), window="bench.slice")
    assert a["chips"] == 4
    assert a["busy_s_per_chip"] == [approx(x * 1e-6) for x in (20, 30, 40, 50)]
    assert a["busy_s"] == approx(35e-6) and a["idle_s"] == approx(65e-6)
    assert a["scopes_per_chip"]["train/loss"] == [
        approx(x * 1e-6) for x in (10, 20, 30, 40)]
    assert a["scopes"]["train/loss"] == approx(25e-6)
    assert a["collective_s"] == approx(10e-6)
    assert a["collective_s_per_chip"] == [approx(10e-6)] * 4
    assert a["modules"]["jit_train_fn"]["launches"] == 1  # a chip
    assert a["modules"]["jit_train_fn"]["seconds"] == approx(60e-6)
    # the learner's phase covers [0, 50): idle there is 40, 30, 20, 10
    (learner,) = a["threads"]
    assert learner["idle"]["learner.train"] == approx(25e-6)
    assert sum(learner["idle"].values()) == approx(a["idle_s"])


def test_a_window_span_clips_both_tables(tmp_path):
    s = Space()
    device(s, 0, [("%fusion.1 = f32[] fusion()", 0, 40, "j/anakin/loss/x"),
                  ("%fusion.2 = f32[] fusion()", 60, 100, "j/anakin/update/x")],
           modules=[("jit_a(1)", 0, 40), ("jit_a(1)", 60, 100)])
    s.plane("/host:CPU").line("python3", 1, [
        ("my.window", 20, 60),  # [20, 80): busy 20 + 20, idle 20
        ("ray_tpu.anakin.call", 0, 50),      # idle overlap [40, 50) = 10
        ("ray_tpu.anakin.readback", 50, 200),  # [50, 60) = 10
    ])
    path = s.write(tmp_path)
    a = da.account(path, window="my.window")
    assert a["window_s"] == approx(60e-6)
    assert a["scopes"] == {"anakin/loss": approx(20e-6),
                           "anakin/update": approx(20e-6)}
    assert a["modules"]["jit_a"]["seconds"] == approx(40e-6)
    (thread,) = a["threads"]
    assert thread["idle"] == {"anakin.call": approx(10e-6),
                              "anakin.readback": approx(10e-6),
                              "other": approx(0)}
    assert thread["seconds"] == {"anakin.call": approx(30e-6),
                                 "anakin.readback": approx(30e-6)}
    # the same window as a pair of nanoseconds; none = the ops' extent
    assert da.account(path, window=(20_000, 80_000))["scopes"] == a["scopes"]
    assert da.account(path)["window_s"] == approx(160e-6)
    with pytest.raises(ValueError, match="no host span"):
        da.account(path, window="bench.slice")
    assert da.account(path, window=(500_000, 600_000)) is None


def test_the_recorded_trace_reads_as_the_benchmark_reads_it():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from lib import trace
    finally:
        sys.path.pop(0)
    theirs = trace.reduce(trace.load(RECORDED))
    ours = da.account(RECORDED, window=trace.WINDOW_SPAN)
    assert ours["busy_s"] == pytest.approx(theirs["busy_s"], abs=1e-9)
    assert ours["window_s"] == pytest.approx(theirs["window_s"], abs=1e-9)
    assert ours["chips"] == theirs["chips"] == 1
    # three launches of one program whose one matrix product has no scope
    assert ours["modules"]["jit__lambda"]["launches"] == 3
    assert ours["unscoped_s"] == pytest.approx(ours["busy_s"], abs=1e-12)
    assert max(ours["scopes"], key=ours["scopes"].get) == "unscoped|fusion"
    assert da.account(os.path.dirname(RECORDED))["busy_s"] > 0  # a directory


def test_a_cpu_trace_has_phases_to_hold_against_the_clock_and_no_account(
        tmp_path):
    import jax
    clock = profiling.PhaseClock()

    def loop():
        clock.bind()
        for _ in range(20):
            with profiling.phase("sebulba.fetch"):
                time.sleep(0.004)
            with profiling.phase("sebulba.env_step") as step:
                time.sleep(0.002)
                step.then("sebulba.upload")
                time.sleep(0.002)

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        thread = threading.Thread(target=loop)
        thread.start()
        thread.join(60)
    finally:
        jax.profiler.stop_trace()
    assert not thread.is_alive()
    assert da.account(str(tmp_path)) is None  # no device plane on the CPU
    (seen,) = da.thread_phases(str(tmp_path))
    snapshot = clock.snapshot()
    assert seen["family"] == "sebulba"
    assert seen["counts"] == snapshot["counts"]
    for name, seconds in snapshot["seconds"].items():
        assert seen["seconds"][name] == pytest.approx(seconds, rel=0.05)


def test_importing_the_module_loads_no_jax_and_no_tensorflow():
    code = ("import sys, json\n"
            "from ray_tpu._private import device_account as da\n"
            f"a = da.account({RECORDED!r})\n"
            "print(json.dumps([a['chips']] + sorted(\n"
            "    m for m in ('jax', 'tensorflow', 'tensorboard')\n"
            "    if m in sys.modules)))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, timeout=120,
                         capture_output=True, text=True, check=True)
    assert json.loads(out.stdout.splitlines()[-1]) == [1]


def test_run_capture_on_the_cpu_has_no_account_and_does_not_raise(tmp_path):
    import jax
    jax.devices()  # a live backend, of CPUs: nothing to trace
    out = profiling.run_capture(0.05, xla_dir=str(tmp_path / "xla"))
    assert out["xla_trace_dir"] is None
    assert "device_account" not in out and "xla_error" not in out


def test_run_capture_accounts_for_the_trace_it_took(tmp_path, monkeypatch):
    """With a device (here: the CPU said to be one) the capture's trace is
    taken with the benchmark's options and reduced; a trace without device
    ops gives None, a reduction that fails an `xla_error`, never a raise."""
    import jax
    jax.devices()
    monkeypatch.setattr(profiling, "owns_device", lambda: True)
    out = profiling.run_capture(0.05, xla_dir=str(tmp_path / "xla"))
    assert out["xla_trace_dir"] == str(tmp_path / "xla")
    assert da.find_xplane(out["xla_trace_dir"]) is not None
    assert out["device_account"] is None and "xla_error" not in out

    def broken(path, window=None):
        raise ValueError("truncated file")

    monkeypatch.setattr(da, "account", broken)
    out = profiling.run_capture(0.05, xla_dir=str(tmp_path / "xla2"))
    assert "device_account" not in out
    assert out["xla_error"] == "ValueError: truncated file"


def test_the_summary_prints_the_account_under_its_process(tmp_path, capsys):
    from ray_tpu.scripts.scripts import _print_profile_summary
    s = Space()
    two_threads_of_one_name(s)
    acct = da.account(s.write(tmp_path), window="bench.slice")
    proc = {"role": "driver", "pid": 7, "node": "node0", "threads": ["a"],
            "folded": {"a;f.py:g": 3}, "xla_trace_dir": "/x"}
    bundle = {"capture_id": "c", "duration_s": 1, "hz": 99,
              "processes": [json.loads(json.dumps(dict(
                  proc, device_account=acct))), dict(proc, pid=8)]}
    _print_profile_summary(bundle)
    text = capsys.readouterr().out
    assert "xla trace: /x" in text
    assert text.count("by scope") == 1  # the second process has no account
    for needle in ("train/loss", "sebulba/apply", "collectives 0.000000 s",
                   "sebulba x 2", "learner x 1", "learner.dequeue",
                   "at least one thread"):
        assert needle in text, needle


@pytest.mark.time_limit(60)
@pytest.mark.parametrize("cpu_clock", [True, False],
                         ids=["cpu", "wall_only"])
def test_the_summary_prints_the_host_account_under_its_process(
        capsys, monkeypatch, cpu_clock):
    """What `run_capture` returns as `host_account`, printed by `profile
    --summarize` under the process: a thread's wall and CPU seconds by
    phase and the process's CPU; wall seconds alone where the platform has
    no per-thread CPU clock."""
    from ray_tpu.scripts.scripts import _print_profile_summary
    if not cpu_clock:
        monkeypatch.delattr(time, "pthread_getcpuclockid")
    clock = profiling.PhaseClock()
    out = {}

    def loop():
        clock.bind()
        before = profiling.host_snapshot([("loop-thread", clock)])
        with profiling.phase("test.work"):
            time.sleep(0.01)
        out["acct"] = profiling.host_account(
            before, profiling.host_snapshot([("loop-thread", clock)]))

    t = threading.Thread(target=loop, daemon=True)
    t.start()
    t.join(60)
    assert not t.is_alive()
    proc = {"role": "driver", "pid": 7, "node": "node0", "threads": ["a"],
            "folded": {"a;f.py:g": 3}}
    bundle = {"capture_id": "c", "duration_s": 1, "hz": 99,
              "processes": [json.loads(json.dumps(dict(
                  proc, host_account=out["acct"]))), dict(proc, pid=8)]}
    _print_profile_summary(bundle)
    text = capsys.readouterr().out
    assert text.count("host account over") == 1  # the second has none
    assert "loop-thread: wall" in text and "test.work" in text
    assert ("python_cores" in text and "off-cpu" in text) == cpu_clock
    assert ("wall seconds only" in text) == (not cpu_clock)


def test_the_module_s_main_prints_the_whole_tables(tmp_path, capsys):
    s = Space()
    two_threads_of_one_name(s)
    assert da.main([s.write(tmp_path), "--window-span", "bench.slice"]) == 0
    text = capsys.readouterr().out
    assert "by module" in text and "host seconds by phase" in text
    assert "idle 0.000080 s" in text
    assert da.render(None) == [
        "no device op in the trace: nothing to account for"]
