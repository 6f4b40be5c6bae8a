"""The program's own clock (`_private/profiling.phase`, `PhaseClock`) and
the names it puts on its time (ISSUE 25):

- phases of a bound thread partition its wall time, counts are exact, an
  unbound thread gets the annotation only, nesting raises, `then` hands
  the time from one name to the next inside one `with`;
- every step of an inline actor's loop is a phase: after two fragments
  each has run, phases + `other_s` is the thread's `wall_s`, and
  `t_fetch_s` / `t_env_s` are views of the same clock;
- an inline actor's thread dispatches compiled programs only (ISSUE 26):
  a profiler session over two `sample()` calls sees its four programs
  and no eager op, nothing is lowered, and what `record` retains are the
  select program's own window handles;
- every program carries its `jax.named_scope`s in its lowered op
  metadata, so that no refactor drops one unnoticed;
- the clock keeps CPU seconds beside wall seconds (ISSUE 50): a phase that
  spins is on the CPU and one that sleeps is not, any thread may read
  another's, an open phase counts up to now, `then` loses no CPU, a
  platform without a per-thread CPU clock reads None, the registry
  forgets what nobody owns, and `run_capture`'s `host_account` adds up.

Every wait has its own timeout (there is no pytest-timeout here).
"""

import queue
import threading
import time

import numpy as np
import pytest

from ray_tpu._private import profiling
from ray_tpu._private.profiling import PhaseClock, phase, sum_snapshots

WAIT_S = 120.0


def _in_thread(target):
    """Run `target` on a fresh thread; its result, or its exception."""
    box = {}

    def body():
        try:
            box["out"] = target()
        except BaseException as e:  # noqa: BLE001 - re-raised below
            box["err"] = e

    t = threading.Thread(target=body, daemon=True)
    t.start()
    t.join(WAIT_S)
    assert not t.is_alive(), "thread did not finish in time"
    if "err" in box:
        raise box["err"]
    return box["out"]


# ---------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------
@pytest.mark.parametrize("bound", [True, False])
def test_phases_partition_a_threads_wall_time(bound):
    rounds = 40

    def loop():
        clock = PhaseClock()
        if bound:
            clock.bind()
        for _ in range(rounds):
            with phase("test.a"):
                time.sleep(0.005)
            with phase("test.b"):
                time.sleep(0.002)
        return clock.snapshot()

    snap = _in_thread(loop)
    if not bound:
        # Annotation only: nothing raised, nothing counted.
        assert snap["seconds"] == {} and snap["counts"] == {}
        return
    assert snap["counts"] == {"test.a": rounds, "test.b": rounds}
    covered = sum(snap["seconds"].values())
    assert snap["seconds"]["test.a"] > snap["seconds"]["test.b"] > 0
    assert abs(covered + snap["other_s"] - snap["wall_s"]) < 1e-9
    assert 0 <= snap["other_s"] <= 0.02 * snap["wall_s"], snap


def test_nested_phases_raise_and_leave_the_clock_usable():
    def body():
        clock = PhaseClock().bind()
        with pytest.raises(RuntimeError, match="do not nest"):
            with phase("test.outer"):
                with phase("test.inner"):
                    pass
        with phase("test.outer"):
            pass
        return clock.snapshot()

    snap = _in_thread(body)
    assert snap["counts"] == {"test.outer": 2}


def test_then_ends_one_phase_and_begins_the_next():
    def body():
        clock = PhaseClock().bind()
        with phase("test.wait") as step:
            time.sleep(0.004)
            step.then("test.work")
            during = clock.snapshot()
            time.sleep(0.002)
        with phase("test.unbound-free"):
            pass
        return during, clock.snapshot()

    during, snap = _in_thread(body)
    assert during["counts"] == {"test.wait": 1, "test.work": 0}
    assert snap["counts"] == {"test.wait": 1, "test.work": 1,
                              "test.unbound-free": 1}
    assert snap["seconds"]["test.wait"] >= 0.004
    assert snap["seconds"]["test.work"] >= 0.002
    assert abs(sum(snap["seconds"].values()) + snap["other_s"]
               - snap["wall_s"]) < 1e-9
    # No clock bound: then() is the annotation's business only.
    with phase("test.wait") as step:
        step.then("test.work")


def test_snapshot_counts_the_open_phase_and_sums_over_threads():
    entered, release = threading.Event(), threading.Event()
    clock = PhaseClock()

    def blocked():
        clock.bind()
        with phase("test.blocked"):
            entered.set()
            assert release.wait(WAIT_S)

    t = threading.Thread(target=blocked, daemon=True)
    t.start()
    try:
        assert entered.wait(WAIT_S)
        time.sleep(0.02)
        during = clock.snapshot()
    finally:
        release.set()
        t.join(WAIT_S)
    assert not t.is_alive()
    after = clock.snapshot()
    # Blocked inside a phase is that phase's time, not `other`.
    assert during["counts"] == {"test.blocked": 0}
    assert during["seconds"]["test.blocked"] >= 0.02
    assert after["counts"] == {"test.blocked": 1}
    assert after["seconds"]["test.blocked"] >= during["seconds"][
        "test.blocked"]
    both = sum_snapshots([during, after])
    assert both["counts"] == {"test.blocked": 1}
    assert both["wall_s"] == during["wall_s"] + after["wall_s"]


def test_importing_the_primitive_does_not_import_jax():
    import subprocess
    import sys
    code = ("import sys; from ray_tpu._private.profiling import phase, "
            "PhaseClock\nc = PhaseClock().bind()\n"
            "with phase('x'): pass\n"
            "assert c.snapshot()['counts'] == {'x': 1}\n"
            "assert 'jax' not in sys.modules, 'jax imported'")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=WAIT_S)


# ---------------------------------------------------------------------
# CPU seconds beside wall seconds (ISSUE 50)
# ---------------------------------------------------------------------
CPU_KEYS = ("cpu_seconds", "cpu_read_seconds", "cpu_s", "other_cpu_s",
            "run_delay_s")
# A CPU clock and `perf_counter` are two clocks: a phase's CPU may pass
# its wall by what reading them costs, a microsecond a phase.
CLOCK_SLACK_S = 2e-3


@pytest.fixture
def cpu_reads():
    """The phases read their CPU: a `phase_cpu_reads()` window, as a
    capture opens one, around the whole test."""
    with profiling.phase_cpu_reads():
        yield


def _spin(cpu_s: float) -> None:
    """Pure Python until the calling thread has run for `cpu_s`."""
    end = time.thread_time() + cpu_s
    while time.thread_time() < end:
        sum(i * i for i in range(200))


def _spin_and_sleep():
    clock = PhaseClock().bind()
    with phase("test.spin"):
        _spin(0.1)
    with phase("test.sleep"):
        time.sleep(0.1)
    return clock.snapshot()


@pytest.mark.time_limit(120)
@pytest.mark.parametrize("name,on_cpu", [("test.spin", True),
                                         ("test.sleep", False)])
def test_a_phase_that_spins_is_on_the_cpu_and_one_that_sleeps_is_not(
        cpu_reads, name, on_cpu):
    # The best of a few tries: on a loaded box a spinning thread may be
    # kept off the cores for a while, and that is not what is tested.
    for _ in range(5):
        snap = _in_thread(_spin_and_sleep)
        wall, cpu = snap["seconds"][name], snap["cpu_seconds"][name]
        if (cpu >= 0.5 * wall) if on_cpu else (cpu < 0.2 * wall):
            break
    else:
        pytest.fail(f"{name}: cpu {cpu} of wall {wall}")
    assert set(snap["cpu_seconds"]) == set(snap["seconds"])
    assert snap["cpu_read_seconds"] == snap["seconds"]  # every one is read
    assert snap["cpu_s"] >= 0.1 - CLOCK_SLACK_S


@pytest.mark.time_limit(120)
def test_wall_covers_cpu_and_what_no_phase_covers_is_not_negative(cpu_reads):
    def loop():
        clock = PhaseClock().bind()
        for _ in range(30):
            with phase("test.spin"):
                _spin(0.002)
            with phase("test.sleep"):
                time.sleep(0.001)
            _spin(0.001)  # in no phase
        return clock.snapshot()

    snap = _in_thread(loop)
    for name, wall in snap["seconds"].items():
        assert wall >= snap["cpu_seconds"][name] - CLOCK_SLACK_S, name
    assert snap["wall_s"] >= snap["cpu_s"] - CLOCK_SLACK_S
    assert snap["other_cpu_s"] >= 30 * 0.001 - CLOCK_SLACK_S
    assert abs(sum(snap["cpu_seconds"].values()) + snap["other_cpu_s"]
               - snap["cpu_s"]) < 1e-9
    assert snap["run_delay_s"] is None or snap["run_delay_s"] >= 0


@pytest.mark.time_limit(120)
def test_any_thread_reads_the_bound_threads_cpu_and_it_outlives_the_thread(
        cpu_reads):
    spun, release = threading.Event(), threading.Event()
    clock, own = PhaseClock(), {}

    def body():
        clock.bind()
        t0 = time.thread_time()
        with phase("test.spin"):
            _spin(0.05)
        own["cpu"] = time.thread_time() - t0
        spun.set()
        assert release.wait(WAIT_S)  # asleep: its CPU clock stands still

    t = threading.Thread(target=body, daemon=True)
    t.start()
    try:
        assert spun.wait(WAIT_S)
        during = clock.snapshot()
    finally:
        release.set()
        t.join(WAIT_S)
    assert not t.is_alive()
    after = clock.snapshot()
    # The reader's clock_gettime on the thread's clock id against the
    # thread's own thread_time: the same kernel counter.
    assert abs(during["cpu_s"] - own["cpu"]) < 0.02, (during, own)
    assert abs(during["cpu_seconds"]["test.spin"] - own["cpu"]) < 0.02
    # Gone: the value the thread last wrote, and no schedstat to read.
    assert after["cpu_seconds"] == during["cpu_seconds"]
    assert 0.05 - CLOCK_SLACK_S <= after["cpu_s"] <= during["cpu_s"] + 1e-9
    assert after["run_delay_s"] is None
    assert after["wall_s"] > during["wall_s"]


@pytest.mark.time_limit(120)
def test_an_open_phases_cpu_counts_up_to_now(cpu_reads):
    entered, stop = threading.Event(), threading.Event()
    clock = PhaseClock()

    def body():
        clock.bind()
        with phase("test.open"):
            entered.set()
            while not stop.is_set():
                _spin(0.005)

    t = threading.Thread(target=body, daemon=True)
    t.start()
    try:
        assert entered.wait(WAIT_S)
        deadline = time.monotonic() + WAIT_S
        during = clock.snapshot()
        while (during["cpu_seconds"]["test.open"] < 0.02
               and time.monotonic() < deadline):
            time.sleep(0.01)
            during = clock.snapshot()
    finally:
        stop.set()
        t.join(WAIT_S)
    assert not t.is_alive()
    assert during["counts"] == {"test.open": 0}
    assert during["cpu_seconds"]["test.open"] >= 0.02
    assert during["seconds"]["test.open"] >= \
        during["cpu_seconds"]["test.open"] - CLOCK_SLACK_S
    after = clock.snapshot()
    assert after["counts"] == {"test.open": 1}
    assert after["cpu_seconds"]["test.open"] >= \
        during["cpu_seconds"]["test.open"] - CLOCK_SLACK_S


@pytest.mark.time_limit(60)
def test_then_loses_no_cpu_between_the_two_phases(cpu_reads, monkeypatch):
    """One reading ends the first phase and begins the second: with a CPU
    clock that ticks once a reading, two phases joined by `then` take the
    readings 1, 2, 3 and account for all of 3 - 1."""
    import itertools
    ticks = itertools.count()
    monkeypatch.setattr(profiling, "_thread_cpu", lambda: float(next(ticks)))

    def body():
        clock = PhaseClock().bind()
        with phase("test.wait") as step:
            step.then("test.work")
        return clock

    clock = _in_thread(body)
    assert {k: v[1:3] for k, v in clock._phases.items()} == {
        "test.wait": [1, 1.0], "test.work": [1, 1.0]}
    assert clock._cpu_last - clock._cpu_start == 3.0


@pytest.mark.time_limit(120)
def test_the_phases_cpu_is_read_only_while_somebody_asks(monkeypatch):
    real, reads = profiling._thread_cpu, []

    def counted():
        reads.append(1)
        return real()

    monkeypatch.setattr(profiling, "_thread_cpu", counted)

    def rounds(n):
        for _ in range(n):
            with phase("test.spin"):
                _spin(0.004)
            with phase("test.lock_wait") as step:
                time.sleep(0.002)
                step.then("test.sleep")
                time.sleep(0.002)

    def loop():
        clock = PhaseClock().bind()
        del reads[:]
        rounds(5)
        quiet = clock.snapshot(), len(reads)
        with profiling.phase_cpu_reads():
            with profiling.phase_cpu_reads():  # windows may overlap
                rounds(5)
            rounds(5)
        asked = clock.snapshot(), len(reads)
        rounds(5)
        return quiet, asked, (clock.snapshot(), len(reads))

    for _ in range(5):  # the best of a few tries, as above
        (quiet, n_quiet), (asked, n_asked), (after, n_after) = \
            _in_thread(loop)
        spin = asked["cpu_seconds"]["test.spin"] / asked[
            "cpu_read_seconds"]["test.spin"]
        if spin >= 0.5:
            break
    assert spin >= 0.5, asked
    # Nobody asked: no read, no CPU by phase, and the thread's own total
    # (read by the snapshot) is there all the same.
    assert n_quiet == 0
    assert set(quiet["cpu_seconds"].values()) == {0.0}
    assert set(quiet["cpu_read_seconds"].values()) == {0.0}
    assert quiet["cpu_s"] >= 5 * 0.004 - CLOCK_SLACK_S
    assert quiet["other_cpu_s"] == quiet["cpu_s"]
    # Asked: two reads a phase, and `then`'s one reading serves both the
    # phase it ends and the one it begins.
    assert n_asked == 10 * (2 + 3)
    assert asked["counts"] == {"test.spin": 15, "test.lock_wait": 15,
                               "test.sleep": 15}
    for name in ("test.lock_wait", "test.sleep"):
        assert asked["cpu_seconds"][name] < \
            0.2 * asked["cpu_read_seconds"][name]
    for name, wall in asked["seconds"].items():
        assert 0.4 * wall < asked["cpu_read_seconds"][name] < 0.9 * wall
    # The window closed: nothing more is read.
    assert n_after == n_asked
    assert after["cpu_read_seconds"] == asked["cpu_read_seconds"]
    assert after["counts"]["test.spin"] == 20
    assert profiling._asked == 0


@pytest.mark.time_limit(60)
def test_sum_snapshots_adds_the_cpu_keys(cpu_reads):
    a, b = _in_thread(_spin_and_sleep), _in_thread(_spin_and_sleep)
    both = sum_snapshots([a, b])
    for key in ("wall_s", "other_s", "cpu_s", "other_cpu_s"):
        assert both[key] == a[key] + b[key], key
    assert both["cpu_seconds"] == {
        name: a["cpu_seconds"][name] + b["cpu_seconds"][name]
        for name in a["cpu_seconds"]}
    if a["run_delay_s"] is not None and b["run_delay_s"] is not None:
        assert both["run_delay_s"] == a["run_delay_s"] + b["run_delay_s"]
    # One thread without a CPU clock: the sums that need it are None.
    c = dict(b, **{key: None for key in CPU_KEYS})
    mixed = sum_snapshots([a, c])
    assert [mixed[key] for key in CPU_KEYS] == [None] * 5
    assert mixed["seconds"] == both["seconds"]


@pytest.mark.time_limit(60)
def test_without_a_per_thread_cpu_clock_the_new_keys_are_none(monkeypatch):
    monkeypatch.delattr(time, "pthread_getcpuclockid")

    def loop():
        clock = PhaseClock().bind()
        with phase("test.a") as step:
            time.sleep(0.002)
            step.then("test.b")
        with phase("test.a"):
            pass
        return clock.snapshot()

    snap = _in_thread(loop)
    assert [snap[key] for key in CPU_KEYS] == [None] * 5
    assert snap["counts"] == {"test.a": 2, "test.b": 1}
    assert snap["seconds"]["test.a"] >= 0.002
    assert abs(sum(snap["seconds"].values()) + snap["other_s"]
               - snap["wall_s"]) < 1e-9
    acct = profiling.host_account(
        {"threads": {"t": snap}, "process": profiling.process_cpu(), "t": 0.0},
        {"threads": {"t": snap}, "process": profiling.process_cpu(), "t": 1.0})
    assert acct["threads"]["t"]["cpu_s"] is None
    assert acct["process"]["python_cores"] is None
    assert "wall seconds only" in profiling.render_host_account(acct)[0]


@pytest.mark.time_limit(60)
def test_the_registry_forgets_a_clock_whose_owner_is_collected():
    import gc

    class Owner:
        def __init__(self):
            self.clock = PhaseClock()

    owner = Owner()
    thread = threading.Thread(target=owner.clock.bind, name="test-owned",
                              daemon=True)
    thread.start()
    thread.join(WAIT_S)
    assert ("test-owned", owner.clock) in profiling.clocks()
    # Its thread has ended: still listed, but no live thread to account for.
    assert "test-owned" not in profiling.host_snapshot()["threads"]
    del owner
    gc.collect()
    assert "test-owned" not in [name for name, _ in profiling.clocks()]
    assert set(profiling.process_cpu()) == {"cpu_s", "cores"}
    assert profiling.process_cpu()["cores"] >= 1


@pytest.mark.time_limit(120)
def test_run_capture_returns_a_host_account_that_adds_up():
    stop = threading.Event()
    clock = PhaseClock()

    def loop():
        clock.bind()
        while not stop.is_set():
            with phase("test.spin"):
                _spin(0.002)
            with phase("test.sleep"):
                time.sleep(0.002)

    t = threading.Thread(target=loop, name="test-capture-loop", daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + WAIT_S
        while not clock.snapshot()["counts"].get("test.sleep") \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        out = profiling.run_capture(0.3)
    finally:
        stop.set()
        t.join(WAIT_S)
    assert not t.is_alive()
    acct = out["host_account"]
    assert 0.3 <= acct["window_s"] < 5.0
    mine = acct["threads"]["test-capture-loop"]
    for thread in acct["threads"].values():
        assert abs(sum(p["wall_s"] for p in thread["phases"].values())
                   - thread["wall_s"]) < 1e-6
        assert abs(sum(p["cpu_s"] for p in thread["phases"].values())
                   - thread["cpu_s"]) < 1e-6
    assert set(mine["phases"]) == {"test.spin", "test.sleep", "other"}
    assert mine["phases"]["test.spin"]["count"] > 0
    assert mine["phases"]["test.spin"]["cpu_s"] > \
        mine["phases"]["test.sleep"]["cpu_s"]
    proc = acct["process"]
    assert abs(proc["python_cpu_s"] + proc["native_cpu_s"]
               - proc["cpu_s"]) < 1e-9
    assert proc["python_cpu_s"] >= mine["cpu_s"] > 0
    assert proc["python_cores"] == proc["python_cpu_s"] / acct["window_s"]
    text = "\n".join(profiling.render_host_account(acct))
    assert "test-capture-loop" in text and "off-cpu" in text


@pytest.mark.time_limit(120)
def test_two_threads_that_spin_under_one_gil_read_one_core():
    stop, clocks = threading.Event(), [PhaseClock(), PhaseClock()]

    def loop(clock):
        clock.bind()
        with phase("test.spin"):
            while not stop.is_set():
                sum(i * i for i in range(200))

    threads = [threading.Thread(target=loop, args=(c,), daemon=True)
               for c in clocks]
    for t in threads:
        t.start()
    named = [("spin-%d" % i, c) for i, c in enumerate(clocks)]
    try:
        deadline = time.monotonic() + WAIT_S
        while not all(c._open for c in clocks) \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        # A reading takes the clocks one after the other; on a loaded box
        # the reader may be kept waiting between two of them, and a window
        # that the threads' walls do not match is taken again.
        for _ in range(5):
            before = profiling.host_snapshot(named)
            time.sleep(0.5)
            acct = profiling.host_account(before,
                                          profiling.host_snapshot(named))
            walls = sum(t["wall_s"] for t in acct["threads"].values())
            if abs(walls - 2 * acct["window_s"]) < 0.1 * acct["window_s"]:
                break
    finally:
        stop.set()
        for t in threads:
            t.join(WAIT_S)
    assert not any(t.is_alive() for t in threads)
    assert abs(walls - 2 * acct["window_s"]) < 0.1 * acct["window_s"]
    # Two threads wanted the CPU the whole time and one GIL let one run.
    assert 0.3 < acct["process"]["python_cores"] <= 1.2, acct["process"]


# ---------------------------------------------------------------------
# the sites: an inline actor's loop
# ---------------------------------------------------------------------
ACTOR_PHASES = ("sebulba.fetch", "sebulba.record", "sebulba.env_step",
                "sebulba.upload", "sebulba.apply", "sebulba.lock_wait",
                "sebulba.select", "sebulba.pack", "sebulba.enqueue")


def _sprite_sampler(delta: bool, onchip_steps: int = 1):
    from ray_tpu.rllib.agents.pg.pg import DEFAULT_CONFIG, PGJaxPolicy
    from ray_tpu.rllib.env.delta_obs import BatchedSpriteAtari
    from ray_tpu.rllib.evaluation.device_sampler import DeviceSebulbaSampler
    # Episodes shorter than a fragment: the delta path's full-row upload
    # and scatter run too.
    envs = [BatchedSpriteAtari(2, episode_len=3, seed=s) for s in (1, 2)]
    cfg = dict(DEFAULT_CONFIG)
    cfg.update({"model": {"fcnet_hiddens": [8],
                          "conv_filters": ((4, 8, 4), (8, 4, 2))},
                "seed": 0})
    policy = PGJaxPolicy(envs[0].observation_space, envs[0].action_space,
                         cfg)
    sampler = DeviceSebulbaSampler(envs, policy, rollout_fragment_length=4,
                                   use_delta=delta,
                                   onchip_steps=onchip_steps)
    assert sampler.delta == delta
    return sampler


@pytest.mark.parametrize("delta", [True, False], ids=["delta", "frames"])
def test_every_step_of_the_actor_loop_is_a_phase(delta):
    from ray_tpu.rllib.optimizers.async_samples_optimizer import (
        InlineActorThread)

    class Learner:
        inqueue = queue.Queue(maxsize=1)

    sampler = _sprite_sampler(delta)
    actor = InlineActorThread(sampler, Learner(), idx=0)
    actor.start()
    try:
        for _ in range(2):
            Learner.inqueue.get(timeout=WAIT_S)
    finally:
        actor.stop()
        deadline = time.monotonic() + WAIT_S
        while actor.is_alive() and time.monotonic() < deadline:
            try:  # a fragment in flight may be waiting for room
                Learner.inqueue.get(timeout=0.05)
            except queue.Empty:
                pass
        actor.join(WAIT_S)
    assert not actor.is_alive() and actor.error is None

    stats = sampler.transfer_stats()
    snap = stats["phases"]
    for name in ACTOR_PHASES:
        assert snap["counts"].get(name, 0) > 0, (name, snap["counts"])
        assert snap["seconds"][name] > 0
    assert set(snap["counts"]) == set(ACTOR_PHASES)
    assert snap["other_s"] >= 0
    assert abs(sum(snap["seconds"].values()) + snap["other_s"]
               - snap["wall_s"]) < 1e-9
    # The benchmark's two older readers see the same clock.
    assert stats["t_fetch_s"] == round(snap["seconds"]["sebulba.fetch"], 3)
    assert stats["t_env_s"] == round(snap["seconds"]["sebulba.env_step"], 3)
    assert sampler.t_fetch == sampler.clock.seconds("sebulba.fetch")
    assert stats["fetch_waits"] == snap["counts"]["sebulba.fetch"]


# ---------------------------------------------------------------------
# the rule of the actor loop: compiled programs only
# ---------------------------------------------------------------------
SAMPLER_PROGRAMS = {"apply_delta", "apply_frame", "apply_full", "select_fn",
                    "pack"}
LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def _programs_dispatched(body, tmp_path) -> dict:
    """Run `body` under a profiler session; the jitted functions whose
    call the host trace holds, by name, with their counts. An op issued
    outside `jit` is one too: jax runs it as a one-primitive program
    named after the primitive (`dynamic_slice`, `_threefry_fold_in`)."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    calls = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("PjitFunction("):
                    name = event.name[len("PjitFunction("):-1]
                    calls[name] = calls.get(name, 0) + 1
    return calls


def test_the_profiler_sees_an_eager_op(tmp_path):
    """The detector of the next test, shown to detect: one index of a
    device array outside `jit` is a program in the host trace."""
    import jax.numpy as jnp
    x = jnp.arange(6.0).reshape(2, 3)
    calls = _programs_dispatched(lambda: x[1].block_until_ready(),
                                 tmp_path)
    assert calls and not set(calls) & SAMPLER_PROGRAMS, calls


@pytest.mark.parametrize("delta,k", [(True, 1), (False, 1), (True, 2)],
                         ids=["delta", "frames", "delta-k2"])
def test_the_actor_thread_dispatches_compiled_programs_only(
        delta, k, tmp_path):
    import jax.monitoring
    sampler = _sprite_sampler(delta, onchip_steps=k)
    lowered = []

    def on_event(event, duration, **kw):
        if event == LOWERED:
            lowered.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        calls = _programs_dispatched(
            lambda: (sampler.sample(), sampler.sample()), tmp_path)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert not lowered, "a program was lowered after the constructor"
    apply_fn = "apply_delta" if delta else "apply_frame"
    assert {apply_fn, "select_fn", "pack"} <= set(calls), calls
    assert set(calls) <= SAMPLER_PROGRAMS, (
        f"eager ops on the actor thread: "
        f"{sorted(set(calls) - SAMPLER_PROGRAMS)}")


@pytest.mark.parametrize("k", [1, 2], ids=["k1", "k2"])
def test_record_retains_the_window_handles_themselves(k):
    sampler = _sprite_sampler(delta=True, onchip_steps=k)
    windows, packed = [], []
    consume, pack_fn = sampler._consume_window, sampler._pack_fn
    sampler._consume_window = (
        lambda g: windows.append((g, g.pending)) or consume(g))
    sampler._pack_fn = lambda *a: packed.append(a) or pack_fn(*a)
    sampler.sample()
    (obs, logp, di, val, boot), = packed
    for gi, g in enumerate(sampler.groups):
        mine = [pending for group, pending in windows if group is g]
        assert len(mine) == sampler.T // k == len(logp[gi])
        assert len(obs[gi]) == sampler.T
        for w, (_, logp_d, di_d, val_d) in enumerate(mine):
            assert logp[gi][w] is logp_d
            assert di[gi][w] is di_d
            assert val[gi][w] is val_d
        assert boot[gi] is g.obs_next


# ---------------------------------------------------------------------
# the names inside the programs
# ---------------------------------------------------------------------
REHEARSAL = {"num_workers": 0, "min_iter_time_s": 0, "seed": 0}


def _lowered(fn, *args) -> str:
    return fn.lower(*args).as_text(debug_info=True)


def _anakin_text():
    from ray_tpu.rllib.agents.registry import get_trainer_class
    t = get_trainer_class("IMPALA")(config=dict(
        REHEARSAL, env="SyntheticAtari-v0", anakin=True,
        num_envs_per_worker=8, rollout_fragment_length=4,
        train_batch_size=32, anakin_updates_per_call=2))
    try:
        opt, pol = t.optimizer, t.optimizer.policy
        return {"anakin_fn": _lowered(
            opt._anakin_fn, pol.params, pol.opt_state, opt._env_state,
            opt._obs, opt._rng, opt._ep_rew, opt._ep_len, opt._pstate)}
    finally:
        t.stop()


def _sebulba_texts():
    from ray_tpu.rllib.agents.registry import get_trainer_class
    t = get_trainer_class("IMPALA")(config=dict(
        REHEARSAL, env="SyntheticAtariFrames-v0", num_inline_actors=1,
        num_envs_per_worker=4, device_frame_stack=4, obs_delta=False,
        rollout_fragment_length=4, train_batch_size=16))
    t.stop()  # the actor and learner threads; the programs stay
    sampler = t.optimizer._inline_actors[0].sampler
    pol, g = sampler.policy, sampler.groups[0]
    batch = pol._device_batch(sampler.sample())
    rng = pol._next_rng()
    return {
        "train_fn": _lowered(pol._train_fn, pol.params, pol.opt_state,
                             batch, rng, pol.loss_state),
        "select_fn": _lowered(sampler._select_fn, pol.params, g.obs_next,
                              pol._host_rng, pol._next_rng_counter(), True),
        "apply_frame": _lowered(sampler._apply_fn, g.stack, g.host_obs,
                                g.host_done),
        "action_fn": _lowered(pol._action_fn, pol.params, g.obs_next, rng,
                              True),
        "pack": _lowered(sampler._pack_fn, [[g.obs_next]], [[g.pending[1]]],
                         [[g.pending[2]]], [[g.pending[3]]], [g.obs_next]),
    }


def _delta_texts():
    from conftest import cpu_mesh, ppo_batch, ppo_policy
    from ray_tpu.rllib.evaluation.device_sampler import apply_full
    sampler = _sprite_sampler(delta=True)
    g = sampler.groups[0]
    packed = np.zeros((g.n, 3 * int(g.env.delta_budget) + 1), np.uint8)
    pol = ppo_policy(cpu_mesh(2), hiddens=(16,))
    batch = pol._device_batch(ppo_batch(32))
    return {
        "apply_delta": _lowered(sampler._apply_fn, g.stack, g.frames_d,
                                packed),
        "apply_full": _lowered(
            apply_full, g.frames_d, np.zeros(1, np.int32),
            np.zeros((1, sampler._hw), np.uint8)),
        "train_fn_mesh2": _lowered(pol._train_fn, pol.params,
                                   pol.opt_state, batch, pol._next_rng(),
                                   pol.loss_state),
        "sgd_fn": _lowered(pol._make_sgd_fn(1, 2, 16), pol.params,
                           pol.opt_state, batch, pol._next_rng(),
                           pol.loss_state),
    }


PROGRAM_SCOPES = [
    ("anakin_fn", "anakin/env_step"), ("anakin_fn", "anakin/inference"),
    ("anakin_fn", "anakin/loss"), ("anakin_fn", "anakin/update"),
    ("anakin_fn", "anakin/pack"),
    ("train_fn", "train/loss"), ("train_fn", "train/update"),
    ("train_fn_mesh2", "train/loss"), ("train_fn_mesh2", "train/update"),
    ("sgd_fn", "train/loss"), ("sgd_fn", "train/update"),
    ("action_fn", "policy/action"),
    ("select_fn", "sebulba/select"), ("apply_frame", "sebulba/apply"),
    ("apply_delta", "sebulba/apply"), ("apply_full", "sebulba/apply"),
    ("pack", "sebulba/pack"),
]


@pytest.fixture(scope="module")
def lowered_texts():
    """Every program lowered once, on the CPU at rehearsal sizes."""
    texts = {}
    for build in (_anakin_text, _sebulba_texts, _delta_texts):
        texts.update(_in_thread(build))
    return texts


@pytest.mark.parametrize("program,scope", PROGRAM_SCOPES)
def test_program_ops_carry_their_scope(lowered_texts, program, scope):
    text = lowered_texts[program]
    # The scope is in the op metadata (a `loc("...")` of the lowered
    # text), not only in a function's name.
    named = [line for line in text.splitlines()
             if line.startswith("#loc") and scope + "/" in line]
    assert named, f"{program}: no op under {scope}"


def test_phase_annotation_is_the_profilers_own(monkeypatch):
    """With jax loaded, a phase opens `TraceAnnotation("ray_tpu.<name>")`:
    the span a profiler session records on the device trace's clock."""
    import jax.profiler
    seen = []

    class Annotation:
        def __init__(self, name):
            seen.append(name)

        def __enter__(self):
            seen.append("enter")

        def __exit__(self, *exc):
            seen.append("exit")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with profiling.phase("sebulba.upload"):
        seen.append("body")
    assert seen == ["ray_tpu.sebulba.upload", "enter", "body", "exit"]
