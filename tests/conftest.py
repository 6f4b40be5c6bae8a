"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform (mirroring the reference's
in-process multi-node `cluster_utils.Cluster` trick, SURVEY.md §4.2: fake
topology so collective code runs in CI without real hardware).

Waiting, the suite's one rule: nothing under `tests/` waits without a
bound. Every test runs under `TIME_LIMIT_S` (a test that truly needs more
carries `@pytest.mark.time_limit(seconds)`), so a `get`, `wait`, `join()`
or `proc.wait()` that blocks on another process may omit its own timeout
and is then left to that limit by design; it passes one where the test
asserts how long the thing may take. A wait for "until X has happened" is
`wait_until(lambda: X, timeout)`, never a `time.sleep` of a guessed
length; a sleep stays where it IS the test (a heartbeat interval, a chaos
delay, a load held for a duration).
"""

import contextlib
import faulthandler
import os
import signal
import sys
import tempfile
import time

# Must happen before jax initializes its backend.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "0"
# Tier-1 compiles thousands of CPU programs; keep them out of the checkout
# (parallel/mesh.py would put them in its in-tree cache), at a fixed path
# so reruns hit.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      "/tmp/ray_tpu_test_jax_cache")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

try:
    import jax
    jax.config.update("jax_platforms", "cpu")
except Exception:
    pass

import pytest

# Seconds one test may take, set-up and teardown included: twice the slowest
# tier-1 test of a whole six-worker run and more (CHANGES.md, PR 58: on the
# builder's eight cores from an empty compile cache the run took 1,180 s of
# the driver's 1,470 and its slowest test 66.8 s, one that lowers a whole
# cell; at PR 57 the slowest was 140 s). A test that needs more than half
# the limit, 90 s, carries `@pytest.mark.time_limit(seconds)` and says why.
TIME_LIMIT_S = 180
# After the limit a raise has this long to unwind the test and its
# fixtures; then the worker process dumps its stacks and exits (xdist
# reports the test failed and starts another worker).
UNWIND_S = 60


def wait_until(predicate, timeout, msg="condition"):
    """Poll `predicate` until it returns something true, and return that;
    fail the test, naming `msg`, when `timeout` seconds pass first."""
    deadline = time.monotonic() + timeout
    while True:
        value = predicate()
        if value:
            return value
        if time.monotonic() >= deadline:
            pytest.fail(f"{msg}: not met within {timeout} s")
        time.sleep(0.02)


# A toy PPO policy on a mesh of the virtual CPU devices, and a batch for
# it: what the tests of the learners' update programs share.
TOY_OBS_SHAPE, TOY_NUM_ACTIONS = (8,), 4


def toy_spaces():
    import numpy as np

    from ray_tpu.rllib.env.spaces import Box, Discrete
    return (Box(low=-np.inf, high=np.inf, shape=TOY_OBS_SHAPE,
                dtype=np.float32), Discrete(TOY_NUM_ACTIONS))


def cpu_mesh(n=8):
    import jax

    from ray_tpu.parallel import mesh as mesh_lib
    devices = jax.devices()[:n]
    if len(devices) < n:
        pytest.skip(f"need {n} devices, have {len(jax.devices())}")
    return mesh_lib.make_mesh(devices=devices, axis_names=("dp",))


def ppo_policy(mesh, overrides=None, hiddens=(16, 16)):
    from ray_tpu.rllib.agents.ppo.ppo import DEFAULT_CONFIG, PPOJaxPolicy
    config = dict(DEFAULT_CONFIG)
    config.update({"_mesh": mesh, "model": {"fcnet_hiddens": list(hiddens)}})
    config.update(overrides or {})
    return PPOJaxPolicy(*toy_spaces(), config)


def ppo_batch(n):
    import __graft_entry__
    return __graft_entry__._synthetic_ppo_batch(
        n, TOY_OBS_SHAPE, TOY_NUM_ACTIONS)


def _all_stacks() -> str:
    with tempfile.TemporaryFile(mode="w+") as f:
        faulthandler.dump_traceback(file=f, all_threads=True)
        f.seek(0)
        return f.read()


def _stop_runtime_and_children():
    """The runtime's processes are this process's descendants: a head
    spawns its workers, a `Cluster` its node agents, an agent its workers.
    Shut down in order, then kill whatever of them is still there."""
    import psutil
    import ray_tpu
    children = psutil.Process().children(recursive=True)
    try:
        ray_tpu.shutdown()
    except BaseException as e:  # the limit again, or a wedged runtime
        sys.stderr.write(f"ray_tpu.shutdown() after a time limit: {e!r}\n")
    for proc in children:
        try:
            proc.kill()
        except psutil.NoSuchProcess:
            pass
    psutil.wait_procs(children, timeout=10)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    """The suite's only time limit. A timer in this (the main) thread
    raises into the test at its limit with every thread's stack in the
    message, and again every `UNWIND_S / 2` while fixtures unwind; behind
    it faulthandler's own thread ends a process whose main thread a raise
    cannot reach (stuck in native code), its dump going to
    `<tmpdir>/ray_tpu_tests_hard_limit.<pid>.log`."""
    marker = item.get_closest_marker("time_limit")
    limit = float(marker.args[0]) if marker else TIME_LIMIT_S
    fired = []

    def on_limit(signum, frame):
        signal.setitimer(signal.ITIMER_REAL, UNWIND_S / 2)
        stacks = "" if fired else _all_stacks()
        fired.append(True)
        pytest.fail(f"{item.nodeid} passed its time limit of {limit:g} s"
                    f"\n{stacks}", pytrace=False)

    previous = signal.signal(signal.SIGALRM, on_limit)
    signal.setitimer(signal.ITIMER_REAL, limit)
    faulthandler.dump_traceback_later(limit + UNWIND_S, exit=True,
                                      file=item.config.hard_limit_log)
    try:
        yield
        if fired:
            _stop_runtime_and_children()
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


def pytest_unconfigure(config):
    # A process that gets here was not ended by the hard limit.
    config.hard_limit_log.close()
    with contextlib.suppress(FileNotFoundError):  # a tmp cleaner's
        os.unlink(config.hard_limit_log.name)


def pytest_configure(config):
    config.hard_limit_log = open(os.path.join(
        tempfile.gettempdir(),
        f"ray_tpu_tests_hard_limit.{os.getpid()}.log"), "w")
    config.addinivalue_line(
        "markers",
        "time_limit(seconds): this test's own time limit, in place of "
        "conftest.TIME_LIMIT_S")
    config.addinivalue_line(
        "markers",
        "soak: long-running chaos workload (opt-in via RAY_TPU_SOAK=1; "
        "parity: ci/long_running_tests)")
    config.addinivalue_line(
        "markers",
        "slow: long chaos soaks and other tier-2 tests excluded from "
        "the tier-1 run (-m 'not slow')")


def pytest_generate_tests(metafunc):
    """A check that the token families share (`tests/token_families.py`)
    takes its cases from the family of the module that binds it."""
    for names, of in getattr(metafunc.function, "family_cases", ()):
        metafunc.parametrize(names, of(metafunc.module.FAMILY))


@pytest.fixture(scope="module")
def family(request):
    """The token family a file's checks are of: its row, `FAMILY`."""
    return request.module.FAMILY


@pytest.fixture(scope="module")
def token_trainer(family):
    """The family's tiny model on the fused Anakin path, one a file,
    after one iteration: the fused program is compiled and Adam's moments
    are not zero. Its cost is the first test's that asks for it, which a
    family's update file makes the loss's check (the first name it
    imports), so that no one test carries every compile."""
    from token_families import token_trainer_config

    from ray_tpu.rllib.agents.impala import IMPALATrainer
    trainer = IMPALATrainer(config=token_trainer_config(family))
    trainer.train()
    yield trainer
    trainer.stop()


@pytest.fixture
def ray_start():
    """Boot a real multi-process runtime for a test, like the reference's
    `ray_start_regular` fixture (`python/ray/tests/conftest.py`)."""
    import ray_tpu
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_local():
    import ray_tpu
    ray_tpu.init(local_mode=True)
    yield ray_tpu
    ray_tpu.shutdown()


# Positions in a block of the decode attention's kernel under `kernel_here`.
KERNEL_BLOCK = 8


@pytest.fixture
def kernel_here(monkeypatch):
    """A program lowered for this CPU takes the branch a TPU's would, the
    decode attention's kernels (`models/decode_attention.py`: a step's, in
    both of its grouped forms, and a block step's) run by the Pallas
    interpreter over blocks of `KERNEL_BLOCK` positions, two rows a grid
    step; the rules take a test's widths."""
    import functools

    from ray_tpu.models import decode_attention, transformer

    def whole_blocks(S, *widths):
        return S % KERNEL_BLOCK == 0 and S >= 2 * KERNEL_BLOCK
    monkeypatch.setattr(decode_attention, "BLOCK", KERNEL_BLOCK)
    monkeypatch.setattr(decode_attention, "ROWS", 2)
    monkeypatch.setattr(decode_attention, "prefix_kernel", functools.partial(
        decode_attention.prefix_kernel, interpret=True))
    monkeypatch.setattr(decode_attention, "block_kernel", functools.partial(
        decode_attention.block_kernel, interpret=True))
    monkeypatch.setattr(decode_attention, "lanes_kernel", functools.partial(
        decode_attention.lanes_kernel, interpret=True))
    monkeypatch.setattr(transformer, "decode_fused", whole_blocks)
    monkeypatch.setattr(transformer, "grouped_fused", whole_blocks)
    monkeypatch.setattr(transformer, "block_fused", whole_blocks)
    monkeypatch.setattr(
        jax.lax, "platform_dependent",
        lambda *args, tpu, default: tpu(*args))


@pytest.fixture
def grouped_pass_is_the_batched_pass(monkeypatch):
    """A check for a token model that holds a share of its experts
    (`model, variables, tokens`, float32): its causal pass made to take the
    grouped form at the tests' sizes, in tiles of 4 rows, gathers a short
    size's rows in some layer, counts them (`dispatch_rows_share`, between
    what landed and 1), and gives the logits, values and parameter
    gradients of the batched form, which gathers none."""
    import jax.numpy as jnp
    from ray_tpu.models import transformer

    def check(model, variables, tokens):
        def run():
            def loss(params):
                (logits, values, _), kept = model.apply(
                    dict(variables, params=params), tokens, None,
                    jnp.zeros(tokens.shape), mutable=["counters"])
                return jnp.sum(jnp.sin(logits)) + jnp.sum(values), (
                    logits, values, kept["counters"])
            # One program a form: the constants below are read when it
            # is traced, and `run` traces anew.
            (_, (logits, values, counted)), grads = jax.jit(
                jax.value_and_grad(loss, has_aux=True))(variables["params"])
            return (logits, values, grads), {
                k: float(v[-1]) for k, v in counted.items()}
        want, counted = run()
        assert counted["dispatch_rows_share"] == 1.0
        monkeypatch.setattr(transformer, "GROUP_COST_ROWS", 0)
        monkeypatch.setattr(transformer, "GROUPED_ROW_COST", 0.0)
        monkeypatch.setattr(transformer, "DISPATCH_TILE", 4)
        got, counted = run()
        assert counted["experts_held_row_share"] \
            <= counted["dispatch_rows_share"] < 1.0
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            scale = float(jnp.max(jnp.abs(w))) + 1e-8
            assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * scale
    return check
