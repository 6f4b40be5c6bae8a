"""Edge-case regression tests (parity: reference `test_advanced*.py`)."""

import time

import pytest


def test_borrowed_error_ref(ray_start):
    """A borrowed ref whose value is an error must become ready and raise on
    get (regression: owner error replies used to hang borrowers)."""
    ray = ray_start

    @ray.remote
    def boom():
        raise ValueError("original failure")

    @ray.remote
    def try_get(refs):
        # refs arrives as a list, so the inner ref is NOT auto-resolved
        # (reference semantics: only top-level args are resolved).
        import ray_tpu
        try:
            ray_tpu.get(refs[0], timeout=30)
            return "no error"
        except ray_tpu.TaskError as e:
            return f"saw: {e.cause}"

    ref = boom.remote()
    # Let the error land in the driver's store first.
    with pytest.raises(ray.TaskError):
        ray.get(ref)
    out = ray.get(try_get.remote([ref]), timeout=60)
    assert "original failure" in out


def test_errored_dependency_fails_dependent(ray_start):
    """A task whose direct ObjectRef arg errored fails with that error."""
    ray = ray_start

    @ray.remote
    def boom():
        raise ValueError("dep failed")

    @ray.remote
    def use(x):
        return x

    with pytest.raises(ray.TaskError, match="dep failed"):
        ray.get(use.remote(boom.remote()), timeout=60)


def test_wait_counts_errors_as_ready(ray_start):
    ray = ray_start

    @ray.remote
    def boom():
        raise ValueError("x")

    ref = boom.remote()
    ready, not_ready = ray.wait([ref], num_returns=1, timeout=30)
    assert ready == [ref]


def test_named_actor_name_reuse_after_death(ray_start):
    ray = ray_start

    @ray.remote
    class A:
        def ping(self):
            return "a"

    h = A.options(name="reusable").remote()
    assert ray.get(h.ping.remote()) == "a"
    ray.kill(h)
    deadline = time.time() + 30
    while True:
        try:
            h2 = A.options(name="reusable").remote()
            assert ray.get(h2.ping.remote(), timeout=30) == "a"
            break
        except Exception:
            if time.time() > deadline:
                raise
            time.sleep(0.3)


def test_sys_exit_in_task_is_task_error(ray_start):
    """sys.exit in a normal task reports an error without killing the pool
    worker or triggering retries."""
    ray = ray_start

    @ray.remote
    def quitter():
        import sys
        sys.exit(3)

    with pytest.raises(ray.TaskError, match="sys.exit"):
        ray.get(quitter.remote(), timeout=60)

    @ray.remote
    def after():
        return "alive"

    assert ray.get(after.remote(), timeout=60) == "alive"


def test_double_init_local_then_cluster(ray_local):
    ray = ray_local
    with pytest.raises(RuntimeError, match="twice"):
        ray.init(num_cpus=1)


def test_unknown_remote_option_rejected(ray_local):
    ray = ray_local
    with pytest.raises(TypeError, match="unknown"):
        @ray.remote(num_gpus=1)
        def f():
            return 1

    with pytest.raises(TypeError, match="unknown"):
        @ray.remote(max_retires=1)  # typo
        def g():
            return 1
