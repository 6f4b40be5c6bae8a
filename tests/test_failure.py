"""Fault-tolerance tests (parity: reference `python/ray/tests/test_failure.py`,
`test_component_failures*.py`, `test_actor_failures.py`)."""

import os
import time

import pytest


def test_task_retry_on_worker_death(ray_start):
    """A task whose worker dies is retried on a fresh worker
    (reference: TaskManager retries, `src/ray/core_worker/task_manager.h:29`)."""
    ray = ray_start
    marker = f"/tmp/retry_marker_{os.getpid()}"
    if os.path.exists(marker):
        os.unlink(marker)

    @ray.remote(max_retries=2)
    def flaky(path):
        import os
        if not os.path.exists(path):
            open(path, "w").close()
            os._exit(1)  # die on first attempt
        return "survived"

    try:
        assert ray.get(flaky.remote(marker), timeout=60) == "survived"
    finally:
        if os.path.exists(marker):
            os.unlink(marker)


def test_task_no_retry_exhausted(ray_start):
    ray = ray_start

    @ray.remote(max_retries=0)
    def die():
        import os
        os._exit(1)

    with pytest.raises(ray.WorkerCrashedError):
        ray.get(die.remote(), timeout=60)


def test_actor_death_fails_inflight(ray_start):
    ray = ray_start

    @ray.remote
    class Doomed:
        def die_slowly(self):
            import os
            import time
            time.sleep(0.2)
            os._exit(1)

    d = Doomed.remote()
    with pytest.raises((ray.ActorDiedError, ray.TaskError)):
        ray.get(d.die_slowly.remote(), timeout=60)


def test_dead_actor_new_calls_fail(ray_start):
    ray = ray_start

    @ray.remote
    class Doomed:
        def ping(self):
            return "pong"

        def die(self):
            import os
            os._exit(1)

    d = Doomed.remote()
    assert ray.get(d.ping.remote()) == "pong"
    d.die.remote()
    with pytest.raises(ray.ActorDiedError):
        ray.get(d.ping.remote(), timeout=60)


def test_error_has_remote_traceback(ray_start):
    ray = ray_start

    @ray.remote
    def nested_error():
        def inner():
            raise KeyError("deep")
        inner()

    try:
        ray.get(nested_error.remote())
        raise AssertionError("should have raised")
    except ray.TaskError as e:
        assert "deep" in str(e)
        assert "inner" in str(e)  # remote traceback included


def test_unpicklable_error_still_reported(ray_start):
    ray = ray_start

    @ray.remote
    def weird_error():
        class Local(Exception):
            pass
        raise Local("custom")

    with pytest.raises(ray.TaskError):
        ray.get(weird_error.remote(), timeout=60)


def test_dial_of_a_dying_peer_is_a_closed_connection(tmp_path, monkeypatch):
    """A peer that dies between accepting the dial (its listen backlog
    does that) and reading the hello: `connect` raises ConnectionClosed,
    which every caller handles (a call to a dying actor then fails with
    ActorDiedError), not a raw BrokenPipeError (PR 30)."""
    import socket

    from ray_tpu._private import protocol
    path = str(tmp_path / "peer.sock")
    server = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    server.bind(path)
    server.listen(1)
    send_msg = protocol._send_msg

    def peer_dies_first(sock, payload):
        server.close()
        send_msg(sock, payload)

    monkeypatch.setattr(protocol, "_send_msg", peer_dies_first)
    with pytest.raises(protocol.ConnectionClosed):
        protocol.connect(path, "caller", lambda conn, msg: None)
