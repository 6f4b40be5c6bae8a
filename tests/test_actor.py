"""Actor API tests (parity: reference `python/ray/tests/test_actor.py`)."""

import time

import numpy as np
import pytest
from conftest import wait_until


def test_counter(ray_start):
    ray = ray_start

    @ray.remote
    class Counter:
        def __init__(self, start=0):
            self.n = start

        def inc(self, by=1):
            self.n += by
            return self.n

        def value(self):
            return self.n

    c = Counter.remote(10)
    assert ray.get(c.inc.remote()) == 11
    assert ray.get(c.inc.remote(5)) == 16
    assert ray.get(c.value.remote()) == 16


def test_actor_ordering(ray_start):
    ray = ray_start

    @ray.remote
    class Appender:
        def __init__(self):
            self.items = []

        def add(self, x):
            self.items.append(x)
            return len(self.items)

        def get_items(self):
            return self.items

    a = Appender.remote()
    for i in range(50):
        a.add.remote(i)
    assert ray.get(a.get_items.remote()) == list(range(50))


def test_actor_method_error(ray_start):
    ray = ray_start

    @ray.remote
    class Bad:
        def boom(self):
            raise RuntimeError("actor kaboom")

        def fine(self):
            return "ok"

    b = Bad.remote()
    with pytest.raises(ray.TaskError, match="actor kaboom"):
        ray.get(b.boom.remote())
    # Actor survives method errors.
    assert ray.get(b.fine.remote()) == "ok"


def test_actor_creation_error(ray_start):
    ray = ray_start

    @ray.remote
    class Broken:
        def __init__(self):
            raise ValueError("cannot construct")

        def m(self):
            return 1

    b = Broken.remote()
    with pytest.raises(ray.ActorDiedError):
        ray.get(b.m.remote())


def test_two_actors_parallel(ray_start):
    ray = ray_start

    @ray.remote
    class Sleeper:
        def nap(self, t):
            time.sleep(t)
            return t

    a, b = Sleeper.remote(), Sleeper.remote()
    t0 = time.time()
    refs = [a.nap.remote(1.0), b.nap.remote(1.0)]
    assert ray.get(refs) == [1.0, 1.0]
    assert time.time() - t0 < 1.9  # ran concurrently


def test_pass_handle_to_task(ray_start):
    ray = ray_start

    @ray.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    @ray.remote
    def bump(counter):
        import ray_tpu
        return ray_tpu.get(counter.inc.remote())

    c = Counter.remote()
    assert sorted(ray.get([bump.remote(c) for _ in range(3)])) == [1, 2, 3]


def test_named_actor(ray_start):
    ray = ray_start

    @ray.remote
    class Store:
        def __init__(self):
            self.v = None

        def set(self, v):
            self.v = v

        def get_value(self):
            return self.v

    Store.options(name="kv_store").remote()
    h = ray.get_actor("kv_store")
    ray.get(h.set.remote(123))
    assert ray.get(h.get_value.remote()) == 123


def test_max_concurrency(ray_start):
    ray = ray_start

    @ray.remote(max_concurrency=4)
    class Parallel:
        def nap(self):
            time.sleep(0.8)
            return 1

    p = Parallel.remote()
    t0 = time.time()
    assert sum(ray.get([p.nap.remote() for _ in range(4)])) == 4
    assert time.time() - t0 < 2.5


def test_asyncio_actor(ray_start):
    ray = ray_start

    @ray.remote(max_concurrency=8)
    class AsyncWorker:
        async def work(self, t):
            import asyncio
            await asyncio.sleep(t)
            return t

    w = AsyncWorker.remote()
    t0 = time.time()
    out = ray.get([w.work.remote(0.8) for _ in range(8)])
    assert out == [0.8] * 8
    assert time.time() - t0 < 3.0


def test_kill_actor(ray_start):
    ray = ray_start

    @ray.remote
    class Victim:
        def ping(self):
            return "pong"

    v = Victim.remote()
    assert ray.get(v.ping.remote()) == "pong"
    ray.kill(v)

    def calls_fail():
        try:
            ray.get(v.ping.remote(), timeout=10)
        except (ray.ActorDiedError, ray.GetTimeoutError):
            return True
        return False

    wait_until(calls_fail, timeout=30)


def test_actor_restart(ray_start):
    ray = ray_start

    @ray.remote(max_restarts=1)
    class Phoenix:
        def __init__(self):
            self.state = 0

        def set_state(self, v):
            self.state = v

        def get_state(self):
            return self.state

        def die(self):
            import os
            os._exit(1)

    p = Phoenix.remote()
    ray.get(p.set_state.remote(42))
    p.die.remote()
    # After restart, state is fresh (creation task replayed).
    deadline = time.time() + 30
    while True:
        try:
            assert ray.get(p.get_state.remote(), timeout=30) == 0
            break
        except ray.ActorDiedError:
            if time.time() > deadline:
                raise
            time.sleep(0.2)


def test_checkpointable_actor_restores_state(ray_start, tmp_path):
    """Parity: `python/ray/actor.py:866` Checkpointable — a killed actor
    resumes from its latest checkpoint instead of a bare creation
    replay; expired checkpoints are reported for deletion."""
    ray = ray_start
    import json
    import os as _os
    ckpt_dir = str(tmp_path)

    from ray_tpu.actor import Checkpointable

    @ray.remote(max_restarts=1)
    class Counter(Checkpointable):
        def __init__(self, ckpt_dir):
            self.ckpt_dir = ckpt_dir
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

        def get(self):
            return self.n

        def die(self):
            import os
            os._exit(1)

        # -- Checkpointable ----------------------------------------
        def should_checkpoint(self, ctx):
            return True  # checkpoint after every task

        def save_checkpoint(self, actor_id, checkpoint_id):
            path = _os.path.join(self.ckpt_dir, checkpoint_id)
            with open(path, "w") as f:
                json.dump({"n": self.n}, f)

        def load_checkpoint(self, actor_id, available_checkpoints):
            for cp in available_checkpoints:  # newest first
                path = _os.path.join(self.ckpt_dir, cp.checkpoint_id)
                if _os.path.exists(path):
                    with open(path) as f:
                        self.n = json.load(f)["n"]
                    return cp.checkpoint_id
            return None

        def checkpoint_expired(self, actor_id, checkpoint_id):
            try:
                _os.unlink(_os.path.join(self.ckpt_dir, checkpoint_id))
            except FileNotFoundError:
                pass

    c = Counter.remote(ckpt_dir)
    for _ in range(3):
        ray.get(c.inc.remote())
    assert ray.get(c.get.remote()) == 3
    c.die.remote()
    deadline = time.time() + 30
    while True:
        try:
            got = ray.get(c.get.remote(), timeout=30)
            break
        except ray.ActorDiedError:
            if time.time() > deadline:
                raise
            time.sleep(0.2)
    # Restored from checkpoint, not replayed from scratch.
    assert got == 3, f"restarted actor lost its state: n={got}"
    # Continues from the restored state.
    assert ray.get(c.inc.remote()) == 4


def test_checkpoint_keep_window_expires(ray_start, tmp_path,
                                        monkeypatch):
    """Only the newest K checkpoint ids are retained; older payloads
    get checkpoint_expired callbacks (num_actor_checkpoints_to_keep)."""
    ray = ray_start
    # Shrink the keep-window on the in-process head.
    from ray_tpu._private import node as node_mod
    hs = node_mod._node.head if node_mod._node is not None else None
    if hs is not None:
        hs._num_actor_checkpoints_to_keep = 2

    import json
    import os as _os
    ckpt_dir = str(tmp_path)

    from ray_tpu.actor import Checkpointable

    @ray.remote
    class C(Checkpointable):
        def __init__(self, d):
            self.d = d
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

        def files(self):
            return sorted(_os.listdir(self.d))

        def should_checkpoint(self, ctx):
            return True

        def save_checkpoint(self, actor_id, checkpoint_id):
            with open(_os.path.join(self.d, checkpoint_id), "w") as f:
                json.dump({"n": self.n}, f)

        def load_checkpoint(self, actor_id, available):
            return None

        def checkpoint_expired(self, actor_id, checkpoint_id):
            try:
                _os.unlink(_os.path.join(self.d, checkpoint_id))
            except FileNotFoundError:
                pass

    keep = 2 if hs is not None else 20
    c = C.remote(ckpt_dir)
    for _ in range(6):
        ray.get(c.inc.remote())
    # The expiry callbacks follow the checkpoints that caused them.
    # files() itself triggers checkpoints too; just bound the window.
    wait_until(lambda: len(ray.get(c.files.remote())) <= keep + 2,
               timeout=30)


def test_actor_large_payload(ray_start):
    ray = ray_start

    @ray.remote
    class Echo:
        def echo(self, x):
            return x

    e = Echo.remote()
    arr = np.random.rand(1 << 17)
    np.testing.assert_array_equal(ray.get(e.echo.remote(arr)), arr)


def test_exit_actor(ray_start):
    ray = ray_start

    @ray.remote
    class Quitter:
        def quit(self):
            import ray_tpu
            ray_tpu.exit_actor()

        def ping(self):
            return "pong"

    q = Quitter.remote()
    assert ray.get(q.ping.remote()) == "pong"
    with pytest.raises(ray.ActorDiedError):
        ray.get(q.quit.remote())


def test_local_mode_actor(ray_local):
    ray = ray_local

    @ray.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    c = Counter.remote()
    assert ray.get(c.inc.remote()) == 1
    assert ray.get(c.inc.remote()) == 2
