"""Chaos plane: deterministic fault injection + the recovery hardening
it gates (idempotent result pushes, duplicate-chunk tolerance, timeout
consistency, heartbeat-silence death, lost-update recovery).

Parity: the reference's chaos-testing suite (`ci/chaos_test/`,
`test_chaos.py`) — here seeded and replayable (`_private/chaos.py`).
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import chaos
from ray_tpu._private.backoff import Backoff

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPEC = "seed=7;wire.send:drop:n3;stripe.send:abort:p0.2"

# Thread-name prefixes owned by the runtime/head/agent service planes:
# after a full shutdown NONE may survive (the PR-3 zero-leak gate).
SERVICE_THREAD_PREFIXES = (
    "conn-recv-", "server-", "stripe-send", "send-batcher",
    "borrow-notify", "metrics-push", "lease-sweeper", "task-exec",
    "agent-monitor", "head-monitor", "task-events-flush", "obj-fetch",
    "object-stripe-send",
)


def _leaked_service_threads():
    return sorted(
        t.name for t in threading.enumerate()
        if t.name.startswith(SERVICE_THREAD_PREFIXES))


def _drive(ctl, rounds=50):
    for i in range(rounds):
        ctl.fire("wire.send", f"msg{i}")
        ctl.fire("stripe.send", f"chunk{i}")
    return ctl.trace


# ---------------------------------------------------------------------
# spec grammar + determinism (pure, no cluster)
# ---------------------------------------------------------------------
class TestSpec:
    def test_parse(self):
        seed, rules = chaos.parse_spec(
            "seed=42;wire.send:drop:n3;exec.before:kill:once2;"
            "wire.recv:delay:every4:0.01;stripe.send:abort:p0.5")
        assert seed == 42
        assert [(r.site, r.kind, r.trigger) for r in rules] == [
            ("wire.send", "drop", "n"), ("exec.before", "kill", "once"),
            ("wire.recv", "delay", "every"), ("stripe.send", "abort", "p")]
        assert rules[2].delay == 0.01

    @pytest.mark.parametrize("bad", [
        "wire.send:drop",            # missing trigger
        "nosite:drop:n1",            # unknown site
        "wire.send:zap:n1",          # unknown kind for site
        "wire.send:drop:x1",         # unknown trigger
        "wire.send:drop:p1.5",       # probability out of range
        "seed=x",                    # bad seed
    ])
    def test_bad_specs_raise(self, bad):
        with pytest.raises(chaos.ChaosSpecError):
            chaos.parse_spec(bad)

    def test_init_rejects_bad_spec_before_boot(self):
        with pytest.raises(chaos.ChaosSpecError):
            ray_tpu.init(chaos="wire.send:drop")
        assert not ray_tpu.is_initialized()

    def test_catalog_covers_every_layer(self):
        # wire / stripe / exec / heartbeat / store: the layer seams the
        # tentpole promises.
        assert {"wire.send", "wire.recv", "stripe.send", "exec.before",
                "exec.after", "agent.heartbeat", "head.heartbeat",
                "store.read"} <= set(chaos.SITES)

    def test_same_seed_identical_trace(self):
        a = _drive(chaos.ChaosController(SPEC))
        b = _drive(chaos.ChaosController(SPEC))
        assert len(a) > 2
        assert chaos.trace_bytes(a) == chaos.trace_bytes(b)

    def test_different_seed_diverges(self):
        a = _drive(chaos.ChaosController(SPEC))
        b = _drive(chaos.ChaosController(
            SPEC.replace("seed=7", "seed=8")))
        assert chaos.trace_bytes(a) != chaos.trace_bytes(b)

    def test_trace_replays_from_seed(self):
        trace = _drive(chaos.ChaosController(SPEC))
        replayed = chaos.replay(SPEC, trace)
        assert chaos.trace_bytes(replayed) == chaos.trace_bytes(trace)

    def test_rule_draws_independent_of_interleaving(self):
        # Rule rngs are seeded per (seed, site, kind): firing OTHER
        # sites in between must not perturb a site's own stream.
        a = chaos.ChaosController(SPEC)
        for i in range(50):
            a.fire("stripe.send", f"chunk{i}")
        b = chaos.ChaosController(SPEC)
        for i in range(50):
            b.fire("wire.recv", "noise")  # unarmed site: no rule reads
            b.fire("stripe.send", f"chunk{i}")
        pick = lambda t: [e for e in t if e["site"] == "stripe.send"]
        assert [e["occ"] for e in pick(a.trace)] \
            == [e["occ"] for e in pick(b.trace)]

    def test_disabled_by_default(self):
        assert chaos.controller is None

    def test_cli_catalog_and_trace(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, "-m", "ray_tpu.scripts", "chaos",
             "--catalog"], cwd=REPO, capture_output=True, text=True,
            timeout=60)
        assert proc.returncode == 0
        assert "stripe.send" in proc.stdout
        trace = tmp_path / "t.jsonl"
        entries = _drive(chaos.ChaosController(SPEC))
        trace.write_text("".join(
            json.dumps(e) + "\n" for e in entries))
        proc = subprocess.run(
            [sys.executable, "-m", "ray_tpu.scripts", "chaos",
             str(trace), "--replay", "--spec", SPEC],
            cwd=REPO, capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "byte-identical" in proc.stdout


# ---------------------------------------------------------------------
# backoff satellite
# ---------------------------------------------------------------------
class TestBackoff:
    def test_exponential_with_cap(self):
        import random
        b = Backoff(base=0.1, factor=2.0, cap=0.5, jitter=0.0,
                    max_attempts=10, rng=random.Random(0))
        delays = [b.next_delay() for _ in range(4)]
        assert delays == [0.1, 0.2, 0.4, 0.5]

    def test_max_attempts_exhausts(self):
        b = Backoff(base=0.0, cap=0.0, jitter=0.0, max_attempts=2)
        assert b.next_delay() is not None
        assert b.next_delay() is not None
        assert b.next_delay() is None
        assert not b.sleep()

    def test_deadline_exhausts(self):
        b = Backoff(base=0.01, deadline_s=0.0)
        assert b.next_delay() is None

    def test_jitter_is_bounded_and_seeded(self):
        import random
        b1 = Backoff(base=0.1, jitter=0.5, max_attempts=100,
                     rng=random.Random(3))
        b2 = Backoff(base=0.1, jitter=0.5, max_attempts=100,
                     rng=random.Random(3))
        d1 = b1.next_delay()
        assert 0.05 <= d1 <= 0.15
        assert d1 == b2.next_delay()  # deterministic under a seeded rng

    def test_reset(self):
        b = Backoff(base=0.1, jitter=0.0, max_attempts=1)
        assert b.next_delay() == 0.1
        assert b.next_delay() is None
        b.reset()
        assert b.next_delay() == 0.1


# ---------------------------------------------------------------------
# recovery hardening: idempotence + timeout consistency
# ---------------------------------------------------------------------
class TestIdempotence:
    def test_duplicate_result_push_ignored(self, ray_start):
        """A replayed push_result (duplicated frame / probe resubmit
        racing the original) must not double-complete the task or
        clobber the delivered value."""
        ray = ray_start

        @ray.remote
        def f(x):
            return x + 1

        from ray_tpu._private import metrics
        from ray_tpu._private import worker_state as ws
        rt = ws.get_runtime()
        r = f.remote(41)
        assert ray.get(r, timeout=60) == 42
        entry = rt.memory.get_if_exists(r.id)
        before = dict(rt._inflight_tasks)
        rt._on_push_result({"object_id": r.id, "data": b"garbage"})
        rt._on_push_result({"object_id": r.id,
                            "error": RuntimeError("late error")})
        assert rt.memory.get_if_exists(r.id) is entry  # untouched
        assert ray.get(r, timeout=60) == 42
        assert rt._inflight_tasks == before
        assert metrics.snapshot()["counters"].get(
            "push_result_duplicates", 0) >= 2

    def test_error_cell_upgraded_by_late_result(self, ray_start):
        """A task wrongly declared lost whose real result then lands:
        the value wins (cell-only; no second completion)."""
        ray = ray_start

        @ray.remote
        def f():
            return "real"

        from ray_tpu._private import worker_state as ws
        from ray_tpu._private.runtime import _Cell
        rt = ws.get_runtime()
        r = f.remote()
        assert ray.get(r, timeout=60) == "real"
        rt.memory.put(r.id, _Cell("error", RuntimeError("transient")))
        from ray_tpu._private import serialization
        rt._on_push_result({"object_id": r.id,
                            "data": serialization.dumps("real")})
        assert ray.get(r, timeout=60) == "real"

    def test_result_picked_up_before_its_push_still_completes(
            self, ray_start):
        """The bytes of a result in the shared store can reach a cell
        before the push_result that announces them is handled (a striped
        transfer sealing; get() finding the sealed entry). That push is
        the task's first delivery, not a replay: it must complete the
        task and free its lease slot (PR 30)."""
        from ray_tpu._private import worker_state as ws
        from ray_tpu._private.ids import TaskID
        from ray_tpu._private.runtime import _Cell
        rt = ws.get_runtime()
        tid = TaskID.generate()
        first, second = tid.object_id(0), tid.object_id(1)
        with rt._lineage_lock:
            rt._inflight_tasks[tid] = {first, second}
        with rt._lease_lock:
            rt._leased_tid_addr[tid] = ("tcp://nowhere", time.monotonic(), 1)
        picked_up = _Cell("value", "the result")
        rt.memory.put(first, picked_up)
        rt._on_push_result({"object_id": first, "in_shm": True})
        assert rt.memory.get_if_exists(first).value is picked_up
        assert rt._inflight_tasks[tid] == {second}
        # Its replay changes nothing ...
        rt._on_push_result({"object_id": first, "in_shm": True})
        assert rt._inflight_tasks[tid] == {second}
        assert tid in rt._leased_tid_addr
        # ... and the task's last result frees the slot.
        rt.memory.put(second, _Cell("value", "the other"))
        rt._on_push_result({"object_id": second, "in_shm": True})
        assert tid not in rt._inflight_tasks
        assert tid not in rt._leased_tid_addr

    def test_duplicate_stripe_chunk_after_seal_ignored(self, ray_start):
        """A replayed chunk for an already-sealed object (overlapping
        retry stream finishing late) must not re-open a receive buffer
        that can never fill."""
        from ray_tpu._private import metrics, serialization
        from ray_tpu._private import worker_state as ws
        from ray_tpu._private.ids import ObjectID
        rt = ws.get_runtime()
        blob = serialization.dumps(np.arange(1024))
        oid = ObjectID.generate()
        half = len(blob) // 2
        chunks = [
            {"kind": "object_chunk", "object_id": oid, "index": 0,
             "offset": 0, "num_chunks": 2, "total": len(blob),
             "codec": 0, "data": blob[:half]},
            {"kind": "object_chunk", "object_id": oid, "index": 1,
             "offset": half, "num_chunks": 2, "total": len(blob),
             "codec": 0, "data": blob[half:]},
        ]
        rt._on_transfer_begin({"object_id": oid, "total": len(blob),
                               "num_chunks": 2})
        for m in chunks:
            rt._on_object_chunk(dict(m))
        assert rt.shm.contains(oid)
        assert oid not in rt._chunk_buf
        before = metrics.snapshot()["counters"].get(
            "wire_chunk_duplicates", 0)
        rt._on_object_chunk(dict(chunks[0]))  # replay after seal
        rt._on_transfer_begin({"object_id": oid, "total": len(blob),
                               "num_chunks": 2})
        assert oid not in rt._chunk_buf  # no resurrected entry
        assert metrics.snapshot()["counters"].get(
            "wire_chunk_duplicates", 0) == before + 1
        np.testing.assert_array_equal(
            rt.shm.get(oid).value, np.arange(1024))

    def test_duplicate_chunk_within_stream_ignored(self, ray_start):
        """Same chunk index twice while the transfer is open (the
        pre-existing overlapping-retry shape) lands once."""
        from ray_tpu._private import serialization
        from ray_tpu._private import worker_state as ws
        from ray_tpu._private.ids import ObjectID
        rt = ws.get_runtime()
        blob = serialization.dumps(list(range(64)))
        oid = ObjectID.generate()
        half = len(blob) // 2
        m0 = {"object_id": oid, "index": 0, "offset": 0,
              "num_chunks": 2, "total": len(blob), "codec": 0,
              "data": blob[:half]}
        rt._on_object_chunk(dict(m0))
        rt._on_object_chunk(dict(m0))  # duplicate mid-stream
        assert not rt.shm.contains(oid)  # still waiting for chunk 1
        rt._on_object_chunk({"object_id": oid, "index": 1,
                             "offset": half, "num_chunks": 2,
                             "total": len(blob), "codec": 0,
                             "data": blob[half:]})
        assert rt.shm.contains(oid)
        assert rt.shm.get(oid).value == list(range(64))


class TestTimeouts:
    def test_get_timeout_on_slow_task(self, ray_start):
        ray = ray_start

        @ray.remote
        def slow():
            time.sleep(10)

        t0 = time.monotonic()
        with pytest.raises(ray.GetTimeoutError):
            ray.get(slow.remote(), timeout=0.5)
        assert time.monotonic() - t0 < 5.0

    def test_wait_returns_partial_at_deadline(self, ray_start):
        """wait(num_returns=k, timeout=t) must hand back what it has at
        the deadline instead of blocking for the stragglers."""
        ray = ray_start

        @ray.remote
        def slow():
            time.sleep(10)
            return 1

        refs = [slow.remote() for _ in range(3)]
        t0 = time.monotonic()
        ready, not_ready = ray.wait(refs, num_returns=3, timeout=0.8)
        assert time.monotonic() - t0 < 3.0
        assert len(ready) + len(not_ready) == 3
        assert not_ready  # the sleepers cannot all be ready

    def test_get_timeout_wins_over_wedged_owner_rpc(self, ray_start):
        """The owner RPC window is clamped to the caller's deadline: a
        get(timeout=1) of a foreign ref whose owner never answers
        raises GetTimeoutError in ~1s, not after the 60s rpc window."""
        ray = ray_start
        from ray_tpu._private import protocol
        from ray_tpu._private import worker_state as ws
        from ray_tpu._private.ids import ObjectID
        from ray_tpu._private.object_ref import ObjectRef
        rt = ws.get_runtime()

        # A peer that accepts the protocol handshake and then ignores
        # every request: reachable but wedged.
        wedged = protocol.Server(
            os.path.join(rt.session_dir, "wedged.sock"),
            handler=lambda conn, msg: None)
        try:
            ref = ObjectRef(ObjectID.generate(), wedged.path)
            t0 = time.monotonic()
            with pytest.raises(ray.GetTimeoutError):
                rt.get(ref, timeout=1.0)
            assert time.monotonic() - t0 < 10.0
        finally:
            # Drop the runtime's cached connection to the wedged peer
            # before closing its server, so no recv thread outlives
            # this test.
            stale = rt._conns.pop(wedged.path, None)
            if stale is not None:
                stale.close()
            wedged.close()

    def test_get_owner_dead_raises_lost_not_hang(self, ray_start):
        ray = ray_start
        from ray_tpu._private import worker_state as ws
        from ray_tpu._private.ids import ObjectID
        from ray_tpu._private.object_ref import ObjectRef
        rt = ws.get_runtime()
        ref = ObjectRef(ObjectID.generate(),
                        os.path.join(rt.session_dir, "no-such.sock"))
        with pytest.raises(ray.ObjectLostError):
            rt.get(ref, timeout=30)


class TestActorRestartRace:
    def test_inflight_call_resolves_never_hangs(self, ray_start):
        """An actor restarting with a call in flight resolves the call
        to a typed error (retryable) — never a silent hang."""
        ray = ray_start

        @ray.remote(max_restarts=1)
        class Phoenix:
            def echo(self, x):
                return x

            def die_slowly(self):
                time.sleep(0.3)
                os._exit(1)

        p = Phoenix.remote()
        assert ray.get(p.echo.remote(1), timeout=60) == 1
        p.die_slowly.remote()
        inflight = p.echo.remote(2)  # racing the death/restart
        with pytest.raises((ray.ActorDiedError,
                            ray.ActorUnavailableError, ray.TaskError)):
            ray.get(inflight, timeout=30)
        # The caller's retry lands on the restarted incarnation.
        deadline = time.time() + 30
        while True:
            try:
                assert ray.get(p.echo.remote(3), timeout=30) == 3
                break
            except (ray.ActorDiedError, ray.ActorUnavailableError):
                if time.time() > deadline:
                    raise
                time.sleep(0.2)


# ---------------------------------------------------------------------
# live injection: single-node recovery paths
# ---------------------------------------------------------------------
class TestLiveInjection:
    def test_worker_kill_before_exec_recovers(self):
        ray_tpu.init(num_cpus=4, chaos="seed=5;exec.before:kill:once1")
        try:
            @ray_tpu.remote
            def f(x):
                return x + 1

            out = ray_tpu.get([f.remote(i) for i in range(4)],
                              timeout=120)
            assert out == [1, 2, 3, 4]
            m = ray_tpu.cluster_metrics()["counters"]
            # The injection counter survives the killed worker (the
            # head folds dead processes' counters into its residue).
            assert m.get("chaos_injections_total", 0) >= 1
            assert m.get("chaos_injected.exec.before.kill", 0) >= 1
        finally:
            ray_tpu.shutdown()

    def test_dropped_result_push_recovers(self, monkeypatch):
        """The lost-update window: result computed, push dropped. The
        lease sweeper's worker probe detects 'done with no result' and
        resubmits instead of hanging the caller forever."""
        monkeypatch.setenv("RAY_TPU_LEASED_PROBE_S", "1.5")
        ray_tpu.init(num_cpus=4,
                     chaos="seed=3;exec.after:drop_result:once1")
        try:
            @ray_tpu.remote
            def f(x):
                return x + 1

            t0 = time.monotonic()
            out = ray_tpu.get([f.remote(i) for i in range(4)],
                              timeout=120)
            assert out == [1, 2, 3, 4]
            assert time.monotonic() - t0 < 60
        finally:
            ray_tpu.shutdown()

    def test_store_corruption_recovers_via_reconstruction(self):
        """store.read:corrupt flips a byte of the stored result; the
        decode failure is treated as a lost object and the owner
        re-executes the task."""
        ray_tpu.init(num_cpus=2,
                     chaos="seed=13;store.read:corrupt:n1")
        try:
            @ray_tpu.remote
            def produce():
                return {"payload": list(range(200))}

            r = produce.remote()
            assert ray_tpu.get(r, timeout=120) \
                == {"payload": list(range(200))}
        finally:
            ray_tpu.shutdown()


# ---------------------------------------------------------------------
# live injection: multi-node (the tier-1 deterministic schedule)
# ---------------------------------------------------------------------
class TestClusterChaos:
    def test_heartbeat_suppression_kills_node(self, monkeypatch):
        """agent.heartbeat:suppress makes a node go silent while its
        TCP connection stays open: the head's deadline liveness must
        declare it dead and the cluster must stay serviceable."""
        monkeypatch.setenv("RAY_TPU_HEARTBEAT_TIMEOUT_S", "2")
        monkeypatch.setenv("RAY_TPU_CHAOS",
                           "seed=2;agent.heartbeat:suppress:every1")
        from ray_tpu.cluster_utils import Cluster
        c = Cluster(head_resources={"CPU": 2})
        try:
            c.add_node(resources={"CPU": 2})
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                nodes = ray_tpu.cluster_info()["nodes"]
                if "node1" not in [nid for nid, n in nodes.items()
                                   if n["alive"]]:
                    break
                time.sleep(0.3)
            else:
                raise AssertionError(
                    "silent node was never declared dead")

            @ray_tpu.remote
            def f(x):
                return x * 3

            assert ray_tpu.get([f.remote(i) for i in range(4)],
                               timeout=60) == [0, 3, 6, 9]
        finally:
            c.shutdown()

    def test_training_loop_survives_fault_schedule(self, monkeypatch,
                                                   tmp_path):
        """Tier-1 acceptance: the fast deterministic schedule (worker
        kill + stripe abort + dropped result push) injected into a live
        2-node PPO training loop, which must complete with correct
        results; the injection trace must replay byte-identical from
        its seed; zero leaked service threads after shutdown."""
        spec = ("seed=9;exec.before:kill:once3;stripe.send:abort:n2;"
                "exec.after:drop_result:once1")
        trace_path = str(tmp_path / "chaos.jsonl")
        # Baseline BEFORE the session: the gate below asserts zero NEW
        # leaked threads (a prior test's connection winding down on its
        # own clock must not fail this one).
        base_threads = set(_leaked_service_threads())
        monkeypatch.setenv("RAY_TPU_CHAOS", spec)
        monkeypatch.setenv("RAY_TPU_CHAOS_TRACE", trace_path)
        monkeypatch.setenv("RAY_TPU_LEASED_PROBE_S", "1.5")
        from ray_tpu.cluster_utils import Cluster
        c = Cluster(head_resources={"CPU": 4})
        try:
            c.add_node(resources={"CPU": 2, "farnode": 1})

            # -- one PPO iteration with a remote rollout worker -------
            from ray_tpu.rllib.agents.ppo import PPOTrainer
            t = PPOTrainer(config={
                "env": "CartPole-v0",
                "num_workers": 1,
                "train_batch_size": 128,
                "sgd_minibatch_size": 64,
                "num_sgd_iter": 2,
                "rollout_fragment_length": 64,
                "num_envs_per_worker": 2,
                "model": {"fcnet_hiddens": [16, 16]},
                "ignore_worker_failures": True,
                "seed": 0,
            })
            r = t.train()
            assert r["timesteps_this_iter"] >= 128
            t.stop()

            # -- normal-task wave (exec kills / dropped pushes) -------
            @ray_tpu.remote
            def f(x):
                return x * x

            assert ray_tpu.get([f.remote(i) for i in range(8)],
                               timeout=120) == [i * i for i in range(8)]

            # -- cross-node striped transfer (stripe.send abort) ------
            @ray_tpu.remote(resources={"farnode": 1})
            def checksum(arr):
                return float(arr.sum())

            big = np.ones((3 << 20,), np.float32)  # ~12 MB: stripes
            assert ray_tpu.get(checksum.remote(ray_tpu.put(big)),
                               timeout=120) == float(big.sum())
        finally:
            c.shutdown()

        # ≥3 distinct fault kinds actually fired ...
        entries = chaos.load_trace(trace_path)
        kinds = {(e["site"], e["kind"]) for e in entries}
        assert len(kinds) >= 3, entries
        # ... and the trace replays byte-identical from its seed.
        replayed = chaos.replay(spec, entries)
        assert chaos.trace_bytes(replayed) == chaos.trace_bytes(entries)

        # Zero NEW leaked service threads (the PR-3 gate).
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            leaked = [t for t in _leaked_service_threads()
                      if t not in base_threads]
            if not leaked:
                break
            time.sleep(0.3)
        assert not leaked, leaked


# ---------------------------------------------------------------------
# long probabilistic soak (opt-in tier-2)
# ---------------------------------------------------------------------
@pytest.mark.slow
def test_chaos_soak_probabilistic(monkeypatch, tmp_path):
    """Wide probabilistic schedule over a sustained task/transfer mix;
    everything must still compute correctly (at-least-once + dedup)."""
    spec = ("seed=1234;wire.send:dup:p0.02;stripe.send:abort:p0.05;"
            "exec.before:kill:once4;exec.after:drop_result:once2;"
            "store.read:evict:p0.01")
    monkeypatch.setenv("RAY_TPU_LEASED_PROBE_S", "2")
    trace_path = str(tmp_path / "soak.jsonl")
    monkeypatch.setenv("RAY_TPU_CHAOS_TRACE", trace_path)
    ray_tpu.init(num_cpus=4, chaos=spec)
    try:
        @ray_tpu.remote
        def square(x):
            return x * x

        @ray_tpu.remote
        def reduce_sum(arr):
            return float(arr.sum())

        for round_i in range(6):
            refs = [square.remote(i) for i in range(16)]
            assert ray_tpu.get(refs, timeout=180) \
                == [i * i for i in range(16)]
            big = np.full((1 << 20,), float(round_i + 1), np.float32)
            assert ray_tpu.get(reduce_sum.remote(ray_tpu.put(big)),
                               timeout=180) == float(big.sum())
    finally:
        ray_tpu.shutdown()
    entries = chaos.load_trace(trace_path)
    replayed = chaos.replay(spec, entries)
    assert chaos.trace_bytes(replayed) == chaos.trace_bytes(entries)
