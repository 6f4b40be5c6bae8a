"""Active profiling plane: coordinated stack/XLA capture, HBM
telemetry, straggler-triggered flamegraphs.

Covers the on-demand capture tentpole end to end: the stdlib stack
sampler (folded stacks, drop accounting, stop/join lifecycle), the
head-coordinated multi-process capture window with Chrome-trace
alignment, HBM gauge degradation on CPU backends, the CLI drill over a
2-node cluster, and the RAY_TPU_STRAGGLER_PROFILE flag->flamegraph
path under seeded chaos.
"""

import glob
import io
import json
import os
import threading
import time
from contextlib import redirect_stdout

import pytest
from conftest import wait_until

import ray_tpu
from ray_tpu._private import config as config_mod
from ray_tpu._private import metrics
from ray_tpu._private import profiling
from ray_tpu.scripts.scripts import main as cli_main


def _spin_hot(stop_event):
    """A recognizably-named hot function for the sampler to catch."""
    while not stop_event.is_set():
        sum(i * i for i in range(200))


class TestStackSampler:
    def test_sampler_captures_known_hot_function(self):
        stop = threading.Event()
        t = threading.Thread(target=_spin_hot, args=(stop,),
                             name="hotspot-thread", daemon=True)
        t.start()
        try:
            sampler = profiling.StackSampler(hz=200).start()
            time.sleep(0.4)
            sampler.stop()
        finally:
            stop.set()
            t.join(timeout=5)
        res = sampler.result()
        assert res["ticks"] > 10
        hot = [s for s in res["folded"]
               if s.startswith("hotspot-thread;") and "_spin_hot" in s]
        assert hot, sorted(res["folded"])
        # Folded stacks are root-first: the thread name leads and the
        # leaf frame sits at the end (flamegraph.pl orientation).
        assert "hotspot-thread" in res["threads"]
        assert sum(res["folded"][s] for s in hot) > 5

    def test_stop_join_leaks_zero_threads(self):
        before = set(threading.enumerate())
        sampler = profiling.StackSampler(hz=200).start()
        time.sleep(0.1)
        sampler.stop()
        leaked = [t for t in threading.enumerate()
                  if t not in before and t.is_alive()]
        assert not leaked, leaked
        assert not sampler._thread.is_alive()
        # stop() is idempotent.
        sampler.stop()

    def test_thread_filter_restricts_to_target(self):
        stop = threading.Event()
        t = threading.Thread(target=_spin_hot, args=(stop,),
                             name="only-me", daemon=True)
        t.start()
        try:
            sampler = profiling.StackSampler(
                hz=200, thread_names={"only-me"}).start()
            time.sleep(0.3)
            sampler.stop()
        finally:
            stop.set()
            t.join(timeout=5)
        res = sampler.result()
        assert res["folded"], "filtered sampler saw nothing"
        assert all(s.startswith("only-me;") for s in res["folded"])
        assert res["threads"] == ["only-me"]

    def test_raw_sample_cap_counts_drops(self):
        stop = threading.Event()
        t = threading.Thread(target=_spin_hot, args=(stop,),
                             name="droppy", daemon=True)
        t.start()
        try:
            sampler = profiling.StackSampler(hz=500, max_samples=3)
            sampler.start()
            time.sleep(0.3)
            sampler.stop()
        finally:
            stop.set()
            t.join(timeout=5)
        res = sampler.result()
        assert len(res["samples"]) <= 3
        assert res["dropped"] > 0
        # Folded accumulation is NOT capped — only raw samples are.
        assert sum(res["folded"].values()) > 3

    def test_sample_once_sees_named_threads(self):
        stop = threading.Event()
        t = threading.Thread(target=_spin_hot, args=(stop,),
                             name="snapshot-me", daemon=True)
        t.start()
        try:
            wait_until(lambda: "snapshot-me" in profiling.sample_once(),
                       timeout=30)
            stacks = profiling.sample_once()
        finally:
            stop.set()
            t.join(timeout=5)
        assert "snapshot-me" in stacks
        assert stacks["snapshot-me"].startswith("snapshot-me;")

    def test_top_frames_ranks_leaves(self):
        folded = {"t;a.py:f;b.py:g": 3, "t;a.py:f;c.py:h": 1}
        top = profiling.top_frames(folded, n=1)
        assert top == [("b.py:g", 3, 0.75)]

    def test_samples_to_chrome_matches_span_clock(self):
        """Sampled stacks re-emit on the same conventions as span
        events: wall-clock microsecond ts and 'role:pid' lane ids —
        the invariant that makes one merged timeline possible."""
        now = time.time()
        proc = {"role": "worker", "pid": 123, "hz": 100.0,
                "samples": [(now, 7, "main", "main;a.py:f;b.py:g")]}
        (ev,) = profiling.samples_to_chrome(proc)
        assert ev["ph"] == "X" and ev["cat"] == "stack_sample"
        assert ev["pid"] == "worker:123"
        assert abs(ev["ts"] - now * 1e6) < 1.0
        assert ev["dur"] == pytest.approx(1e4)  # one period at 100 Hz
        assert ev["name"] == "b.py:g"
        assert ev["args"]["stack"] == "main;a.py:f;b.py:g"


class _FakeDevice:
    def __init__(self, id, stats):
        self.id = id
        self.platform = "tpu"
        self.device_kind = "fake-tpu"
        self._stats = stats

    def memory_stats(self):
        return self._stats


class TestDeviceTelemetry:
    @pytest.fixture(autouse=True)
    def _backend_up(self):
        # Telemetry reads devices only once this process's own code has
        # brought the backend up (profiling._live_devices).
        import jax
        jax.devices()

    def test_graceful_when_memory_stats_returns_none(self, monkeypatch):
        import jax
        monkeypatch.setattr(
            jax, "local_devices",
            lambda: [_FakeDevice(0, None), _FakeDevice(1, {})])
        assert profiling.device_memory_stats() == []
        assert profiling.publish_device_gauges() == 0

    def test_cpu_backend_degrades_without_error(self):
        # Whatever the CPU backend reports (None on most versions),
        # the telemetry path must not raise and must return a list.
        stats = profiling.device_memory_stats()
        assert isinstance(stats, list)
        profiling.publish_device_gauges()

    def test_gauges_published_with_max_rollup(self, monkeypatch):
        import jax
        monkeypatch.setattr(jax, "local_devices", lambda: [
            _FakeDevice(0, {"bytes_in_use": 100, "peak_bytes_in_use": 200,
                            "bytes_limit": 1000})])
        metrics.reset()
        try:
            assert profiling.publish_device_gauges() == 3
            snap = metrics.snapshot()
            assert snap["gauges"]["hbm_used_bytes.d0"] == 100.0
            assert snap["gauges"]["hbm_peak_bytes.d0"] == 200.0
            assert snap["gauges"]["hbm_limit_bytes.d0"] == 1000.0
            assert snap["rollups"]["hbm_peak_bytes.d0"] == "max"
        finally:
            metrics.reset()

    def test_owns_device_false_on_cpu_backend(self):
        assert profiling.owns_device() is False


class TestXlaProfileGating:
    def test_clear_error_without_any_device(self, monkeypatch):
        import jax
        monkeypatch.setattr(jax, "local_devices", lambda: [])
        with pytest.raises(RuntimeError, match="learner"):
            ray_tpu.xla_profile("/tmp/nope")

    def test_still_works_with_cpu_devices(self, tmp_path):
        # The CPU backend owns devices, so the satellite's gate must
        # not break the existing driver-side trace path
        # (test_observability.py::test_xla_profile_captures_device_trace).
        import jax
        assert jax.local_devices()
        with ray_tpu.xla_profile(str(tmp_path / "prof")):
            pass


class TestCoordinatedCapture:
    def test_two_process_capture_merges_with_aligned_clocks(self,
                                                            tmp_path):
        ray_tpu.init(num_cpus=2)
        try:
            @ray_tpu.remote
            def busy(stop_file):
                end = time.time() + 60
                x = 0
                while time.time() < end and not os.path.exists(stop_file):
                    x += 1
                return x

            stop_file = str(tmp_path / "stop")
            ref = busy.remote(stop_file)
            # The worker has booted and is in the loop, however long
            # that takes on a loaded box.
            wait_until(lambda: ray_tpu.tasks(state="RUNNING"),
                       timeout=60)
            bundle = ray_tpu.profile(0.8, hz=200)
            open(stop_file, "w").close()
            ray_tpu.get(ref, timeout=60)

            procs = bundle["processes"]
            by_role = {p["role"]: p for p in procs}
            assert "head" in by_role and "worker" in by_role, procs
            assert len({(p["role"], p["pid"]) for p in procs}) >= 2
            assert not bundle["missing"]
            for p in (by_role["head"], by_role["worker"]):
                assert p["folded"], p["role"]
                assert p["ticks"] > 10
            # The busy worker's hot loop is in its folded stacks.
            assert any("busy" in s
                       for s in by_role["worker"]["folded"]), \
                sorted(by_role["worker"]["folded"])[:5]

            # Chrome events: every sampled stack lands inside the
            # capture window on the span timeline's own clock.
            stacks = [e for e in bundle["trace_events"]
                      if e.get("cat") == "stack_sample"]
            assert stacks
            lanes = {e["pid"] for e in stacks}
            assert lanes == {"%s:%s" % (p["role"], p["pid"])
                             for p in procs}
            t0_us, t1_us = bundle["t0"] * 1e6, bundle["t1"] * 1e6
            assert all(t0_us - 1e5 <= e["ts"] <= t1_us + 1e5
                       for e in stacks)
        finally:
            ray_tpu.shutdown()

    def test_profile_dispatch_and_validation(self):
        ray_tpu.init(num_cpus=1)
        try:
            span = ray_tpu.profile("a-span")
            with span:
                pass
            with pytest.raises(TypeError):
                ray_tpu.profile("a-span", duration_s=0.1)
            # Numeric positional arg == duration_s keyword.
            b1 = ray_tpu.profile(0.2, target="head")
            b2 = ray_tpu.profile(duration_s=0.2, target="head")
            for b in (b1, b2):
                assert b["processes"][0]["role"] == "head"
        finally:
            ray_tpu.shutdown()

    def test_duration_clamped_to_max(self):
        config_mod.set_override("RAY_TPU_PROFILE_MAX_S", "0.3")
        ray_tpu.init(num_cpus=1)
        try:
            t0 = time.monotonic()
            bundle = ray_tpu.profile(30.0, target="head")
            assert time.monotonic() - t0 < 15.0
            assert bundle["duration_s"] == pytest.approx(0.3)
        finally:
            ray_tpu.shutdown()
            config_mod.clear_override("RAY_TPU_PROFILE_MAX_S")

    def test_debug_dump_gains_profiling_section(self, tmp_path):
        ray_tpu.init(num_cpus=1)
        try:
            path = ray_tpu.debug_dump(str(tmp_path / "fr.json"))
            with open(path) as f:
                dump = json.load(f)
            prof = dump["profiling"]
            # One-shot stacks of both the head's and the dumping
            # process's threads (same process here, distinct keys).
            assert prof["head_stacks"]
            assert prof["driver_stacks"]
            assert any("head-monitor" in k for k in prof["head_stacks"])
            assert "host_mem_frac" in prof
            # Pretty-printer renders the new section.
            buf = io.StringIO()
            with redirect_stdout(buf):
                cli_main(["dump", path])
            assert "profiling:" in buf.getvalue()
        finally:
            ray_tpu.shutdown()


class TestClusterProfileDrill:
    def test_cli_profile_over_two_node_cluster(self, tmp_path):
        """Acceptance drill: `scripts profile --duration` against a
        2-node session produces ONE merged bundle with folded stacks
        from >= 3 distinct processes (head, node agent, worker) plus
        Chrome-trace events, and a flamegraph-ready .folded sidecar."""
        from ray_tpu.cluster_utils import Cluster
        cluster = Cluster(head_resources={"CPU": 1})
        try:
            cluster.add_node(resources={"CPU": 2})

            @ray_tpu.remote(num_cpus=1)
            def busy(t):
                end = time.time() + t
                x = 0
                while time.time() < end:
                    x += 1
                return x

            refs = [busy.remote(4.0) for _ in range(2)]
            # A worker has booted and is in the loop (a lease may run
            # the two one behind the other).
            wait_until(lambda: ray_tpu.tasks(state="RUNNING"), timeout=60)
            out = str(tmp_path / "bundle.json")
            buf = io.StringIO()
            with redirect_stdout(buf):
                cli_main(["profile", "--address", cluster.head_addr,
                          "--duration", "1", "--out", out])
            ray_tpu.get(refs)
            text = buf.getvalue()
            assert "wrote" in text and "flamegraph" in text

            with open(out) as f:
                bundle = json.load(f)
            procs = bundle["processes"]
            roles = {p["role"] for p in procs}
            assert {"head", "node_agent", "worker"} <= roles, procs
            assert len({(p["role"], p["pid"]) for p in procs}) >= 3
            sampled = [p for p in procs if p.get("folded")]
            assert len(sampled) >= 3
            stacks = [e for e in bundle["trace_events"]
                      if e.get("cat") == "stack_sample"]
            assert len({e["pid"] for e in stacks}) >= 3

            # Flamegraph sidecar: role:pid-prefixed folded lines with
            # trailing counts.
            folded_path = str(tmp_path / "bundle.folded")
            with open(folded_path) as f:
                lines = f.read().splitlines()
            assert lines
            assert all(line.rsplit(" ", 1)[1].isdigit()
                       for line in lines)

            # --summarize renders the bundle offline.
            buf = io.StringIO()
            with redirect_stdout(buf):
                cli_main(["profile", "--summarize", out])
            assert "process(es)" in buf.getvalue()

            # Satellite: node_mem_frac published as a max-rollup gauge
            # with per-node series (agent + driver pushes).
            deadline = time.monotonic() + 15
            agg = {}
            while time.monotonic() < deadline:
                agg = ray_tpu.cluster_metrics()
                if "node_mem_frac" in agg.get("gauges", {}) \
                        and "node1" in agg.get("per_node", {}):
                    break
                time.sleep(0.5)
            assert "node_mem_frac" in agg["gauges"], agg["gauges"]
            assert "node_mem_frac" in \
                agg["per_node"]["node1"]["gauges"], agg["per_node"]
        finally:
            cluster.shutdown()


class TestStragglerTriggeredCapture:
    def test_chaos_delayed_actor_is_profiled_exactly(self):
        """RAY_TPU_STRAGGLER_PROFILE=1 turns the a1 straggler flag
        (seeded chaos delay on 1 of 4 inline actors) into a targeted
        capture of exactly inline-actor-1's thread."""
        from ray_tpu.rllib.agents.registry import get_trainer_class
        spec = "seed=7;actor.sample:delay:every1:a1@0.3"
        config_mod.set_override("RAY_TPU_STRAGGLER_PROFILE", "1")
        ray_tpu.init(num_cpus=2, chaos=spec)
        t = None
        try:
            t = get_trainer_class("IMPALA")(config={
                "env": "CartPole-v0",
                "num_workers": 0,
                "num_inline_actors": 4,
                "num_envs_per_worker": 4,
                "rollout_fragment_length": 10,
                "train_batch_size": 40,
                "min_iter_time_s": 0,
                "seed": 0,
            })
            deadline = time.monotonic() + 120
            report = {}
            while time.monotonic() < deadline:
                result = t.train()
                report = result.get("stragglers") or {}
                if report.get("profiles", {}).get("a1"):
                    break
            # The cumulative fields: `flagged` is rebuilt at every
            # evaluation, and the capture lands an evaluation or more
            # after the one that flagged a1.
            assert set(report.get("flag_counts") or {}) == {"a1"}, report
            profiles = report.get("profiles") or {}
            # Exactly the chaos-delayed actor was captured.
            assert set(profiles) == {"a1"}, profiles
            path = profiles["a1"]
            assert os.path.exists(path)
            with open(path) as f:
                lines = f.read().splitlines()
            assert lines, path
            # Every folded stack belongs to a1's thread, and the chaos
            # delay (time.sleep in the actor loop) dominates it.
            assert all(line.startswith("inline-actor-1;")
                       for line in lines), lines[:3]
            snap = metrics.snapshot()
            assert snap["counters"].get(
                "straggler_profiles_total", 0) >= 1
        finally:
            if t is not None:
                t.stop()
            ray_tpu.shutdown()
            config_mod.clear_override("RAY_TPU_STRAGGLER_PROFILE")
