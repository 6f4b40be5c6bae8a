"""Tail-plane tests (ISSUE 9): mergeable histograms, rate rings,
straggler detection, and the crash flight recorder.

- Histogram primitive: log-bucketed observe/timer, EXACT cross-process
  bucket merge in aggregate() (cluster quantiles come from the merged
  distribution, not an average of per-process percentiles), quantile
  estimates within the bucket-width error bound, golden Prometheus
  histogram exposition + label escaping.
- Live cluster: driver + worker observations of the same histogram
  merge at the head; get/task-exec/weight-sync tails appear in
  `cluster_metrics()["quantiles"]`, `stat --metrics`, and `/metrics`.
- Rate ring: trailing-window counter derivatives via
  `ray_tpu.cluster_rates()` and `stat --rates`.
- Straggler detector: MAD-median verdicts (unit) and the end-to-end
  chaos drill — a seeded `actor.sample` delay on ONE of four inline
  actors flags exactly that actor in the trainer results, and the
  injection trace replays byte-identical.
- Flight recorder: `ray_tpu.debug_dump()` and the driver-fatal
  excepthook leave a readable postmortem; `scripts dump` renders it.
"""

import io
import json
import math
import random
import sys
import time
import urllib.request
from contextlib import redirect_stdout

import pytest

import ray_tpu
from ray_tpu._private import metrics
from ray_tpu._private.straggler import StragglerDetector, robust_sigma


def _synthetic_snap(node, counters=None, gauges=None, hist_values=(),
                    hist_name="h_s", rollups=None):
    """Build one process's snapshot the way runtime.metrics_push ships
    it (int bucket keys — the pickle wire preserves them)."""
    h = {"buckets": {}, "sum": 0.0, "count": 0.0, "min": None,
         "max": None}
    for v in hist_values:
        i = metrics.bucket_index(v)
        h["buckets"][i] = h["buckets"].get(i, 0.0) + 1.0
        h["sum"] += v
        h["count"] += 1.0
        h["min"] = v if h["min"] is None else min(h["min"], v)
        h["max"] = v if h["max"] is None else max(h["max"], v)
    return {"node": node, "counters": counters or {},
            "gauges": gauges or {}, "rollups": rollups or {},
            "hists": {hist_name: h} if hist_values else {}}


class TestHistogramPrimitive:
    def test_observe_and_timer(self):
        metrics.reset()
        try:
            metrics.observe("lat_s", 0.5)
            metrics.observe("lat_s", 2.0)
            with metrics.timer("lat_s"):
                time.sleep(0.01)
            snap = metrics.snapshot()
            h = snap["hists"]["lat_s"]
            assert h["count"] == 3
            assert h["min"] <= 0.02  # the timed sleep
            assert h["max"] == 2.0
            assert abs(h["sum"] - 2.5) < 0.1
        finally:
            metrics.reset()

    def test_cross_process_bucket_merge_is_exact(self):
        """Two processes with disjoint latency regimes: the merged p99
        must land in the slow process's tail. Averaging per-process
        p99s (the classic wrong merge) would report ~half the true
        tail; summed buckets report the real one."""
        fast = [0.001 * (1 + i % 7) for i in range(95)]
        slow = [1.0] * 5  # a second process's 1 s tail (5% of mass)
        agg = metrics.aggregate({
            "p1": _synthetic_snap("node0", hist_values=fast),
            "p2": _synthetic_snap("node1", hist_values=slow),
        })
        h = agg["hists"]["h_s"]
        assert h["count"] == 100
        assert abs(h["sum"] - (sum(fast) + 5.0)) < 1e-9
        # Exact merge: every bucket count is the sum of the inputs.
        b1 = _synthetic_snap("x", hist_values=fast)["hists"]["h_s"]
        b2 = _synthetic_snap("x", hist_values=slow)["hists"]["h_s"]
        for idx, c in h["buckets"].items():
            assert c == (b1["buckets"].get(idx, 0)
                         + b2["buckets"].get(idx, 0))
        q = agg["quantiles"]["h_s"]
        assert q["p99"] >= 0.5, "p99 must see the slow process's tail"
        assert q["p50"] <= 0.01
        # Per-node breakdown keeps each process's histogram separate.
        assert agg["per_node"]["node1"]["hists"]["h_s"]["count"] == 5

    def test_merge_hist_coerces_string_bucket_keys(self):
        # JSON round-trips stringify int keys; merge must still fold.
        dst = {}
        metrics.merge_hist(dst, {"buckets": {"3": 2.0}, "sum": 1.0,
                                 "count": 2.0, "min": 0.5, "max": 0.6})
        metrics.merge_hist(dst, {"buckets": {3: 1.0}, "sum": 0.5,
                                 "count": 1.0, "min": 0.4, "max": 0.6})
        assert dst["buckets"] == {3: 3.0}
        assert dst["count"] == 3.0 and dst["min"] == 0.4

    def test_quantile_error_bound(self):
        """Estimates are bucket upper bounds clamped to min/max: each
        quantile is within HIST_FACTOR-1 (~18.9%) of a true sample."""
        rng = random.Random(0)
        values = [math.exp(rng.gauss(-3.0, 1.5)) for _ in range(5000)]
        agg = metrics.aggregate(
            {"p": _synthetic_snap("n", hist_values=values)})
        s = sorted(values)
        tol = metrics.HIST_FACTOR - 1.0 + 1e-6
        for q in (0.50, 0.95, 0.99):
            true = s[min(len(s) - 1, int(q * len(s)))]
            est = metrics.hist_quantile(agg["hists"]["h_s"], q)
            assert abs(est - true) / true <= tol, (q, est, true)

    def test_gauge_rollups(self):
        snaps = {
            "p1": _synthetic_snap("n0", gauges={"pct": 90.0, "hw": 3.0,
                                                "tot": 5.0},
                                  rollups={"pct": "mean", "hw": "max"}),
            "p2": _synthetic_snap("n1", gauges={"pct": 110.0, "hw": 7.0,
                                                "tot": 2.0},
                                  rollups={"pct": "mean", "hw": "max"}),
        }
        agg = metrics.aggregate(snaps)
        assert agg["gauges"]["pct"] == 100.0  # mean, not 200
        assert agg["gauges"]["hw"] == 7.0     # max
        assert agg["gauges"]["tot"] == 7.0    # undeclared -> sum

    def test_golden_prometheus_exposition(self):
        agg = metrics.aggregate({
            "p1": _synthetic_snap('no"de\\1', counters={"reqs": 3.0},
                                  hist_values=[1.0, 1.0, 4.0]),
        })
        text = metrics.prometheus_text(agg)
        lines = text.splitlines()
        # Counter: TYPE line, total, per-node labeled series with the
        # quote and backslash in the node id escaped.
        assert "# TYPE ray_tpu_reqs counter" in lines
        assert "ray_tpu_reqs 3" in lines
        assert 'ray_tpu_reqs{node="no\\"de\\\\1"} 3' in lines
        # Histogram trio: cumulative buckets, +Inf == count, sum.
        i1 = metrics.bucket_index(1.0)
        i4 = metrics.bucket_index(4.0)
        le1 = f"{metrics.bucket_upper(i1):.6g}"
        le4 = f"{metrics.bucket_upper(i4):.6g}"
        assert "# TYPE ray_tpu_h_s histogram" in lines
        assert f'ray_tpu_h_s_bucket{{le="{le1}"}} 2' in lines
        assert f'ray_tpu_h_s_bucket{{le="{le4}"}} 3' in lines
        assert 'ray_tpu_h_s_bucket{le="+Inf"} 3' in lines
        assert "ray_tpu_h_s_sum 6" in lines
        assert "ray_tpu_h_s_count 3" in lines
        # Buckets are cumulative and non-decreasing.
        cum = [float(l.rsplit(" ", 1)[1]) for l in lines
               if l.startswith("ray_tpu_h_s_bucket{le=") and
               "+Inf" not in l]
        assert cum == sorted(cum)


class TestStragglerDetector:
    SLOW_A1 = {
        "a0": {"throughput": 100.0},
        "a1": {"throughput": 8.0},
        "a2": {"throughput": 98.0},
        "a3": {"throughput": 103.0},
    }

    def test_flags_slow_actor_only(self):
        det = StragglerDetector(k=3.0, min_peers=3)
        # One window's reading is not yet a flag; the next one that
        # repeats it is (straggler.CONFIRM_WINDOWS).
        v = det.update(self.SLOW_A1)
        assert v["a1"]["reasons"] == ["throughput"]
        assert v["a1"]["outlier_windows"] == 1 and not v["a1"]["flagged"]
        assert det.flag_counts == {}
        v = det.update(self.SLOW_A1)
        assert v["a1"]["flagged"] and v["a1"]["reasons"] == ["throughput"]
        assert not any(v[t]["flagged"] for t in ("a0", "a2", "a3"))
        assert det.flag_counts == {"a1": 1}

    @pytest.mark.parametrize("starved", [0.0, 40.0])
    def test_one_starved_window_is_not_a_flag(self, starved):
        # A healthy actor thread that a busy host did not schedule
        # reads one fragment, or none, below its peers in a short
        # window. It is not flagged, and it does not break a1's run.
        det = StragglerDetector(k=3.0, min_peers=3)
        fleet = {**self.SLOW_A1, "a4": {"throughput": 101.0}}
        windows = [fleet,
                   {**fleet, "a2": {"throughput": starved}},
                   fleet,
                   {**fleet, "a3": {"throughput": starved}},
                   fleet]
        ever, tripped = set(), set()
        for w in windows:
            v = det.update(w)
            ever |= {t for t, x in v.items() if x["flagged"]}
            tripped |= {t for t, x in v.items() if x["reasons"]}
        assert tripped == {"a1", "a2", "a3"}
        assert ever == {"a1"}
        assert det.flag_counts == {"a1": 4}
        # a1 recovers: its run of outlier windows starts again.
        v = det.update({**fleet, "a1": {"throughput": 99.0}})
        assert not v["a1"]["flagged"] and v["a1"]["outlier_windows"] == 0
        assert not det.update(fleet)["a1"]["flagged"]

    def test_identical_fleet_flags_divergent(self):
        # MAD = 0 -> the sigma floor (5% of median) still catches a
        # genuinely divergent actor instead of dividing by zero.
        det = StragglerDetector(k=3.0, min_peers=3)
        window = {t: {"throughput": 100.0} for t in ("a0", "a1", "a2")} \
            | {"a3": {"throughput": 50.0}}
        det.update(window)
        assert det.update(window)["a3"]["flagged"]

    def test_fetch_latency_flag(self):
        det = StragglerDetector(k=3.0, min_peers=3)
        window = {
            "a0": {"throughput": 100.0, "fetch_latency_s": 0.010},
            "a1": {"throughput": 100.0, "fetch_latency_s": 0.011},
            "a2": {"throughput": 100.0, "fetch_latency_s": 0.300},
            "a3": {"throughput": 100.0, "fetch_latency_s": 0.009},
        }
        det.update(window)
        v = det.update(window)
        assert v["a2"]["flagged"]
        assert "fetch_latency" in v["a2"]["reasons"]

    def test_min_peers_gate(self):
        det = StragglerDetector(k=3.0, min_peers=3)
        v = det.update({"a0": {"throughput": 100.0},
                        "a1": {"throughput": 1.0}})
        assert not any(x["flagged"] for x in v.values())

    def test_robust_sigma_resists_outlier(self):
        # One outlier of four inflates stddev ~8x; MAD barely moves.
        vals = [100.0, 101.0, 99.0, 10.0]
        assert robust_sigma(vals) < 5.0


class TestLiveTailPlane:
    def test_cross_process_histogram_merge_and_tails(self, monkeypatch):
        """2-process acceptance: the driver and a worker each observe
        the same histogram; the head's aggregate carries the merged
        distribution, plus get/task-exec/weight-sync tails, via the
        JSON API, `stat --metrics`, and the Prometheus endpoint."""
        import socket
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        monkeypatch.setenv("RAY_TPU_METRICS_INTERVAL_S", "0.3")
        monkeypatch.setenv("RAY_TPU_METRICS_PORT", str(port))
        ray_tpu.init(num_cpus=2)
        try:
            @ray_tpu.remote
            def observe_tail():
                import numpy as np
                from ray_tpu._private import metrics as m
                from ray_tpu._private.weight_sync import (
                    WeightSyncDecoder, WeightSyncEncoder)
                m.observe("merge_probe_s", 1.0)  # worker-side sample
                enc = WeightSyncEncoder(codec="full")
                dec = WeightSyncDecoder()
                for p in enc.encode({"w": np.zeros(64, np.float32)}):
                    dec.apply(p)
                return 1

            metrics.observe("merge_probe_s", 0.001)  # driver-side
            assert ray_tpu.get(observe_tail.remote(), timeout=30) == 1
            deadline = time.monotonic() + 30
            agg = {}
            while time.monotonic() < deadline:
                agg = ray_tpu.cluster_metrics()
                q = (agg.get("quantiles") or {}).get("merge_probe_s")
                if q and q["count"] >= 2 \
                        and "weight_sync_apply_s" in agg["quantiles"] \
                        and "task_exec_s" in agg["quantiles"]:
                    break
                time.sleep(0.2)
            q = agg["quantiles"]["merge_probe_s"]
            # Merged across processes: both samples, true min AND max.
            assert q["count"] == 2
            assert q["min"] == 0.001 and q["max"] == 1.0
            assert q["p99"] >= 0.5
            for name in ("get_wall_s", "task_exec_s",
                         "task_queue_wait_s", "weight_sync_encode_s",
                         "weight_sync_apply_s"):
                tail = agg["quantiles"].get(name)
                assert tail and tail["count"] >= 1, name
                assert tail["p50"] is not None and tail["p99"] is not None

            from ray_tpu._private import node as node_mod
            addr = node_mod._node.head.sock_path
            from ray_tpu.scripts.scripts import main as cli_main
            buf = io.StringIO()
            with redirect_stdout(buf):
                cli_main(["stat", "--metrics", "--address", addr])
            out = buf.getvalue()
            assert "histograms (seconds):" in out
            assert "merge_probe_s" in out
            assert "task_exec_s" in out

            text = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) \
                .read().decode()
            assert "# TYPE ray_tpu_merge_probe_s histogram" in text
            assert 'ray_tpu_merge_probe_s_bucket{le="+Inf"} 2' in text
            assert "ray_tpu_get_wall_s_count" in text
            # Counters now carry per-node labels too.
            assert 'ray_tpu_tasks_executed{node="node0"}' in text
        finally:
            ray_tpu.shutdown()

    def test_rate_ring_and_cli(self, monkeypatch):
        monkeypatch.setenv("RAY_TPU_METRICS_INTERVAL_S", "0.2")
        monkeypatch.setenv("RAY_TPU_RATE_RING_INTERVAL_S", "0.3")
        ray_tpu.init(num_cpus=2)
        try:
            @ray_tpu.remote
            def f(i):
                return i

            deadline = time.monotonic() + 45
            rates = {}
            while time.monotonic() < deadline:
                ray_tpu.get([f.remote(i) for i in range(4)], timeout=30)
                rates = ray_tpu.cluster_rates()
                if rates.get("tasks_submitted"):
                    break
                time.sleep(0.3)
            assert rates.get("tasks_submitted", 0) > 0
            assert all(v >= 0 for v in rates.values())

            from ray_tpu._private import node as node_mod
            addr = node_mod._node.head.sock_path
            from ray_tpu.scripts.scripts import main as cli_main
            buf = io.StringIO()
            with redirect_stdout(buf):
                cli_main(["stat", "--rates", "--address", addr])
            out = buf.getvalue()
            assert "rates" in out
            assert "tasks_submitted" in out
        finally:
            ray_tpu.shutdown()

    def test_flight_recorder_dump_and_cli(self, tmp_path):
        ray_tpu.init(num_cpus=2)
        try:
            @ray_tpu.remote
            def f():
                return 41

            assert ray_tpu.get(f.remote(), timeout=30) == 41
            # The worker's RUNNING/FINISHED events push on their own
            # cadence; wait for the terminal record before dumping.
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                if ray_tpu.tasks(state="FINISHED", limit=5):
                    break
                time.sleep(0.2)
            metrics.observe("dump_probe_s", 0.123)
            path = ray_tpu.debug_dump(str(tmp_path / "fr.json"))
            with open(path) as fh:
                dump = json.load(fh)
            # The bundle: task tail, merged metrics (incl. the
            # histogram observed moments before the dump — debug_dump
            # flushes, it does not wait out the push cadence), node
            # health, spans, errors.
            assert dump["session_dir"]
            assert dump["task_state_counts"].get("FINISHED", 0) >= 1
            assert any(t["name"] and "f" in t["name"]
                       for t in dump["tasks"])
            assert "dump_probe_s" in dump["metrics"]["quantiles"]
            assert isinstance(dump["nodes"], list) and dump["nodes"]
            assert "recent_errors" in dump and "spans" in dump

            from ray_tpu.scripts.scripts import main as cli_main
            buf = io.StringIO()
            with redirect_stdout(buf):
                cli_main(["dump", path])
            out = buf.getvalue()
            assert "flight recorder dump" in out
            assert "dump_probe_s" in out
            assert "FINISHED" in out
        finally:
            ray_tpu.shutdown()

    def test_excepthook_writes_dump_on_fatal(self, monkeypatch,
                                             tmp_path, capsys):
        """A driver-fatal exception leaves a readable postmortem: the
        chained excepthook dumps BEFORE the traceback prints."""
        target = tmp_path / "postmortem.json"
        monkeypatch.setenv("RAY_TPU_FLIGHT_RECORDER_PATH", str(target))
        ray_tpu.init(num_cpus=2)
        try:
            assert sys.excepthook is not sys.__excepthook__
            try:
                raise RuntimeError("driver-fatal drill")
            except RuntimeError:
                sys.excepthook(*sys.exc_info())
            assert target.exists()
            with open(target) as fh:
                dump = json.load(fh)
            assert dump["metrics"] is not None
            assert "task_state_counts" in dump
            err = capsys.readouterr().err
            assert "flight recorder" in err
            assert "driver-fatal drill" in err  # traceback still prints
        finally:
            ray_tpu.shutdown()
        # shutdown restores the prior hook chain's behavior for the
        # next test process state (hook stays but runtime is gone —
        # it must degrade to a no-op, not raise).
        try:
            raise RuntimeError("post-shutdown drill")
        except RuntimeError:
            sys.excepthook(*sys.exc_info())


class TestStragglerChaosDrill:
    def test_seeded_delay_flags_exactly_that_actor(self):
        """Satellite: a chaos delay rule targeting inline actor a1's
        sample loop (`actor.sample:delay:every1:a1@0.3`) must flag a1 —
        and ONLY a1 — in the trainer's iteration results, annotate the
        metrics plane, and leave a trace that replays byte-identical
        from the seed."""
        from ray_tpu._private import chaos
        from ray_tpu.rllib.agents.registry import get_trainer_class
        spec = "seed=7;actor.sample:delay:every1:a1@0.3"
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
                # The evaluation this drill is about: a1 alone, for its
                # throughput. On a loaded box a window's spread can be
                # wide enough to hide even a1's, or flag it for its
                # fetch latency alone; the next window decides again.
                if report.get("flagged") == ["a1"] and "throughput" in \
                        report["per_actor"]["a1"]["reasons"]:
                    break
            assert report.get("flagged") == ["a1"], report
            verdict = report["per_actor"]["a1"]
            assert "throughput" in verdict["reasons"], report
            assert verdict["throughput"] < verdict["throughput_median"]
            assert report["flag_counts"].get("a1", 0) >= 1
            snap = metrics.snapshot()
            assert snap["counters"].get("straggler_flags_total", 0) >= 1
            assert snap["counters"].get("straggler_flags.a1", 0) >= 1

            # Every injection hit a1's loop, and the trace replays
            # byte-for-byte from the seed (determinism gate).
            entries = list(chaos.controller.trace)
            assert entries and all(e["detail"] == "a1" for e in entries)
            replayed = chaos.replay(spec, entries)
            assert chaos.trace_bytes(replayed) == \
                chaos.trace_bytes(entries)
        finally:
            if t is not None:
                t.stop()
            ray_tpu.shutdown()
