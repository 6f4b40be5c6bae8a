"""Tune: variant generation, schedulers, trial runner end-to-end.

Parity model: `python/ray/tune/tests/` (trial_runner/scheduler tests).
"""

import json
import os
import struct
import time

import numpy as np
import pytest

from ray_tpu.tune import grid_search, sample_from, uniform
from ray_tpu.tune.suggest.variant_generator import generate_variants


class TestVariantGenerator:
    def test_grid(self):
        spec = {"a": grid_search([1, 2]), "b": grid_search(["x", "y"]),
                "c": 7}
        variants = list(generate_variants(spec))
        assert len(variants) == 4
        configs = [cfg for _, cfg in variants]
        assert {(c["a"], c["b"]) for c in configs} == {
            (1, "x"), (1, "y"), (2, "x"), (2, "y")}
        assert all(c["c"] == 7 for c in configs)

    def test_nested_grid_and_sample(self):
        spec = {"model": {"lr": grid_search([0.1, 0.2])},
                "seed": uniform(0, 1)}
        variants = list(generate_variants(spec))
        assert len(variants) == 2
        seeds = [cfg["seed"] for _, cfg in variants]
        assert all(0 <= s <= 1 for s in seeds)
        assert [cfg["model"]["lr"] for _, cfg in variants] == [0.1, 0.2]

    def test_resolved_vars_recorded(self):
        spec = {"lr": grid_search([0.1])}
        resolved, cfg = next(generate_variants(spec))
        assert resolved == {"lr": 0.1}


class TestSchedulers:
    def _mk_trial(self, tid):
        from ray_tpu.tune.trial import Trial
        t = Trial("PPO", trial_id=tid)
        return t

    def test_asha_stops_bottom(self):
        from ray_tpu.tune.schedulers import AsyncHyperBandScheduler
        from ray_tpu.tune.schedulers.trial_scheduler import TrialScheduler
        s = AsyncHyperBandScheduler(
            metric="score", mode="max", grace_period=1, max_t=100,
            reduction_factor=2)
        trials = [self._mk_trial(f"t{i}") for i in range(4)]
        for t in trials:
            s.on_trial_add(None, t)
        # All trials report at iteration 1; later (worse) ones stop.
        decisions = []
        for i, t in enumerate(trials):
            decisions.append(s.on_trial_result(
                None, t, {"training_iteration": 1, "score": float(i)}))
        # First trial cannot be judged (too few); at least one low scorer
        # after enough samples must STOP.
        assert TrialScheduler.STOP not in decisions[:1]
        # feed a clearly-bad trial after quorum:
        bad = self._mk_trial("bad")
        s.on_trial_add(None, bad)
        d = s.on_trial_result(
            None, bad, {"training_iteration": 1, "score": -100.0})
        assert d == TrialScheduler.STOP

    def test_median_stopping(self):
        from ray_tpu.tune.schedulers import MedianStoppingRule
        from ray_tpu.tune.schedulers.trial_scheduler import TrialScheduler
        s = MedianStoppingRule(metric="score", mode="max", grace_period=0,
                               min_samples_required=2)
        good = [self._mk_trial(f"g{i}") for i in range(3)]
        for i, t in enumerate(good):
            for it in range(3):
                assert s.on_trial_result(
                    None, t, {"training_iteration": it,
                              "score": 10.0 + i}) \
                    == TrialScheduler.CONTINUE
        bad = self._mk_trial("bad")
        d = s.on_trial_result(
            None, bad, {"training_iteration": 2, "score": 0.0})
        assert d == TrialScheduler.STOP

    def test_pbt_explore(self):
        from ray_tpu.tune.schedulers.pbt import explore
        cfg = {"lr": 0.1, "clip": 0.2}
        out = explore(cfg, {"lr": [0.01, 0.1, 1.0]}, 0.0, None)
        assert out["lr"] in (0.01, 1.0)   # neighbor step
        assert out["clip"] == 0.2
        out2 = explore(cfg, {"lr": lambda: 0.5}, 1.0, None)
        assert out2["lr"] == 0.5


def _quadratic(config, reporter):
    # Maximize -(x-3)^2: best at x=3.
    for i in range(5):
        reporter(score=-(config["x"] - 3.0) ** 2, training_iteration=i + 1)


def _crc32c(data: bytes) -> int:
    """CRC-32C bit by bit (reflected polynomial 0x82F63B78)."""
    crc = 0xFFFFFFFF
    for byte in data:
        crc ^= byte
        for _ in range(8):
            crc = (crc >> 1) ^ (0x82F63B78 & -(crc & 1))
    return crc ^ 0xFFFFFFFF


def _tfrecords(data: bytes) -> list:
    """A TFRecord file's payloads: u64 length, masked CRC of the length,
    payload, masked CRC of the payload."""
    def masked(chunk):
        crc = _crc32c(chunk)
        return ((crc >> 15 | crc << 17) + 0xA282EAD8) & 0xFFFFFFFF

    records = []
    while data:
        length, length_crc = struct.unpack("<QI", data[:12])
        assert length_crc == masked(data[:8])
        record = data[12:12 + length]
        assert len(record) == length
        crc, = struct.unpack("<I", data[12 + length:16 + length])
        assert crc == masked(record)
        records.append(record)
        data = data[16 + length:]
    return records


def _proto_fields(buf: bytes):
    """(field number, value) of one protobuf message: varints as ints,
    64-bit, length-delimited and 32-bit fields as their bytes."""
    def varint(i):
        n = shift = 0
        while True:
            n |= (buf[i] & 0x7F) << shift
            shift += 7
            i += 1
            if not buf[i - 1] & 0x80:
                return n, i

    i = 0
    while i < len(buf):
        key, i = varint(i)
        wire = key & 7
        if wire == 0:
            value, i = varint(i)
        else:
            if wire == 2:
                size, i = varint(i)
            else:
                size = {1: 8, 5: 4}[wire]
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _decode_event(record: bytes) -> dict:
    """event.proto: wall_time=1, step=2, file_version=3, summary=5;
    summary.proto: value=1 of {tag=1, simple_value=2}."""
    event = {"step": 0, "file_version": "", "scalars": {}}
    for number, value in _proto_fields(record):
        if number == 1:
            event["wall_time"], = struct.unpack("<d", value)
        elif number == 2:
            event["step"] = value
        elif number == 3:
            event["file_version"] = value.decode()
        elif number == 5:
            for _, summary_value in _proto_fields(value):
                fields = dict(_proto_fields(summary_value))
                event["scalars"][fields[1].decode()], = \
                    struct.unpack("<f", fields[2])
    return event


class TestTuneRun:
    def test_function_trainable_grid(self, ray_start, tmp_path):
        from ray_tpu import tune
        analysis = tune.run(
            _quadratic,
            name="quad",
            config={"x": tune.grid_search([0.0, 3.0, 5.0])},
            stop={"training_iteration": 5},
            local_dir=str(tmp_path))
        assert len(analysis.trials) == 3
        best = analysis.get_best_trial(metric="score", mode="max")
        assert best.config["x"] == 3.0
        assert best.last_result["score"] == 0.0
        # Json logs written per trial
        dfs = analysis.trial_dataframes()
        assert all(len(rows) >= 1 for rows in dfs.values())

    def test_trial_logdir_holds_tensorboard_scalars(self, ray_start,
                                                   tmp_path):
        """Every reported scalar reaches an `events.out.tfevents.*` file
        in the trial's logdir, at its `training_iteration`, in records a
        TensorBoard reads: framing, checksums and protos are decoded by
        the reader above, written from the formats and sharing nothing
        with the writer, and by TensorBoard's own classes as well where
        the package is installed."""
        import glob
        from ray_tpu import tune
        analysis = tune.run(
            _quadratic, name="tb", config={"x": 5.0},
            stop={"training_iteration": 5}, local_dir=str(tmp_path))
        trial, = analysis.trials
        path, = glob.glob(os.path.join(trial.logdir,
                                       "events.out.tfevents.*"))
        records = _tfrecords(open(path, "rb").read())
        events = [_decode_event(r) for r in records]
        assert events[0]["file_version"] == "brain.Event:2"
        assert [e["step"] for e in events[1:]] == [1, 2, 3, 4, 5]
        for e in events:
            assert abs(e["wall_time"] - time.time()) < 600
        # Every number of every result (result.json has them all).
        rows = [json.loads(line) for line in
                open(os.path.join(trial.logdir, "result.json"))]
        assert len(rows) == 5
        for e, row in zip(events[1:], rows):
            assert e["scalars"]["score"] == -4.0
            assert e["scalars"]["training_iteration"] == e["step"]
            numbers = {k: v for k, v in row.items()
                       if isinstance(v, (int, float))}
            assert len(numbers) >= 5, row
            for k, v in numbers.items():
                assert e["scalars"][k] == np.float32(v), (k, row)
        assert _crc32c(b"123456789") == 0xE3069283  # Castagnoli's check
        try:
            from tensorboard.compat.proto import event_pb2
        except ImportError:
            return
        theirs = [event_pb2.Event.FromString(r) for r in records]
        assert [(e.step, {v.tag: v.simple_value for v in e.summary.value})
                for e in theirs] == [(e["step"], e["scalars"])
                                     for e in events]

    def test_trial_process_imports_no_framework(self, ray_start, tmp_path):
        """Logging a trial's results costs its process no import of torch
        or tensorflow (PR 30: ~10 s a trial before its first result)."""
        from ray_tpu import tune

        def loaded(config, reporter):
            import sys
            reporter(frameworks=",".join(
                m for m in ("torch", "tensorflow") if m in sys.modules))

        analysis = tune.run(loaded, name="mods",
                            stop={"training_iteration": 1},
                            local_dir=str(tmp_path))
        assert analysis.trials[0].last_result["frameworks"] == ""

    def test_trainable_class_checkpointing(self, ray_start, tmp_path):
        from ray_tpu import tune

        class MyTrainable(tune.Trainable):
            def _setup(self, config):
                self.x = 0

            def _train(self):
                self.x += 1
                return {"score": self.x}

            def _save(self, d):
                import json
                p = os.path.join(d, "state.json")
                with open(p, "w") as f:
                    json.dump({"x": self.x}, f)
                return p

            def _restore(self, path):
                import json
                with open(path) as f:
                    self.x = json.load(f)["x"]

        analysis = tune.run(
            MyTrainable, name="ckpt",
            stop={"training_iteration": 4},
            checkpoint_freq=2, checkpoint_at_end=True,
            local_dir=str(tmp_path))
        t = analysis.trials[0]
        assert t.last_result["score"] == 4
        ckpt = t.checkpoint
        assert ckpt is not None and os.path.exists(ckpt.value)

    def test_asha_end_to_end(self, ray_start, tmp_path):
        from ray_tpu import tune
        from ray_tpu.tune.schedulers import AsyncHyperBandScheduler

        def trainfn(config, reporter):
            for i in range(20):
                reporter(score=config["x"] * (i + 1),
                         training_iteration=i + 1)
                time.sleep(0.01)

        sched = AsyncHyperBandScheduler(
            metric="score", mode="max", grace_period=2, max_t=20,
            reduction_factor=2)
        analysis = tune.run(
            trainfn, name="asha",
            config={"x": tune.grid_search([1.0, 2.0, 3.0, 4.0])},
            scheduler=sched,
            stop={"training_iteration": 20},
            local_dir=str(tmp_path),
            raise_on_failed_trial=False)
        assert len(analysis.trials) == 4
        best = analysis.get_best_trial(metric="score", mode="max")
        assert best.config["x"] == 4.0

    def test_experiment_resume(self, ray_start, tmp_path):
        from ray_tpu import tune
        from ray_tpu.tune.trial import Trial

        analysis = tune.run(
            _quadratic, name="resume",
            config={"x": tune.grid_search([1.0, 2.0])},
            stop={"training_iteration": 5},
            local_dir=str(tmp_path))
        state_file = os.path.join(
            analysis.trials[0].local_dir, "experiment_state.json")
        # run() keeps local_dir under <local_dir>/<name>
        exp_dir = os.path.dirname(analysis.trials[0].logdir)
        assert os.path.exists(os.path.join(exp_dir,
                                           "experiment_state.json"))
        # Resume: everything already TERMINATED -> no rerun, same trials.
        analysis2 = tune.run(
            _quadratic, name="resume",
            config={"x": tune.grid_search([1.0, 2.0])},
            stop={"training_iteration": 5},
            local_dir=str(tmp_path), resume=True)
        assert len(analysis2.trials) == 2
        assert all(t.status == Trial.TERMINATED
                   for t in analysis2.trials)

    def test_pbt_end_to_end(self, ray_start, tmp_path):
        from ray_tpu import tune
        from ray_tpu.tune.schedulers import PopulationBasedTraining

        class Learner(tune.Trainable):
            """Score grows by lr each step; best lr should dominate."""

            def _setup(self, config):
                self.score = 0.0

            def _train(self):
                self.score += self.config["lr"]
                return {"score": self.score,
                        "training_iteration": self._iteration + 1}

            def _save(self, d):
                p = os.path.join(d, "s.txt")
                with open(p, "w") as f:
                    f.write(str(self.score))
                return p

            def _restore(self, p):
                with open(p) as f:
                    self.score = float(f.read())

        pbt = PopulationBasedTraining(
            time_attr="training_iteration", metric="score", mode="max",
            perturbation_interval=2,
            hyperparam_mutations={"lr": [0.1, 1.0]})
        analysis = tune.run(
            Learner, name="pbt",
            config={"lr": tune.grid_search([0.1, 0.1, 1.0, 1.0])},
            scheduler=pbt,
            stop={"training_iteration": 8},
            local_dir=str(tmp_path),
            raise_on_failed_trial=False)
        assert len(analysis.trials) == 4
        scores = [t.last_result.get("score", 0) for t in analysis.trials]
        # With exploit/explore the population should trend toward lr=1.0
        # performance; at minimum the best trial reflects lr 1.0 progress.
        assert max(scores) >= 6.0


class TestRLlibTuneIntegration:
    def test_tune_runs_ppo_trial(self, ray_start, tmp_path):
        from ray_tpu import tune
        analysis = tune.run(
            "PPO", name="ppo_tune",
            config={
                "env": "CartPole-v0",
                "num_workers": 0,
                "train_batch_size": 128,
                "sgd_minibatch_size": 64,
                "num_sgd_iter": 2,
                "rollout_fragment_length": 64,
                "model": {"fcnet_hiddens": [16]},
            },
            stop={"training_iteration": 2},
            local_dir=str(tmp_path))
        t = analysis.trials[0]
        assert t.last_result["training_iteration"] == 2
        assert "episode_reward_mean" in t.last_result


class TestHyperBand:
    def test_hyperband_end_to_end(self, ray_start, tmp_path):
        """Synchronous halving drops bottom trials at milestones and the
        winner survives to max_t."""
        import json as _json
        from ray_tpu import tune
        from ray_tpu.tune.schedulers import HyperBandScheduler
        from ray_tpu.tune.trial import Trial

        class Linear(tune.Trainable):
            """score = x * iter; pausable (HyperBand milestones move
            trials through memory checkpoints)."""

            def _setup(self, config):
                self.i = 0

            def _train(self):
                self.i += 1
                return {"score": self.config["x"] * self.i}

            def _save(self, d):
                p = os.path.join(d, "s.json")
                with open(p, "w") as f:
                    _json.dump({"i": self.i}, f)
                return p

            def _restore(self, path):
                with open(path) as f:
                    self.i = _json.load(f)["i"]

        sched = HyperBandScheduler(
            metric="score", mode="max", max_t=9, reduction_factor=3)
        analysis = tune.run(
            Linear, name="hb",
            config={"x": tune.grid_search([1.0, 2.0, 3.0, 4.0])},
            scheduler=sched,
            stop={"training_iteration": 9},
            local_dir=str(tmp_path),
            raise_on_failed_trial=False)
        assert len(analysis.trials) == 4
        assert all(t.status == Trial.TERMINATED for t in analysis.trials)
        best = analysis.get_best_trial(metric="score", mode="max")
        assert best.config["x"] == 4.0
        # Halving actually cut someone short of max_t.
        iters = sorted(t.last_result.get("training_iteration", 0)
                       for t in analysis.trials)
        assert iters[0] < 9
        assert iters[-1] == 9

    def test_resume_restores_from_checkpoint(self, ray_start, tmp_path):
        """An interrupted experiment resumes trials from their newest disk
        checkpoint instead of restarting from scratch."""
        import json as _json
        from ray_tpu import tune
        from ray_tpu.tune.trial import Trial

        marker_dir = str(tmp_path / "marks")
        os.makedirs(marker_dir, exist_ok=True)

        class Counting(tune.Trainable):
            def _setup(self, config):
                self.x = 0
                self._mark = os.path.join(
                    config["marker_dir"], "calls.txt")

            def _train(self):
                self.x += 1
                with open(self._mark, "a") as f:
                    f.write(f"{self.x}\n")
                return {"score": self.x}

            def _save(self, d):
                p = os.path.join(d, "state.json")
                with open(p, "w") as f:
                    _json.dump({"x": self.x}, f)
                return p

            def _restore(self, path):
                with open(path) as f:
                    self.x = _json.load(f)["x"]

        analysis = tune.run(
            Counting, name="resume_ckpt",
            config={"marker_dir": marker_dir},
            stop={"training_iteration": 3},
            checkpoint_freq=1, checkpoint_at_end=True,
            local_dir=str(tmp_path))
        exp_dir = os.path.dirname(analysis.trials[0].logdir)
        state_path = os.path.join(exp_dir, "experiment_state.json")
        # Simulate an interrupted run: mark the trial unfinished.
        with open(state_path) as f:
            state = _json.load(f)
        for rec in state["trials"]:
            rec["status"] = Trial.RUNNING
        with open(state_path, "w") as f:
            _json.dump(state, f)

        analysis2 = tune.run(
            Counting, name="resume_ckpt",
            config={"marker_dir": marker_dir},
            stop={"training_iteration": 5},
            checkpoint_freq=1,
            local_dir=str(tmp_path), resume=True)
        t = analysis2.trials[0]
        assert t.status == Trial.TERMINATED
        assert t.last_result["training_iteration"] == 5
        assert t.last_result["score"] == 5
        # 3 calls in run 1 + 2 after restore-at-3 (not 5) in run 2.
        with open(os.path.join(marker_dir, "calls.txt")) as f:
            calls = [int(x) for x in f.read().split()]
        assert calls == [1, 2, 3, 4, 5], calls


class TestDurableCheckpoints:
    def test_durable_trainable_survives_logdir_loss(self, tmp_path):
        """Parity: tune/durable_trainable.py — checkpoints persist in
        upload_dir and restore on a 'different node' (fresh trainable
        with the local logdir wiped)."""
        import shutil
        from ray_tpu.tune import DurableTrainable

        class Counter(DurableTrainable):
            def _setup(self, config):
                self.n = 0

            def _train(self):
                self.n += 1
                return {"value": self.n}

            def _save(self, checkpoint_dir):
                import os
                path = os.path.join(checkpoint_dir, "state.txt")
                with open(path, "w") as f:
                    f.write(str(self.n))
                return path

            def _restore(self, path):
                with open(path) as f:
                    self.n = int(f.read())

        upload = str(tmp_path / "durable")
        t = Counter(config={"upload_dir": upload})
        t.train()
        t.train()
        durable_path = t.save()
        assert durable_path.startswith(upload)
        # local copy cleaned up after upload; durable copy authoritative
        local_logdir = t.logdir
        t.stop()
        shutil.rmtree(local_logdir, ignore_errors=True)  # "node lost"

        t2 = Counter(config={"upload_dir": upload})
        t2.restore(durable_path)
        assert t2.train()["value"] == 3
        t2.stop()

    def test_shared_upload_dir_no_clobber(self, tmp_path):
        """Two trials sharing one upload_dir keep distinct durable
        checkpoints (namespaced names)."""
        from ray_tpu.tune import DurableTrainable

        class V(DurableTrainable):
            def _setup(self, config):
                self.v = config["v"]

            def _train(self):
                return {"value": self.v}

            def _save(self, d):
                import os
                p = os.path.join(d, "v.txt")
                open(p, "w").write(str(self.v))
                return p

            def _restore(self, path):
                self.v = int(open(path).read())

        upload = str(tmp_path / "shared")
        a = V(config={"upload_dir": upload, "v": 1})
        b = V(config={"upload_dir": upload, "v": 2})
        a.train(); b.train()
        pa, pb = a.save(), b.save()
        assert pa != pb
        a2 = V(config={"upload_dir": upload, "v": 0})
        a2.restore(pa)
        assert a2.v == 1
        b2 = V(config={"upload_dir": upload, "v": 0})
        b2.restore(pb)
        assert b2.v == 2
        for t in (a, b, a2, b2):
            t.stop()

    def test_save_to_object_skips_sync(self, tmp_path):
        """Pause/exploit blobs stay in-memory (no durable side copies)."""
        import os
        from ray_tpu.tune import DurableTrainable

        class C(DurableTrainable):
            def _setup(self, config):
                self.n = 5

            def _train(self):
                return {"value": self.n}

            def _save(self, d):
                p = os.path.join(d, "n.txt")
                open(p, "w").write(str(self.n))
                return p

            def _restore(self, path):
                self.n = int(open(path).read())

        upload = str(tmp_path / "durable2")
        t = C(config={"upload_dir": upload})
        t.train()
        blob = t.save_to_object()
        assert os.listdir(upload) == []  # nothing synced
        t.n = 99
        t.restore_from_object(blob)
        assert t.n == 5
        t.stop()


class TestTuneCLI:
    """`python -m ray_tpu.tune` offline inspection (parity:
    `python/ray/tune/scripts.py` list-trials/list-experiments)."""

    def _run_small_experiment(self, tmp_path):
        import ray_tpu
        from ray_tpu.tune import grid_search as gs, run
        ray_tpu.init(num_cpus=2)
        try:
            def trainable(config, reporter):
                for i in range(3):
                    reporter(
                        episode_reward_mean=config["x"] * (i + 1),
                        training_iteration=i + 1)

            analysis = run(trainable,
                           config={"x": gs([1, 10])},
                           stop={"training_iteration": 3},
                           local_dir=str(tmp_path),
                           name="cli-exp")
        finally:
            ray_tpu.shutdown()
        return analysis

    def test_list_and_best(self, tmp_path, capsys):
        self._run_small_experiment(tmp_path)
        from ray_tpu.tune.__main__ import main
        exp_dir = str(tmp_path / "cli-exp")
        main(["list-trials", exp_dir])
        out = capsys.readouterr().out
        assert "2 trial(s)" in out and "iter=3" in out
        main(["best", exp_dir, "--metric", "episode_reward_mean"])
        out = capsys.readouterr().out
        assert "episode_reward_mean = 30" in out
        assert "x: 10" in out
        main(["list-experiments", str(tmp_path)])
        out = capsys.readouterr().out
        assert "cli-exp" in out and "trials=2" in out

    def test_missing_dir_errors(self, tmp_path):
        import pytest as _pytest
        from ray_tpu.tune.__main__ import main
        with _pytest.raises(SystemExit):
            main(["list-trials", str(tmp_path / "nope")])
