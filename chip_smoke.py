"""First proof on every commit that the RL training path starts on the chip.

    python3 chip_smoke.py        # on a TPU host; there is no other mode

Drives the Nature-CNN IMPALA trainer (84x84x4 uint8 frames, `bench.py`'s
shapes) for a few iterations through the entry points a user calls, on
every chip of the host:

- anakin, through the CLI: `python -m ray_tpu.rllib.train` -> tune -> trial
  actor -> IMPALATrainer(anakin) on SyntheticAtari-v0;
- remote workers, through the CLI, from the tracked
  `tuned_examples/synthetic-atari-impala.yaml` with the fleet cut to two
  CPU rollout workers: a driver, two CPU processes and one chip-owning
  trial actor side by side;
- Sebulba, in the process that owns the chip: inline actors with
  device-resident rollouts, once on full frames (the reference feed) and
  once on the delta-scatter feed.

Each leg checks what came out (the process that trained was on the TPU
with every chip, every iteration trained steps and reported a finite loss,
params and the observation batch occupy all chips) and prints one
`smoke-observation` line. These are single smoke runs: proof of life and a
first look at set-up time and memory, not benchmark numbers. Any failed
check raises; nothing is carried past it.

A chip belongs to one process at a time, so this process never imports
jax: every leg trains in a child, and the chip is free again when the
child is gone. Everything the script writes goes under
`chiprun_out/chip_smoke/`. The last line of stdout is one JSON object,
`{"ok": true, "device": {...}}`, with the device as jax reported it in
the processes that trained.
"""

from __future__ import annotations

import glob
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
TUNED_YAML = os.path.join(ROOT, "ray_tpu", "rllib", "tuned_examples",
                          "synthetic-atari-impala.yaml")
# The whole script has 1200 s, compilation included.
LEG_TIMEOUT_S = 420


def run_child(cmd: list, log_path: str, timeout_s: float) -> float:
    """Run one child to its end in its own process group, output to
    `log_path`; returns wall seconds. Whatever the child started dies
    with it: a trial actor left holding the chip would fail the next
    leg."""
    t0 = time.time()
    with open(log_path, "ab") as log:
        proc = subprocess.Popen(cmd, cwd=ROOT, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout_s)
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    if rc != 0:
        with open(log_path, errors="replace") as f:
            tail = f.read()[-4000:]
        raise RuntimeError(
            f"{' '.join(cmd[:4])} ... exited {rc}; end of {log_path}:\n{tail}")
    return time.time() - t0


def check_results(name: str, results: list, n_chips: int,
                  platform: str) -> dict:
    """The checks every leg shares, over its per-iteration trainer
    results; returns the device block of the last one.

    An iteration is one or more learner updates. On the asynchronous
    paths the batch it trained on may have been sampled during the
    previous iteration, so what must hold every time is that steps were
    trained; sampled steps must be positive over the leg (under Anakin
    the two are the same number)."""
    if not results:
        raise AssertionError(f"{name}: no training result was reported")
    if not sum(r["timesteps_this_iter"] for r in results) > 0:
        raise AssertionError(f"{name}: no timesteps sampled")
    trained = 0
    for r in results:
        it = r["training_iteration"]
        dev = r["device"]
        if dev["platform"] != platform or dev["count"] != n_chips:
            raise AssertionError(
                f"{name} iter {it}: trained on {dev}, expected "
                f"{n_chips} x {platform}")
        if not r["num_steps_trained"] > trained:
            raise AssertionError(f"{name} iter {it}: no steps trained")
        trained = r["num_steps_trained"]
        loss = r["info"]["learner"].get("total_loss")
        if loss is None or not math.isfinite(loss):
            raise AssertionError(
                f"{name} iter {it}: loss {loss!r} is not finite "
                f"(learner stats {r['info']['learner']})")
        if dev["params_on"] != n_chips or dev["batch_on"] != n_chips:
            raise AssertionError(
                f"{name} iter {it}: params occupy {dev['params_on']} and "
                f"the obs batch {dev['batch_on']} of {n_chips} device(s)")
    return results[-1]["device"]


def observe(name: str, device: dict, results: list, setup_s: float,
            steady_s: float, **extra) -> dict:
    obs = {
        "leg": name,
        "device_kind": device["kind"],
        "platform": device["platform"],
        "device_count": device["count"],
        "iterations": len(results),
        "timesteps": int(sum(r["timesteps_this_iter"] for r in results)),
        # Everything but the steady iterations: process start, runtime
        # boot, opening the chip, compilation, the first iteration.
        "setup_s": round(setup_s, 1),
        "steady_s": round(steady_s, 2),
        "peak_bytes_in_use": device["peak_bytes_in_use"],
        **extra,
    }
    print("smoke-observation " + json.dumps(obs), flush=True)
    return obs


# ---------------------------------------------------------------------
# CLI legs: this process -> `rllib train` driver -> trial actor (chip)
# ---------------------------------------------------------------------
def cli_leg(name: str, experiment: dict, n_chips: int, platform: str,
            out_dir: str, timeout_s: float = LEG_TIMEOUT_S) -> dict:
    """Train `experiment` (tuned_examples yaml format) through `python -m
    ray_tpu.rllib.train` and check the trial's logged results."""
    import yaml
    leg_dir = os.path.join(out_dir, name)
    shutil.rmtree(leg_dir, ignore_errors=True)  # one trial, one result.json
    os.makedirs(leg_dir)
    yaml_path = os.path.join(leg_dir, "experiment.yaml")
    with open(yaml_path, "w") as f:
        yaml.safe_dump({name: dict(experiment, local_dir=leg_dir)}, f)
    wall = run_child(
        [sys.executable, "-m", "ray_tpu.rllib.train", "-f", yaml_path],
        os.path.join(leg_dir, "train.log"), timeout_s)
    (result_path,) = glob.glob(os.path.join(leg_dir, name, "*",
                                            "result.json"))
    with open(result_path) as f:
        results = [json.loads(line) for line in f]
    device = check_results(name, results, n_chips, platform)
    steady = sum(r["time_this_iter_s"] for r in results[1:])
    return observe(name, device, results, wall - steady, steady)


def anakin_cli_leg(n_chips: int, platform: str, out_dir: str,
                   envs_per_chip: int = 4096, frag: int = 16,
                   updates_per_call: int = 8, iters: int = 3) -> dict:
    n_envs = envs_per_chip * n_chips
    return cli_leg("anakin_cli", {
        "run": "IMPALA",
        "env": "SyntheticAtari-v0",
        "stop": {"training_iteration": iters},
        "config": {
            "anakin": True,
            "num_workers": 0,
            "num_envs_per_worker": n_envs,
            "rollout_fragment_length": frag,
            "train_batch_size": n_envs * frag,
            "anakin_updates_per_call": updates_per_call,
            "num_tpus_for_learner": n_chips,
            "lr": 6e-4,
            "min_iter_time_s": 0,
            "seed": 0,
        },
    }, n_chips, platform, out_dir)


def remote_workers_cli_leg(n_chips: int, platform: str, out_dir: str,
                           num_workers: int = 2, iters: int = 3,
                           **config_overrides) -> dict:
    """The tracked tuned example, its fleet cut to `num_workers`."""
    import yaml
    with open(TUNED_YAML) as f:
        (experiment,) = yaml.safe_load(f).values()
    experiment["stop"] = {"training_iteration": iters}
    experiment["config"].update(
        num_workers=num_workers, num_tpus_for_learner=n_chips, seed=0,
        **config_overrides)
    return cli_leg("remote_workers_cli", experiment, n_chips, platform,
                   out_dir)


# ---------------------------------------------------------------------
# Sebulba legs: the trainer lives in the process that owns the chip
# ---------------------------------------------------------------------
def sebulba_leg(name: str, env: str, obs_delta, n_chips: int,
                platform: str, n_actors: int = 4, n_envs: int = 256,
                frag: int = 25, iters: int = 6) -> dict:
    """IMPALA with inline actors and device-resident rollouts, in this
    process (`bench.bench_sebulba`'s configuration)."""
    import ray_tpu
    from ray_tpu.rllib.agents.registry import get_trainer_class

    t0 = time.time()
    ray_tpu.init(num_cpus=2)
    try:
        trainer = get_trainer_class("IMPALA")(config={
            "env": env,
            "num_workers": 0,
            "num_inline_actors": n_actors,
            "num_envs_per_worker": n_envs,
            "rollout_fragment_length": frag,
            "train_batch_size": n_envs * frag,
            "device_frame_stack": 4,
            "obs_delta": obs_delta,
            "num_tpus_for_learner": n_chips,
            # Queued batches retain device-resident obs columns
            # (N*T x 84x84x4 uint8 each): a small queue bounds HBM.
            "learner_queue_size": 2,
            "lr": 6e-4,
            "min_iter_time_s": 0,
            "seed": 0,
        })
        try:
            results = [trainer.train()]
            setup_s = time.time() - t0
            results += [trainer.train() for _ in range(iters - 1)]
            steady_s = time.time() - t0 - setup_s
            device = check_results(name, results, n_chips, platform)
            # The retained per-step observations are the arrays that
            # never pass through the policy's batch placement.
            samplers = [a.sampler for a in trainer.optimizer._inline_actors]
            for s in samplers:
                if s.delta != (obs_delta is not False):
                    raise AssertionError(
                        f"{name}: sampler delta mode is {s.delta} under "
                        f"obs_delta={obs_delta!r}")
                for g in s.groups:
                    on = len(g.obs_next.sharding.device_set)
                    if on != n_chips:
                        raise AssertionError(
                            f"{name}: a retained obs batch occupies {on} "
                            f"of {n_chips} device(s)")
        finally:
            trainer.stop()
        return observe(name, device, results, setup_s, steady_s,
                       h2d_bytes=sum(s.bytes_h2d for s in samplers))
    finally:
        ray_tpu.shutdown()


def sebulba_legs(n_chips: int, platform: str, **sizes) -> None:
    sebulba_leg("sebulba_fullframe", "SyntheticAtariFrames-v0", False,
                n_chips, platform, **sizes)
    sebulba_leg("sebulba_delta", "SpriteAtari-v0", "auto",
                n_chips, platform, **sizes)
    # Actor uploads have stopped: time the raw host->device link
    # (ROADMAP S1's first question).
    import bench
    mbps = bench.measure_link_bandwidth_mbps()
    print("smoke-observation " + json.dumps(
        {"leg": "host_to_device_link",
         "mb_per_s_single_stream": round(mbps, 1)}), flush=True)


def sebulba_child_leg(n_chips: int, platform: str, out_dir: str) -> list:
    """Both Sebulba legs in one child that owns the chip for their
    duration (this process must stay off jax); relays and returns the
    observations the child printed."""
    out_dir = os.path.join(out_dir, "sebulba")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    log_path = os.path.join(out_dir, "train.log")
    run_child(
        [sys.executable, "-c", "import chip_smoke; "
         f"chip_smoke.sebulba_legs({n_chips}, {platform!r})"],
        log_path, 2 * LEG_TIMEOUT_S)
    obs = []
    with open(log_path, errors="replace") as f:
        for line in f:
            if line.startswith("smoke-observation "):
                print(line, end="", flush=True)
                obs.append(json.loads(line.split(" ", 1)[1]))
    return obs


def main() -> None:
    from ray_tpu._private.node import detect_tpus
    n_chips = int(detect_tpus())
    if n_chips == 0:
        sys.exit("chip_smoke: this host has no TPU (no /dev/accel* or "
                 "/dev/vfio/* device file); there is nothing to prove "
                 "on a CPU")
    os.makedirs(OUT, exist_ok=True)
    obs = [anakin_cli_leg(n_chips, "tpu", OUT),
           remote_workers_cli_leg(n_chips, "tpu", OUT)]
    obs += sebulba_child_leg(n_chips, "tpu", OUT)
    if "jax" in sys.modules:
        raise AssertionError("chip_smoke's parent process imported jax")
    (kind,) = {o["device_kind"] for o in obs if "device_kind" in o}
    if len(obs) != 5:
        raise AssertionError(f"expected 5 observations, got {len(obs)}")
    print(json.dumps({"ok": True, "device": {
        "platform": "tpu", "kind": kind, "count": n_chips}}))


if __name__ == "__main__":
    main()
