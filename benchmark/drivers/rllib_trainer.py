"""Driver for cells that are an RLlib trainer training in this process.

The process that runs this owns the chip: the trainer is built here and
`train()` is called in a loop; CPU rollout workers, where a cell has them,
are children of this process through `ray_tpu.init`. There is no CLI or
tune wrapping on the measured path.

A driver gives `run.py` an object with:
  warm_up()               train until every program of the cell is compiled
  iterate() -> dict       one iteration: {"ok", "steps", "why"}
  steps_trained() -> int  cumulative trained env steps
  check_outputs(seed)     the comparison with the plain reference
  device_report() -> dict where the program says it ran
  close()                 stop threads and child processes, and wait
and the handles the per-layer readers need (`trainer`, `optimizer`).
"""

from __future__ import annotations

import copy
import math

import numpy as np

from lib import reference

# Seeds reach the envs as `np.random.seed(seed + 1000 * worker_index)`,
# which takes 32 bits; the driver's seeds are larger.
SEED_SPACE = 2 ** 30


def merge(base: dict, extra: dict) -> dict:
    out = copy.deepcopy(base)
    for key, value in (extra or {}).items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = merge(out[key], value)
        else:
            out[key] = copy.deepcopy(value)
    return out


class Session:
    def __init__(self, config: dict, workload: dict, seed: int,
                 chips: int, rehearse: bool):
        import ray_tpu
        from ray_tpu.rllib.agents.registry import get_trainer_class

        self.config, self.workload = config, workload
        trainer_config = merge(config["trainer_config"],
                               workload.get("trainer_config"))
        if rehearse:
            trainer_config = merge(trainer_config,
                                   workload.get("rehearse_trainer_config"))
        trainer_config["seed"] = seed % SEED_SPACE
        trainer_config["num_tpus_for_learner"] = chips
        self._runtime = None
        runtime = workload.get("runtime")
        if runtime:
            ray_tpu.init(**runtime)
            self._runtime = ray_tpu
        self.trainer = get_trainer_class(config["trainer"])(
            config=trainer_config)
        self.optimizer = self.trainer.optimizer
        self.policy = self.trainer.get_policy()
        self.last_result = None
        self._stopped = False

    # ------------------------------------------------------------------
    def steps_trained(self) -> int:
        return int(self.optimizer.num_steps_trained)

    def iterate(self) -> dict:
        before = self.steps_trained()
        try:
            result = self.trainer.train()
        except Exception as e:  # noqa: BLE001 - a failed iteration is counted
            return {"ok": False, "steps": 0, "why": repr(e), "raised": e}
        self.last_result = result
        steps = self.steps_trained() - before
        loss = ((result.get("info") or {}).get("learner") or {}).get(
            "total_loss")
        why = None
        if steps <= 0:
            why = "trained no step"
        elif loss is None or not math.isfinite(float(loss)):
            why = f"total_loss {loss!r}"
        return {"ok": why is None, "steps": steps, "why": why}

    def warm_up(self) -> None:
        """The cell's own shapes and no others: iterate until trained
        steps have moved `warmup.iterations` times."""
        need = int((self.workload.get("warmup") or {}).get("iterations", 2))
        moved = 0
        for _ in range(need * 20):
            out = self.iterate()
            if out.get("raised") is not None:
                raise out["raised"]
            if out["why"] and out["steps"] > 0:
                raise RuntimeError(f"warm-up iteration failed: {out['why']}")
            moved += out["steps"] > 0
            if moved >= need:
                return
        raise RuntimeError(f"warm-up trained steps {moved} times of {need}")

    # ------------------------------------------------------------------
    def device_report(self) -> dict:
        return dict((self.last_result or {}).get("device") or {})

    def check_outputs(self, seed: int) -> dict:
        """Logits and value of the system's inference program against the
        plain float32 reference, same parameters, 256 seeded frames. The
        trainer is stopped first: a learner thread that is still training
        would land an update between the two forwards."""
        self._stop_trainer()
        net = self.config["network"]
        rng = np.random.default_rng(seed)
        obs = rng.integers(0, 256, size=(256, *net["obs_shape"]),
                           dtype=np.uint8)
        _, _, extra = self.policy.compute_actions(obs, explore=False)
        system = (extra["action_dist_inputs"], extra["vf_preds"])
        params = self.policy.get_weights()["params"]
        ref = reference.forward(
            params, obs, [s for _, _, s in net["conv_filters"]])
        verdict = reference.compare(system, [np.asarray(r) for r in ref])
        verdict["param_count"] = int(sum(
            np.size(x) for layer in params.values() for x in layer.values()))
        return verdict

    def _stop_trainer(self) -> None:
        if not self._stopped:
            self._stopped = True
            self.trainer.stop()

    def close(self) -> None:
        try:
            self._stop_trainer()
        finally:
            if self._runtime is not None:
                self._runtime.shutdown()


def open_session(config: dict, workload: dict, seed: int, chips: int,
                 rehearse: bool) -> Session:
    return Session(config, workload, seed, chips, rehearse)
