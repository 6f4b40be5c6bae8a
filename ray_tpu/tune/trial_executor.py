"""Trial executor: runs Trainables as remote actors.

Parity: `python/ray/tune/ray_trial_executor.py:39` — `start_trial` (:227)
creates the trainable actor, `fetch_result` consumes train futures,
pause/unpause moves state through in-memory checkpoints.
"""

from __future__ import annotations

import logging
import time
import traceback
from typing import Dict, Optional

import ray_tpu

from .checkpoint_manager import Checkpoint
from .registry import get_trainable_cls
from .trial import Trial

logger = logging.getLogger(__name__)


class RayTrialExecutor:
    def __init__(self):
        self._running: Dict = {}          # train-result ref -> trial
        self._trial_actor: Dict = {}      # trial -> actor handle

    # ------------------------------------------------------------------
    def has_resources(self, resources: dict) -> bool:
        avail = ray_tpu.available_resources()
        for k, v in (resources or {}).items():
            if v and avail.get(k, 0) < v:
                return False
        return True

    # ------------------------------------------------------------------
    def start_trial(self, trial: Trial,
                    checkpoint: Optional[Checkpoint] = None) -> bool:
        cls = get_trainable_cls(trial.trainable_name)
        trial.init_logdir()
        remote_cls = ray_tpu.remote(cls)
        # The trial actor itself takes 1 CPU; its own rollout-worker
        # actors claim theirs separately (the full footprint is what
        # `has_resources` gates on). The TPU claim is the trial actor's
        # alone: it is what makes the head spawn this worker — and no
        # other — with the device (head._spawn_worker_locked).
        request = cls.default_resource_request(trial.config) or {}
        logdir = trial.logdir

        def logger_creator(config, _logdir=logdir):
            from .logger import UnifiedLogger
            return UnifiedLogger(config, _logdir)

        try:
            runner = remote_cls.options(
                num_cpus=1, num_tpus=request.get("TPU") or None).remote(
                config=trial.config, logger_creator=logger_creator)
            trial.runner = runner
            self._trial_actor[trial] = runner
            if checkpoint is None and trial.restore_blob is None:
                # Experiment resume / recovery: fall back to the trial's
                # newest disk checkpoint (reference ray_trial_executor
                # start_trial consults trial.checkpoint).
                checkpoint = trial.checkpoint
            if checkpoint is not None:
                self.restore(trial, checkpoint)
            elif trial.restore_blob is not None:
                ray_tpu.get(
                    runner.restore_from_object.remote(trial.restore_blob))
                trial.restore_blob = None  # consumed
            trial.status = Trial.RUNNING
            trial.start_time = time.time()
            self.continue_training(trial)
            return True
        except Exception:
            logger.exception("failed to start trial %s", trial)
            trial.error_msg = traceback.format_exc()
            trial.status = Trial.ERROR
            return False

    def continue_training(self, trial: Trial):
        ref = trial.runner.train.remote()
        self._running[ref] = trial

    def stop_trial(self, trial: Trial, error: bool = False,
                   error_msg: Optional[str] = None):
        trial.status = Trial.ERROR if error else Trial.TERMINATED
        trial.error_msg = error_msg
        self._kill_runner(trial)

    def _kill_runner(self, trial: Trial):
        runner = self._trial_actor.pop(trial, None)
        trial.runner = None
        # Drop any in-flight result refs for this trial.
        for ref in [r for r, t in self._running.items() if t is trial]:
            del self._running[ref]
        if runner is not None:
            try:
                ray_tpu.get(runner.stop.remote(), timeout=10)
            except Exception:
                pass
            try:
                ray_tpu.kill(runner)
            except Exception:
                pass

    def pause_trial(self, trial: Trial):
        """Checkpoint to memory and release the actor (parity:
        `trial_executor.py pause_trial`)."""
        try:
            trial.restore_blob = ray_tpu.get(
                trial.runner.save_to_object.remote())
        except Exception:
            logger.exception("pause of %s failed; stopping", trial)
            self.stop_trial(trial, error=True)
            return
        self._kill_runner(trial)
        trial.status = Trial.PAUSED

    # ------------------------------------------------------------------
    def get_next_available_trial(self,
                                 timeout: Optional[float] = None
                                 ) -> Optional[Trial]:
        if not self._running:
            return None
        ready, _ = ray_tpu.wait(list(self._running), num_returns=1,
                                timeout=timeout)
        if not ready:
            return None
        self._last_ref = ready[0]
        return self._running[ready[0]]

    def fetch_result(self, trial: Trial):
        ref = self._last_ref
        assert self._running.get(ref) is trial
        del self._running[ref]
        return ray_tpu.get(ref)

    # ------------------------------------------------------------------
    def save(self, trial: Trial, storage: str = Checkpoint.DISK,
             result: Optional[dict] = None) -> Checkpoint:
        if storage == Checkpoint.MEMORY:
            blob = ray_tpu.get(trial.runner.save_to_object.remote())
            ckpt = Checkpoint(storage, blob, result or trial.last_result)
        else:
            path = ray_tpu.get(trial.runner.save.remote())
            ckpt = Checkpoint(storage, path, result or trial.last_result)
        trial.checkpoint_manager.on_checkpoint(ckpt)
        return ckpt

    def restore(self, trial: Trial, checkpoint: Checkpoint):
        if checkpoint.storage == Checkpoint.MEMORY:
            ray_tpu.get(
                trial.runner.restore_from_object.remote(checkpoint.value))
        else:
            ray_tpu.get(trial.runner.restore.remote(checkpoint.value))

    def num_running(self) -> int:
        return len(set(self._running.values()))
