"""Trainer: the user-facing algorithm runner.

Parity: `rllib/agents/trainer.py:335` — extends the Tune `Trainable`,
builds a WorkerSet in `_setup` (:494), runs the policy optimizer per
`train()` with worker-failure handling (:425), checkpoints policy +
optimizer state via get/set state (:857), and exposes
`compute_action`/`get_policy`/`workers`.

COMMON_CONFIG mirrors the reference's vocabulary (:39): num_workers,
num_envs_per_worker, rollout_fragment_length (the reference's
sample_batch_size), train_batch_size, gamma, lr, model, ... plus
TPU-specific: num_tpus_for_learner (mesh size for the learner program).
"""

from __future__ import annotations

import logging
import os
import pickle
from typing import Callable, Optional, Type

import ray_tpu
from ray_tpu.exceptions import RayError

from ...tune.trainable import Trainable
from ..env.registry import make_env
from ..evaluation.metrics import collect_episodes, summarize_episodes
from ..evaluation.worker_set import WorkerSet

logger = logging.getLogger(__name__)

COMMON_CONFIG = {
    # === Rollouts ===
    "num_workers": 0,
    "num_envs_per_worker": 1,
    # Sebulba inline actors: threads on the learner process stepping a
    # BatchedEnv with TPU-batched inference (num_envs_per_worker env
    # slots each). The TPU-native answer to "the chip starves behind
    # remote CPU-inference workers" — see
    # `optimizers/async_samples_optimizer.py:InlineActorThread`.
    "num_inline_actors": 0,
    "rollout_fragment_length": 200,
    "batch_mode": "truncate_episodes",
    "horizon": None,
    "observation_filter": "NoFilter",
    # === Training ===
    "gamma": 0.99,
    "lr": 5e-5,
    "train_batch_size": 200,
    "model": {},
    "optimizer": {},
    "grad_clip": None,
    "seed": None,
    # Weight broadcast codec (_private/weight_sync.py): "auto" defers
    # to RAY_TPU_WEIGHT_CODEC (default q8_delta — int8 block-quantized
    # deltas with sender-side error feedback and a version handshake
    # that full-syncs stale receivers); "full" ships the whole float32
    # tree every sync.
    "weight_sync_codec": "auto",
    # Learner parameter partition rule table (_private/spec_layout.py):
    # "auto" defers to RAY_TPU_PARAM_SHARDING ("replicate" keeps the
    # legacy fully-replicated layout; "fsdp" shards large params and
    # their optax moments over the dp mesh axis), or an explicit
    # [(regex, PartitionSpec)] rule list.
    "param_sharding": "auto",
    # Learner compute dtype: "auto" defers to RAY_TPU_COMPUTE_DTYPE
    # ("f32" | "bf16"). bf16 casts parameters at the loss boundary
    # only — master weights, gradients and optax state stay f32.
    "compute_dtype": "auto",
    # === Environment ===
    "env": None,
    "env_config": {},
    # Compress observation columns (lz4 if available, else zlib) before
    # sample batches cross the worker->learner process boundary
    # (parity: `rllib/utils/compression.py` + `compress_observations`).
    # No effect on inline/device rollouts (no process hop to compress).
    "compress_observations": False,
    # === Offline I/O (parity: rllib/offline/io_context.py) ===
    # "sampler" = fresh env experience; a path = JSON-lines replay dir.
    "input": "sampler",
    # None = discard; a path = record experiences as JSON-lines files.
    "output": None,
    # === Resources ===
    "num_cpus_per_worker": 1,
    # TPU devices the learner's mesh spans (0 = single default device).
    "num_tpus_for_learner": 0,
    # === Fault tolerance (parity: trainer.py:425) ===
    "ignore_worker_failures": False,
    # === Evaluation (parity: trainer.py:560 `_evaluate`) ===
    "evaluation_interval": None,
    "evaluation_num_episodes": 10,
    # Config overrides applied to the evaluation worker's policy/env.
    "evaluation_config": {},
    # === Reporting ===
    "min_iter_time_s": 0,
    "timesteps_per_iteration": 0,
}


from ..utils.config import deep_merge  # noqa: E402  (re-export)


def with_common_config(extra: dict) -> dict:
    cfg = deep_merge({}, COMMON_CONFIG)
    return deep_merge(cfg, extra)


class Trainer(Trainable):
    _name = "Trainer"
    _default_config = COMMON_CONFIG
    _policy_cls = None

    def __init__(self, config: Optional[dict] = None,
                 env: Optional[str] = None, logger_creator=None):
        config = config or {}
        if env is not None:
            config["env"] = env
        super().__init__(config, logger_creator)

    # ------------------------------------------------------------------
    def _setup(self, config: dict):
        merged = deep_merge(deep_merge({}, self._default_config), config)
        self.config = merged
        env_name = merged.get("env")
        if callable(env_name):
            self.env_creator = env_name
        elif env_name is not None:
            self.env_creator = lambda cfg, _n=env_name: make_env(_n, cfg)
        else:
            raise ValueError("config['env'] is required")
        k = merged.get("device_frame_stack") or 0
        if k:
            # On-device frame stacking (device_frame_stack.py): the env
            # emits single frames, the device sampler stacks in HBM. The
            # probe env must advertise the STACKED space so policies
            # build the right network.
            from ..env.device_frame_stack import stacked_space
            inner_creator = self.env_creator

            def stacked_creator(cfg, _mk=inner_creator, _k=k):
                env = _mk(cfg)
                env.observation_space = stacked_space(
                    env.observation_space, _k)
                return env

            self.env_creator = stacked_creator
        self._make_mesh()
        self._init(merged, self.env_creator)

    def _make_mesh(self):
        """Build the learner mesh. Requesting more devices than exist is
        an error, not a silent single-device fallback — and so is getting
        CPU devices for `num_tpus_for_learner`: jax registers its TPU
        backend to fail quietly, so a chip that is missing or held by
        another process would otherwise train on the host and exit 0.
        Only a process put on the CPU out loud (JAX_PLATFORMS=cpu: the
        test meshes, rollout workers) may stand in CPU devices."""
        import jax
        from ...parallel import mesh as mesh_lib
        n = self.config.get("num_tpus_for_learner") or 0
        devices = jax.devices()
        if n > len(devices):
            raise ValueError(
                f"num_tpus_for_learner={n} but only {len(devices)} "
                f"device(s) visible to this process")
        if n and devices[0].platform == "cpu" \
                and jax.config.jax_platforms != "cpu":
            raise RuntimeError(
                f"num_tpus_for_learner={n} but jax gave this process "
                f"{devices[0].device_kind!r} devices: the TPU is missing "
                "or another process holds it (one process drives all "
                "chips of a host). Set JAX_PLATFORMS=cpu to train on the "
                "host on purpose.")
        self.learner_mesh = mesh_lib.make_mesh(num_devices=n or 1)
        d = self.learner_mesh.devices.flat[0]
        self._device = {"platform": d.platform, "kind": d.device_kind,
                        "count": int(self.learner_mesh.devices.size)}

    def _init(self, config, env_creator):
        """Subclasses/templates build workers + optimizer here."""
        raise NotImplementedError

    def _make_workers(self, policy_cls) -> WorkerSet:
        return WorkerSet(
            self.env_creator, policy_cls, self.config,
            num_workers=self.config["num_workers"],
            local_mesh=self.learner_mesh)

    # ------------------------------------------------------------------
    def _train(self) -> dict:
        """One training iteration with worker-failure retry (parity:
        `Trainer.train`, trainer.py:425). Recovery attempts are bounded
        and jittered (backoff.py) — recreating workers into the same
        fault (a node still dying, chaos still injecting) back-to-back
        just multiplies the failure."""
        import time

        from ray_tpu._private.backoff import Backoff
        backoff = Backoff(base=0.2, factor=2.0, cap=2.0, max_attempts=3)
        while True:
            t0 = time.monotonic()
            try:
                result = self._train_inner()
                self._maybe_evaluate(result)
                self._push_train_metrics(result, time.monotonic() - t0)
                # Which device trained, read where it trained: a CLI
                # caller can tell a TPU run from a CPU one from the
                # result alone, without touching jax itself.
                from ray_tpu._private.profiling import device_memory_stats
                result["device"] = dict(
                    self._device, peak_bytes_in_use=max(
                        (s["peak"] for s in device_memory_stats()),
                        default=None))
                policy = getattr(self.workers.local_worker, "policy", None)
                if hasattr(policy, "devices_in_use"):
                    result["device"].update(policy.devices_in_use())
                return result
            except RayError as e:
                if not self.config.get("ignore_worker_failures"):
                    raise
                if backoff.expired():
                    raise RuntimeError(
                        "training failed after worker recovery attempts"
                    ) from e
                logger.warning("worker failure: %s; recreating workers", e)
                backoff.sleep()
                self._recover_workers()

    def _push_train_metrics(self, result: dict, iter_time: float):
        """Per-iteration timing/throughput into the cluster metrics
        plane, so the Prometheus endpoint (`ray_tpu_train_*`) and
        dashboard cover training health, not just the object store.
        Gauges hold the LAST iteration's values; the runtime's metric
        push loop ships them to the head on its cadence."""
        from ray_tpu._private import metrics as metrics_mod
        opt = getattr(self, "optimizer", None)
        metrics_mod.inc("train_iterations")
        metrics_mod.set_gauge("train_iter_time_s", iter_time)
        steps = float(result.get("timesteps_this_iter") or 0)
        if iter_time > 0:
            metrics_mod.set_gauge("train_env_throughput",
                                  steps / iter_time)
        # Per-iteration phase breakdown from the optimizer's cumulative
        # timers (sample wait / learn / weight exchange).
        last = getattr(self, "_last_timer_totals", {})
        totals = {}
        for key, gauge in (("sample", "train_sample_time_s"),
                           ("learn", "train_learn_time_s"),
                           ("allreduce", "train_allreduce_time_s")):
            timer = (getattr(opt, "timers", None) or {}).get(key)
            if timer is None:
                continue
            totals[key] = timer.total
            metrics_mod.set_gauge(
                gauge, max(0.0, timer.total - last.get(key, 0.0)))
        if iter_time > 0 and "sample" in totals:
            metrics_mod.set_gauge(
                "train_sample_wait_fraction",
                max(0.0, totals["sample"] - last.get("sample", 0.0))
                / iter_time)
        trained = float(getattr(opt, "num_steps_trained", 0) or 0)
        last_trained = getattr(self, "_last_steps_trained_metric", 0.0)
        if iter_time > 0:
            metrics_mod.set_gauge("train_learner_throughput",
                                  (trained - last_trained) / iter_time)
        self._last_steps_trained_metric = trained
        self._last_timer_totals = totals

    def _train_inner(self) -> dict:
        raise NotImplementedError

    def _recover_workers(self):
        healthy = []
        for w in list(self.workers.remote_workers):
            try:
                ray_tpu.get(w.ping.remote(), timeout=10)
                healthy.append(w)
            except Exception:
                try:
                    self.workers.recreate_failed_worker(w)
                except Exception:
                    logger.exception("failed to recreate worker")
        return healthy

    def _result_from_optimizer(self, optimizer, extra: dict = None) -> dict:
        episodes = collect_episodes(self.workers)
        inline = getattr(optimizer, "inline_episodes", None)
        if inline is not None:
            episodes.extend(inline())
        self._episode_history = getattr(self, "_episode_history", [])
        result = summarize_episodes(
            episodes, smoothed=self._episode_history)
        self._episode_history = (self._episode_history + episodes)[-100:]
        result.update(optimizer.stats())
        result["timesteps_this_iter"] = (
            optimizer.num_steps_sampled
            - getattr(self, "_last_steps_sampled", 0))
        self._last_steps_sampled = optimizer.num_steps_sampled
        result["info"] = {"learner": getattr(optimizer, "learner_stats", {})}
        if extra:
            result.update(extra)
        return result

    # ------------------------------------------------------------------
    def _maybe_evaluate(self, result: dict):
        interval = self.config.get("evaluation_interval")
        if not interval:
            return
        self._iters_since_eval = getattr(self, "_iters_since_eval", 0) + 1
        if self._iters_since_eval < interval:
            return
        self._iters_since_eval = 0
        result["evaluation"] = self._evaluate()

    def _evaluate(self) -> dict:
        """Run `evaluation_num_episodes` deterministic episodes on a
        dedicated eval worker (parity: `trainer.py:560` — a separate
        evaluation WorkerSet synced to the learner weights, with
        `evaluation_config` overrides applied)."""
        from ..evaluation.rollout_worker import RolloutWorker
        if getattr(self, "_eval_worker", None) is None:
            cfg = deep_merge(deep_merge({}, self.config),
                             self.config.get("evaluation_config") or {})
            cfg.pop("_mesh", None)
            self._eval_worker = RolloutWorker(
                self.env_creator, type(self.get_policy()), cfg,
                num_envs=cfg.get("num_envs_per_worker", 1),
                rollout_fragment_length=cfg.get(
                    "rollout_fragment_length", 100),
                worker_index=0,
                seed=cfg.get("seed"),
                observation_filter=cfg.get(
                    "observation_filter", "NoFilter"),
                explore=False,
                env_config=cfg.get("env_config"),
                horizon=cfg.get("horizon"))
        # local_worker.get_weights() returns {policy_id: weights} in
        # multi-agent mode and a bare tree otherwise — symmetric with
        # the eval worker's set_weights.
        self._eval_worker.set_weights(
            self.workers.local_worker.get_weights())
        if hasattr(self.workers.local_worker, "get_filters"):
            self._eval_worker.sync_filters(
                self.workers.local_worker.get_filters())
        n = self.config.get("evaluation_num_episodes", 10)
        self._eval_worker.get_metrics()  # drain stale episodes
        episodes = []
        while len(episodes) < n:
            self._eval_worker.sample()
            episodes.extend(self._eval_worker.get_metrics())
        return summarize_episodes(episodes)

    def get_policy(self):
        return self.workers.local_worker.policy

    def compute_action(self, obs, state=None, explore=False):
        action, _, _ = self.get_policy().compute_single_action(
            obs, state, explore=explore)
        return action

    # ------------------------------------------------------------------
    def __getstate__(self) -> dict:
        """Checkpointable state (parity: `trainer.py:857`). In
        multi-agent mode `policy` holds {policy_id: state}."""
        state = {"policy": self.workers.local_worker.get_policy_state(),
                 "config_overrides": {}}
        if hasattr(self.workers.local_worker, "obs_filter"):
            state["obs_filter"] = \
                self.workers.local_worker.get_filters()
        opt = getattr(self, "optimizer", None)
        if opt is not None:
            state["optimizer"] = opt.save()
        return state

    def __setstate__(self, state: dict):
        self.workers.local_worker.set_policy_state(state["policy"])
        if "obs_filter" in state:
            self.workers.local_worker.sync_filters(state["obs_filter"])
        opt = getattr(self, "optimizer", None)
        if opt is not None and "optimizer" in state:
            opt.restore(state["optimizer"])
        self.workers.sync_weights()

    def _save(self, checkpoint_dir: str) -> str:
        path = os.path.join(checkpoint_dir, "checkpoint.pkl")
        with open(path, "wb") as f:
            pickle.dump(self.__getstate__(), f)
        return path

    def _restore(self, checkpoint_path: str):
        with open(checkpoint_path, "rb") as f:
            self.__setstate__(pickle.load(f))

    def _stop(self):
        if getattr(self, "_eval_worker", None) is not None:
            self._eval_worker.stop()
        if hasattr(self, "workers"):
            self.workers.stop()
        opt = getattr(self, "optimizer", None)
        if opt is not None:
            opt.stop()

    @classmethod
    def default_resource_request(cls, config: dict):
        cfg = deep_merge(deep_merge({}, cls._default_config), config or {})
        return {
            "CPU": 1 + cfg["num_workers"] * cfg.get("num_cpus_per_worker", 1),
            "TPU": cfg.get("num_tpus_for_learner", 0),
        }
