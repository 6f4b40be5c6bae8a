"""WorkerSet: one local RolloutWorker + N remote RolloutWorker actors.

Parity: `rllib/evaluation/worker_set.py`. The local worker holds the
learner-side policy (TPU); remote workers are actors that claim no TPU, so
the head starts them on CPU JAX (Podracer-style actor/learner split).
"""

from __future__ import annotations

from typing import Callable, List, Optional

import ray_tpu

from .rollout_worker import RolloutWorker


class WorkerSet:
    def __init__(self,
                 env_creator: Callable,
                 policy_cls,
                 config: dict,
                 num_workers: int = 0,
                 local_mesh=None):
        self._env_creator = env_creator
        self._policy_cls = policy_cls
        self._config = config
        # Resolve a string policy_mapping_fn against the DRIVER's registry
        # here, before any worker ships: remote actors run in fresh
        # processes that only have the built-in registrations, so the
        # resolved closure (cloudpickle-able) travels in the config.
        self._resolve_mapping_fn(config)
        policy_config = dict(config.get("policy_config") or config)
        local_policy_config = dict(policy_config)
        if local_mesh is not None:
            local_policy_config["_mesh"] = local_mesh

        self.local_worker = RolloutWorker(
            env_creator, policy_cls, local_policy_config,
            num_envs=config.get("num_envs_per_worker", 1),
            rollout_fragment_length=config.get("rollout_fragment_length", 100),
            worker_index=0,
            seed=config.get("seed"),
            observation_filter=config.get("observation_filter", "NoFilter"),
            env_config=config.get("env_config"),
            horizon=config.get("horizon"),
            pack_fragments=config.get("pack_fragments", False))
        self.remote_workers: List = []
        self._broadcaster = None  # weight-sync delta plane (lazy)
        self._remote_cls = None
        # Monotonic worker index: fleet joins/replacements always get a
        # FRESH index (never reuse a dead worker's), so per-actor
        # ledgers and recovery histories stay attributable.
        self._next_index = num_workers + 1
        if num_workers > 0:
            self._remote_cls = ray_tpu.remote(RolloutWorker)
            for i in range(num_workers):
                self.remote_workers.append(self._make_remote_worker(i + 1))
            # Block until all workers are constructed.
            ray_tpu.get([w.ping.remote() for w in self.remote_workers])

    @staticmethod
    def _resolve_mapping_fn(config: dict) -> None:
        for holder in (config, config.get("policy_config") or {}):
            ma = holder.get("multiagent") or {}
            mfn = ma.get("policy_mapping_fn")
            if isinstance(mfn, str):
                from ..utils.registry import resolve_policy_mapping_fn
                pids = sorted(ma.get("policies")
                              or {"default_policy": None})
                ma = dict(ma)
                ma["policy_mapping_fn"] = resolve_policy_mapping_fn(
                    mfn, pids)
                holder["multiagent"] = ma

    def _make_remote_worker(self, index: int):
        cfg = self._config
        # Rollout policies never touch the TPU: the chip stays with the
        # learner process (SURVEY.md §5.8 TPU-native equivalent) because
        # these actors claim CPUs only (head._spawn_worker_locked).
        policy_config = dict(cfg.get("policy_config") or cfg)
        policy_config.pop("_mesh", None)
        return self._remote_cls.options(
            num_cpus=cfg.get("num_cpus_per_worker", 1)).remote(
                self._env_creator, self._policy_cls, policy_config,
                num_envs=cfg.get("num_envs_per_worker", 1),
                rollout_fragment_length=cfg.get(
                    "rollout_fragment_length", 100),
                worker_index=index,
                seed=cfg.get("seed"),
                observation_filter=cfg.get("observation_filter", "NoFilter"),
                env_config=cfg.get("env_config"),
                horizon=cfg.get("horizon"),
                pack_fragments=cfg.get("pack_fragments", False))

    # ------------------------------------------------------------------
    def sync_weights(self):
        """Broadcast local policy weights to all remote workers through
        the weight-sync delta plane (one encode + put per call; each
        worker gets the q8 delta against the version it holds, or the
        full blob when its base is stale/missing)."""
        if not self.remote_workers:
            return
        if self._broadcaster is None:
            from ..utils.weight_broadcast import WeightBroadcaster
            policy_config = dict(
                self._config.get("policy_config") or self._config)
            self._broadcaster = WeightBroadcaster(
                self.local_worker.get_weights,
                codec=policy_config.get("weight_sync_codec", "auto"))
        self._broadcaster.sync_all_blocking(self.remote_workers)

    def sync_filters(self):
        """Merge remote MeanStdFilter deltas into the local filter and
        push the result back (parity: `FilterManager.synchronize`,
        `rllib/utils/filter_manager.py:14`)."""
        from ..utils.filter import FilterManager, NoFilter
        if not self.remote_workers or isinstance(
                self.local_worker.obs_filter, NoFilter):
            return
        FilterManager.synchronize(
            self.local_worker.obs_filter, self.remote_workers,
            get_ref=lambda w: w.get_filters.remote(flush_after=True),
            sync_call=lambda w, f: w.sync_filters.remote(f))

    def add_worker(self):
        """Grow the fleet by one remote worker at a fresh index (fleet
        controller join path). Blocks until the actor is constructed."""
        if self._remote_cls is None:
            self._remote_cls = ray_tpu.remote(RolloutWorker)
        w = self._make_remote_worker(self._next_index)
        self._next_index += 1
        ray_tpu.get(w.ping.remote())
        self.remote_workers.append(w)
        return w

    def remove_worker(self, worker):
        """Retire one remote worker: drop it from the set, prune its
        weight-sync version entry, and kill the actor (fleet controller
        shrink/evict path)."""
        try:
            self.remote_workers.remove(worker)
        except ValueError:
            pass
        if self._broadcaster is not None:
            self._broadcaster.remove_worker(worker)
        try:
            ray_tpu.kill(worker)
        except Exception:
            pass

    def recreate_failed_worker(self, worker):
        """Replace a dead remote worker (reference: `ignore_worker_failures`
        path in `trainer.py:425`)."""
        idx = self.remote_workers.index(worker)
        new = self._make_remote_worker(idx + 1)
        ray_tpu.get(new.ping.remote())
        self.remote_workers[idx] = new
        if self._broadcaster is not None:
            # The replacement holds no delta base: next sync full-blobs.
            # Full removal (not just forget) also drops the dead
            # handle's pending acks.
            self._broadcaster.remove_worker(worker)
        return new

    def stop(self):
        for w in self.remote_workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.local_worker.stop()
