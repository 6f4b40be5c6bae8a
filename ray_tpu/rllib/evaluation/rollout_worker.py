"""RolloutWorker: the sampling unit.

Parity: `rllib/evaluation/rollout_worker.py:55` — builds env + policy +
sampler; `sample` (:463), `learn_on_batch` (:595),
`compute_gradients`/`apply_gradients` (:542/:574), `get/set_weights`
(:528/:537). Created locally on the trainer and as remote actors for
parallel sampling (`WorkerSet`). Remote rollout workers run JAX on CPU —
TPU chips belong to the learner (Podracer/Sebulba split, SURVEY.md §7.1).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as np

from .. import sample_batch as sb
from ..env.registry import make_env
from ..env.vector_env import VectorEnv
from ..sample_batch import SampleBatch
from ..utils.filter import get_filter
from .postprocessing import compute_advantages
from .sampler import SyncSampler


class RolloutWorker:
    def __init__(self,
                 env_creator: Callable,
                 policy_cls,
                 policy_config: dict,
                 num_envs: int = 1,
                 rollout_fragment_length: int = 100,
                 worker_index: int = 0,
                 seed: Optional[int] = None,
                 observation_filter: str = "NoFilter",
                 explore: bool = True,
                 env_config: Optional[dict] = None,
                 horizon: Optional[int] = None,
                 pack_fragments: bool = False):
        self.worker_index = worker_index
        # Receiver side of the weight-sync delta plane (lazily built on
        # the first versioned payload).
        self._weight_decoder = None
        # Compression only pays where batches cross a process boundary
        # (remote worker -> learner); the local worker's batches are
        # consumed in-process.
        self._compress_observations = bool(
            policy_config.get("compress_observations")) and worker_index > 0
        env_config = dict(env_config or {})
        env_config["worker_index"] = worker_index
        # Offline I/O (parity: `rollout_worker.py` IOContext wiring).
        self._init_offline_io(policy_config)
        multiagent = (policy_config.get("multiagent") or {}).get("policies")
        if multiagent:
            if policy_config.get("remote_worker_envs"):
                raise NotImplementedError(
                    "remote_worker_envs is not supported with a policy "
                    "map yet (the multi-agent sampler builds in-process "
                    "envs)")
            self._init_multiagent(
                env_creator, policy_cls, policy_config, num_envs,
                rollout_fragment_length, seed, explore, env_config,
                horizon)
            return
        self.policy_map = None
        if policy_config.get("remote_worker_envs"):
            # Env-per-actor stepping (reference: RemoteVectorEnv).
            from ..env.remote_vector_env import RemoteVectorEnv
            self.env = RemoteVectorEnv(
                env_creator, num_envs, env_config)
        else:
            self.env = VectorEnv(lambda: env_creator(env_config), num_envs)
        if seed is not None:
            self.env.seed(seed + worker_index * 1000)
            np.random.seed(seed + worker_index * 1000)
        cfg = dict(policy_config)
        if seed is not None:
            cfg["seed"] = seed + worker_index
        self.policy = policy_cls(
            self.env.observation_space, self.env.action_space, cfg)
        # Filter shapes follow the preprocessed obs (Discrete -> one-hot);
        # policies without a preprocessor (e.g. RandomPolicy) filter raw obs.
        self.preprocessor = getattr(self.policy, "preprocessor", None)
        self.obs_filter = get_filter(
            observation_filter,
            self.preprocessor.shape if self.preprocessor is not None
            else self.env.observation_space.shape)

        gamma = cfg.get("gamma", 0.99)
        lambda_ = cfg.get("lambda", 1.0)
        use_gae = cfg.get("use_gae", True)
        use_critic = cfg.get("use_critic", True)

        def postprocess(chunk: SampleBatch, bootstrap_obs,
                        bootstrap_state=None):
            if bootstrap_obs is None or not use_gae:
                last_r = 0.0
            elif getattr(self.policy, "recurrent", False):
                # Bootstrap value is state-dependent: evaluate at the
                # RNN state reached after the fragment's last step.
                last_r = float(self.policy.value_function(
                    bootstrap_obs[None], state=bootstrap_state)[0])
            else:
                last_r = float(self.policy.value_function(
                    bootstrap_obs[None])[0])
            if sb.VF_PREDS in chunk or use_gae:
                chunk = compute_advantages(
                    chunk, last_r, gamma=gamma, lambda_=lambda_,
                    use_gae=use_gae and sb.VF_PREDS in chunk,
                    use_critic=use_critic)
            chunk = self.policy.postprocess_trajectory(chunk)
            if getattr(self.policy, "recurrent", False):
                from ..policy.rnn_sequencing import pad_chunk_to_sequences
                chunk = pad_chunk_to_sequences(
                    chunk, self.policy.train_seq_len)
            return chunk

        # sample_async runs the env loop on a background thread
        # (parity: `sampler.py:121` AsyncSampler, A3C's default).
        sampler_cls = SyncSampler
        if policy_config.get("sample_async"):
            from .async_sampler import AsyncSampler
            sampler_cls = AsyncSampler
        self.sampler = sampler_cls(
            self.env, self.policy, rollout_fragment_length,
            # Packed fragments (IMPALA/V-trace) compute targets on the
            # learner; GAE postprocessing only applies to episode chunks.
            postprocess_fn=None if pack_fragments else postprocess,
            obs_filter=self.obs_filter if observation_filter != "NoFilter"
            else None,
            explore=explore,
            horizon=horizon,
            preprocessor=self.preprocessor,
            pack_fragments=pack_fragments)

    def _init_multiagent(self, env_creator, default_policy_cls,
                         policy_config, num_envs,
                         rollout_fragment_length, seed, explore,
                         env_config, horizon):
        """Policy-map worker (parity: `rollout_worker.py:114` — the ctor
        builds one policy per spec in `multiagent.policies` and a
        mapping fn routes agent ids to policies)."""
        from ..utils.config import deep_merge
        from .multi_agent_sampler import MultiAgentSyncSampler
        if policy_config.get("observation_filter",
                             "NoFilter") != "NoFilter":
            raise NotImplementedError(
                "observation_filter is not supported with a policy map "
                "yet; use NoFilter")
        ma_cfg = policy_config["multiagent"]
        probe_env = env_creator(dict(env_config))
        self.policy_map = {}
        for idx, (pid, spec) in enumerate(ma_cfg["policies"].items()):
            cls, obs_space, act_space, overrides = spec
            cls = cls or default_policy_cls
            obs_space = obs_space if obs_space is not None \
                else probe_env.observation_space
            act_space = act_space if act_space is not None \
                else probe_env.action_space
            cfg = deep_merge(deep_merge({}, policy_config),
                             overrides or {})
            cfg.pop("multiagent", None)
            if seed is not None:
                # Offset per policy so same-spec policies initialize
                # independently rather than as identical twins.
                cfg["seed"] = seed + self.worker_index + idx * 10007
            self.policy_map[pid] = cls(obs_space, act_space, cfg)
        probe_env.close()
        self.policy = self.policy_map.get(
            "default_policy", next(iter(self.policy_map.values())))
        self.preprocessor = None
        self.obs_filter = get_filter("NoFilter", ())
        self.env = None
        mapping = ma_cfg.get("policy_mapping_fn") \
            or (lambda aid: next(iter(self.policy_map)))
        if isinstance(mapping, str):
            # yaml configs name a registered mapping fn (parity with the
            # reference's registry lookups); config text is never eval'd.
            from ..utils.registry import resolve_policy_mapping_fn
            mapping = resolve_policy_mapping_fn(
                mapping, sorted(self.policy_map))

        def postprocess(pid, chunk, bootstrap_obs):
            # Read GAE knobs from the policy's own merged config so
            # per-policy overrides in `multiagent.policies` apply.
            policy = self.policy_map[pid]
            pcfg = policy.config
            use_gae = pcfg.get("use_gae", True)
            if bootstrap_obs is None or not use_gae:
                last_r = 0.0
            else:
                last_r = float(policy.value_function(
                    bootstrap_obs[None])[0])
            if sb.VF_PREDS in chunk or use_gae:
                chunk = compute_advantages(
                    chunk, last_r, gamma=pcfg.get("gamma", 0.99),
                    lambda_=pcfg.get("lambda", 1.0),
                    use_gae=use_gae and sb.VF_PREDS in chunk,
                    use_critic=pcfg.get("use_critic", True))
            return policy.postprocess_trajectory(chunk)

        self.sampler = MultiAgentSyncSampler(
            env_creator, self.policy_map, mapping,
            rollout_fragment_length, num_envs=num_envs,
            postprocess_fn=postprocess, explore=explore,
            horizon=horizon, env_config=env_config, seed=seed)

    def _init_offline_io(self, policy_config: dict):
        self._input_reader = None
        self._output_writer = None
        inp = policy_config.get("input", "sampler")
        if inp != "sampler":
            from ..offline import JsonReader
            self._input_reader = JsonReader(inp)
        out = policy_config.get("output")
        if out:
            from ..offline import JsonWriter
            self._output_writer = JsonWriter(out)

    # -- sampling --------------------------------------------------------
    def sample(self) -> SampleBatch:
        if self._input_reader is not None:
            return self._input_reader.next()
        batch = self.sampler.sample()
        if self._output_writer is not None:
            self._output_writer.write(batch)
        if self._compress_observations:
            from ..utils.compression import compress_batch
            compress_batch(batch)
        return batch

    def sample_with_count(self):
        batch = self.sample()
        return batch, batch.count

    # -- learning (used when the worker doubles as a learner) ------------
    def learn_on_batch(self, batch) -> Dict:
        from ..sample_batch import MultiAgentBatch
        if isinstance(batch, MultiAgentBatch):
            return {pid: self.policy_map[pid].learn_on_batch(b)
                    for pid, b in batch.policy_batches.items()}
        return self.policy.learn_on_batch(batch)

    def compute_gradients(self, batch):
        return self.policy.compute_gradients(batch)

    def sample_and_compute_grads(self):
        """One fragment + its gradients (A3C's per-worker unit of work;
        parity: `a3c.py` sample-then-grad remote call chain)."""
        batch = self.sample()
        grads, stats = self.policy.compute_gradients(batch)
        return grads, stats, batch.count

    def apply_gradients(self, grads):
        return self.policy.apply_gradients(grads)

    # -- weights ---------------------------------------------------------
    def get_weights(self):
        if self.policy_map is not None:
            return {pid: p.get_weights()
                    for pid, p in self.policy_map.items()}
        return self.policy.get_weights()

    def set_weights(self, weights):
        """Apply a weight sync: either a raw weights pytree (legacy
        path) or a versioned `WeightSyncPayload` from the delta plane.
        Returns a status dict the sender's handshake reads — a "stale"
        status (delta against a base this worker doesn't hold) leaves
        the current weights untouched and makes the sender fall back to
        a full payload."""
        import time as _time

        from ray_tpu._private import metrics
        from ray_tpu._private.weight_sync import WeightSyncPayload
        if isinstance(weights, WeightSyncPayload):
            if self._weight_decoder is None:
                from ray_tpu._private.weight_sync import WeightSyncDecoder
                self._weight_decoder = WeightSyncDecoder()
            t0 = _time.perf_counter()
            decoded, status = self._weight_decoder.apply(weights)
            metrics.set_gauge("weight_apply_ms",
                              1e3 * (_time.perf_counter() - t0))
            if status == "stale":
                metrics.inc("weight_sync_stale_received")
            if decoded is None:
                return {"status": status,
                        "version": self._weight_decoder.version}
            weights = decoded
        elif self._weight_decoder is not None:
            # A raw-dict sync outside the versioned stream invalidates
            # the delta base (checkpoint restore, manual set_weights).
            self._weight_decoder.reset()
        if self.policy_map is not None:
            for pid, w in weights.items():
                self.policy_map[pid].set_weights(w)
        else:
            self.policy.set_weights(weights)
        version = (self._weight_decoder.version
                   if self._weight_decoder is not None else 0)
        return {"status": "ok", "version": version}

    def weight_sync_version(self) -> int:
        """The sync version this worker's decoder holds (0 = no base).
        The fleet controller's join path asks for it so a warm rejoin
        can be routed a delta instead of the full blob
        (`WeightBroadcaster.bootstrap`)."""
        return (self._weight_decoder.version
                if self._weight_decoder is not None else 0)

    # -- filters (parity: FilterManager.synchronize) ---------------------
    def get_filters(self, flush_after: bool = False):
        f = self.obs_filter.as_serializable()
        if flush_after:
            self.obs_filter.clear_buffer()
        return f

    def sync_filters(self, new_filter):
        self.obs_filter.sync(new_filter)

    def apply(self, fn, *args):
        """Run fn(self, *args) — generic hook used by trainers to reach
        into remote workers (parity: `rollout_worker.py apply`)."""
        return fn(self, *args)

    def foreach_policy(self, fn):
        """fn(policy, policy_id) over all policies (reference signature,
        `rollout_worker.py foreach_policy`)."""
        if self.policy_map is not None:
            return [fn(p, pid) for pid, p in self.policy_map.items()]
        return [fn(self.policy, "default_policy")]

    def get_policy(self, policy_id: str = "default_policy"):
        if self.policy_map is not None:
            return self.policy_map[policy_id]
        return self.policy

    # -- metrics / introspection -----------------------------------------
    def get_metrics(self) -> List:
        return self.sampler.get_metrics()

    def get_policy_state(self):
        if self.policy_map is not None:
            return {pid: p.get_state()
                    for pid, p in self.policy_map.items()}
        return self.policy.get_state()

    def set_policy_state(self, state):
        if self.policy_map is not None:
            for pid, s in state.items():
                self.policy_map[pid].set_state(s)
            return
        self.policy.set_state(state)

    def ping(self):
        return "ok"

    def stop(self):
        if hasattr(self.sampler, "stop"):
            self.sampler.stop()
        if self.env is not None:
            self.env.close()
        elif self.policy_map is not None:
            for e in self.sampler.envs:
                e.close()

