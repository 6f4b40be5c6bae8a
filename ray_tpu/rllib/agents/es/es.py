"""Evolution Strategies (Salimans et al. 2017) + ARS.

Parity: `rllib/agents/es/es.py` + `rllib/agents/ars/ars.py` — population
perturbation search: N worker actors evaluate antithetic parameter
perturbations; the trainer aggregates centered-rank-weighted noise into
a gradient estimate. Embarrassingly parallel — a natural fit for this
runtime's actor fan-out.

TPU re-architecture notes: evaluation rollouts are pure CPU inference
(workers run JAX-CPU); the shared noise table is regenerated from a seed
inside every worker instead of shipping hundreds of MB through the
object store (same trick as the reference's `SharedNoiseTable`, which
shares one block via plasma — regeneration costs one RNG pass and zero
transfer). Parameters travel as one flat float32 vector.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

import ray_tpu

from ....tune.trainable import Trainable
from ...env.registry import make_env
from ...utils.config import deep_merge
from ..trainer import COMMON_CONFIG
from ...utils.filter import MeanStdFilter, NoFilter

DEFAULT_CONFIG = deep_merge(deep_merge({}, COMMON_CONFIG), {
    "num_workers": 2,
    "episodes_per_batch": 20,
    "train_batch_size": 2000,     # min timesteps per iteration
    "noise_stdev": 0.02,
    "stepsize": 0.01,
    "l2_coeff": 0.005,
    "noise_table_size": 5_000_000,
    "noise_seed": 12345,
    "observation_filter": "MeanStdFilter",
    "report_length": 10,
    # ARS mode: keep only the top fraction of directions.
    "top_directions_frac": 1.0,
    "model": {"fcnet_hiddens": [64, 64]},
})

ARS_DEFAULT_CONFIG = deep_merge(deep_merge({}, DEFAULT_CONFIG), {
    # ARS (Mania et al. 2018; reference agents/ars/ars.py): fewer,
    # elite directions and reward normalization by their std.
    "noise_stdev": 0.025,
    "stepsize": 0.02,
    "episodes_per_batch": 16,
    "top_directions_frac": 0.5,
    "l2_coeff": 0.0,
})


def make_noise_table(seed: int, size: int) -> np.ndarray:
    return np.random.RandomState(seed).randn(size).astype(np.float32)


def centered_ranks(x: np.ndarray) -> np.ndarray:
    """Rank-transform to [-0.5, 0.5] (reference es.py compute_centered_ranks)."""
    flat = x.ravel()
    ranks = np.empty(len(flat), dtype=np.float32)
    ranks[flat.argsort()] = np.arange(len(flat), dtype=np.float32)
    ranks = ranks.reshape(x.shape)
    return ranks / (x.size - 1) - 0.5


class _FlatPolicy:
    """Deterministic flat-vector policy over the catalog model."""

    def __init__(self, obs_space, action_space, config):
        import jax
        import jax.numpy as jnp
        from jax.flatten_util import ravel_pytree
        from ....models import catalog
        from ....models.distributions import get_action_dist

        self.dist_class, dist_dim = get_action_dist(action_space)
        self.preprocessor = catalog.get_preprocessor(obs_space)
        self.model = catalog.get_model(obs_space, dist_dim,
                                       config.get("model"))
        dummy = np.zeros((1,) + tuple(self.preprocessor.shape),
                         self.preprocessor.dtype)
        params = self.model.init(jax.random.PRNGKey(0), dummy)
        flat, self._unravel = ravel_pytree(params)
        self.num_params = int(flat.shape[0])
        self.flat = np.asarray(flat, np.float32)

        def act(flat_params, obs):
            p = self._unravel(flat_params)
            dist_inputs, _ = self.model.apply(p, obs)
            return self.dist_class(dist_inputs).deterministic_sample()

        self._act = jax.jit(act)

    def set_flat(self, flat: np.ndarray):
        self.flat = np.asarray(flat, np.float32)

    def compute_action(self, obs):
        return np.asarray(self._act(self.flat, obs[None]))[0]


class ESWorker:
    """Evaluates antithetic perturbations (runs as a remote actor)."""

    def __init__(self, env_name, env_config, config, seed):
        self.config = config
        self.env = make_env(env_name, dict(env_config or {}))
        self.policy = _FlatPolicy(self.env.observation_space,
                                  self.env.action_space, config)
        self.noise = make_noise_table(config["noise_seed"],
                                      config["noise_table_size"])
        self._rng = np.random.RandomState(seed)
        if config.get("observation_filter") == "MeanStdFilter":
            self.obs_filter = MeanStdFilter(self.policy.preprocessor.shape)
        else:
            self.obs_filter = NoFilter()

    def _rollout(self) -> Tuple[float, int]:
        obs = self.env.reset()
        total, steps = 0.0, 0
        done = False
        horizon = self.config.get("horizon") or 1000
        while not done and steps < horizon:
            obs_p = self.policy.preprocessor.transform(obs)
            obs_f = self.obs_filter(obs_p)
            action = self.policy.compute_action(obs_f)
            obs, r, done, _ = self.env.step(action)
            total += float(r)
            steps += 1
        return total, steps

    def do_rollouts(self, flat_params, num_pairs: int):
        """num_pairs antithetic evaluations -> (indices, returns+-, lens)."""
        flat = np.asarray(flat_params, np.float32)
        sigma = self.config["noise_stdev"]
        dim = self.policy.num_params
        indices: List[int] = []
        returns: List[Tuple[float, float]] = []
        lengths = 0
        for _ in range(num_pairs):
            idx = int(self._rng.randint(
                0, len(self.noise) - dim + 1))
            eps = self.noise[idx:idx + dim]
            self.policy.set_flat(flat + sigma * eps)
            r_pos, n1 = self._rollout()
            self.policy.set_flat(flat - sigma * eps)
            r_neg, n2 = self._rollout()
            indices.append(idx)
            returns.append((r_pos, r_neg))
            lengths += n1 + n2
        # Ship this round's filter deltas and flush them (reference:
        # get_filters(flush_after=True)).
        snapshot = self.obs_filter.as_serializable()
        self.obs_filter.clear_buffer()
        return indices, returns, lengths, snapshot

    def evaluate(self, flat_params, episodes: int):
        self.policy.set_flat(np.asarray(flat_params, np.float32))
        rewards = [self._rollout()[0] for _ in range(episodes)]
        return rewards

    def sync_filter(self, f):
        self.obs_filter.sync(f)
        self.obs_filter.clear_buffer()

    def ping(self):
        return "ok"


class ESTrainer(Trainable):
    """Parity: `rllib/agents/es/es.py` ESTrainer."""

    _name = "ES"
    _default_config = DEFAULT_CONFIG

    def _setup(self, config):
        self.config = deep_merge(deep_merge({}, self._default_config),
                                 config)
        env_name = self.config["env"]
        env = make_env(env_name, self.config.get("env_config"))
        self.policy = _FlatPolicy(env.observation_space, env.action_space,
                                  self.config)
        self.noise = make_noise_table(self.config["noise_seed"],
                                      self.config["noise_table_size"])
        if self.config.get("observation_filter") == "MeanStdFilter":
            self.obs_filter = MeanStdFilter(self.policy.preprocessor.shape)
        else:
            self.obs_filter = NoFilter()
        self._remote_cls = ray_tpu.remote(ESWorker)
        self._workers = [
            self._remote_cls.remote(
                env_name, self.config.get("env_config"), self.config,
                seed=(self.config.get("seed") or 0) + i + 1)
            for i in range(max(1, self.config["num_workers"]))]
        ray_tpu.get([w.ping.remote() for w in self._workers])
        # Flat-vector Adam (reference es/optimizers.py Adam).
        self._adam_m = np.zeros(self.policy.num_params, np.float32)
        self._adam_v = np.zeros(self.policy.num_params, np.float32)
        self._adam_t = 0
        self._episodes_total = 0
        self._timesteps_total = 0
        self._reward_history: List[float] = []

    def _train(self):
        cfg = self.config
        num_pairs_total = max(1, cfg["episodes_per_batch"] // 2)
        per_worker = max(1, num_pairs_total // len(self._workers))
        flat_ref = ray_tpu.put(self.policy.flat)

        indices: List[int] = []
        pos: List[float] = []
        neg: List[float] = []
        steps = 0
        while steps < cfg["train_batch_size"]:
            results = ray_tpu.get([
                w.do_rollouts.remote(flat_ref, per_worker)
                for w in self._workers])
            for idx_list, rets, length, filt in results:
                indices.extend(idx_list)
                for rp, rn in rets:
                    pos.append(rp)
                    neg.append(rn)
                steps += length
                # Merge the worker's buffered deltas (not replace).
                self.obs_filter.apply_changes(filt)
        # Push the merged filter back (reference FilterManager behavior).
        merged = self.obs_filter.as_serializable()
        ray_tpu.get([w.sync_filter.remote(merged) for w in self._workers])

        pos_a, neg_a = np.asarray(pos), np.asarray(neg)
        all_returns = np.concatenate([pos_a, neg_a])
        dim = self.policy.num_params
        sigma = cfg["noise_stdev"]

        # ARS elite-direction selection (top_directions_frac < 1).
        frac = cfg.get("top_directions_frac", 1.0)
        keep = np.arange(len(indices))
        if frac < 1.0:
            k = max(1, int(len(indices) * frac))
            score = np.maximum(pos_a, neg_a)
            keep = np.argsort(-score)[:k]

        if frac < 1.0:
            # ARS: raw reward differences normalized by elite-reward std.
            used = np.concatenate([pos_a[keep], neg_a[keep]])
            denom = max(1e-6, float(used.std()))
            weights = (pos_a[keep] - neg_a[keep]) / denom
        else:
            ranked = centered_ranks(np.stack([pos_a, neg_a], axis=1))
            weights = ranked[:, 0] - ranked[:, 1]

        grad = np.zeros(dim, np.float32)
        for w_i, j in zip(weights, keep):
            grad += w_i * self.noise[indices[j]:indices[j] + dim]
        grad /= (len(keep) * sigma)
        grad -= cfg["l2_coeff"] * self.policy.flat

        # Adam ascent step on the flat vector.
        self._adam_t += 1
        b1, b2, eps = 0.9, 0.999, 1e-8
        self._adam_m = b1 * self._adam_m + (1 - b1) * grad
        self._adam_v = b2 * self._adam_v + (1 - b2) * grad ** 2
        mhat = self._adam_m / (1 - b1 ** self._adam_t)
        vhat = self._adam_v / (1 - b2 ** self._adam_t)
        self.policy.set_flat(
            self.policy.flat
            + cfg["stepsize"] * mhat / (np.sqrt(vhat) + eps))

        self._episodes_total += len(all_returns)
        self._timesteps_total += steps
        mean_r = float(all_returns.mean())
        self._reward_history.append(mean_r)
        window = self._reward_history[-cfg["report_length"]:]
        return {
            "episode_reward_mean": float(np.mean(window)),
            "episode_reward_max": float(all_returns.max()),
            "episode_reward_min": float(all_returns.min()),
            "episodes_this_iter": len(all_returns),
            "timesteps_this_iter": steps,
            "timesteps_total": self._timesteps_total,
            "info": {"grad_norm": float(np.linalg.norm(grad)),
                     "update_ratio": float(
                         np.linalg.norm(grad) /
                         max(1e-9, np.linalg.norm(self.policy.flat)))},
        }

    def compute_action(self, obs, state=None, explore=False):
        obs_p = self.policy.preprocessor.transform(obs)
        return self.policy.compute_action(self.obs_filter(
            obs_p, update=False))

    def _save(self, checkpoint_dir):
        import os
        import pickle
        path = os.path.join(checkpoint_dir, "checkpoint.pkl")
        with open(path, "wb") as f:
            pickle.dump({"flat": self.policy.flat,
                         "filter": self.obs_filter.as_serializable(),
                         "adam": (self._adam_m, self._adam_v,
                                  self._adam_t)}, f)
        return path

    def _restore(self, checkpoint_path):
        import pickle
        with open(checkpoint_path, "rb") as f:
            state = pickle.load(f)
        self.policy.set_flat(state["flat"])
        self.obs_filter.sync(state["filter"])
        self._adam_m, self._adam_v, self._adam_t = state["adam"]

    def _stop(self):
        for w in getattr(self, "_workers", []):
            try:
                ray_tpu.kill(w)
            except Exception:
                pass


class ARSTrainer(ESTrainer):
    """Parity: `rllib/agents/ars/ars.py` — ES with elite directions."""

    _name = "ARS"
    _default_config = ARS_DEFAULT_CONFIG
