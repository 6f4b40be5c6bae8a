"""DQN-family policy: Q-network, target network, epsilon-greedy.

Parity: `rllib/agents/dqn/dqn_policy.py` (QLoss, build_q_models, epsilon-
greedy exploration, `postprocess_nstep_and_prio`) + `simple_q_policy.py`.

TPU re-architecture: the whole update — online forward, target forward,
double-Q argmax, huber TD loss, optax step — is ONE donated-buffer jitted
program; the target network lives in `loss_state` so swapping it never
retraces. Epsilon-greedy sampling is jitted alongside the Q forward, so
rollout inference stays a single device program per env step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ....models.networks import QNetwork
from ... import sample_batch as sb
from ...sample_batch import SampleBatch
from ...policy.jax_policy import JaxPolicy
from ...utils.config import deep_merge

PRIO_WEIGHTS = "weights"


def huber_loss(x, delta: float = 1.0):
    """Reference: `rllib/utils/error.py` huber_loss."""
    return jnp.where(
        jnp.abs(x) < delta,
        0.5 * x ** 2,
        delta * (jnp.abs(x) - 0.5 * delta))


def dqn_loss(policy, params, batch, rng, loss_state):
    cfg = policy.config
    n = policy.num_actions
    q_t, _ = policy.apply(params, batch[sb.OBS])
    one_hot = jax.nn.one_hot(batch[sb.ACTIONS].astype(jnp.int32), n)
    q_t_selected = jnp.sum(q_t * one_hot, axis=-1)

    q_tp1_target, _ = policy.apply(loss_state["target"], batch[sb.NEW_OBS])
    if cfg["double_q"]:
        q_tp1_online, _ = policy.apply(params, batch[sb.NEW_OBS])
        best = jnp.argmax(q_tp1_online, axis=-1)
    else:
        best = jnp.argmax(q_tp1_target, axis=-1)
    q_tp1_best = jnp.sum(
        q_tp1_target * jax.nn.one_hot(best, n), axis=-1)

    not_done = 1.0 - batch[sb.DONES]
    # n-step postprocessing already folded gamma^k into rewards, so the
    # bootstrap term is discounted by gamma^n_step.
    gamma_n = cfg["gamma"] ** cfg["n_step"]
    target = batch[sb.REWARDS] + gamma_n * q_tp1_best * not_done
    td_error = q_t_selected - jax.lax.stop_gradient(target)

    is_weights = batch.get(PRIO_WEIGHTS)
    if is_weights is None:
        is_weights = jnp.ones_like(td_error)
    loss = jnp.mean(is_weights * huber_loss(td_error))
    stats = {
        "loss": loss,
        "mean_q": jnp.mean(q_t_selected),
        "min_q": jnp.min(q_t),
        "max_q": jnp.max(q_t),
        "mean_td_error": jnp.mean(td_error),
        "td_error": td_error,  # vector; popped before scalar reporting
    }
    return loss, stats


def adjust_nstep(n_step: int, gamma: float, batch: SampleBatch) -> None:
    """Fold the next n-1 rewards into each row (in place, vectorized).

    Parity: `dqn_policy.py` `_adjust_nstep` — rewards[i] +=
    sum_j gamma^j * rewards[i+j]; new_obs/dones shift to row i+n-1
    (truncated at the fragment end, matching the reference).
    """
    if n_step == 1:
        return
    dones = np.asarray(batch[sb.DONES])
    if dones[:-1].any():
        raise ValueError("unexpected done in the middle of a trajectory "
                         "fragment passed to n-step adjustment")
    L = batch.count
    idx = np.minimum(np.arange(L) + n_step - 1, L - 1)
    batch[sb.NEW_OBS] = np.asarray(batch[sb.NEW_OBS])[idx]
    batch[sb.DONES] = dones[idx]
    rewards = np.asarray(batch[sb.REWARDS], dtype=np.float32)
    padded = np.concatenate([rewards, np.zeros(n_step - 1, np.float32)])
    windows = np.lib.stride_tricks.sliding_window_view(padded, n_step)
    disc = (gamma ** np.arange(n_step)).astype(np.float32)
    batch[sb.REWARDS] = windows @ disc


def postprocess_nstep_and_prio(policy, batch, other_agent_batches=None,
                               episode=None):
    """Parity: `dqn_policy.py postprocess_nstep_and_prio` — n-step reward
    folding plus (optionally) worker-side TD errors so APEX replay shards
    can set initial priorities without a learner round-trip."""
    adjust_nstep(policy.config["n_step"], policy.config["gamma"], batch)
    if policy.config.get("worker_side_prioritization"):
        batch["td_error"] = policy.compute_td_error(batch)
    return batch


DQN_POLICY_DEFAULTS = {
    "double_q": True,
    "dueling": True,
    "hiddens": [256],
    "n_step": 1,
    "gamma": 0.99,
    "lr": 5e-4,
    "adam_epsilon": 1e-8,
    "grad_clip": 40.0,
    "use_gae": False,  # no advantage postprocessing for Q-learning
    "worker_side_prioritization": False,
}


class DQNPolicy(JaxPolicy):
    """Q-learning policy. dist_inputs are the Q-values; exploration is
    epsilon-greedy with a host-controlled epsilon scalar."""

    def __init__(self, observation_space, action_space, config):
        cfg = deep_merge(deep_merge({}, DQN_POLICY_DEFAULTS), config)
        if not hasattr(action_space, "n"):
            raise ValueError("DQN requires a Discrete action space")
        self.num_actions = action_space.n

        def make_model(obs_space, act_space, model_cfg):
            mcfg = model_cfg.get("model") or {}
            # Reference layering: the catalog model (fcnet_hiddens) feeds
            # the Q-head stack (`hiddens`) — honored here as trunk sizes
            # fcnet_hiddens ++ hiddens (conv trunk replaces fcnet for
            # image obs).
            trunk = tuple(mcfg.get("fcnet_hiddens") or ()) \
                if len(obs_space.shape) < 3 else ()
            return QNetwork(
                num_actions=act_space.n,
                hiddens=trunk + tuple(cfg["hiddens"]),
                activation=mcfg.get("fcnet_activation", "relu"),
                dueling=cfg["dueling"],
                conv_filters=tuple(
                    tuple(f) for f in
                    (mcfg.get("conv_filters")
                     or ((32, 8, 4), (64, 4, 2), (64, 3, 1)))))

        super().__init__(observation_space, action_space, cfg,
                         loss_fn=dqn_loss,
                         make_model=make_model,
                         postprocess_fn=postprocess_nstep_and_prio)
        self.cur_epsilon = 1.0
        # Device-side copy so later donated updates can't invalidate it.
        self._tree_copy = jax.jit(
            lambda p: jax.tree.map(jnp.copy, p))
        self.loss_state["target"] = self._tree_copy(self.params)

        def eps_action_fn(params, obs, rng, eps):
            q, value = self.apply(params, obs)
            greedy = jnp.argmax(q, axis=-1)
            k1, k2 = jax.random.split(rng)
            rand = jax.random.randint(k1, greedy.shape, 0, self.num_actions)
            take_rand = jax.random.uniform(k2, greedy.shape) < eps
            actions = jnp.where(take_rand, rand, greedy)
            return actions, q, value

        self._eps_action_fn = jax.jit(eps_action_fn)

        def td_fn(params, target_params, batch):
            q_t, _ = self.apply(params, batch[sb.OBS])
            one_hot = jax.nn.one_hot(
                batch[sb.ACTIONS].astype(jnp.int32), self.num_actions)
            q_sel = jnp.sum(q_t * one_hot, axis=-1)
            q_tp1, _ = self.apply(target_params, batch[sb.NEW_OBS])
            if cfg["double_q"]:
                # Match dqn_loss: online argmax, target gather — so Ape-X
                # worker-side initial priorities use the learner's TD
                # definition (reference computes them from the loss graph).
                q_tp1_online, _ = self.apply(params, batch[sb.NEW_OBS])
                best_idx = jnp.argmax(q_tp1_online, axis=-1)
                best = jnp.take_along_axis(
                    q_tp1, best_idx[:, None], axis=-1)[:, 0]
            else:
                best = jnp.max(q_tp1, axis=-1)
            gamma_n = self.config["gamma"] ** self.config["n_step"]
            target = batch[sb.REWARDS] + gamma_n * best \
                * (1.0 - batch[sb.DONES])
            return q_sel - target

        self._td_fn = jax.jit(td_fn)

    # -- exploration -----------------------------------------------------
    def set_epsilon(self, epsilon: float) -> None:
        self.cur_epsilon = float(epsilon)

    def compute_actions(self, obs_batch, state_batches=None, explore=True,
                        prev_action_batch=None, prev_reward_batch=None):
        obs = jnp.asarray(obs_batch)
        eps = self.cur_epsilon if explore else 0.0
        with self._update_lock:
            actions, q, value = self._eps_action_fn(
                self.params, obs, self._next_rng(), eps)
        return np.asarray(actions), [], {}

    # -- learning --------------------------------------------------------
    def learn_with_td(self, batch):
        """One update; returns (scalar stats, |td_error| per row) so the
        caller can refresh replay priorities."""
        dev_batch = self._device_batch(batch)
        with self._update_lock:
            self.params, self.opt_state, stats = self._train_fn(
                self.params, self.opt_state, dev_batch,
                self._next_rng(), self.loss_state)
        self.global_timestep += batch.count
        stats = dict(stats)
        td = np.asarray(stats.pop("td_error"))
        return {k: float(v) for k, v in stats.items()}, np.abs(td)

    def learn_on_batch(self, batch):
        stats, _ = self.learn_with_td(batch)
        return stats

    def compute_td_error(self, batch) -> np.ndarray:
        dev = {k: jnp.asarray(np.asarray(batch[k]).astype(np.float32)
                              if np.asarray(batch[k]).dtype
                              in (np.float64, np.bool_)
                              else np.asarray(batch[k]))
               for k in (sb.OBS, sb.ACTIONS, sb.REWARDS, sb.NEW_OBS,
                         sb.DONES)}
        with self._update_lock:
            td = self._td_fn(self.params, self.loss_state["target"], dev)
        return np.asarray(td)

    # -- target network --------------------------------------------------
    def update_target(self) -> None:
        """Copy online params into the target network (reference:
        `dqn_policy.py update_target`)."""
        with self._update_lock:
            self.loss_state["target"] = self._tree_copy(self.params)

    # -- weights ---------------------------------------------------------
    # Weights include BOTH networks: the reference's TFPolicy.get_weights
    # returns all graph variables incl. the target tower, so workers doing
    # worker-side prioritization score TD against a current target.
    def get_weights(self):
        with self._update_lock:
            return {"online": jax.tree.map(np.asarray, self.params),
                    "target": jax.tree.map(
                        np.asarray, self.loss_state["target"])}

    def set_weights(self, weights):
        from ....parallel import mesh as mesh_lib
        with self._update_lock:
            if isinstance(weights, dict) and "online" in weights:
                self.params = mesh_lib.put_replicated(
                    weights["online"], self.mesh)
                self.loss_state["target"] = mesh_lib.put_replicated(
                    weights["target"], self.mesh)
            else:  # bare online tree (e.g. cross-policy transfer)
                self.params = mesh_lib.put_replicated(weights, self.mesh)

    # -- checkpointing ---------------------------------------------------
    def get_state(self):
        # weights cover online+target; the scalar loss_state path must
        # not see the target pytree. Single lock hold (no nested
        # get_weights call — the lock is not reentrant).
        with self._update_lock:
            state = {
                "weights": {
                    "online": jax.tree.map(np.asarray, self.params),
                    "target": jax.tree.map(
                        np.asarray, self.loss_state["target"])},
                "opt_state": jax.tree.map(np.asarray, self.opt_state),
                "loss_state": {k: float(v)
                               for k, v in self.loss_state.items()
                               if k != "target"},
                "global_timestep": self.global_timestep,
            }
        state["cur_epsilon"] = self.cur_epsilon
        return state

    def set_state(self, state):
        self.cur_epsilon = state.pop("cur_epsilon", self.cur_epsilon)
        super().set_state(state)
