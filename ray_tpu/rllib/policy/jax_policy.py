"""JaxPolicy: the single policy stack (replaces the reference's dual
TFPolicy/TorchPolicy towers).

Parity: `rllib/policy/tf_policy.py` + `dynamic_tf_policy.py`, re-designed
for XLA:

- One flax model forward returns (dist_inputs, value); action sampling,
  log-probs and value predictions compile into ONE jitted program used by
  rollouts (`_action_fn`).
- `learn_on_batch` is one donated-buffer jitted update (loss → grad →
  optax), replacing feed-dict sess.run loss updates (`tf_policy.py:173`).
- `sgd_learn` compiles the ENTIRE PPO-style minibatch-SGD phase
  (num_sgd_iter epochs × minibatches, with on-device shuffling) into a
  single XLA program — the TPU-native replacement for
  `LocalSyncParallelOptimizer.optimize`'s per-minibatch feed_dict loop
  (`rllib/optimizers/multi_gpu_impl.py:225`).
- On a multi-device mesh, parameters are replicated and batches sharded on
  the "dp" axis; XLA inserts gradient all-reduces over ICI (the replacement
  for in-graph tower averaging, `multi_gpu_impl.py:310`): that psum is
  the one gradient exchange. `compute_dtype` runs the forward/backward in
  bf16 against fp32 master weights (parallel/precision.py).
"""

from __future__ import annotations

import functools
import itertools
import threading
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..._private.profiling import phase
from ...models import catalog
from ...models.distributions import get_action_dist
from ...parallel import mesh as mesh_lib
from ...parallel import precision
from .. import sample_batch as sb
from .policy import Policy

# Columns that the device-side loss consumes; everything else stays host-side.
_DEVICE_COLUMNS = (
    sb.OBS, sb.NEW_OBS, sb.ACTIONS, sb.REWARDS, sb.DONES, sb.ACTION_LOGP,
    sb.ACTION_DIST_INPUTS, sb.VF_PREDS, sb.ADVANTAGES, sb.VALUE_TARGETS,
    sb.PREV_ACTIONS, sb.PREV_REWARDS, sb.BOOTSTRAP_OBS, "weights",
    "seq_mask", "state_in_c", "state_in_h",
)

# A trajectory keeps the behaviour policy's distribution inputs of a step
# only while a row of them is this narrow. Wider (a vocabulary: 200 kB a
# step) it keeps the taken action's log-probability and the value, and the
# losses read ACTION_LOGP.
MAX_KEPT_DIST_INPUTS = 4096


def default_optimizer(config: dict) -> optax.GradientTransformation:
    clip = config.get("grad_clip")
    lr = config.get("lr", 5e-5)
    tx = optax.adam(lr, eps=config.get("adam_epsilon", 1e-7))
    if clip:
        tx = optax.chain(optax.clip_by_global_norm(clip), tx)
    return tx


def _newest(kept: dict, collection: str) -> dict:
    """What a model sowed into `collection` in one `apply`: each name's
    newest value ({} where it sowed nothing there)."""
    return {k: v[-1] for k, v in kept.get(collection, {}).items()}


class JaxPolicy(Policy):
    """A policy defined by a flax model + a loss function.

    loss_fn(policy, params, batch, rng, loss_state) -> (loss, stats);
    it should call `policy.apply(params, batch[OBS])` for model outputs.
    `loss_state` is a small dict of device scalars owned by the policy
    (e.g. an adaptive KL coefficient) that can change between updates
    without retracing. All computation inside loss_fn must be traceable.
    """

    def __init__(self, observation_space, action_space, config: dict,
                 loss_fn: Callable,
                 make_model: Optional[Callable] = None,
                 optimizer_fn: Optional[Callable] = None,
                 extra_action_out_fn: Optional[Callable] = None,
                 postprocess_fn: Optional[Callable] = None,
                 seed: Optional[int] = None):
        super().__init__(observation_space, action_space, config)
        mesh_lib.refuse_allreduce_codec(config)
        self.dist_class, self.dist_dim = get_action_dist(action_space)
        self.keeps_dist_inputs = self.dist_dim <= MAX_KEPT_DIST_INPUTS
        # Compute dtype resolves BEFORE the model is built so catalog
        # networks thread it through their flax layers (bf16 trunk
        # activations, not just bf16-cast weights). Custom make_model
        # models still get bf16 weights via the loss-boundary cast.
        self.compute_dtype = precision.resolve_compute_dtype(
            config.get("compute_dtype", "auto"))
        if make_model is not None:
            self.model = make_model(observation_space, action_space, config)
        else:
            mcfg = dict(config.get("model") or {})
            if mcfg.get("compute_dtype", "auto") in (None, "auto") \
                    and self.compute_dtype == jnp.bfloat16:
                mcfg["compute_dtype"] = "bf16"
            self.model = catalog.get_model(
                observation_space, self.dist_dim, mcfg)
        self._loss_fn = loss_fn
        self._postprocess_fn = postprocess_fn
        self._extra_action_out_fn = extra_action_out_fn

        self.preprocessor = catalog.get_preprocessor(observation_space)
        obs_shape = self.preprocessor.shape
        obs_dtype = self.preprocessor.dtype

        seed = seed if seed is not None else config.get("seed") or 0
        self._host_rng = jax.random.PRNGKey(seed)
        # `next()` of it is one C call, so atomic among threads without a
        # lock of its own.
        self._rng_counter = itertools.count(1)

        model_cfg = dict(catalog.MODEL_DEFAULTS)
        model_cfg.update(config.get("model") or {})
        # Recurrent path (parity: rnn_sequencing + lstm_v1 use_lstm): the
        # sampler threads (c, h) state through rollouts; training runs the
        # LSTM scan over [B, train_seq_len] sequences with per-sequence
        # initial state and done-driven resets. Detected from the MODEL
        # (catalog returns LSTMNetwork for use_lstm), not the config flag
        # alone — subclasses supplying non-recurrent custom models via
        # make_model must not be forced down the recurrent path.
        self.recurrent = hasattr(self.model, "initial_state")
        self.cell_size = int(model_cfg.get("lstm_cell_size", 256))
        self.train_seq_len = int(
            config.get("_train_seq_len")
            or model_cfg.get("max_seq_len", 20)) if self.recurrent else 1

        if self.recurrent:
            dummy = np.zeros((1, 1) + tuple(obs_shape), dtype=obs_dtype)
            dummy_state = self.model.initial_state(1)
            dummy_mask = np.zeros((1, 1), np.float32)
            # One program: a model of several hundred million parameters
            # is thousands of eager operations otherwise (most of two
            # minutes on a chip's host).
            self.params = jax.jit(self.model.init)(
                self._next_rng(), dummy, dummy_state, dummy_mask)
        else:
            dummy = np.zeros((1,) + tuple(obs_shape), dtype=obs_dtype)
            self.params = self.model.init(self._next_rng(), dummy)
        self.optimizer = (optimizer_fn or default_optimizer)(config)
        if set(self.params) - {"params"}:
            # What a model keeps outside "params" is constant: no update,
            # no optimizer state (and no part in the gradient's norm).
            self.optimizer = optax.multi_transform(
                {"params": self.optimizer, "constant": optax.set_to_zero()},
                {k: "params" if k == "params" else "constant"
                 for k in self.params})
        self.opt_state = self.optimizer.init(self.params)

        # Mesh + layout: the param/opt-state shardings resolve through
        # the SpecLayout rule table (config "param_sharding": "auto" ->
        # RAY_TPU_PARAM_SHARDING). The default "replicate" table
        # reproduces the legacy fully-replicated layout exactly; "fsdp"
        # shards large params and their optax moments over "dp" so each
        # replica owns only its slice of the weight update.
        from ..._private import spec_layout
        self.mesh = config.get("_mesh")
        if self.mesh is None:
            self.mesh = mesh_lib.make_mesh(num_devices=1)
        table = config.get("param_sharding", "auto")
        self.layout = spec_layout.SpecLayout.from_config(
            self.mesh, None if table == "auto" else table)
        self._param_sh = self.layout.shardings(self.params)
        self._opt_sh = self.layout.shardings(self.opt_state)
        self.params = jax.device_put(self.params, self._param_sh)
        self.opt_state = jax.device_put(self.opt_state, self._opt_sh)
        self._repl = mesh_lib.replicated(self.mesh)
        self._bsharded = mesh_lib.batch_sharded(self.mesh)

        # Mutable device scalars consumed by the loss (adaptive KL etc.).
        self.loss_state: Dict = {
            k: jnp.asarray(v, jnp.float32)
            for k, v in (config.get("loss_state") or {}).items()}

        self._build_jitted_fns()
        self._sgd_fns: Dict = {}
        self.global_timestep = 0
        self._batch_on = 0  # devices under the newest train batch's obs
        # Updates donate self.params; serialize them against weight
        # reads/writes from other threads (async optimizers run learning
        # on a LearnerThread while the driver broadcasts weights).
        self._update_lock = threading.Lock()

    # ------------------------------------------------------------------
    def apply(self, params, obs, *args, **kwargs):
        """Model forward: (dist_inputs, value) — recurrent models take
        (obs[B,T], state, reset_mask) and also return the final carry."""
        return self.model.apply(params, obs, *args, **kwargs)

    def apply_batch(self, params, batch):
        """Forward over a flat training batch -> flat (dist_inputs, value).

        Feedforward: one apply over [N]. Recurrent: reshape to
        [B, train_seq_len], run the LSTM scan with each sequence's stored
        initial state and done-driven resets, flatten back to [N]."""
        if not self.recurrent:
            return self.apply(params, batch[sb.OBS])
        (dist_bt, val_bt, _), _, _ = self.apply_sequences(params, batch)
        O = dist_bt.shape[-1]
        return dist_bt.reshape(-1, O), val_bt.reshape(-1)

    def apply_sequences(self, params, batch):
        """Stateful forward over [B, L] sequences.

        Returns ((dist_inputs[B,L,O], value[B,L], final_carry), what the
        model counted in the pass: its "counters" collection, {} for a
        model that counts nothing, and the loss terms of its own that the
        objective is to add: its "losses" collection, as a rule {}). Initial
        state is each sequence's recorded one: `state_in`, a pytree with a
        row a sequence (device-resident rollouts), or the first row of
        the per-step `state_in_c/h` columns (host samplers); a batch with
        neither starts every sequence from the model's initial state.
        Resets fire WITHIN a sequence where the previous step was done
        (packed fragments cross episodes; padded chunks never do), and at
        its first step where `reset_in` says the step before it was."""
        L = self.train_seq_len
        obs = batch[sb.OBS]
        B = obs.shape[0] // L
        obs_bt = obs.reshape((B, L) + obs.shape[1:])
        if sb.STATE_IN in batch:
            state = batch[sb.STATE_IN]
        elif "state_in_c" in batch:
            state = (batch["state_in_c"].reshape(B, L, -1)[:, 0],
                     batch["state_in_h"].reshape(B, L, -1)[:, 0])
        else:
            state = self.model.initial_state(B)
        dones = batch[sb.DONES].reshape(B, L)
        # reset before step t iff step t-1 (same sequence) was terminal
        first = batch["reset_in"][:, None] if "reset_in" in batch \
            else jnp.zeros((B, 1), jnp.float32)
        reset = jnp.concatenate([first, dones[:, :-1]], axis=1)
        return self._apply_counted(params, obs_bt, state, reset)

    def _apply_counted(self, params, obs_bt, state, reset, **static):
        """`apply` of a stateful model, its "counters" collection and its
        "losses" collection (a model computes a loss of its own only where
        the caller keeps that collection, as this one does)."""
        out, kept = self.apply(params, obs_bt, state, reset,
                               mutable=["counters", "losses"], **static)
        return out, _newest(kept, "counters"), _newest(kept, "losses")

    def initial_state(self, batch_size: int):
        """The model's rollout state for `batch_size` rows, as the pytree
        its forward takes (() for feedforward policies)."""
        return self.model.initial_state(batch_size) if self.recurrent else ()

    def step_state(self, params, obs, state, reset):
        """One rollout step of a stateful policy: obs [B], reset [B] (1
        where the previous step ended an episode) -> (dist_inputs [B, O],
        value [B], state, what the model counted in the step). ONE action a
        row: the caller samples it from `dist_inputs`. A policy whose step
        is a block of positions a row has `block_step_state` instead. The
        model is told that the step is a rollout's (`rollout=True`: nothing
        differentiates it, so it may take a form that has no derivative;
        the learner's passes, its one-step bootstrap among them, go through
        `apply` and are not)."""
        (dist_bt, val_bt, state), counted, _ = self._apply_counted(
            params, obs[:, None], state, reset[:, None], rollout=True)
        return dist_bt[:, 0], val_bt[:, 0], state, counted

    @property
    def block_len(self) -> int:
        """Positions a rollout step of this policy yields a row: a model
        that generates by diffusion over blocks declares its block (0: one
        action a row a step, `step_state`'s contract)."""
        return getattr(self.model, "block_len", 0)

    def block_step_state(self, params, obs, state, reset, rng):
        """`step_state`'s sibling for a policy that declares a `block_len`
        L: one rollout step is one BLOCK a row, sampled inside the model
        (the passes that unmask it and the draws interleave, hence `rng`).
        obs [B] (read where a row begins an episode: its first position is
        given), reset [B] -> (actions [B, L], their log-probabilities at the
        pass each was unmasked [B, L], that pass [B, L] (-1 and
        log-probability 0 where the position was given, not chosen), ONE
        value [B]: the state's before the block, state, what the model
        counted)."""
        (actions, logp, steps, value, state), kept = self.model.apply(
            params, obs, state, reset, rng, method="block_step",
            mutable=["counters"])
        return actions, logp, steps, value, state, _newest(kept, "counters")

    def apply_blocks(self, params, batch):
        """The learner's pass of a policy that declares a `block_len` over
        [B, L] sequences of whole episodes, replayed on the rollout's own
        trace (`sb.UNMASK_STEPS`): ((logits [B, L, V] of each row's token
        at the pass it was unmasked, value [B, L / block_len] a block), the
        model's "counters", its "losses"). Resets as `apply_sequences`'."""
        L = self.train_seq_len
        tokens = batch[sb.OBS]
        B = tokens.shape[0] // L
        dones = batch[sb.DONES].reshape(B, L)
        reset = jnp.concatenate(
            [jnp.zeros((B, 1), jnp.float32), dones[:, :-1]], axis=1)
        out, kept = self.model.apply(
            params, tokens.reshape(B, L),
            batch[sb.UNMASK_STEPS].reshape(B, L), reset,
            method="block_causal", mutable=["counters", "losses"])
        return out, _newest(kept, "counters"), _newest(kept, "losses")

    def get_initial_state(self, batch_size: int = 1):
        """Per-env rollout state columns ([] for feedforward policies)."""
        if not self.recurrent:
            return []
        return [np.zeros((batch_size, self.cell_size), np.float32),
                np.zeros((batch_size, self.cell_size), np.float32)]

    def _next_rng_counter(self) -> np.uint32:
        """The next value of the key counter. A program that folds it into
        `_host_rng` itself (`fold_in(base, counter)`) draws the key
        `_next_rng()` would have returned, without an eager op."""
        return np.uint32(next(self._rng_counter))

    def _next_rng(self):
        return jax.random.fold_in(self._host_rng, self._next_rng_counter())

    def _build_jitted_fns(self):
        if self.recurrent:
            @jax.named_scope("policy/action")
            def action_fn(params, obs, state, rng, explore):
                # One time step: [B] -> [B, 1].
                obs_bt = obs[:, None]
                reset = jnp.zeros((obs.shape[0], 1), jnp.float32)
                dist_bt, val_bt, carry = self.apply(
                    params, obs_bt, state, reset)
                dist_inputs, value = dist_bt[:, 0], val_bt[:, 0]
                dist = self.dist_class(dist_inputs)
                actions = jax.lax.cond(
                    explore,
                    lambda: dist.sample(rng),
                    lambda: dist.deterministic_sample())
                logp = dist.logp(actions)
                return actions, logp, dist_inputs, value, carry

            self._action_fn = jax.jit(action_fn)

            def value_fn(params, obs, state):
                obs_bt = obs[:, None]
                reset = jnp.zeros((obs.shape[0], 1), jnp.float32)
                _, val_bt, _ = self.apply(params, obs_bt, state, reset)
                return val_bt[:, 0]

            self._value_fn = jax.jit(value_fn)
        else:
            @jax.named_scope("policy/action")
            def action_fn(params, obs, rng, explore):
                dist_inputs, value = self.apply(params, obs)
                dist = self.dist_class(dist_inputs)
                actions = jax.lax.cond(
                    explore,
                    lambda: dist.sample(rng),
                    lambda: dist.deterministic_sample())
                logp = dist.logp(actions)
                return actions, logp, dist_inputs, value

            self._action_fn = jax.jit(action_fn)
            self._value_fn = jax.jit(
                lambda params, obs: self.apply(params, obs)[1])

        # One local loss+grad, shared by every learn path. bf16 compute
        # casts the f32 master params at this boundary only: autodiff
        # transposes the cast, so gradients (and optax state) stay f32.
        cdt = self.compute_dtype

        @jax.named_scope("train/loss")
        def local_loss_grad(params, batch, rng, loss_state):
            def lf(p):
                if cdt != jnp.float32:
                    p = precision.cast_float_tree(p, cdt)
                return self._loss_fn(self, p, batch, rng, loss_state)
            (loss, stats), grads = jax.value_and_grad(
                lf, has_aux=True)(params)
            return loss, dict(stats), grads

        self._loss_grad = local_loss_grad

        @jax.named_scope("train/update")
        def apply_update(params, opt_state, grads, stats):
            updates, opt_state = self.optimizer.update(
                grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            stats = dict(stats)
            stats["grad_gnorm"] = optax.global_norm(grads)
            return params, opt_state, stats

        self._apply_update = apply_update

        def train_fn(params, opt_state, batch, rng, loss_state):
            loss, stats, grads = local_loss_grad(
                params, batch, rng, loss_state)
            return apply_update(params, opt_state, grads, stats)

        self._train_fn = jax.jit(
            train_fn, donate_argnums=(0, 1),
            in_shardings=(self._param_sh, self._opt_sh,
                          self._bsharded, self._repl, self._repl),
            out_shardings=(self._param_sh, self._opt_sh, self._repl))

        def grad_fn(params, batch, rng, loss_state):
            loss, stats, grads = local_loss_grad(
                params, batch, rng, loss_state)
            return grads, stats

        self._grad_fn = jax.jit(
            grad_fn,
            in_shardings=(self._param_sh, self._bsharded, self._repl,
                          self._repl),
            out_shardings=(self._param_sh, self._repl))

        def apply_grads_fn(params, opt_state, grads):
            updates, opt_state = self.optimizer.update(
                grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

        self._apply_grads_fn = jax.jit(
            apply_grads_fn, donate_argnums=(0, 1))

    # ------------------------------------------------------------------
    # rollout inference
    # ------------------------------------------------------------------
    def compute_actions(self, obs_batch, state_batches=None, explore=True,
                        prev_action_batch=None, prev_reward_batch=None):
        obs = jnp.asarray(obs_batch)
        if self.recurrent:
            if not state_batches:
                state_batches = self.get_initial_state(len(obs_batch))
            state = (jnp.asarray(state_batches[0]),
                     jnp.asarray(state_batches[1]))
            with self._update_lock:
                actions, logp, dist_inputs, value, carry = self._action_fn(
                    self.params, obs, state, self._next_rng(), explore)
            extra = {
                sb.ACTION_LOGP: np.asarray(logp),
                sb.ACTION_DIST_INPUTS: np.asarray(dist_inputs),
                sb.VF_PREDS: np.asarray(value),
                # Pre-step state rows: the learner takes each training
                # sequence's first row as its initial LSTM state.
                "state_in_c": np.asarray(state_batches[0]),
                "state_in_h": np.asarray(state_batches[1]),
            }
            state_out = [np.asarray(carry[0]), np.asarray(carry[1])]
        else:
            with self._update_lock:
                actions, logp, dist_inputs, value = self._action_fn(
                    self.params, obs, self._next_rng(), explore)
            extra = {
                sb.ACTION_LOGP: np.asarray(logp),
                sb.ACTION_DIST_INPUTS: np.asarray(dist_inputs),
                sb.VF_PREDS: np.asarray(value),
            }
            state_out = []
        if self._extra_action_out_fn is not None:
            extra.update(self._extra_action_out_fn(self, extra))
        return np.asarray(actions), state_out, extra

    def compute_log_likelihoods(self, obs_batch, actions,
                                state_batches=None):
        """Log-prob of given (possibly externally chosen) actions under the
        current policy (parity: `rllib/policy/policy.py`
        compute_log_likelihoods). Used by the sampler to relabel
        ExternalEnv.log_action steps."""
        if not hasattr(self, "_logp_fn"):
            if self.recurrent:
                def logp_fn(params, obs, state, acts):
                    obs_bt = obs[:, None]
                    reset = jnp.zeros((obs.shape[0], 1), jnp.float32)
                    dist_bt, _, _ = self.apply(params, obs_bt, state, reset)
                    return self.dist_class(dist_bt[:, 0]).logp(acts)
            else:
                def logp_fn(params, obs, acts):
                    dist_inputs, _ = self.apply(params, obs)
                    return self.dist_class(dist_inputs).logp(acts)
            self._logp_fn = jax.jit(logp_fn)
        obs = jnp.asarray(obs_batch)
        acts = jnp.asarray(actions)
        with self._update_lock:
            if self.recurrent:
                if not state_batches:
                    state_batches = self.get_initial_state(len(obs_batch))
                state = (jnp.asarray(state_batches[0]),
                         jnp.asarray(state_batches[1]))
                out = self._logp_fn(self.params, obs, state, acts)
            else:
                out = self._logp_fn(self.params, obs, acts)
        return np.asarray(out)

    def value_function(self, obs_batch, state=None):
        obs = jnp.asarray(obs_batch)
        if self.recurrent:
            if not state:
                state = self.get_initial_state(len(obs_batch))
            return np.asarray(self._value_fn(
                self.params, obs,
                (jnp.asarray(state[0]), jnp.asarray(state[1]))))
        return np.asarray(self._value_fn(self.params, obs))

    # ------------------------------------------------------------------
    # learning
    # ------------------------------------------------------------------
    def _device_batch(self, batch) -> dict:
        out = {}
        for k in _DEVICE_COLUMNS:
            if k in batch:
                v = batch[k]
                if isinstance(v, jax.Array):
                    # Already device-resident (DeviceSebulbaSampler
                    # rollouts): at most a device-side reshard, never a
                    # host round-trip.
                    if v.dtype == jnp.float64:
                        v = v.astype(jnp.float32)
                    elif v.dtype == jnp.bool_:
                        v = v.astype(jnp.float32)
                    out[k] = jax.device_put(v, self._bsharded)
                    continue
                v = np.asarray(v)
                if v.dtype == np.float64:
                    v = v.astype(np.float32)
                if v.dtype == np.bool_:
                    v = v.astype(np.float32)
                out[k] = jax.device_put(v, self._bsharded)
        if sb.OBS in out:
            self._batch_on = len(out[sb.OBS].sharding.device_set)
        return out

    def devices_in_use(self) -> Dict:
        """How many devices the parameters and the newest train batch's
        observations occupy, read from the arrays' own shardings — what a
        mesh was built for and where `device_put` really left the data
        are different questions."""
        with self._update_lock:
            params_on = set().union(*(
                x.sharding.device_set for x in jax.tree.leaves(self.params)))
        return {"params_on": len(params_on), "batch_on": self._batch_on}

    def postprocess_trajectory(self, batch, other_agent_batches=None,
                               episode=None):
        if self._postprocess_fn is not None:
            return self._postprocess_fn(self, batch, other_agent_batches,
                                        episode)
        return batch

    def _locked_update(self, fn, dev_batch):
        """One donated-buffer update program under `_update_lock`, as
        phases of the calling (learner) thread: the wait for the lock,
        then the dispatch."""
        with phase("learner.lock_wait") as step:
            self._update_lock.acquire()
            try:
                step.then("learner.train")
                self.params, self.opt_state, stats = fn(
                    self.params, self.opt_state, dev_batch,
                    self._next_rng(), self.loss_state)
            finally:
                self._update_lock.release()
        return stats

    def learn_on_batch(self, batch) -> Dict:
        with phase("learner.h2d"):
            dev_batch = self._device_batch(batch)
        stats = self._locked_update(self._train_fn, dev_batch)
        self.global_timestep += batch.count if hasattr(batch, "count") \
            else len(next(iter(batch.values())))
        with phase("learner.readback"):
            return {k: float(v) for k, v in stats.items()}

    def sgd_learn(self, batch, num_sgd_iter: int, minibatch_size: int,
                  seq_len: int = 1) -> Dict:
        """Whole minibatch-SGD phase as one XLA program (see module doc).

        With seq_len > 1 (V-trace/recurrent losses that reshape flat rows
        into [B, seq_len] fragments), shuffling and minibatch slicing
        happen at sequence granularity so fragment contiguity survives.
        """
        n = batch.count
        if seq_len > 1 and minibatch_size % seq_len:
            raise ValueError(
                f"sgd minibatch_size {minibatch_size} must be a multiple "
                f"of sequence length {seq_len}")
        # Drop the remainder so minibatches tile exactly (same behavior as
        # the reference's tower loader truncation, multi_gpu_impl.py:116).
        num_mb = max(1, n // minibatch_size)
        usable = num_mb * minibatch_size
        if sb.BOOTSTRAP_OBS in batch:
            # No np.asarray: the column may be device-resident
            # (DeviceSebulbaSampler) and must not round-trip the host.
            boot = batch[sb.BOOTSTRAP_OBS]
            if seq_len <= 1 or len(boot) * seq_len != n:
                raise ValueError(
                    f"BOOTSTRAP_OBS has {len(boot)} fragments but the "
                    f"batch has {n} rows at seq_len={seq_len}; packed "
                    "fragment batches must run with seq_len == "
                    "rollout_fragment_length")
            if usable != n:
                # Row truncation at fragment granularity: keep the
                # matching bootstrap rows (slice() drops the column).
                sliced = batch.slice(0, usable)
                sliced[sb.BOOTSTRAP_OBS] = boot[:usable // seq_len]
                batch = sliced
        elif usable != n:
            batch = batch.slice(0, usable)
        with phase("learner.h2d"):
            dev_batch = self._device_batch(batch)
        key = (num_sgd_iter, num_mb, minibatch_size, seq_len)
        if key not in self._sgd_fns:
            self._sgd_fns[key] = self._make_sgd_fn(*key)
        stats = self._locked_update(self._sgd_fns[key], dev_batch)
        from ..sample_batch import real_count
        self.global_timestep += real_count(batch)
        with phase("learner.readback"):
            return {k: float(v) for k, v in stats.items()}

    def _make_sgd_fn(self, num_sgd_iter: int, num_mb: int, mb_size: int,
                     seq_len: int = 1):
        def sgd_fn(params, opt_state, batch, rng, loss_state):
            usable = num_mb * mb_size
            num_seq = usable // seq_len

            def epoch(carry, erng):
                params, opt_state = carry
                # Permute whole sequences: rows within a seq_len block stay
                # contiguous (seq_len=1 degenerates to row shuffling).
                perm = jax.random.permutation(erng, num_seq)
                idx = (perm[:, None] * seq_len
                       + jnp.arange(seq_len)[None, :]).reshape(-1)
                # BOOTSTRAP_OBS is fragment-indexed ([num_seq, ...]):
                # it follows the sequence permutation, not the row index.
                row_batch = {k: v for k, v in batch.items()
                             if k != sb.BOOTSTRAP_OBS}
                shuffled = jax.tree.map(lambda x: x[idx], row_batch)
                mbs = jax.tree.map(
                    lambda x: x.reshape((num_mb, mb_size) + x.shape[1:]),
                    shuffled)
                if sb.BOOTSTRAP_OBS in batch:
                    boot = batch[sb.BOOTSTRAP_OBS][perm]
                    mbs[sb.BOOTSTRAP_OBS] = boot.reshape(
                        (num_mb, mb_size // seq_len) + boot.shape[1:])

                def mb_step(carry, mb):
                    params, opt_state = carry
                    loss, stats, grads = self._loss_grad(
                        params, mb, erng, loss_state)
                    params, opt_state, stats = self._apply_update(
                        params, opt_state, grads, stats)
                    return (params, opt_state), stats

                (params, opt_state), stats = jax.lax.scan(
                    mb_step, (params, opt_state), mbs)
                return (params, opt_state), jax.tree.map(
                    lambda s: s[-1], stats)  # stats of last minibatch

            rngs = jax.random.split(rng, num_sgd_iter)
            (params, opt_state), stats = jax.lax.scan(
                epoch, (params, opt_state), rngs)
            return params, opt_state, jax.tree.map(
                lambda s: s[-1], stats)

        return jax.jit(
            sgd_fn, donate_argnums=(0, 1),
            in_shardings=(self._param_sh, self._opt_sh,
                          self._bsharded, self._repl, self._repl),
            out_shardings=(self._param_sh, self._opt_sh, self._repl))

    def compute_gradients(self, batch):
        dev_batch = self._device_batch(batch)
        grads, stats = self._grad_fn(self.params, dev_batch,
                                     self._next_rng(), self.loss_state)
        host = jax.tree.map(np.asarray, grads)
        return host, {k: float(v) for k, v in stats.items()}

    def apply_gradients(self, gradients):
        with self._update_lock:
            self.params, self.opt_state = self._apply_grads_fn(
                self.params, self.opt_state, gradients)

    # ------------------------------------------------------------------
    # weights
    # ------------------------------------------------------------------
    def get_weights(self):
        with self._update_lock:
            return jax.tree.map(np.asarray, self.params)

    def set_weights(self, weights):
        with self._update_lock:
            self.params = jax.device_put(weights, self._param_sh)

    def get_state(self):
        return {
            "weights": self.get_weights(),
            "opt_state": jax.tree.map(np.asarray, self.opt_state),
            "loss_state": {k: float(v) for k, v in self.loss_state.items()},
            "global_timestep": self.global_timestep,
        }

    def set_state(self, state):
        # An older checkpoint may also hold "ef_state" (the removed q8
        # exchange's residuals): every key but the four below is ignored.
        self.set_weights(state["weights"])
        self.opt_state = jax.device_put(
            jax.tree.map(jnp.asarray, state["opt_state"]), self._opt_sh)
        self.global_timestep = state.get("global_timestep", 0)
        for k, v in state.get("loss_state", {}).items():
            self.loss_state[k] = jnp.asarray(v, jnp.float32)

    def update_loss_state(self, **kwargs) -> None:
        for k, v in kwargs.items():
            self.loss_state[k] = jnp.asarray(v, jnp.float32)

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(self.params))

    def export_model(self, export_dir: str) -> str:
        """Serialize deterministic inference as a STANDALONE artifact
        (parity: `rllib/policy/policy.py:280` export_model / the TF
        SavedModel export at `tf_policy.py:389`): a StableHLO program
        via `jax.export` plus host weights — reloadable with
        `policy/export.py:load_exported_policy` and NO framework code.
        The batch dimension exports SYMBOLICALLY (any batch size at
        serving time, no padding waste) and the program targets both
        cpu and tpu, so a TPU-trained policy serves from CPU hosts.
        Feedforward policies only (recurrent export needs carried
        state; same scoping as the reference's torch export)."""
        import json
        import os
        import pickle

        from jax import export as jax_export
        if self.recurrent:
            raise NotImplementedError(
                "export_model supports feedforward policies only")
        obs_shape = tuple(self.preprocessor.shape)
        obs_dtype = np.dtype(self.preprocessor.dtype)

        def infer(params, obs):
            dist_inputs, value = self.apply(params, obs)
            dist = self.dist_class(dist_inputs)
            return dist.deterministic_sample(), dist_inputs, value

        host_params = self.get_weights()
        batch = jax_export.symbolic_shape("b")[0]
        exported = jax_export.export(
            jax.jit(infer), platforms=("cpu", "tpu"))(
            jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                host_params),
            jax.ShapeDtypeStruct((batch,) + obs_shape, obs_dtype))
        os.makedirs(export_dir, exist_ok=True)
        with open(os.path.join(export_dir,
                               "inference.stablehlo"), "wb") as f:
            f.write(exported.serialize())
        with open(os.path.join(export_dir, "params.pkl"), "wb") as f:
            pickle.dump(host_params, f)
        with open(os.path.join(export_dir, "meta.json"), "w") as f:
            json.dump({
                "obs_shape": list(obs_shape),
                "obs_dtype": obs_dtype.name,
                "action_space": repr(self.action_space),
            }, f)
        return export_dir
