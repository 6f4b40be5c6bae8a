"""V-trace actor-critic policy (IMPALA's learner loss).

Parity: `rllib/agents/impala/vtrace_policy.py` (VTraceTFPolicy) — policy
gradient with V-trace-corrected advantages + value loss + entropy bonus.

Layout: the learner receives packed fragments (see sampler pack mode) —
a flat [B*T] batch where each consecutive run of T rows is one contiguous
env fragment. The loss reshapes to [B, T], transposes to time-major
[T, B], and fuses the whole V-trace scan + update into one XLA program.
Bootstrap values come from the last row's NEW_OBS per sequence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import sample_batch as sb
from ...policy.jax_policy_template import build_jax_policy
from ..trainer import with_common_config
from . import vtrace

DEFAULT_CONFIG = with_common_config({
    "lr": 0.0005,
    "gamma": 0.99,
    "grad_clip": 40.0,
    "vf_loss_coeff": 0.5,
    "entropy_coeff": 0.01,
    "vtrace_clip_rho_threshold": 1.0,
    "vtrace_clip_pg_rho_threshold": 1.0,
    "lambda": 1.0,
    "rollout_fragment_length": 50,
    "train_batch_size": 500,
    "min_iter_time_s": 10,
    "num_workers": 2,
    "num_envs_per_worker": 1,
    # IMPALA sequences cross episode boundaries (V-trace cuts at dones).
    "pack_fragments": True,
    "use_gae": False,
    # Learner queue/broadcast knobs (reference: impala.py:14-17).
    "max_sample_requests_in_flight_per_worker": 2,
    "broadcast_interval": 1,
    "learner_queue_size": 16,
    "num_sgd_iter": 1,
    # 0 = one full-batch update per train batch; >0 enables the fused
    # minibatch-SGD program (must be a multiple of rollout_fragment_length).
    "sgd_minibatch_size": 0,
    # Anakin mode (`optimizers/anakin_optimizer.py`): env + rollout +
    # V-trace update fused into one XLA program. Requires a JaxEnv
    # registration for config["env"] (`env/jax_env.py`); env slots =
    # num_envs_per_worker, batch-sharded over the learner mesh.
    "anakin": False,
    "anakin_updates_per_call": 10,
    # Device-resident inline rollouts (`evaluation/device_sampler.py`):
    # obs ship to HBM once and train in place. "auto" uses them for
    # feedforward policies; False forces the host-side VectorSampler.
    "device_rollouts": "auto",
    # Stack depth for on-device frame stacking (0 = off). Requires an
    # env that emits single-channel frames (see device_frame_stack.py).
    "device_frame_stack": 0,
    # Delta-encoded observation uploads (`env/delta_obs.py`): the device
    # retains the frame batch; the host ships only changed pixels.
    # "auto" = envs with native delta support; True also wraps other
    # frame envs in the generic host-side `DeltaEncoder`; False = off.
    "obs_delta": "auto",
    # Max changed pixels per env-row before falling back to a full-frame
    # row (generic DeltaEncoder only; native envs set their own budget).
    "obs_delta_budget": 256,
    # Double-buffered env groups per inline actor (device rollouts
    # only): while one group's inference + action fetch is in flight,
    # the other groups' envs step on the host, hiding the device
    # round-trip. Lag-0: trajectories are byte-identical to a single
    # group. Falls back to the largest count that tiles the env slots
    # and the learner mesh.
    "sebulba_env_groups": 2,
    # k-step on-device action selection (opt-in second gear): the
    # select program samples k actions per device sync, amortizing the
    # blocked round-trip by k at the price of up to k-1 steps of
    # behavior-policy lag — recorded per transition (POLICY_LAG) and
    # absorbed by V-trace since the stored behavior logits are the
    # ones that actually selected each action. Requires
    # rollout_fragment_length % k == 0.
    "sebulba_onchip_steps": 1,
})


def _time_major(x, seq_len: int):
    """[B*T, ...] -> [T, B, ...]."""
    b = x.shape[0] // seq_len
    x = x.reshape((b, seq_len) + x.shape[1:])
    return jnp.swapaxes(x, 0, 1)


def forward_with_bootstrap(policy, params, batch, T: int):
    """`forward_counted` without what the model counted or lost."""
    return forward_counted(policy, params, batch, T)[:3]


def forward_counted(policy, params, batch, T: int):
    """Model forward over a packed [B*T] fragment batch plus the
    per-fragment bootstrap value.

    Handles both fragment-batch layouts: a BOOTSTRAP_OBS column of shape
    [B, ...] (VectorSampler / Anakin batches), or a full per-row NEW_OBS
    column whose last row per fragment is the bootstrap observation
    (remote-worker pack mode). Returns (dist_inputs[B*T, O],
    values[B*T], bootstrap_value[B], what the model counted in the pass,
    the loss terms of the model's own: sums over the batch, weighted, for
    the objective to add).
    """
    counters, model_losses = {}, {}
    if policy.recurrent:
        (dist_bt, val_bt, carry), counters, model_losses = \
            policy.apply_sequences(params, batch)
        dist_inputs = dist_bt.reshape(-1, dist_bt.shape[-1])
        values_flat = val_bt.reshape(-1)
        B = batch[sb.OBS].shape[0] // T
        if sb.BOOTSTRAP_OBS in batch:
            last_new_obs = batch[sb.BOOTSTRAP_OBS]
        else:
            new_obs = batch[sb.NEW_OBS]
            last_new_obs = new_obs.reshape(
                (B, T) + new_obs.shape[1:])[:, -1]
        # One more step from the final carry (reset where the fragment's
        # last step was terminal: the bootstrap is then V(s0) of the next
        # episode, masked anyway by discount 0 at the boundary).
        last_done = batch[sb.DONES].reshape(B, T)[:, -1]
        _, boot_bt, _ = policy.apply(
            params, last_new_obs[:, None], carry, last_done[:, None])
        bootstrap_value = boot_bt[:, 0]
    else:
        if sb.OBS_TIME_MAJOR in batch:
            # The model is row-wise, so it reads the observations where
            # the rollout wrote them; only its narrow outputs are put in
            # OBS's (env-major) row order.
            view = batch[sb.OBS_TIME_MAJOR]
            dist_inputs, values_flat = (
                sb.packed_from_time_major(
                    x.reshape(view.shape[:3] + x.shape[1:]))
                for x in policy.apply(
                    params, view.reshape((-1,) + view.shape[3:])))
        else:
            dist_inputs, values_flat = policy.apply(params, batch[sb.OBS])
        if sb.BOOTSTRAP_OBS in batch:
            boot_obs = batch[sb.BOOTSTRAP_OBS]
        else:
            boot_obs = _time_major(batch[sb.NEW_OBS], T)[-1]
        _, bootstrap_value = policy.apply(params, boot_obs)
    return dist_inputs, values_flat, bootstrap_value, counters, model_losses


def block_vtrace_loss(policy, params, batch, rng, loss_state):
    """`vtrace_loss` of a policy whose step is a BLOCK of `policy.block_len`
    positions a row (generation by diffusion over blocks): the block is one
    action of the decision process and V-trace's time axis. Fragments are
    whole episodes of T positions. The learner replays the sampler's trace
    (`policy.apply_blocks`): a row's log-probability is its token's at the
    pass it was unmasked at, log pi(block) the sum over its rows, log mu the
    same sum of the rollout's, the block's reward the sum of its rows', its
    discount gamma, done at the episode's last block (so the bootstrap value
    never counts), its value the state's before the block; the entropy is
    summed over the same rows. A GIVEN row (`sb.UNMASK_STEPS` -1: an
    episode's first position) weighs nothing anywhere."""
    cfg = policy.config
    T, L = cfg["rollout_fragment_length"], policy.block_len
    (logits, values), counters, model_losses = policy.apply_blocks(
        params, batch)
    B = logits.shape[0]

    def blocks(x):
        """[B * T] rows -> [T / L, B, L]: time-major blocks."""
        return jnp.swapaxes(x.reshape(B, T // L, L), 0, 1)
    generated = blocks(batch[sb.UNMASK_STEPS]) >= 0

    def by_block(rows):
        """The sum over each block's generated rows, [T / L, B]."""
        return jnp.sum(jnp.where(generated, blocks(rows), 0.0), axis=-1)
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    taken = jnp.take_along_axis(
        logp_all, batch[sb.ACTIONS].reshape(B, T, 1).astype(jnp.int32),
        axis=-1)
    target_logp = by_block(taken.reshape(-1))
    log_rhos = target_logp - by_block(batch[sb.ACTION_LOGP])
    dones = jnp.max(blocks(batch[sb.DONES]), axis=-1)
    values = values.T

    returns = vtrace.from_importance_weights(
        log_rhos=log_rhos,
        discounts=cfg["gamma"] * (1.0 - dones),
        rewards=by_block(batch[sb.REWARDS]),
        values=values,
        bootstrap_value=jnp.zeros(B, values.dtype),
        clip_rho_threshold=cfg["vtrace_clip_rho_threshold"],
        clip_pg_rho_threshold=cfg["vtrace_clip_pg_rho_threshold"],
        lambda_=cfg["lambda"])
    vs = jax.lax.stop_gradient(returns.vs)
    pg_advantages = jax.lax.stop_gradient(returns.pg_advantages)

    pi_loss = -jnp.sum(target_logp * pg_advantages)
    vf_loss = 0.5 * jnp.sum((values - vs) ** 2)
    entropy = jnp.sum(by_block(
        -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1).reshape(-1)))
    total = (pi_loss
             + cfg["vf_loss_coeff"] * vf_loss
             - cfg["entropy_coeff"] * entropy)
    for term in model_losses.values():
        total = total + term
    n = jnp.sum(generated)
    rhos = jnp.exp(log_rhos)
    stats = {
        "total_loss": total,
        "policy_loss": pi_loss / n,
        "vf_loss": vf_loss / n,
        "entropy": entropy / n,
        "mean_kl_behaviour": jnp.mean(-log_rhos),
        "vtrace_mean_vs": jnp.mean(vs),
        "is_ratio_mean": jnp.mean(rhos),
        "is_ratio_max": jnp.max(rhos),
        # Rows of the minibatch that were given, not generated.
        "given_rows": generated.size - n,
        **counters,
    }
    return total, stats


def vtrace_loss(policy, params, batch, rng, loss_state):
    if policy.block_len:
        return block_vtrace_loss(policy, params, batch, rng, loss_state)
    cfg = policy.config
    T = cfg["rollout_fragment_length"]
    gamma = cfg["gamma"]

    dist_inputs, values_flat, bootstrap_value, counters, model_losses = \
        forward_counted(policy, params, batch, T)

    # Time-major [T, B] log-probabilities of the taken actions and the
    # target policy's entropy. Logits narrow enough for the trajectory to
    # keep are laid out time-major whole: that undoes the transpose the
    # batch was packed with, so XLA moves nothing. Logits a vocabulary wide
    # are reduced row by row first and only the scalars a step are moved;
    # their behaviour policy left the taken action's log-probability.
    actions = batch[sb.ACTIONS]
    if sb.ACTION_DIST_INPUTS in batch:
        actions = _time_major(actions, T)
        target_dist = policy.dist_class(_time_major(dist_inputs, T))
        target_logp = target_dist.logp(actions)
        behaviour_logp = policy.dist_class(_time_major(
            batch[sb.ACTION_DIST_INPUTS], T)).logp(actions)
    else:
        target_dist = policy.dist_class(dist_inputs)
        target_logp = _time_major(target_dist.logp(actions), T)
        behaviour_logp = _time_major(batch[sb.ACTION_LOGP], T)
    log_rhos = target_logp - behaviour_logp
    rewards = _time_major(batch[sb.REWARDS], T)
    dones = _time_major(batch[sb.DONES], T)
    values = _time_major(values_flat, T)
    discounts = gamma * (1.0 - dones)

    returns = vtrace.from_importance_weights(
        log_rhos=log_rhos,
        discounts=discounts,
        rewards=rewards,
        values=values,
        bootstrap_value=bootstrap_value,
        clip_rho_threshold=cfg["vtrace_clip_rho_threshold"],
        clip_pg_rho_threshold=cfg["vtrace_clip_pg_rho_threshold"],
        lambda_=cfg["lambda"])
    vs = jax.lax.stop_gradient(returns.vs)
    pg_advantages = jax.lax.stop_gradient(returns.pg_advantages)

    pi_loss = -jnp.sum(target_logp * pg_advantages)
    delta = values - vs
    vf_loss = 0.5 * jnp.sum(delta ** 2)
    entropy = jnp.sum(target_dist.entropy())

    total = (pi_loss
             + cfg["vf_loss_coeff"] * vf_loss
             - cfg["entropy_coeff"] * entropy)
    for term in model_losses.values():
        total = total + term
    n = values_flat.shape[0]
    rhos = jnp.exp(log_rhos)
    stats = {
        "total_loss": total,
        "policy_loss": pi_loss / n,
        "vf_loss": vf_loss / n,
        "entropy": entropy / n,
        "mean_kl_behaviour": jnp.mean(-log_rhos),
        "vtrace_mean_vs": jnp.mean(vs),
        # The importance ratios V-trace corrects by: 1 wherever the batch
        # is on-policy, off it from the second minibatch of a rollout on.
        "is_ratio_mean": jnp.mean(rhos),
        "is_ratio_max": jnp.max(rhos),
        **counters,
    }
    return total, stats


VTraceJaxPolicy = build_jax_policy(
    "VTraceJaxPolicy", vtrace_loss,
    get_default_config=lambda: DEFAULT_CONFIG)
