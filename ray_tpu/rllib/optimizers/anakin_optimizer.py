"""Anakin optimizer: env + inference + learner fused into one XLA program.

The reference's IMPALA moves every observation across process and host
boundaries: env -> rollout worker -> object store -> learner GPU
(`rllib/optimizers/async_samples_optimizer.py:19`). On TPU hosts where
the host<->device link is the bottleneck, the idiomatic design inverts:
for envs expressible as pure JAX functions (`env/jax_env.py`), the
WHOLE actor-learner loop — `lax.scan` over env steps with policy
inference, then the V-trace update — compiles into a single donated-
buffer XLA program. Observations live and die in HBM; the host only
dispatches the program and reads back scalar stats. This is the
"Anakin" architecture of the Podracer line of work (see PAPERS.md),
and it composes with the device mesh: env slots are batch-sharded
across chips, params replicated, gradient all-reduce inserted by XLA —
the same sharding contract as `JaxPolicy._train_fn`.

Semantics: on-policy IMPALA — each scan iteration rolls out under the
current params and then learns from that rollout. With one update a
rollout (`sgd_minibatch_size` 0) V-trace's importance ratios are 1 and the
correction is a no-op. With `sgd_minibatch_size` set, the rollout is
consumed as minibatches of whole fragments, each an optimizer update
inside the same program (`num_sgd_iter` passes), so from the second
minibatch on the learner's params have left the rollout's and the
correction runs (`is_ratio_mean` / `is_ratio_max` in the stats). The loss
is byte-for-byte the one the async (Sebulba / remote-worker) paths feed
off-policy: one loss serves both feeding architectures.

Policy state. A stateful policy (`policy.recurrent`: an LSTM's `(c, h)`, a
transformer's key/value cache) has its state carried through the rollout
scan as a pytree and reset where the previous step ended an episode. A
recurrent policy's fragments are replayed by the learner from the state
the rollout began them with (`state_in`). A policy whose state is a context
of `context_len` positions (a transformer's caches, whatever each layer
keeps of them: every position, or a ring of its own window) is replayed
from an empty context, so its fragments must be whole episodes: the
optimizer refuses anything else. Where a policy's episode has a position
more than the env has steps (a block policy's, below), whole episodes are
counted in positions.

Block policies. A policy that declares a `block_len` L (generation by
diffusion over blocks: `JaxPolicy.block_step_state`) yields L positions a
slot a step, sampled inside the policy over several passes. The rollout
scan is then over BLOCKS, T / L iterations a fragment of T positions, and
the env is stepped inside an iteration once for each position the policy
generated; an episode's first position is GIVEN (the env's own first
observation, which the block's passes read unmasked): the env is not
stepped for it, it earns nothing, its row weighs nothing in the loss, and
`steps` counts ACTIONS, a fragment's rows less its given ones. An episode
is whole blocks (env `episode_len` + 1 positions), so an episode ends where
a block does.

Trajectory. A step keeps obs, action, reward, done, and the behaviour
policy's distribution inputs; where those are too wide to keep
(`policy.keeps_dist_inputs`, decided from the action space's size) it
keeps the taken action's log-probability and the value in their place. A
block policy's step keeps a row a position: the token there, its reward and
done, its log-probability at the pass it was unmasked at and that pass's
number (`sb.UNMASK_STEPS`, -1 where given), and the block's one value.

Packing. The learner's batch is packed fragments, env-major: row
`n * T + t` is step `t` of env slot `n`. The columns that are a scalar or
a short vector a step (actions, rewards, dones, log-probabilities,
values, narrow logits; a token or CartPole's four numbers as the
observation) are stacked `[T, N, ..]` by the rollout's scan and
transposed (`em`): 65,536 scalars a column, and the transposition is the
one `vtrace_loss` undoes. Observations that are arrays a step (frames:
`obs.ndim > 2`) are not stacked. On the chip every activation of such a
program is laid out batch-minor (the rows lie along the lanes), so
env-major rows interleave the `T` steps along the lanes: a transposed
copy of every frame whatever the dtype, and no in-place write can make
it (a 4-byte write every `4 T` bytes). What a row-wise model needs is
the frames, not their order. So where the policy is feed-forward, the
rollout is learned in one update and a device's env slots fill whole
lane tiles, step `t` writes its frames in place, in the env's dtype, at
rows `[t * N, (t + 1) * N)` of a buffer `[T * N, ..]` a device
(`write_step`: one aligned `dynamic_update_slice` along the lanes,
carried through both scans so that it is zeroed once a call). The batch
hands that buffer over as `sb.OBS_TIME_MAJOR`; `sb.OBS` is its
env-major view, bit for bit what `em` of the stacked frames was, which
XLA never lays out unless a loss reads it. `vtrace_policy.forward_counted`
feeds the model the rows as they lie and puts its logits and values in
`sb.OBS`'s order. Every other case (scalar observations, stateful
policies, minibatches, env counts that leave a lane tile part-filled)
stacks and transposes as before. A block policy's columns are stacked
[T / L, N, L] and packed alike: row `n * T + p` is POSITION p of slot n,
and `sb.OBS` and `sb.ACTIONS` are both the token at it.
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..._private.profiling import PhaseClock, phase
from .. import sample_batch as sb
from .policy_optimizer import PolicyOptimizer

# Rows of one lane tile of the chip's layouts: a step's in-place write of
# `N` rows a device is a plain aligned store only where `N` is whole tiles
# (XLA:TPU read and wrote the whole buffer a step otherwise: 6.4 ms for
# 1.85 GB against 0.24 ms).
LANE_TILE = 128


class AnakinOptimizer(PolicyOptimizer):
    """Fused device-resident IMPALA (see module docstring)."""

    def __init__(self, workers, jax_env, num_envs: int,
                 rollout_fragment_length: int,
                 updates_per_call: int = 10,
                 sgd_minibatch_size: int = 0,
                 num_sgd_iter: int = 1,
                 seed: int = 0):
        super().__init__(workers)
        self.policy = workers.local_worker.policy
        self.env = jax_env
        self.num_envs = num_envs
        self.T = rollout_fragment_length
        self.updates_per_call = updates_per_call
        # Minibatches of whole fragments a rollout (1 = one update on all
        # of it), and passes over them.
        batch = num_envs * rollout_fragment_length
        self.minibatch = sgd_minibatch_size or batch
        if batch % self.minibatch or self.minibatch % self.T:
            raise ValueError(
                f"sgd_minibatch_size ({self.minibatch}) must tile the "
                f"rollout ({num_envs} envs x {self.T} steps) in whole "
                "fragments")
        self.num_sgd_iter = num_sgd_iter
        self.learner_stats: Dict = {}
        self._ep_reward_mean = float("nan")
        self._ep_len_mean = float("nan")
        self._episodes_total = 0
        self._grad_time_total = 0.0
        self._grad_calls = 0
        # The calling thread's time by phase: anakin.call, anakin.readback.
        self.clock = PhaseClock()

        policy = self.policy
        mesh_size = int(policy.mesh.devices.size) \
            if policy.mesh is not None else 1
        self._mesh_size = max(1, mesh_size)
        if num_envs % self._mesh_size:
            raise ValueError(
                f"num_envs ({num_envs}) must divide evenly across the "
                f"learner mesh ({mesh_size} devices)")
        context = getattr(policy.model, "context_len", None)
        # Rows of a fragment that no action filled: a block policy's
        # episode begins with a GIVEN position (the env's first
        # observation), so its episode is one position longer than its
        # actions.
        block = policy.block_len
        self._rows_given = 0
        if context is not None:
            episode = getattr(jax_env, "episode_len", None)
            positions = episode and episode + bool(block)
            if (not episode or positions > context or self.T % positions
                    or positions % (block or 1)):
                raise ValueError(
                    "a policy with a context of positions learns each "
                    "fragment from an empty one: rollout_fragment_length "
                    f"({self.T}) must be whole episodes of the env "
                    f"(episode_len {episode}: {positions} positions, in "
                    f"whole blocks of {block or 1}) and an episode must fit "
                    f"the context ({context} positions)")
            if block:
                self._rows_given = self.T // positions
        elif block:
            raise ValueError(
                "a policy that yields a block of positions a step states "
                "its context (`context_len`)")
        self._replays_state = policy.recurrent and context is None
        # Trace-time facts of the rollout's decode step and the learner's
        # pass over a minibatch of fragments, where the model states any:
        # host values put beside the program's stats.
        counters = getattr(policy.model, "static_counters", None)
        self._static_counters = {} if counters is None else counters(
            num_envs, self.T, policy.mesh.devices.flat[0].platform,
            self.minibatch)

        # Device-resident env state: one slot per env, batch-sharded.
        vreset = jax.vmap(self.env.reset)
        init_keys = jax.random.split(jax.random.PRNGKey(seed), num_envs)
        env_state, obs = jax.jit(
            vreset, out_shardings=(policy._bsharded, policy._bsharded))(
                init_keys)
        self._env_state = env_state
        self._obs = obs
        self._rng = jax.device_put(
            jax.random.PRNGKey(seed + 1), policy._repl)
        self._ep_rew = jax.device_put(
            jnp.zeros(num_envs, jnp.float32), policy._bsharded)
        self._ep_len = jax.device_put(
            jnp.zeros(num_envs, jnp.int32), policy._bsharded)
        # (state, reset) of a stateful policy, () of a feedforward one:
        # every slot starts an episode.
        self._pstate = jax.device_put(
            (policy.initial_state(num_envs),
             jnp.ones(num_envs, jnp.float32)), policy._bsharded) \
            if policy.recurrent else ()
        self._anakin_fn = self._build_fn()

    # ------------------------------------------------------------------
    def learn(self, params, opt_state, batch, lkey):
        """One optimizer update on `batch` (packed fragments, as the
        rollout leaves them): what the fused program runs a minibatch,
        traceable. Returns (params, opt_state, the loss's stats)."""
        policy = self.policy
        with jax.named_scope("anakin/loss"):
            (loss, stats), grads = jax.value_and_grad(
                policy._loss_fn, argnums=1, has_aux=True)(
                    policy, params, batch, lkey, policy.loss_state)
        with jax.named_scope("anakin/update"):
            updates, opt_state = policy.optimizer.update(
                grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, stats

    def _build_fn(self):
        policy = self.policy
        env = self.env
        N, T, M = self.num_envs, self.T, self.updates_per_call
        vstep = jax.vmap(env.step)

        def em(x):
            """[T, N, ...] -> env-major flat [N*T, ...]."""
            return jnp.swapaxes(x, 0, 1).reshape((N * T,) + x.shape[2:])

        num_mb = (N * T) // self.minibatch
        mb_frags = self.minibatch // T
        stateful = policy.recurrent
        one_update_a_rollout = num_mb == 1 and self.num_sgd_iter == 1
        # Frames written where the learner reads them (module docstring):
        # a buffer [D, T * n, ..], D the devices the env slots are sharded
        # over, n the slots of one.
        D, n = self._mesh_size, N // self._mesh_size
        row = self._obs.shape[1:]
        in_place = (len(row) > 1 and not stateful and one_update_a_rollout
                    and n % LANE_TILE == 0)

        def write_step(frames, obs, t):
            zero = jnp.zeros((), jnp.int32)
            return jax.lax.dynamic_update_slice(
                frames, obs.reshape((D, n) + row),
                (zero, t * n) + (zero,) * len(row),
                allow_negative_indices=False)

        def tally(ep_rew, ep_len, ep_acc, reward, done, stepped=1):
            """Episode bookkeeping of one env step of all slots (`stepped`:
            1, or 0 for a slot that was not stepped): the running sums, the
            completed episodes' sums and count, and `done` as float32."""
            ep_rew = ep_rew + reward
            ep_len = ep_len + stepped
            donef = done.astype(jnp.float32)
            ep_acc = (ep_acc[0] + jnp.sum(donef * ep_rew),
                      ep_acc[1] + jnp.sum(donef * ep_len),
                      ep_acc[2] + jnp.sum(donef))
            ep_rew = jnp.where(done, 0.0, ep_rew)
            ep_len = jnp.where(done, 0, ep_len)
            return ep_rew, ep_len, ep_acc, donef

        def rollout_step(params, scarry, t):
            """Env step `t` of all slots under `params`: the carry, and
            (the step of the trajectory, what a stateful model counted)."""
            (env_state, obs, rng, ep_rew, ep_len, ep_acc, pstate,
             frames) = scarry
            with jax.named_scope("anakin/inference"):
                rng, akey, ekey = jax.random.split(rng, 3)
                counted = {}
                if stateful:
                    dist_inputs, value, state, counted = policy.step_state(
                        params, obs, *pstate)
                else:
                    dist_inputs, value = policy.apply(params, obs)
                dist = policy.dist_class(dist_inputs)
                action = dist.sample(akey)
                kept = (dist_inputs,) if policy.keeps_dist_inputs \
                    else (dist.logp(action), value)
            with jax.named_scope("anakin/env_step"):
                env_state, next_obs, reward, done = vstep(
                    env_state, action, jax.random.split(ekey, N))
                ep_rew, ep_len, ep_acc, donef = tally(
                    ep_rew, ep_len, ep_acc, reward, done)
                if stateful:
                    pstate = (state, donef)
            if in_place:
                with jax.named_scope("anakin/pack"):
                    frames = write_step(frames, obs, t)
            out = (None if in_place else obs, action, reward, done) + kept
            return (env_state, next_obs, rng, ep_rew, ep_len, ep_acc,
                    pstate, frames), (out, counted)

        L = policy.block_len

        def block_rollout_step(params, scarry, _):
            """`rollout_step` of a policy whose step is a block of L
            positions a slot: the policy samples the block
            (`block_step_state`), then the env takes its tokens one by one,
            but for a GIVEN one (the episode's first position: the env's own
            observation), which the env is not stepped for and which earns
            nothing. The block's rows are positions: (token, reward, done,
            log-probability, unmask step) [N, L] and one value [N]."""
            (env_state, obs, rng, ep_rew, ep_len, ep_acc, pstate,
             frames) = scarry
            with jax.named_scope("anakin/inference"):
                rng, akey, ekey = jax.random.split(rng, 3)
                tokens, logp, unmask, value, state, counted = \
                    policy.block_step_state(params, obs, *pstate, akey)
            with jax.named_scope("anakin/env_step"):
                rewards, dones = [], []
                for j in range(L):
                    given = unmask[:, j] < 0
                    stepped, next_obs, reward, done = vstep(
                        env_state, tokens[:, j],
                        jax.random.split(jax.random.fold_in(ekey, j), N))
                    env_state = jax.tree.map(
                        lambda old, new: jnp.where(given.reshape(
                            (N,) + (1,) * (new.ndim - 1)), old, new),
                        env_state, stepped)
                    obs = jnp.where(given, obs, next_obs)
                    reward = jnp.where(given, 0.0, reward)
                    done = done & ~given
                    ep_rew, ep_len, ep_acc, donef = tally(
                        ep_rew, ep_len, ep_acc, reward, done,
                        (~given).astype(ep_len.dtype))
                    rewards.append(reward)
                    dones.append(done)
                # An episode is whole blocks: it ends with one.
                pstate = (state, donef)
            out = (tokens, jnp.stack(rewards, 1), jnp.stack(dones, 1), logp,
                   unmask, value)
            return (env_state, obs, rng, ep_rew, ep_len, ep_acc, pstate,
                    frames), (out, counted)

        def block_batch_of(traj, obs):
            """A block policy's rollout as the learner's packed fragment
            batch: a row a POSITION, `n * T + p` position p of slot n; the
            token at it is what the model reads (`sb.OBS`) and what the
            loss takes the log-probability of (`sb.ACTIONS`)."""
            def rows(x):
                """[T / L, N, L] -> [N * T]."""
                return jnp.swapaxes(x, 0, 1).reshape(N * T)
            tokens, rew, done, logp, unmask, value = traj
            tokens = rows(tokens)
            return {
                sb.OBS: tokens,
                sb.ACTIONS: tokens,
                sb.REWARDS: rows(rew),
                sb.DONES: rows(done).astype(jnp.float32),
                sb.ACTION_LOGP: rows(logp),
                sb.UNMASK_STEPS: rows(unmask),
                # A block's value, at each of its rows.
                sb.VF_PREDS: rows(jnp.repeat(value[..., None], L, axis=-1)),
                sb.BOOTSTRAP_OBS: obs,
            }

        def batch_of(traj, obs, pstate_in, frames):
            """The rollout as the learner's packed fragment batch."""
            if L:
                return block_batch_of(traj, obs)
            obs_t, act_t, rew_t, done_t, *kept = traj
            if in_place:
                view = frames.reshape((D, T, n) + row)
                packed = sb.packed_from_time_major(view)
            else:
                packed = em(obs_t)
            batch = {
                sb.OBS: packed,
                sb.ACTIONS: em(act_t),
                sb.REWARDS: em(rew_t),
                sb.DONES: em(done_t).astype(jnp.float32),
                sb.BOOTSTRAP_OBS: obs,
            }
            if policy.keeps_dist_inputs:
                # Behaviour log-probs equal target log-probs
                # on-policy; losses that want them recompute from
                # the logits.
                batch[sb.ACTION_DIST_INPUTS] = em(kept[0])
            else:
                batch[sb.ACTION_LOGP] = em(kept[0])
                batch[sb.VF_PREDS] = em(kept[1])
            if in_place:
                batch[sb.OBS_TIME_MAJOR] = view
            if self._replays_state:
                batch[sb.STATE_IN], batch["reset_in"] = pstate_in
            return batch

        learn = self.learn
        # A scan iteration is an env step a slot, or a block of them.
        a_step, scan_len = (block_rollout_step, T // L) if L \
            else (rollout_step, T)

        def learn_minibatches(params, opt_state, batch, lkey):
            """`num_sgd_iter` passes over the rollout, `num_mb` updates a
            pass, fragments in rollout order. Row columns are [N*T, ..],
            fragment columns (BOOTSTRAP_OBS, STATE_IN, reset_in) [N, ..]."""
            def split(x):
                rows = self.minibatch if x.shape[0] == N * T else mb_frags
                return x.reshape((num_mb, rows) + x.shape[1:])
            mbs = jax.tree.map(split, batch)

            def mb_step(carry, mb):
                params, opt_state = carry
                params, opt_state, stats = learn(
                    params, opt_state, mb, lkey)
                return (params, opt_state), stats

            def one_pass(carry, _):
                return jax.lax.scan(mb_step, carry, mbs)

            (params, opt_state), stats = jax.lax.scan(
                one_pass, (params, opt_state), None,
                length=self.num_sgd_iter)
            return params, opt_state, reduce_stats(stats)

        def reduce_stats(stats):
            """Scalar stats of several updates as one: a `*_max` is the
            largest, anything else the mean."""
            return {k: jnp.max(v) if k.endswith("_max") else jnp.mean(v)
                    for k, v in stats.items()}

        # Every op of the program sits under one of the scopes
        # `anakin/{inference,env_step,pack,loss,update}` (`jax.named_scope`:
        # op metadata, nothing at run time), so a trace attributes device
        # time by name; `pack` is what lays the trajectory out for the
        # learner (the in-place write, `em`, `batch_of`); a rollout with
        # its own update loop also has `anakin/decode` and `anakin/learn`
        # around the two halves. Scopes nest where a scan is called inside
        # one; an op belongs to the innermost (last) `anakin/<scope>` of
        # its name.
        def one_update(carry, _):
            (params, opt_state, env_state, obs, rng,
             ep_rew, ep_len, ep_acc, pstate, frames) = carry
            pstate_in = pstate

            # The rollout loop's own ops (stacking the trajectory) count
            # as env_step.
            with jax.named_scope("anakin/decode" if stateful
                                 else "anakin/env_step"):
                (env_state, obs, rng, ep_rew, ep_len, ep_acc, pstate,
                 frames), (traj, counted) = jax.lax.scan(
                        lambda c, t: a_step(params, c, t),
                        (env_state, obs, rng, ep_rew, ep_len, ep_acc,
                         pstate, frames),
                        jnp.arange(T) if in_place else None, length=scan_len)
            with jax.named_scope("anakin/pack"):
                batch = batch_of(traj, obs, pstate_in, frames)
            with jax.named_scope("anakin/loss"):
                rng, lkey = jax.random.split(rng)
            if one_update_a_rollout:
                params, opt_state, stats = learn(
                    params, opt_state, batch, lkey)
            else:
                with jax.named_scope("anakin/learn"):
                    params, opt_state, stats = learn_minibatches(
                        params, opt_state, batch, lkey)
            # What the rollout's steps counted, as one value a rollout.
            stats = {**stats, **reduce_stats(counted)}
            return (params, opt_state, env_state, obs, rng,
                    ep_rew, ep_len, ep_acc, pstate, frames), stats

        @jax.named_scope("anakin/update")
        def anakin_fn(params, opt_state, env_state, obs, rng,
                      ep_rew, ep_len, pstate):
            ep_acc = (jnp.zeros((), jnp.float32),
                      jnp.zeros((), jnp.float32),
                      jnp.zeros((), jnp.float32))
            # Every slot of the buffer is overwritten each rollout: it is
            # carried through the updates and zeroed once a call.
            frames = jax.lax.with_sharding_constraint(
                jnp.zeros((D, T * n) + row, obs.dtype),
                policy._bsharded) if in_place else ()
            carry, stats = jax.lax.scan(
                one_update,
                (params, opt_state, env_state, obs, rng,
                 ep_rew, ep_len, ep_acc, pstate, frames),
                None, length=M)
            (params, opt_state, env_state, obs, rng,
             ep_rew, ep_len, ep_acc, pstate, _) = carry
            # One value a call for the M rollouts' scalar stats.
            stats = reduce_stats(stats)
            stats["_ep_reward_sum"] = ep_acc[0]
            stats["_ep_len_sum"] = ep_acc[1]
            stats["_ep_count"] = ep_acc[2]
            return params, opt_state, env_state, obs, rng, ep_rew, \
                ep_len, pstate, stats

        repl, bshard = policy._repl, policy._bsharded
        return jax.jit(
            anakin_fn,
            donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7),
            in_shardings=(repl, repl, bshard, bshard, repl, bshard,
                          bshard, bshard),
            out_shardings=(repl, repl, bshard, bshard, repl, bshard,
                           bshard, bshard, repl))

    # ------------------------------------------------------------------
    def step(self) -> dict:
        policy = self.policy
        t0 = time.perf_counter()
        self.clock.bind()
        with policy._update_lock:
            with phase("anakin.call"):
                (policy.params, policy.opt_state, self._env_state,
                 self._obs, self._rng, self._ep_rew, self._ep_len,
                 self._pstate, stats) = self._anakin_fn(
                    policy.params, policy.opt_state, self._env_state,
                    self._obs, self._rng, self._ep_rew, self._ep_len,
                    self._pstate)
            with phase("anakin.readback"):
                stats = {k: float(v) for k, v in stats.items()}
        policy._batch_on = len(self._obs.sharding.device_set)
        self._grad_time_total += time.perf_counter() - t0
        self._grad_calls += 1
        # Steps are actions: a fragment's rows but for the given ones.
        n = self.updates_per_call * self.num_envs * (
            self.T - self._rows_given)
        self.num_steps_sampled += n
        self.num_steps_trained += n
        policy.global_timestep += n
        from ..._private import metrics as metrics_mod
        metrics_mod.inc("rllib_steps_trained", n)
        metrics_mod.inc("rllib_steps_sampled", n)
        cnt = stats.pop("_ep_count")
        rew_sum = stats.pop("_ep_reward_sum")
        len_sum = stats.pop("_ep_len_sum")
        if cnt > 0:
            self._ep_reward_mean = rew_sum / cnt
            self._ep_len_mean = len_sum / cnt
            self._episodes_total += int(cnt)
        stats.update(self._static_counters)
        self.learner_stats = stats
        return stats

    def stats(self) -> dict:
        out = super().stats()
        out.update({
            "anakin": True,
            "updates_per_call": self.updates_per_call,
            # Episode metrics are device-aggregated (sum/count), not
            # per-episode records — the mean overrides the (empty)
            # sampler summary in Trainer results.
            "episode_reward_mean": self._ep_reward_mean,
            "episode_len_mean": self._ep_len_mean,
            "episodes_total": self._episodes_total,
            "timing": {
                "anakin_call_time_ms": round(
                    1000 * self._grad_time_total
                    / max(1, self._grad_calls), 3),
            },
        })
        return out

    def stop(self):
        pass
