"""Device-resident Sebulba sampler: observations ship to HBM once.

The round-3 inline-actor path (`vector_sampler.py`) shipped every
observation to the device TWICE — once for inference, once inside the
train batch — and fetched four arrays back per step (actions, logp,
dist_inputs, value). Through a bandwidth-limited host->device link that
is the whole bottleneck (VERDICT.md r3 weak #1). This sampler is the
Podracer/Sebulba answer (SURVEY.md §7.1; the reference's analogous
staging layer is `rllib/optimizers/aso_multi_gpu_learner.py:140`
`_LoaderThread`, which pre-loads tower buffers on the GPU):

- One device "apply" program per env step: upload newest frames ->
  (optional) on-device frame-stack update -> the step's observation
  batch, retained in HBM. One "select" program per WINDOW of k steps:
  model forward at the newest observation -> k sampled action arrays,
  fetched in a single [k, N] D2H copy (started async at dispatch).
- Every per-step device observation and every window's logp /
  dist_inputs / value handle is RETAINED as the programs returned it; at
  fragment end ONE compiled `pack` program takes the handles of all
  groups and returns the train batch's OBS / BOOTSTRAP_OBS /
  ACTION_DIST_INPUTS / ACTION_LOGP / VF_PREDS columns, laid out as the
  learner's batch — `JaxPolicy._device_batch` passes them through
  without a host round-trip. Host->device traffic per timestep drops to
  one frame (k x smaller again under `DeviceFrameStack`).
- THE ACTOR THREAD DISPATCHES COMPILED PROGRAMS ONLY (`apply_*`,
  `apply_full`, `select_fn`, `pack`) plus `jax.device_put` of host
  buffers: no `jnp` call, no index of a device array, no `jax.random`
  call runs outside `jit` in `sample()`. An eager op is a Python-level
  dispatch of a one-primitive program under the GIL; a dozen of them a
  group-step were a third of an actor thread, and those under
  `policy._update_lock` capped all actors together (PERF.md, PR 26).
  The window's key is folded from `(policy._host_rng, counter)` inside
  `select_fn`; the host only increments the counter.
- DELTA MODE (round 5; see `env/delta_obs.py`): when the env supports
  the delta protocol, the device retains the current frame batch in HBM
  and the host uploads only changed pixels ([N, K] uint16 indices +
  uint8 values, one XLA scatter) — full-frame rows only for resets and
  over-budget rows. For Atari-statistics frames this cuts per-step
  upload bytes ~9x below even the single-frame mode, which is what the
  15k steps/s/chip anchor requires of a multi-MB/s host->device link
  (VERDICT.md r4 next #1).

Round 6 breaks the action-fetch wall (BENCH_r05: `action_fetch_pct`
~387% — actors spent their wall-clock blocked in a synchronous
device round-trip per env step while the link sat at 45%):

- DOUBLE-BUFFERED ENV GROUPS (`sebulba_env_groups=G`): the actor's N
  env slots split into G groups with independent frame stacks / delta
  state / pending handles. While group B's inference + D2H fetch is in
  flight, group A's envs step on the host — the device round-trip
  hides behind the other groups' env stepping and dispatch work
  instead of serializing with it. Pipeline algebra: a serial actor's
  turn costs RTT + host_work; a grouped actor's turn costs
  ~max(RTT, G*host_work/G) + epsilon because each group's fetch has
  the other G-1 groups' host work in flight behind it. Groups hide
  HOST time under DEVICE time; they cannot shrink the RTT itself.
- K-STEP ON-DEVICE ACTION SELECTION (`sebulba_onchip_steps=k`, the
  opt-in second gear): the select program's jitted scan samples k
  action arrays against the retained device frames, so the host syncs
  with the device once per k env steps — the blocked RTT is amortized
  by k. The price is policy lag: the action for sub-step j of a window
  was selected from the observation at the window head, j steps stale
  (`POLICY_LAG` column records j per transition). The stored behavior
  logits/logp are the ones that ACTUALLY selected each action, so
  V-trace's importance ratios see the true behavior policy and absorb
  the lag — exactly the off-policyness IMPALA's correction exists for
  (PAPERS: "Podracer architectures for scalable RL").

Byte accounting is kept on the instance (`bytes_h2d`, `bytes_d2h`,
`policy_lag_sum`, `fetch_waits`); time accounting on `self.clock`, the
`PhaseClock` of whichever thread samples: every step of the loop below
is a `phase("sebulba.<step>")`, so the thread's wall time is partitioned
by name (`transfer_stats()["phases"]`; `t_fetch` / `t_env` are views of
it) and the same names are spans on a `jax.profiler` trace.
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from ..._private.profiling import PhaseClock, phase
from .. import sample_batch as sb
from ..sample_batch import SampleBatch
from .sampler import RolloutMetrics


@functools.partial(jax.jit, donate_argnums=(0,))
@jax.named_scope("sebulba/apply")
def apply_full(frames, rows, fulls):
    """Bucketed whole-row replacement: rows [b] int32 (pad == n,
    dropped), fulls [b, HW] uint8. One jitted function for every bucket
    and every sampler: jit keeps a program per shape."""
    return frames.at[rows].set(fulls, mode="drop")


@jax.named_scope("sebulba/pack")
def pack(obs, logp, di, val, boot):
    """A fragment's device columns from the handles an actor retained,
    per group: `obs` T step observations [n, ...]; `logp` T/k window
    arrays [k, n]; `di`, `val` T/k window arrays [n, A], [n] (a window's
    k steps share them, so each counts k times); `boot` the observation
    after the last step. Rows are env-major within a group ([n*T]: env 0's
    T steps, then env 1's), group 0 first."""
    k = logp[0][0].shape[0]

    def rows(per_group):  # G x [n, T, ...] -> [G*n*T, ...]
        a = jnp.concatenate(per_group)
        return a.reshape((-1,) + a.shape[2:])

    def windows(per_group):
        return rows([jnp.repeat(jnp.stack(w, axis=1), k, axis=1)
                     for w in per_group])

    return {
        sb.OBS: rows([jnp.stack(steps, axis=1) for steps in obs]),
        sb.ACTION_LOGP: rows([jnp.concatenate(w).T for w in logp]),
        sb.ACTION_DIST_INPUTS: windows(di),
        sb.VF_PREDS: windows(val),
        sb.BOOTSTRAP_OBS: jnp.concatenate(boot),
    }


@functools.lru_cache(maxsize=None)
def pack_program(sharding):
    """`pack` jitted with every column laid out as the learner's batch
    (`sharding` = `policy._bsharded`, so `_device_batch` has nothing to
    move). One function per mesh, shared by every sampler: jit keeps a
    program per fragment shape."""
    return jax.jit(pack, out_shardings=sharding)


def _full_bucket(count: int, n: int) -> int:
    """Rows of the full-row scatter that takes `count` of `n` rows: the
    next power of two, at most n."""
    return min(1 << (count - 1).bit_length(), n)


class _EnvGroup:
    """One double-buffered slice of an inline actor's env slots.

    Owns everything that must be independent for the group's device
    pipeline to run while its siblings' fetches are in flight: the env,
    frame stack, retained delta frames, episode bookkeeping, and the
    pending (dispatched, unfetched) select-program outputs.
    """

    def __init__(self, sampler: "DeviceSebulbaSampler", env, eps_base: int):
        self.env = env
        n = env.num_envs
        self.n = n
        self.ep_rew = np.zeros(n, np.float64)
        self.ep_len = np.zeros(n, np.int64)
        self.cur_eps = eps_base + np.arange(n, dtype=np.int64)
        self.host_done = np.ones(n, bool)
        # Dispatched select outputs: (actions[k,n], logp[k,n], di, val).
        self.pending = None
        # The fetched window: its actions are consumed sub-step by
        # sub-step; the device handles are retained whole for the pack.
        self.win_actions = None  # host [k, n]
        self.win_logp = None     # device [k, n]
        self.win_di = None       # device [n, A]
        self.win_val = None      # device [n]
        # Device obs for the NEXT transition (output of the last apply).
        self.obs_next = None
        policy = sampler.policy
        if sampler.frame_stack:
            space = env.observation_space
            self.stack = jax.device_put(
                np.zeros((n,) + space.shape, space.dtype),
                policy._bsharded)
        else:
            self.stack = None
        if sampler.delta:
            ds = env.vector_reset_delta()
            self.frames_d = jax.device_put(
                np.ascontiguousarray(ds.full_frames), policy._bsharded)
            sampler.bytes_h2d += ds.full_frames.nbytes
            self.host_delta = None
        else:
            self.host_obs = np.asarray(env.vector_reset())


class DeviceSebulbaSampler:
    """Steps BatchedEnv groups for T steps per sample(); obs live on
    device.

    Feedforward policies only (the LSTM path keeps host state threading;
    use `VectorSampler`). Output layout matches `VectorSampler`: flat
    [N*T] rows, fragment-major (group 0's envs first), plus per-fragment
    BOOTSTRAP_OBS — except the big columns are jax arrays already
    resident on the learner mesh.

    `batched_env` may be a single BatchedEnv (one group — the serial
    pipeline) or a list of same-sized BatchedEnvs (one per group).
    """

    def __init__(self, batched_env, policy,
                 rollout_fragment_length: int,
                 explore: bool = True,
                 eps_id_offset: int = 0,
                 use_delta: bool = True,
                 onchip_steps: int = 1):
        if getattr(policy, "recurrent", False):
            raise ValueError(
                "DeviceSebulbaSampler supports feedforward policies only")
        envs: List = (list(batched_env)
                      if isinstance(batched_env, (list, tuple))
                      else [batched_env])
        if len({e.num_envs for e in envs}) != 1:
            raise ValueError(
                "all env groups must have the same number of env slots; "
                f"got {[e.num_envs for e in envs]}")
        self.policy = policy
        self.T = rollout_fragment_length
        self.k = max(1, int(onchip_steps))
        if self.T % self.k:
            raise ValueError(
                f"rollout_fragment_length ({self.T}) must be a multiple "
                f"of sebulba_onchip_steps ({self.k}) — fragments tile "
                "whole selection windows")
        self.explore = explore
        self.frame_stack = int(getattr(
            envs[0], "device_frame_stack", 0))
        self.delta = bool(use_delta
                          and all(hasattr(e, "delta_budget") for e in envs))
        self._n = sum(e.num_envs for e in envs)
        self._eps_counter = eps_id_offset
        self.metrics: List[RolloutMetrics] = []
        # ---- transfer accounting (read by bench.py) ------------------
        self.bytes_h2d = 0       # delta entries / frames + flags shipped
        self.bytes_d2h = 0       # action arrays fetched down
        self.steps_total = 0
        self.policy_lag_sum = 0  # sum over transitions of selection lag
        self.fetch_waits = 0     # blocking D2H action fetches (windows)
        # The sampling thread's time by phase; bound in sample().
        self.clock = PhaseClock()

        if self.delta:
            frame_space = getattr(envs[0], "inner", envs[0])\
                .observation_space
            fs = frame_space.shape
            self._frame_shape = fs
            self._hw = int(np.prod(fs))

        self.groups: List[_EnvGroup] = []
        for env in envs:
            self.groups.append(
                _EnvGroup(self, env, self._eps_counter))
            self._eps_counter += env.num_envs
        self._build_fns()
        if self.delta:
            self._warm_full_buckets()
        # Prime every group's pipeline: obs_0 onto the device, first
        # selection window dispatched.
        for g in self.groups:
            self._dispatch_apply(g)
            self._dispatch_select(g)
        self._pack_fn = pack_program(policy._bsharded)
        self._warm_pack()

    # ------------------------------------------------------------------
    def _build_fns(self):
        policy = self.policy
        S = self.frame_stack
        k = self.k

        def update_stack(stack, frame, done):
            """Newest frame into the rolling [*, S] stack; episode
            boundary restarts the stack filled with the new episode's
            first frame (host FrameStack semantics, reference
            `atari_wrappers.py` FrameStack.reset)."""
            filled = jnp.broadcast_to(frame, stack.shape).astype(
                stack.dtype)
            rolled = jnp.concatenate(
                [stack[..., 1:], frame.astype(stack.dtype)], axis=-1)
            return jnp.where(done[:, None, None, None], filled, rolled)

        if self.delta:
            shape = self._frame_shape
            K = int(self.groups[0].env.delta_budget)

            @jax.named_scope("sebulba/apply")
            def apply_delta(stack, frames, packed):
                # frames: [N, HW] uint8 retained on device. packed:
                # [N, 3K+1] uint8 — ONE upload per step carrying the
                # sparse delta and done flags (layout in _pack_step:
                # idx as little-endian uint16 pairs | val | done).
                # Fewer per-step transfers matter on high-RTT links.
                n = frames.shape[0]
                idx = jax.lax.bitcast_convert_type(
                    packed[:, :2 * K].reshape(n, K, 2), jnp.uint16)
                val = packed[:, 2 * K:3 * K]
                done = packed[:, 3 * K] != 0
                frames = frames.at[
                    jnp.arange(n)[:, None], idx.astype(jnp.int32)].set(
                        val, mode="drop")
                frame = frames.reshape((n,) + shape)
                obs = update_stack(stack, frame, done) if S else frame
                return obs, frames

            # frames (arg 1) is donated: the old frame buffer is dead
            # once the new one exists; saves an HBM copy per step. The
            # stack is NOT donated — it aliases the previous step's obs,
            # which the train batch retains.
            self._apply_fn = jax.jit(apply_delta, donate_argnums=(1,))
        else:
            @jax.named_scope("sebulba/apply")
            def apply_frame(stack, frame, done):
                return update_stack(stack, frame, done) if S else frame

            self._apply_fn = jax.jit(apply_frame)

        @jax.named_scope("sebulba/select")
        def select_fn(params, obs, base, counter, explore):
            """Model forward at the newest obs, then k sampled action
            arrays. All k actions of a window are selected from THIS
            observation's distribution — sub-step j executes with lag j,
            and these dist_inputs/logp are the true behavior policy that
            V-trace corrects against. The window's key is derived here,
            `fold_in(base, counter)` as `policy._next_rng()` derives it,
            so the caller hands over a host integer and no eager op."""
            rng = jax.random.fold_in(base, counter)
            dist_inputs, value = policy.apply(params, obs)
            dist = policy.dist_class(dist_inputs)
            if k == 1:
                actions = jax.lax.cond(
                    explore,
                    lambda: dist.sample(rng),
                    lambda: dist.deterministic_sample())
                logp = dist.logp(actions)
                return actions[None], logp[None], dist_inputs, value

            def pick(carry, key):
                a = jax.lax.cond(
                    explore,
                    lambda: dist.sample(key),
                    lambda: dist.deterministic_sample())
                return carry, (a, dist.logp(a))

            _, (actions, logp) = jax.lax.scan(
                pick, 0, jax.random.split(rng, k))
            return actions, logp, dist_inputs, value

        self._select_fn = jax.jit(select_fn)

    def _pack_step(self, idx: np.ndarray, val: np.ndarray,
                   done: np.ndarray) -> np.ndarray:
        """One contiguous uint8 buffer per step (layout read back by
        `apply_delta`): [idx as LE uint16 bytes | val | done]."""
        assert idx.dtype == np.uint16
        return np.concatenate(
            [np.ascontiguousarray(idx).view(np.uint8),
             val, done.astype(np.uint8)[:, None]], axis=1)

    def _warm_full_buckets(self):
        """Run every bucket of `apply_full` once, as an all-pad scatter
        that leaves the frames as they are: which bucket a step needs
        depends on how many envs reset in it, and the first burst of a
        new size would otherwise compile in the middle of a run."""
        g, policy = self.groups[0], self.policy
        for b in sorted({_full_bucket(c, g.n) for c in range(1, g.n + 1)}):
            g.frames_d = apply_full(
                g.frames_d,
                jax.device_put(np.full(b, g.n, np.int32), policy._repl),
                jax.device_put(np.zeros((b, self._hw), np.uint8),
                               policy._repl))

    def _warm_pack(self):
        """Run the fragment's pack once on the primed handles, which have
        the shapes and shardings of every later step's and window's: the
        first fragment would otherwise compile it in the middle of a
        run."""
        steps, wins = self.T, self.T // self.k
        jax.block_until_ready(self._pack_fn(
            [[g.obs_next] * steps for g in self.groups],
            [[g.pending[1]] * wins for g in self.groups],
            [[g.pending[2]] * wins for g in self.groups],
            [[g.pending[3]] * wins for g in self.groups],
            [g.obs_next for g in self.groups]))

    # ------------------------------------------------------------------
    def _dispatch_apply(self, g: _EnvGroup):
        """Upload the group's newest env output and dispatch the obs
        apply (delta scatter / frame-stack update). Returns immediately
        (async JAX dispatch); `g.obs_next` is the device handle for the
        next transition's observation.
        """
        policy = self.policy
        done = g.host_done
        if self.delta:
            ds = g.host_delta
            if ds is not None and len(ds.full_rows):
                # Resets / over-budget rows: bucketed full-row scatter
                # ahead of the sparse delta (delta entries for these
                # rows are pad, per the DeltaStep contract).
                with phase("sebulba.upload"):
                    b = _full_bucket(len(ds.full_rows), g.n)
                    rows = np.full(b, g.n, np.int32)
                    rows[:len(ds.full_rows)] = ds.full_rows
                    fulls = np.zeros((b, self._hw), np.uint8)
                    fulls[:len(ds.full_rows)] = ds.full_frames
                    rows_d = jax.device_put(rows, policy._repl)
                    fulls_d = jax.device_put(fulls, policy._repl)
                    self.bytes_h2d += rows.nbytes + fulls.nbytes
                with phase("sebulba.apply"):
                    g.frames_d = apply_full(g.frames_d, rows_d, fulls_d)
            with phase("sebulba.upload"):
                if ds is None:
                    # First step after reset: frames already uploaded
                    # whole; an all-pad delta leaves them untouched.
                    from ..env.delta_obs import all_pad_delta
                    pad = all_pad_delta(
                        g.n, int(g.env.delta_budget), self._hw)
                    idx, val = pad.idx, pad.val
                else:
                    idx, val = ds.idx, ds.val
                packed = self._pack_step(idx, val, done)
                packed_d = jax.device_put(packed, policy._bsharded)
                self.bytes_h2d += packed.nbytes
            with phase("sebulba.apply"):
                g.obs_next, g.frames_d = self._apply_fn(
                    g.stack, g.frames_d, packed_d)
        else:
            frame = g.host_obs
            with phase("sebulba.upload"):
                frame_d = jax.device_put(frame, policy._bsharded)
                done_d = jax.device_put(done, policy._bsharded)
                self.bytes_h2d += frame.nbytes + done.nbytes
            with phase("sebulba.apply"):
                g.obs_next = self._apply_fn(g.stack, frame_d, done_d)
        if self.frame_stack:
            g.stack = g.obs_next

    def _dispatch_select(self, g: _EnvGroup):
        """Dispatch the selection window for the group's newest obs and
        start the D2H action copy so the eventual fetch is a cache hit.
        Reads live params — serialized against learner updates: the lock
        covers that read and the one call, nothing else."""
        policy = self.policy
        with phase("sebulba.lock_wait") as step:
            counter = policy._next_rng_counter()
            policy._update_lock.acquire()
            try:
                step.then("sebulba.select")
                out = self._select_fn(
                    policy.params, g.obs_next, policy._host_rng, counter,
                    self.explore)
            finally:
                policy._update_lock.release()
            out[0].copy_to_host_async()
            g.pending = out

    def _consume_window(self, g: _EnvGroup):
        """Block on the group's dispatched selection window — the ONLY
        device fetch on the hot path, one [k, n] array per k steps."""
        acts_d, logp_d, di_d, val_d = g.pending
        g.pending = None
        with phase("sebulba.fetch"):
            g.win_actions = np.asarray(acts_d)
        self.fetch_waits += 1
        self.bytes_d2h += g.win_actions.nbytes
        g.win_logp, g.win_di, g.win_val = logp_d, di_d, val_d

    # ------------------------------------------------------------------
    def sample(self) -> SampleBatch:
        self.clock.bind()
        T, k = self.T, self.k
        G = len(self.groups)
        obs_buf = [[] for _ in range(G)]
        logp_buf = [[] for _ in range(G)]
        di_buf = [[] for _ in range(G)]
        vf_buf = [[] for _ in range(G)]
        act_host = [[] for _ in range(G)]
        rew_buf = [[] for _ in range(G)]
        done_buf = [[] for _ in range(G)]
        eps_ids = [np.empty((T, g.n), np.int64) for g in self.groups]
        ts = [np.empty((T, g.n), np.int64) for g in self.groups]

        for t in range(T):
            jw = t % k
            for gi, g in enumerate(self.groups):
                if jw == 0:
                    # While this fetch blocks, every OTHER group's
                    # apply/select programs keep running on device —
                    # the double-buffering that hides the round-trip.
                    self._consume_window(g)
                with phase("sebulba.record"):
                    # Handles as they are: nothing here touches the
                    # device, `pack` takes the windows whole.
                    obs_buf[gi].append(g.obs_next)
                    if jw == 0:
                        logp_buf[gi].append(g.win_logp)
                        di_buf[gi].append(g.win_di)
                        vf_buf[gi].append(g.win_val)
                    actions = g.win_actions[jw]
                with phase("sebulba.env_step"):
                    if self.delta:
                        g.host_delta, rewards, dones = \
                            g.env.vector_step_delta(actions)
                    else:
                        next_obs, rewards, dones = \
                            g.env.vector_step(actions)
                        g.host_obs = np.asarray(next_obs)
                with phase("sebulba.record"):
                    eps_ids[gi][t] = g.cur_eps
                    ts[gi][t] = g.ep_len
                    act_host[gi].append(actions)
                    rew_buf[gi].append(np.asarray(rewards, np.float32))
                    done_buf[gi].append(np.asarray(dones))
                    g.ep_rew += rewards
                    g.ep_len += 1
                    if dones.any():
                        done_idx = np.nonzero(dones)[0]
                        for i in done_idx:
                            self.metrics.append(RolloutMetrics(
                                int(g.ep_len[i]), float(g.ep_rew[i])))
                        g.ep_rew[dones] = 0.0
                        g.ep_len[dones] = 0
                        g.cur_eps[dones] = self._eps_counter + np.arange(
                            len(done_idx), dtype=np.int64)
                        self._eps_counter += len(done_idx)
                    g.host_done = np.asarray(dones)
                    # Per-turn accounting (not per-fragment): the bench's
                    # windowed bytes-per-step ratio needs finer ticks than
                    # fragment completions on LOW-rate configs — the
                    # full-frame continuity line completes only ~2-3
                    # fragments per 10s window, quantizing the ratio by
                    # 2-3x. Total per fragment is unchanged.
                    self.steps_total += g.n
                # Prefetch: the obs apply for the NEXT step runs while
                # this turn finishes bookkeeping (and while the learner
                # trains); at window end the next selection dispatches.
                self._dispatch_apply(g)
                if jw == k - 1:
                    self._dispatch_select(g)

        with phase("sebulba.pack"):
            # Selection lag per transition: sub-step j of a window executed
            # an action chosen from the window-head obs, j steps stale.
            lags = (np.arange(T, dtype=np.int64) % k).astype(np.int32)
            self.policy_lag_sum += int(lags.sum()) * self._n

            # Each group's obs_next is the post-fragment bootstrap
            # observation AND step 0 of the next fragment — computed once.
            device_columns = self._pack_fn(
                obs_buf, logp_buf, di_buf, vf_buf,
                [g.obs_next for g in self.groups])

            def hpack(gbufs):
                parts = []
                for g, bufs in zip(self.groups, gbufs):
                    a = np.stack(bufs)
                    parts.append(np.swapaxes(a, 0, 1).reshape(
                        (g.n * T,) + a.shape[2:]))
                return parts[0] if G == 1 else np.concatenate(parts, axis=0)

            def hpack_tn(arrs):
                return np.concatenate(
                    [np.swapaxes(a, 0, 1).reshape(-1) for a in arrs])

            return SampleBatch({
                **device_columns,
                sb.ACTIONS: hpack(act_host),
                sb.REWARDS: hpack(rew_buf),
                sb.DONES: hpack(done_buf),
                sb.EPS_ID: hpack_tn(eps_ids),
                sb.T: hpack_tn(ts),
                sb.POLICY_LAG: np.tile(lags, self._n),
            })

    def get_metrics(self) -> List[RolloutMetrics]:
        out = self.metrics
        self.metrics = []
        return out

    @property
    def t_fetch(self) -> float:
        """Seconds the host was blocked waiting for actions."""
        return self.clock.seconds("sebulba.fetch")

    @property
    def t_env(self) -> float:
        """Seconds the host spent inside the envs' vector step."""
        return self.clock.seconds("sebulba.env_step")

    def transfer_stats(self) -> dict:
        return {
            "bytes_h2d": self.bytes_h2d,
            "bytes_d2h": self.bytes_d2h,
            "t_fetch_s": round(self.t_fetch, 3),
            "t_env_s": round(self.t_env, 3),
            "steps": self.steps_total,
            "policy_lag_sum": self.policy_lag_sum,
            "fetch_waits": self.fetch_waits,
            "phases": self.clock.snapshot(),
        }
