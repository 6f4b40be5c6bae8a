"""IMPALA-style async optimizer: decoupled sampling and learning.

Parity: `rllib/optimizers/async_samples_optimizer.py:19`
(`AsyncSamplesOptimizer`), `aso_learner.py:13` (`LearnerThread`),
`aso_aggregator.py:178` (`SimpleAggregator`).

TPU re-architecture (Podracer/Sebulba shape, SURVEY.md §7.1): CPU actor
workers sample continuously with up to K requests in flight; the learner
thread owns the TPU mesh and runs one donated-buffer XLA update per train
batch. Host→device staging happens on the learner thread right before the
update while the previous update is still executing on device (JAX
dispatch is async), double-buffering the feed — the replacement for the
reference's `_LoaderThread` (`aso_multi_gpu_learner.py:140`).
"""

from __future__ import annotations

import logging
import os
import queue
import tempfile
import threading
import time
from typing import List

import ray_tpu

from ..._private.profiling import (PhaseClock, host_snapshot, off_cpu_s,
                                   phase, sum_snapshots)
from ..sample_batch import SampleBatch
from ..utils.actors import TaskPool
from ..utils.compression import decompress_batch
from ..utils.window_stat import WindowStat
from .policy_optimizer import PolicyOptimizer

logger = logging.getLogger(__name__)

LEARNER_QUEUE_MAX_SIZE = 16


class LearnerThread(threading.Thread):
    """Consumes train batches from inqueue, updates the policy on device.

    Parity: `aso_learner.py:13`. Runs on the trainer process so rollout
    collection never blocks on the device update.
    """

    def __init__(self, local_worker, learner_queue_size: int = 16,
                 num_sgd_iter: int = 1, sgd_minibatch_size: int = 0,
                 sgd_sequence_length: int = 1):
        super().__init__(daemon=True, name="learner")
        self.local_worker = local_worker
        self.inqueue: "queue.Queue[SampleBatch]" = queue.Queue(
            maxsize=learner_queue_size)
        self.outqueue: "queue.Queue" = queue.Queue()
        self.num_sgd_iter = num_sgd_iter
        self.sgd_minibatch_size = sgd_minibatch_size
        self.sgd_sequence_length = sgd_sequence_length
        self.stopped = False
        self.weights_updated = False
        self.stats = {}
        self.error = None  # first exception that killed the thread
        self.learner_queue_size = WindowStat("learner_queue_size", 50)
        self.grad_timer = _Timer()
        # This thread's time by phase: learner.dequeue here, and
        # learner.h2d / lock_wait / train / readback inside the policy's
        # learn calls (the span of grad_timer).
        self.clock = PhaseClock()
        # The wait for a batch, as a timer's readings: a view of the clock.
        self.queue_timer = _PhaseTimer(self.clock, "learner.dequeue")
        self.daemon = True
        self._hbm_last = 0.0

    def run(self):
        self.clock.bind()
        while not self.stopped:
            try:
                self.step()
            except Exception as e:  # noqa: BLE001 — surfaced to driver
                logger.exception("learner thread died")
                self.error = e
                self.stopped = True

    def step(self):
        from ..._private import metrics as metrics_mod
        t0 = time.perf_counter()
        with phase("learner.dequeue"):
            try:
                batch = self.inqueue.get(timeout=0.5)
            except queue.Empty:
                return
        # Phase histograms (one sample per consumed batch — empty-queue
        # timeouts stay out so the queue-wait distribution reflects
        # batches, not idle polling).
        metrics_mod.observe("learner_queue_wait_s",
                            time.perf_counter() - t0)
        self.learner_queue_size.push(self.inqueue.qsize())
        t1 = time.perf_counter()
        with self.grad_timer:
            policy = self.local_worker.policy
            if self.sgd_minibatch_size:
                # Sequence-granular shuffling keeps V-trace fragments
                # contiguous inside each minibatch.
                stats = policy.sgd_learn(
                    batch, self.num_sgd_iter, self.sgd_minibatch_size,
                    seq_len=self.sgd_sequence_length)
            else:
                for _ in range(self.num_sgd_iter):
                    stats = policy.learn_on_batch(batch)
            self.stats = stats
        metrics_mod.observe("learner_grad_s", time.perf_counter() - t1)
        now = time.monotonic()
        if now - self._hbm_last >= 2.0:
            # The learner owns the mesh, so its process is where HBM
            # peaks move: refresh the per-device used/peak/limit gauges
            # right after a grad step (the runtime's 2s metrics push
            # ships them; no-op without accelerators).
            self._hbm_last = now
            from ..._private import profiling as profiling_mod
            profiling_mod.publish_device_gauges()
        self.weights_updated = True
        metrics_mod.inc("rllib_steps_trained", batch.count)
        self.outqueue.put(batch.count)

    def stop(self):
        self.stopped = True


# An actor's phases that block on nothing but the interpreter: numpy and
# Python, no device call and no lock of the program's own.
INTERPRETER_PHASES = ("sebulba.env_step", "sebulba.record")


class InlineActorThread(threading.Thread):
    """Sebulba-style inline actor: steps a BatchedEnv on this process,
    with inference batched through the LEARNER's TPU policy (the policy's
    `_update_lock` serializes dispatch against concurrent updates), and
    feeds packed fragments straight into the learner queue.

    Replaces remote CPU-inference rollout workers on hosts where the
    chip would otherwise starve (VERDICT.md round-2 headline gap): no
    object-store hop, no weight broadcasts (the actor always reads the
    live params), one jitted inference call per step for all env slots.
    """

    def __init__(self, sampler, learner: LearnerThread, idx: int = 0):
        super().__init__(daemon=True, name=f"inline-actor-{idx}")
        self.sampler = sampler
        self.learner = learner
        self.idx = idx
        self.stopped = False
        self.error = None  # first exception that killed the thread
        self.steps_sampled = 0  # monotonic; read without lock (int swap)
        self._gauge_last = None
        # Pinned at construction: an actor orphaned by a failed stop()
        # must not fire occurrences into a controller some LATER
        # ray_tpu.init(chaos=...) installs — that would perturb the
        # new session's seeded occurrence streams.
        from ..._private import chaos
        self._chaos = chaos.controller

    def run(self):
        try:
            while not self.stopped:
                c = self._chaos
                if c is not None:
                    # actor.sample chaos: a targeted delay rule (param
                    # "a1@0.25") slows exactly one actor — the drill
                    # the straggler detector must attribute.
                    rule = c.fire("actor.sample", f"a{self.idx}")
                    if rule is not None and rule.kind == "delay":
                        time.sleep(rule.delay)
                batch = self.sampler.sample()
                self.steps_sampled += batch.count
                if not self.stopped:
                    # An actor whose stop/join raced a long in-flight
                    # sample must not ghost-write the aK gauges of a
                    # successor trainer's same-tag actor.
                    self._publish_pipeline_gauges()
                # On the clock that sample() bound to this thread.
                with phase("sebulba.enqueue"):
                    while not self.stopped:
                        try:
                            self.learner.inqueue.put(batch, timeout=1.0)
                            break
                        except queue.Full:
                            continue
        except Exception as e:  # noqa: BLE001 — surfaced to driver
            logger.exception("inline actor died")
            self.error = e
            self.stopped = True

    def _publish_pipeline_gauges(self):
        """Per-actor pipeline balance into the metrics plane (visible in
        `scripts stat --metrics` / Prometheus), so a pipeline regression
        shows up live instead of only inside a 10 s bench window:
        `sebulba_action_fetch_pct.aK` (host blocked on the device
        round-trip), `sebulba_env_step_pct.aK` (both shares of the
        actor thread's clock: phases `sebulba.fetch`, `sebulba.env_step`
        over `wall_s`), `sebulba_gil_wait_pct.aK` (wall less CPU seconds
        of the phases that block on nothing but the interpreter, over
        `wall_s`: the thread wanted the GIL, or a core, and did not have
        it; published for a fragment sampled inside a
        `profiling.phase_cpu_reads()` window, as a capture opens one), and
        `sebulba_policy_lag_steps.aK` (mean selection lag)."""
        if not hasattr(self.sampler, "transfer_stats"):
            return  # host-side VectorSampler: no device pipeline
        stats = self.sampler.transfer_stats()
        now, last = stats["phases"], self._gauge_last
        dt = now["wall_s"] - last["phases"]["wall_s"] if last else 0.0
        if last is not None and dt >= 0.5:
            from ..._private import metrics as metrics_mod
            tag = f"a{self.idx}"
            # Mean roll-up: the cluster series must stay a percentage
            # (4 actors at ~97% read ~97%, not the 387% a sum renders);
            # per-actor values stay attributable under per_node.
            for gauge, name in (
                    ("sebulba_action_fetch_pct", "sebulba.fetch"),
                    ("sebulba_env_step_pct", "sebulba.env_step")):
                spent = (now["seconds"].get(name, 0.0)
                         - last["phases"]["seconds"].get(name, 0.0))
                metrics_mod.set_gauge(
                    f"{gauge}.{tag}", 100.0 * spent / dt, rollup="mean")
            off_cpu = off_cpu_s(now, last["phases"], INTERPRETER_PHASES)
            if off_cpu is not None:
                metrics_mod.set_gauge(
                    f"sebulba_gil_wait_pct.{tag}", 100.0 * off_cpu / dt,
                    rollup="mean")
            dsteps = stats["steps"] - last["steps"]
            if dsteps > 0:
                metrics_mod.set_gauge(
                    f"sebulba_policy_lag_steps.{tag}",
                    (stats.get("policy_lag_sum", 0)
                     - last.get("policy_lag_sum", 0)) / dsteps,
                    rollup="mean")
        if last is None or dt >= 0.5:
            self._gauge_last = stats

    def stop(self):
        self.stopped = True


class AsyncSamplesOptimizer(PolicyOptimizer):
    """Keep workers sampling continuously; learn as batches arrive."""

    def __init__(self, workers,
                 train_batch_size: int = 500,
                 rollout_fragment_length: int = 50,
                 max_sample_requests_in_flight_per_worker: int = 2,
                 broadcast_interval: int = 1,
                 learner_queue_size: int = LEARNER_QUEUE_MAX_SIZE,
                 num_sgd_iter: int = 1,
                 sgd_minibatch_size: int = 0,
                 sgd_sequence_length: int = 1,
                 num_inline_actors: int = 0,
                 inline_env=None,
                 inline_num_envs: int = 1,
                 inline_env_config=None,
                 inline_seed=None,
                 device_rollouts: str = "auto",
                 device_frame_stack: int = 0,
                 obs_delta="auto",
                 obs_delta_budget: int = 256,
                 sebulba_env_groups: int = 1,
                 sebulba_onchip_steps: int = 1,
                 weight_sync_codec: str = "auto"):
        super().__init__(workers)
        self.train_batch_size = train_batch_size
        self.rollout_fragment_length = rollout_fragment_length
        self.broadcast_interval = broadcast_interval
        self.max_in_flight = max_sample_requests_in_flight_per_worker
        self.learner = LearnerThread(
            workers.local_worker,
            learner_queue_size=learner_queue_size,
            num_sgd_iter=num_sgd_iter,
            sgd_minibatch_size=sgd_minibatch_size,
            sgd_sequence_length=sgd_sequence_length)
        # The learner thread's grad timer IS this optimizer's learn
        # phase — alias it so the trainer's train_* gauges see it.
        self.timers["learn"] = self.learner.grad_timer
        self.learner.start()

        self.sample_tasks = TaskPool()
        self._batch_buffer: List[SampleBatch] = []
        self._batch_buffer_count = 0
        self.num_steps_since_broadcast = 0
        # The weight-sync delta plane: one encode+put per learner
        # update; per-worker versions route q8 deltas vs full blobs and
        # skip workers that already hold the current broadcast.
        from ..utils.weight_broadcast import WeightBroadcaster
        self._broadcaster = WeightBroadcaster(
            lambda: self.workers.local_worker.get_weights(),
            codec=weight_sync_codec)
        self.learner_stats = {}
        self._inline_actors: List[InlineActorThread] = []
        self._inline_sampled_seen = 0
        # The thread that calls step() in inline mode: one phase,
        # `driver.collect`, so that every Python thread of the path has a
        # clock and the process's CPU less theirs is its native threads'.
        self._driver_clock = PhaseClock()
        self._compiled = False
        # Straggler detection (straggler.py): per-actor throughput /
        # fetch-latency windows judged against the fleet median each
        # stats() call; verdicts ride into trainer results.
        from ..._private.straggler import StragglerDetector
        self._straggler = StragglerDetector()
        self._straggler_report = {}
        # Flag -> diagnosis (RAY_TPU_STRAGGLER_PROFILE): a flagged
        # inline actor gets a short stack capture of exactly its
        # thread; folded stacks land in <session>/logs and the paths
        # ride the straggler report.
        self._strag_capture = None
        from ..._private import config as _config
        if _config.get("RAY_TPU_STRAGGLER_PROFILE"):
            from ..._private import worker_state as _ws
            from ..._private.straggler import TriggeredCapture
            rt = _ws.get_runtime_or_none()
            out_dir = os.path.join(rt.session_dir, "logs") \
                if rt is not None else tempfile.gettempdir()
            self._strag_capture = TriggeredCapture(out_dir)
        self._strag_prev = {}
        self._strag_t0 = time.monotonic()
        self._worker_tags = {}
        self._worker_sampled = {}
        self._worker_fetch_s = {}
        self._worker_fetch_n = {}
        self._worker_last_task = {}

        if num_inline_actors > 0:
            from ..env.registry import make_batched_env
            from ..evaluation.device_sampler import DeviceSebulbaSampler
            from ..evaluation.vector_sampler import VectorSampler
            policy = workers.local_worker.policy
            mesh = getattr(policy, "mesh", None)
            mesh_size = int(mesh.devices.size) if mesh is not None else 1
            if inline_num_envs % max(1, mesh_size):
                raise ValueError(
                    f"num_envs_per_worker ({inline_num_envs}) must divide "
                    f"evenly across the learner mesh ({mesh_size} devices)"
                    " — fragment batches (and their per-fragment bootstrap"
                    " rows) are batch-sharded over the mesh")
            # Device-resident rollouts (see device_sampler.py): the
            # default for feedforward policies; LSTM keeps the host path.
            use_device = (
                device_rollouts is True
                or (device_rollouts == "auto"
                    and not getattr(policy, "recurrent", False)))
            if device_frame_stack and not use_device:
                raise ValueError(
                    "device_frame_stack requires device rollouts "
                    "(feedforward policy + device_rollouts auto/True)")
            onchip = max(1, int(sebulba_onchip_steps))
            if onchip > 1 and not use_device:
                raise ValueError(
                    "sebulba_onchip_steps > 1 requires device rollouts "
                    "(feedforward policy + device_rollouts auto/True) — "
                    "the host-side VectorSampler has no retained device "
                    "frames to select against")
            # Double-buffered env groups (device path only): the largest
            # group count <= requested that tiles both the env slots and
            # the mesh; host-path samplers have no device pipeline to
            # double-buffer, so they always run one group.
            groups = max(1, int(sebulba_env_groups)) if use_device else 1
            while groups > 1 and (
                    inline_num_envs % groups
                    or (inline_num_envs // groups) % max(1, mesh_size)):
                groups -= 1
            if use_device and groups != max(1, int(sebulba_env_groups)):
                logger.info(
                    "sebulba_env_groups=%s does not tile %d envs over a "
                    "%d-device mesh; running %d group(s)",
                    sebulba_env_groups, inline_num_envs, mesh_size,
                    groups)
            for ai in range(num_inline_actors):
                def _seed(gi):
                    if inline_seed is None:
                        return None
                    return inline_seed + 1000 * (ai + 1) + 131 * gi
                if use_device:
                    envs = [make_batched_env(
                        inline_env, inline_num_envs // groups,
                        inline_env_config, seed=_seed(gi),
                        device_frame_stack=device_frame_stack,
                        obs_delta=obs_delta,
                        obs_delta_budget=obs_delta_budget)
                        for gi in range(groups)]
                    sampler = DeviceSebulbaSampler(
                        envs if groups > 1 else envs[0], policy,
                        rollout_fragment_length,
                        eps_id_offset=(ai + 1) << 40,
                        use_delta=obs_delta is not False,
                        onchip_steps=onchip)
                else:
                    benv = make_batched_env(
                        inline_env, inline_num_envs, inline_env_config,
                        seed=_seed(0),
                        device_frame_stack=device_frame_stack,
                        obs_delta=False,
                        obs_delta_budget=obs_delta_budget)
                    sampler = VectorSampler(
                        benv, policy, rollout_fragment_length,
                        eps_id_offset=(ai + 1) << 40)
                self._inline_actors.append(
                    InlineActorThread(sampler, self.learner, idx=ai))
            for a in self._inline_actors:
                a.start()

        # Elastic fleet (fleet.py): membership policy over the remote
        # sampler fleet — grow/shrink/evict/preempt mid-run, straggler
        # remediation (RAY_TPU_STRAGGLER_EVICT), and the
        # actor_recovery_s clock from death/evict to first post-rejoin
        # sample.
        self._fleet = None
        self._worker_seq = len(workers.remote_workers)
        self._straggler_evict = _config.get("RAY_TPU_STRAGGLER_EVICT")
        if workers.remote_workers:
            self._broadcast_weights()
            for i, w in enumerate(workers.remote_workers):
                self._worker_tags[w] = f"w{i}"
                for _ in range(self.max_in_flight):
                    self.sample_tasks.add(w, w.sample.remote())
            from ..._private.fleet import FleetController
            self._fleet = FleetController(
                spawn=self._fleet_spawn, retire=self._fleet_retire,
                size=lambda: len(self.workers.remote_workers))
            self._fleet.publish()

    # ------------------------------------------------------------------
    @property
    def num_weight_broadcasts(self) -> int:
        return self._broadcaster.num_broadcasts

    @property
    def fleet(self):
        """The elastic-fleet controller (None without remote workers)."""
        return self._fleet

    def _fleet_spawn(self):
        """Mechanics of one fleet join (called by FleetController):
        spawn the actor at a fresh index/tag, bootstrap it through the
        versioned weight plane (delta when it still holds the current
        base, full blob for cold joins), and prime its in-flight sample
        requests."""
        w = self.workers.add_worker()
        tag = f"w{self._worker_seq}"
        self._worker_seq += 1
        self._worker_tags[w] = tag
        held = None
        try:
            held = ray_tpu.get(w.weight_sync_version.remote())
        except Exception:  # noqa: BLE001 — treat as a cold join
            held = None
        self._broadcaster.bootstrap(w, held or None)
        for _ in range(self.max_in_flight):
            self.sample_tasks.add(w, w.sample.remote())
        return w, tag

    def _fleet_retire(self, worker):
        """Mechanics of one fleet removal: drain the worker's in-flight
        sample tasks, prune its weight-sync version entry and straggler
        ledgers, and kill the actor. `worker=None` retires the newest
        member (shrink). Returns the retired tag (None = no-op)."""
        if worker is None:
            if not self.workers.remote_workers:
                return None
            worker = self.workers.remote_workers[-1]
        tag = self._worker_tags.pop(worker, None)
        if tag is None:
            return None  # already retired (double-eviction race)
        self.sample_tasks.remove_worker(worker)
        self._broadcaster.remove_worker(worker)
        self.workers.remove_worker(worker)
        for ledger in (self._worker_sampled, self._worker_fetch_s,
                       self._worker_fetch_n, self._worker_last_task,
                       self._strag_prev):
            ledger.pop(tag, None)
        return tag

    def save_learner_state(self):
        """Checkpoint the FULL learner state through the object plane:
        policy params + optax moments + loss state + timestep, plus the
        weight-sync encoder's version counter / receiver-view base / EF
        residual.
        A learner restored from the returned ref RESUMES — the
        versioned broadcast stream continues, so surviving workers keep
        their delta path instead of full-resyncing."""
        state = {
            "policy": self.workers.local_worker.policy.get_state(),
            "weight_sync": self._broadcaster.get_state(),
            "num_steps_sampled": self.num_steps_sampled,
            "num_steps_trained": self.num_steps_trained,
        }
        return ray_tpu.put(state)

    def restore_learner_state(self, state_or_ref) -> None:
        state = state_or_ref
        if not isinstance(state, dict):
            state = ray_tpu.get(state_or_ref)
        self.workers.local_worker.policy.set_state(state["policy"])
        self._broadcaster.set_state(state["weight_sync"])
        self.num_steps_sampled = state.get(
            "num_steps_sampled", self.num_steps_sampled)
        self.num_steps_trained = state.get(
            "num_steps_trained", self.num_steps_trained)

    def _broadcast_weights(self):
        self._broadcaster.broadcast()
        self.num_steps_since_broadcast = 0

    def step(self) -> dict:
        if self._inline_actors:
            return self._step_inline()
        if not self.workers.remote_workers:
            return self._step_local()
        sampled = 0
        trained = 0
        deadline = time.monotonic() + 60.0
        while (trained == 0 and time.monotonic() < deadline):
            sampled += self._pull_and_enqueue()
            while not self.learner.outqueue.empty():
                trained += self.learner.outqueue.get()
            if trained == 0:
                time.sleep(0.001)
        self.num_steps_sampled += sampled
        self.num_steps_trained += trained
        self.learner_stats = self.learner.stats
        return self.learner_stats

    def _pull_and_enqueue(self) -> int:
        """Collect finished sample tasks, refill in-flight requests, build
        train batches, and feed the learner (parity: SimpleAggregator
        `iter_train_batches` + optimizer `_step`)."""
        from ..._private import chaos
        sampled = 0
        for worker, ref in self.sample_tasks.completed(blocking_wait=True):
            tag = self._worker_tags.get(worker)
            tf0 = time.perf_counter()
            batch = ray_tpu.get(ref)
            fetch_dt = time.perf_counter() - tf0
            decompress_batch(batch)
            sampled += batch.count
            preempted = False
            if self._fleet is not None and tag is not None:
                # A replacement's first harvested sample closes its
                # actor_recovery_s clock.
                self._fleet.note_sample(tag)
                if chaos.controller is not None:
                    # agent.preempt: one occurrence per harvested sample
                    # task. A window:<start>:<period> rule turns this
                    # into the deterministic rolling-preemption
                    # schedule: the sampler that shipped the matching
                    # fragment is killed and replaced mid-run.
                    rule = chaos.controller.fire("agent.preempt", tag)
                    if rule is not None and rule.kind == "kill":
                        self._fleet.preempt(worker, tag)
                        preempted = True
            if tag is not None:
                # Per-worker throughput / fetch-latency ledger the
                # straggler detector windows over.
                self._worker_sampled[tag] = \
                    self._worker_sampled.get(tag, 0) + batch.count
                self._worker_fetch_s[tag] = \
                    self._worker_fetch_s.get(tag, 0.0) + fetch_dt
                self._worker_fetch_n[tag] = \
                    self._worker_fetch_n.get(tag, 0) + 1
                try:
                    self._worker_last_task[tag] = \
                        ref.id.task_id().hex()
                except Exception:
                    pass
            self._batch_buffer.append(batch)
            self._batch_buffer_count += batch.count
            if self._batch_buffer_count >= self.train_batch_size:
                train_batch = SampleBatch.concat_samples(self._batch_buffer)
                self._batch_buffer = []
                self._batch_buffer_count = 0
                try:
                    self.learner.inqueue.put(train_batch, timeout=30.0)
                except queue.Full:
                    logger.warning("learner queue full; dropping batch")
            # Refresh weights on the worker if the learner moved on.
            if self.learner.weights_updated and \
                    self.num_steps_since_broadcast >= self.broadcast_interval:
                self.learner.weights_updated = False
                self._broadcast_weights()
            self.num_steps_since_broadcast += 1
            if preempted:
                # The worker is dead and its replacement was already
                # primed by the fleet join path — nothing to resubmit.
                continue
            # Version-gated sync: a worker already holding the current
            # broadcast is skipped (no redundant re-send per completed
            # sample task); behind-base workers fall back to full blobs
            # via the handshake in the broadcaster.
            self._broadcaster.sync(worker)
            self.sample_tasks.add(worker, worker.sample.remote())
        return sampled

    def _step_inline(self) -> dict:
        """Inline-actor mode: actors run free on their own threads; one
        optimizer step = at least one learner update drained."""
        trained = 0
        # First step compiles the inference + learner programs; steady
        # state still allows for a slow host->device link.
        timeout = 600.0 if not self._compiled else 180.0
        deadline = time.monotonic() + timeout
        self._driver_clock.bind()
        with phase("driver.collect"):
            while trained == 0 and time.monotonic() < deadline:
                self._check_learner_alive()
                try:
                    trained += self.learner.outqueue.get(timeout=1.0)
                except queue.Empty:
                    continue
        if trained == 0:
            raise RuntimeError(
                "inline actors produced no trained batch within "
                f"{timeout}s (learner stalled?)")
        self._compiled = True
        while not self.learner.outqueue.empty():
            trained += self.learner.outqueue.get()
        sampled_total = sum(a.steps_sampled for a in self._inline_actors)
        self.num_steps_sampled += sampled_total - self._inline_sampled_seen
        self._inline_sampled_seen = sampled_total
        self.num_steps_trained += trained
        self.learner_stats = self.learner.stats
        return self.learner_stats

    def host_account(self) -> dict:
        """Cumulative time of this optimizer's own threads, the one handle
        a reader or an operator's script takes deltas of
        (`profiling.host_account(before, after)`), as
        `profiling.host_snapshot` makes it: `threads` = a
        `PhaseClock.snapshot()` each (wall and CPU seconds by phase) for
        the inline actors, the learner and the thread that calls
        `step()`; `process` = `profiling.process_cpu()`, native threads
        included; `t` the time of the reading."""
        named = [(a.name, a.sampler.clock) for a in self._inline_actors
                 if hasattr(a.sampler, "clock")]
        named.append((self.learner.name, self.learner.clock))
        named.append(("driver", self._driver_clock))
        return host_snapshot(named)

    def inline_episodes(self):
        """Drain episode metrics from inline-actor samplers (merged into
        trainer results by `Trainer._result_from_optimizer`)."""
        out = []
        for a in self._inline_actors:
            out.extend(a.sampler.get_metrics())
        return out

    def _check_learner_alive(self):
        """Fail fast with the real cause when the learner thread or an
        inline actor died (neither has a recovery path: any loss/device/
        env error kills its thread)."""
        if self.learner.error is not None:
            raise RuntimeError(
                "learner thread died") from self.learner.error
        if not self.learner.is_alive() and not self.learner.stopped:
            raise RuntimeError("learner thread exited unexpectedly")
        for a in self._inline_actors:
            if a.error is not None:
                raise RuntimeError("inline actor died") from a.error

    def _step_local(self) -> dict:
        """Degenerate num_workers=0 mode: sample locally, learn inline."""
        batches = []
        count = 0
        while count < self.train_batch_size:
            b = self.workers.local_worker.sample()
            batches.append(b)
            count += b.count
        train_batch = SampleBatch.concat_samples(batches)
        self.learner.inqueue.put(train_batch)
        # Generous timeout: the first update includes XLA compilation,
        # which can take minutes for large programs.
        deadline = time.monotonic() + 600.0
        trained = None
        while trained is None:
            self._check_learner_alive()
            try:
                trained = self.learner.outqueue.get(timeout=1.0)
            except queue.Empty:
                if time.monotonic() >= deadline:
                    raise RuntimeError(
                        "learner produced no result within 600s")
        self.num_steps_sampled += count
        self.num_steps_trained += trained
        self.learner_stats = self.learner.stats
        return self.learner_stats

    def _update_stragglers(self) -> dict:
        """Window the per-actor ledgers since the last call, render
        fleet-median verdicts, and push the side effects: the
        straggler_flags counters and ANNOTATE marks on the flagged
        workers' latest task records. Returns the stats()/trainer view
        (straggler.py module doc)."""
        now = time.monotonic()
        dt = now - self._strag_t0
        if dt < 0.5:
            return self._straggler_report
        cum = {}
        for a in self._inline_actors:
            tag = f"a{a.idx}"
            if hasattr(a.sampler, "transfer_stats"):
                ts = a.sampler.transfer_stats()
                cum[tag] = {"steps": a.steps_sampled,
                            "fetch_s": ts.get("t_fetch_s", 0.0),
                            "fetch_n": ts.get("steps", 0)}
            else:
                cum[tag] = {"steps": a.steps_sampled,
                            "fetch_s": None, "fetch_n": 0}
        for tag, steps in self._worker_sampled.items():
            cum[tag] = {"steps": steps,
                        "fetch_s": self._worker_fetch_s.get(tag, 0.0),
                        "fetch_n": self._worker_fetch_n.get(tag, 0)}
        samples = {}
        for tag, c in cum.items():
            prev = self._strag_prev.get(
                tag, {"steps": 0, "fetch_s": 0.0, "fetch_n": 0})
            sample = {"throughput": (c["steps"] - prev["steps"]) / dt}
            if c["fetch_s"] is not None:
                dn = c["fetch_n"] - prev["fetch_n"]
                if dn > 0:
                    sample["fetch_latency_s"] = \
                        (c["fetch_s"] - (prev["fetch_s"] or 0.0)) / dn
            samples[tag] = sample
        self._strag_prev = cum
        self._strag_t0 = now
        verdicts = self._straggler.update(samples)
        flagged = [t for t, v in verdicts.items() if v["flagged"]]
        if flagged:
            from ..._private import task_events as te
            from ..._private import worker_state as _ws
            rt = _ws.get_runtime_or_none()
            if rt is not None and hasattr(rt, "task_events"):
                for tag in flagged:
                    tid = self._worker_last_task.get(tag)
                    if tid:
                        rt.task_events.record(tid, te.ANNOTATE,
                                              straggler=tag)
        if flagged and self._fleet is not None and self._straggler_evict:
            # Remediation (RAY_TPU_STRAGGLER_EVICT=1): a flagged REMOTE
            # sampler is evicted and replaced instead of just
            # annotated. The fleet controller throttles per tag and
            # caps evictions per window; inline-actor tags (aK) are
            # threads of this process — nothing to evict.
            tag_to_worker = {t: w for w, t in self._worker_tags.items()}
            for tag in flagged:
                w = tag_to_worker.get(tag)
                if w is not None:
                    self._fleet.evict(w, tag, reason="straggler")
        if flagged and self._strag_capture is not None:
            for tag in flagged:
                # Inline-actor tags map to threads of THIS process, so
                # a targeted capture reaches them; remote-worker tags
                # have no local thread to sample.
                if tag.startswith("a") and tag[1:].isdigit():
                    self._strag_capture.maybe_trigger(
                        tag, thread_name=f"inline-actor-{tag[1:]}")
        self._straggler_report = self._straggler.report(verdicts)
        if self._strag_capture is not None:
            profiles = self._strag_capture.paths()
            if profiles:
                self._straggler_report["profiles"] = profiles
        return self._straggler_report

    def stats(self) -> dict:
        out = super().stats()
        out.update(self._broadcaster.stats())
        out.update({
            "num_weight_broadcasts": self.num_weight_broadcasts,
            "learner_queue": self.learner.learner_queue_size.stats(),
            "timing": {
                "learner_grad_time_ms": round(
                    1000 * self.learner.grad_timer.mean, 3),
                "learner_queue_wait_ms": round(
                    1000 * self.learner.queue_timer.mean, 3),
            },
        })
        transfer = [a.sampler.transfer_stats()
                    for a in self._inline_actors
                    if hasattr(a.sampler, "transfer_stats")]
        if transfer:
            out["transfer"] = {
                k: sum(t[k] for t in transfer) for k in transfer[0]
                if k != "phases"}
            out["transfer"]["phases"] = sum_snapshots(
                [t["phases"] for t in transfer])
        stragglers = self._update_stragglers()
        if stragglers:
            out["stragglers"] = stragglers
        if self._fleet is not None:
            out["fleet"] = self._fleet.stats()
        return out

    def stop(self):
        for a in self._inline_actors:
            a.stop()
        self.learner.stop()
        if self._strag_capture is not None:
            # Abort in-flight straggler captures BEFORE joining the
            # actors they sample.
            self._strag_capture.stop()
        for a in self._inline_actors:
            a.join(timeout=5.0)
        self.learner.join(timeout=5.0)


class _Timer:
    """Tiny context-manager timer (parity: ray.timer.TimerStat)."""

    def __init__(self):
        self.total = 0.0
        self.count = 0
        self._start = None

    def __enter__(self):
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.total += time.perf_counter() - self._start
        self.count += 1

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0


class _PhaseTimer:
    """A `_Timer`'s readings (`total`, `count`, `mean`) of one phase of a
    `PhaseClock`: what timed the phase's span a second time is a view of
    the clock."""

    def __init__(self, clock: PhaseClock, name: str):
        self._clock, self._name = clock, name

    @property
    def total(self) -> float:
        return self._clock.seconds(self._name)

    @property
    def count(self) -> int:
        return self._clock.count(self._name)

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0
