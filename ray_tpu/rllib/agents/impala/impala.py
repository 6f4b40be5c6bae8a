"""IMPALA: Importance-Weighted Actor-Learner Architecture.

Parity: `rllib/agents/impala/impala.py:109` — V-trace policy +
`AsyncSamplesOptimizer`. The TPU learner owns the device mesh; CPU actor
workers stream packed fragments; weights broadcast back through the
object store (Podracer/Sebulba split).
"""

from __future__ import annotations

import logging

from ...optimizers.async_samples_optimizer import AsyncSamplesOptimizer
from ..trainer_template import build_trainer
from .vtrace_policy import DEFAULT_CONFIG, VTraceJaxPolicy

logger = logging.getLogger(__name__)


def make_async_optimizer(workers, config):
    if config.get("anakin"):
        from ...env.jax_env import make_jax_env
        from ...optimizers.anakin_optimizer import AnakinOptimizer
        return AnakinOptimizer(
            workers,
            jax_env=make_jax_env(config["env"], config.get("env_config")),
            num_envs=config["_anakin_num_envs"],
            rollout_fragment_length=config["rollout_fragment_length"],
            updates_per_call=config.get("anakin_updates_per_call", 10),
            sgd_minibatch_size=config.get("sgd_minibatch_size", 0),
            num_sgd_iter=config.get("num_sgd_iter", 1),
            seed=config.get("seed") or 0)
    return AsyncSamplesOptimizer(
        workers,
        train_batch_size=config["train_batch_size"],
        rollout_fragment_length=config["rollout_fragment_length"],
        max_sample_requests_in_flight_per_worker=config[
            "max_sample_requests_in_flight_per_worker"],
        broadcast_interval=config["broadcast_interval"],
        learner_queue_size=config["learner_queue_size"],
        num_sgd_iter=config["num_sgd_iter"],
        sgd_minibatch_size=config.get("sgd_minibatch_size", 0),
        # Minibatches shuffle/slice at fragment granularity so V-trace's
        # [B, T] reshape stays valid.
        sgd_sequence_length=config["rollout_fragment_length"],
        # Sebulba inline actors: batched TPU inference on the learner
        # process (see `InlineActorThread`).
        num_inline_actors=config.get("num_inline_actors", 0),
        inline_env=config.get("env"),
        inline_num_envs=config.get("_inline_num_envs", 1),
        inline_env_config=config.get("env_config"),
        inline_seed=config.get("seed"),
        device_rollouts=config.get("device_rollouts", "auto"),
        device_frame_stack=config.get("device_frame_stack", 0),
        obs_delta=config.get("obs_delta", "auto"),
        obs_delta_budget=config.get("obs_delta_budget", 256),
        # Sebulba pipeline gears (see evaluation/device_sampler.py):
        # double-buffered env groups + k-step on-device selection.
        sebulba_env_groups=config.get("sebulba_env_groups", 2),
        sebulba_onchip_steps=config.get("sebulba_onchip_steps", 1),
        weight_sync_codec=config.get("weight_sync_codec", "auto"))


def validate_config(config):
    if config.get("device_frame_stack") and \
            not config.get("num_inline_actors"):
        raise ValueError(
            "device_frame_stack only applies to the inline-actor "
            "(Sebulba) path; set num_inline_actors >= 1")
    if config.get("num_inline_actors"):
        if config.get("num_workers"):
            raise ValueError(
                "num_inline_actors and num_workers are alternative "
                "sampling architectures; set num_workers=0 for the "
                "inline (Sebulba) path or num_inline_actors=0 for "
                "remote rollout workers")
        if config.get("anakin"):
            raise ValueError(
                "num_inline_actors is ignored in anakin mode — the "
                "fused program does its own device-resident rollouts")
        onchip = config.get("sebulba_onchip_steps", 1)
        if onchip < 1:
            raise ValueError("sebulba_onchip_steps must be >= 1")
        if config["rollout_fragment_length"] % onchip:
            raise ValueError(
                "rollout_fragment_length must be a multiple of "
                "sebulba_onchip_steps (fragments tile whole k-step "
                "selection windows)")
        if config.get("sebulba_env_groups", 1) < 1:
            raise ValueError("sebulba_env_groups must be >= 1")
        # Inline actors own the real env batch; the local RolloutWorker
        # keeps a single probe env (spaces only).
        config["_inline_num_envs"] = config.get("num_envs_per_worker", 1)
        config["num_envs_per_worker"] = 1
        # One actor fragment IS the train batch in this mode; align the
        # config key so downstream consumers (and users reading results)
        # see the effective value instead of a silently-ignored one.
        effective = config["_inline_num_envs"] \
            * config["rollout_fragment_length"]
        if config.get("train_batch_size") not in (None, effective):
            logger.info(
                "inline-actor mode trains on whole %d-step fragments "
                "(num_envs_per_worker * rollout_fragment_length); "
                "overriding train_batch_size=%s",
                effective, config.get("train_batch_size"))
        config["train_batch_size"] = effective
    if config.get("anakin"):
        if config.get("num_workers"):
            raise ValueError(
                "anakin mode is fully device-resident; num_workers must "
                "be 0 (env slots come from num_envs_per_worker)")
        # The device-resident env slots are the optimizer's; the local
        # RolloutWorker keeps a single probe env (spaces only).
        config["_anakin_num_envs"] = config.get("num_envs_per_worker", 1)
        config["num_envs_per_worker"] = 1
        # Each fused update trains on one num_envs x T fragment batch.
        effective = config["_anakin_num_envs"] \
            * config["rollout_fragment_length"]
        if config.get("train_batch_size") not in (None, effective):
            logger.info(
                "anakin mode trains on whole %d-step fragment batches; "
                "overriding train_batch_size=%s",
                effective, config.get("train_batch_size"))
        config["train_batch_size"] = effective
    # A stateful policy (LSTM, transformer) trains on the packed fragments
    # themselves: one fragment = one sequence.
    config["_train_seq_len"] = config["rollout_fragment_length"]
    if config["train_batch_size"] % config["rollout_fragment_length"] != 0:
        raise ValueError(
            "train_batch_size must be a multiple of "
            "rollout_fragment_length (V-trace sequences reshape to "
            "[B, T] with no padding)")
    mb = config.get("sgd_minibatch_size", 0)
    if mb and mb % config["rollout_fragment_length"] != 0:
        raise ValueError(
            "sgd_minibatch_size must be a multiple of "
            "rollout_fragment_length")
    if not config.get("pack_fragments", True):
        raise ValueError("IMPALA requires pack_fragments=True")


IMPALATrainer = build_trainer(
    name="IMPALA",
    default_policy=VTraceJaxPolicy,
    default_config=DEFAULT_CONFIG,
    make_policy_optimizer=make_async_optimizer,
    validate_config=validate_config)
