"""Policy/value networks in flax.

Parity: the reference model zoo — `rllib/models/tf/fcnet_v2.py`
(FullyConnectedNetwork), `rllib/models/tf/visionnet_v1.py` (Nature CNN),
`rllib/models/tf/lstm_v1.py` — re-designed for TPU:

- Every network returns `(dist_inputs, value)` from one forward pass, so
  rollout inference and the learner share a single fused XLA program.
- Vision nets compute in bfloat16 (MXU-native) with float32 heads/outputs.
- uint8 frames are normalized on-device (keeps host→device transfers at
  1 byte/pixel).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

Dtype = Any


def _activation(name: str) -> Callable:
    return {"tanh": nn.tanh, "relu": nn.relu, "swish": nn.swish,
            "elu": nn.elu}[name]


class FullyConnectedNetwork(nn.Module):
    """MLP with separate (or shared) policy and value towers."""

    num_outputs: int
    hiddens: Sequence[int] = (256, 256)
    activation: str = "tanh"
    vf_share_layers: bool = False
    free_log_std: bool = False  # Box policies: state-independent log_std
    # Trunk compute dtype (RAY_TPU_COMPUTE_DTYPE via catalog): params
    # stay f32 (flax casts per-layer); logits/value heads compute f32.
    compute_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, obs):
        act = _activation(self.activation)
        x = obs.reshape(obs.shape[0], -1).astype(self.compute_dtype)

        h = x
        for i, size in enumerate(self.hiddens):
            h = act(nn.Dense(size, name=f"fc_{i}",
                             dtype=self.compute_dtype)(h))
        h = h.astype(jnp.float32)
        num_out = self.num_outputs // 2 if self.free_log_std \
            else self.num_outputs
        logits = nn.Dense(num_out, name="logits",
                          kernel_init=nn.initializers.normal(0.01))(h)
        if self.free_log_std:
            log_std = self.param(
                "log_std", nn.initializers.zeros, (num_out,))
            logits = jnp.concatenate(
                [logits, jnp.broadcast_to(log_std, logits.shape)], axis=-1)

        if self.vf_share_layers:
            value = nn.Dense(1, name="value")(h)
        else:
            v = x
            for i, size in enumerate(self.hiddens):
                v = act(nn.Dense(size, name=f"vf_{i}",
                                 dtype=self.compute_dtype)(v))
            v = v.astype(jnp.float32)
            value = nn.Dense(1, name="value")(v)
        return logits, value[..., 0]


class VisionNetwork(nn.Module):
    """Nature-CNN for 84x84xC frames; bfloat16 conv trunk for the MXU."""

    num_outputs: int
    conv_filters: Sequence[Tuple[int, int, int]] = (
        (32, 8, 4), (64, 4, 2), (64, 3, 1))
    hidden: int = 512
    vf_share_layers: bool = True
    compute_dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, obs):
        x = obs.astype(self.compute_dtype) / jnp.asarray(
            255.0, self.compute_dtype)
        for i, (ch, k, s) in enumerate(self.conv_filters):
            x = nn.relu(nn.Conv(ch, (k, k), strides=(s, s), padding="VALID",
                                dtype=self.compute_dtype,
                                name=f"conv_{i}")(x))
        x = x.reshape(x.shape[0], -1)
        h = nn.relu(nn.Dense(self.hidden, dtype=self.compute_dtype,
                             name="fc")(x))
        h32 = h.astype(jnp.float32)
        logits = nn.Dense(self.num_outputs, name="logits",
                          kernel_init=nn.initializers.normal(0.01))(h32)
        value = nn.Dense(1, name="value")(h32)  # vision nets share trunk
        return logits, value[..., 0]


class QNetwork(nn.Module):
    """Q-value network for DQN-family policies.

    Parity: `rllib/agents/dqn/dqn_policy.py` QValuePolicy graphs (dueling /
    noisy options; we implement dueling). Returns `(q_values, max_q)` so it
    plugs into the standard `(dist_inputs, value)` policy interface —
    dist_inputs ARE the q-values and the greedy value doubles as the
    state-value estimate.

    3-D observations get a bfloat16 Nature-CNN trunk (MXU-native); flat
    observations get an MLP trunk.
    """

    num_actions: int
    hiddens: Sequence[int] = (256,)
    activation: str = "relu"
    dueling: bool = True
    conv_filters: Sequence[Tuple[int, int, int]] = (
        (32, 8, 4), (64, 4, 2), (64, 3, 1))
    compute_dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, obs):
        act = _activation(self.activation)
        if obs.ndim == 4:  # [B, H, W, C] image frames
            x = obs.astype(self.compute_dtype) / jnp.asarray(
                255.0, self.compute_dtype)
            for i, (ch, k, s) in enumerate(self.conv_filters):
                x = act(nn.Conv(ch, (k, k), strides=(s, s), padding="VALID",
                                dtype=self.compute_dtype,
                                name=f"conv_{i}")(x))
            h = x.reshape(x.shape[0], -1).astype(jnp.float32)
        else:
            h = obs.reshape(obs.shape[0], -1).astype(jnp.float32)
        for i, size in enumerate(self.hiddens):
            h = act(nn.Dense(size, name=f"fc_{i}")(h))
        if self.dueling:
            adv = nn.Dense(self.num_actions, name="advantage")(h)
            value = nn.Dense(1, name="state_value")(h)
            q = value + adv - jnp.mean(adv, axis=-1, keepdims=True)
        else:
            q = nn.Dense(self.num_actions, name="q")(h)
        return q, jnp.max(q, axis=-1)


class DeterministicActor(nn.Module):
    """mu(s) -> action in [low, high] (DDPG/TD3 actors).

    Parity: `rllib/agents/ddpg/ddpg_policy.py` policy network (tanh
    squash to the action bounds).
    """

    action_dim: int
    low: float = -1.0
    high: float = 1.0
    hiddens: Sequence[int] = (256, 256)
    activation: str = "relu"

    @nn.compact
    def __call__(self, obs):
        act = _activation(self.activation)
        h = obs.reshape(obs.shape[0], -1).astype(jnp.float32)
        for i, size in enumerate(self.hiddens):
            h = act(nn.Dense(size, name=f"fc_{i}")(h))
        raw = nn.Dense(self.action_dim, name="out",
                       kernel_init=nn.initializers.uniform(3e-3))(h)
        return self.low + (jnp.tanh(raw) + 1.0) \
            * (self.high - self.low) / 2.0


class StochasticActor(nn.Module):
    """pi(s) -> (mean, log_std) inputs for a SquashedGaussian (SAC)."""

    action_dim: int
    hiddens: Sequence[int] = (256, 256)
    activation: str = "relu"

    @nn.compact
    def __call__(self, obs):
        act = _activation(self.activation)
        h = obs.reshape(obs.shape[0], -1).astype(jnp.float32)
        for i, size in enumerate(self.hiddens):
            h = act(nn.Dense(size, name=f"fc_{i}")(h))
        return nn.Dense(2 * self.action_dim, name="out")(h)


class ContinuousQNetwork(nn.Module):
    """Q(s, a) -> scalar (DDPG/TD3/SAC critics); `twin` builds two
    independent towers and returns (q1, q2) (TD3/SAC clipped double-Q)."""

    hiddens: Sequence[int] = (256, 256)
    activation: str = "relu"
    twin: bool = False

    @nn.compact
    def __call__(self, obs, action):
        act = _activation(self.activation)
        x = jnp.concatenate(
            [obs.reshape(obs.shape[0], -1).astype(jnp.float32),
             action.astype(jnp.float32)], axis=-1)

        def tower(name):
            h = x
            for i, size in enumerate(self.hiddens):
                h = act(nn.Dense(size, name=f"{name}_fc_{i}")(h))
            return nn.Dense(1, name=f"{name}_out")(h)[..., 0]

        q1 = tower("q1")
        if self.twin:
            return q1, tower("q2")
        return q1, q1


class LSTMNetwork(nn.Module):
    """Feature trunk + LSTM core (parity: `lstm_v1.py` use_lstm wrapping).

    Call with (obs[B,T,...], state (c,h)[B,H], seq mask[B,T]) and get
    (dist_inputs[B,T,O], value[B,T], new_state). The scan runs over the
    time axis with `nn.scan` — XLA-friendly static unroll.
    """

    num_outputs: int
    cell_size: int = 256
    hiddens: Sequence[int] = (256,)
    activation: str = "tanh"

    @nn.compact
    def __call__(self, obs, state, reset_mask, rollout=False):
        # `rollout`: a stateful model's `JaxPolicy.step_state` says so; this
        # one has one form.
        act = _activation(self.activation)
        B, T = obs.shape[0], obs.shape[1]
        x = obs.reshape(B, T, -1).astype(jnp.float32)
        for i, size in enumerate(self.hiddens):
            x = act(nn.Dense(size, name=f"fc_{i}")(x))

        cell = nn.OptimizedLSTMCell(self.cell_size, name="lstm")

        def step(cell_obj, carry, inputs):
            xt, reset_t = inputs
            c, h = carry
            # Zero state at episode starts (reset_mask=1 at boundaries).
            keep = (1.0 - reset_t)[:, None]
            carry = (c * keep, h * keep)
            carry, out = cell_obj(carry, xt)
            return carry, out

        scan = nn.scan(step, variable_broadcast="params",
                       split_rngs={"params": False},
                       in_axes=1, out_axes=1)
        carry, outs = scan(cell, state, (x, reset_mask))
        logits = nn.Dense(self.num_outputs, name="logits",
                          kernel_init=nn.initializers.normal(0.01))(outs)
        value = nn.Dense(1, name="value")(outs)[..., 0]
        return logits, value, carry

    def initial_state(self, batch_size: int):
        return (jnp.zeros((batch_size, self.cell_size), jnp.float32),
                jnp.zeros((batch_size, self.cell_size), jnp.float32))
