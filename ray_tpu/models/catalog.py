"""ModelCatalog: spaces → preprocessors, networks, action distributions.

Parity: `rllib/models/catalog.py` (`get_action_dist`:109, `get_model_v2`:254,
`get_preprocessor`:358) with the same MODEL_DEFAULTS vocabulary
(fcnet_hiddens, conv_filters, use_lstm, ...).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from ..rllib.env.spaces import Box, Discrete
from .distributions import get_action_dist  # re-export  # noqa: F401
from .networks import FullyConnectedNetwork, LSTMNetwork, VisionNetwork

MODEL_DEFAULTS = {
    "fcnet_hiddens": [256, 256],
    "fcnet_activation": "tanh",
    "conv_filters": None,  # None -> nature CNN for image obs
    "vf_share_layers": False,
    "free_log_std": False,
    "use_lstm": False,
    "lstm_cell_size": 256,
    "max_seq_len": 20,
    "framework": "jax",
    # Trunk compute dtype: "auto" defers to RAY_TPU_COMPUTE_DTYPE. At
    # the default f32 each network keeps its own default (the Vision
    # trunk stays bf16 for the MXU); "bf16"/"f32" force it everywhere.
    "compute_dtype": "auto",
    # A model family by name (`CUSTOM_MODELS`) with its own settings;
    # overrides the choice by observation space.
    "custom_model": None,
    "custom_model_config": {},
}


def _olmoe(obs_space, num_outputs, cfg, dtype):
    from .transformer import olmoe_from_config
    return olmoe_from_config(num_outputs, cfg, dtype)


def _glm4_moe_lite(obs_space, num_outputs, cfg, dtype):
    from .transformer import glm4_moe_lite_from_config
    return glm4_moe_lite_from_config(num_outputs, cfg, dtype)


def _smallthinker(obs_space, num_outputs, cfg, dtype):
    from .transformer import smallthinker_from_config
    return smallthinker_from_config(num_outputs, cfg, dtype)


def _lfm2_moe(obs_space, num_outputs, cfg, dtype):
    from .transformer import lfm2_moe_from_config
    return lfm2_moe_from_config(num_outputs, cfg, dtype)


def _kimi_linear(obs_space, num_outputs, cfg, dtype):
    from .transformer import kimi_linear_from_config
    return kimi_linear_from_config(num_outputs, cfg, dtype)


def _nemotron_h(obs_space, num_outputs, cfg, dtype):
    from .transformer import nemotron_h_from_config
    return nemotron_h_from_config(num_outputs, cfg, dtype)


def _sdar_moe(obs_space, num_outputs, cfg, dtype):
    from .transformer import sdar_moe_from_config
    return sdar_moe_from_config(num_outputs, cfg, dtype)


def _qwen3_next(obs_space, num_outputs, cfg, dtype):
    from .transformer import qwen3_next_from_config
    return qwen3_next_from_config(num_outputs, cfg, dtype)


def _laguna(obs_space, num_outputs, cfg, dtype):
    from .transformer import laguna_from_config
    return laguna_from_config(num_outputs, cfg, dtype)


# name -> builder(obs_space, num_outputs, custom_model_config, dtype or None)
CUSTOM_MODELS = {"olmoe": _olmoe, "glm4_moe_lite": _glm4_moe_lite,
                 "smallthinker": _smallthinker, "lfm2_moe": _lfm2_moe,
                 "kimi_linear": _kimi_linear, "nemotron_h": _nemotron_h,
                 "sdar_moe": _sdar_moe, "qwen3_next": _qwen3_next,
                 "laguna": _laguna}


def _resolve_compute_dtype(cfg):
    """MODEL_DEFAULTS["compute_dtype"] -> jnp dtype or None (= keep
    each network's own default)."""
    value = cfg.get("compute_dtype", "auto")
    explicit = value not in (None, "auto")
    from ..parallel import precision
    dtype = precision.resolve_compute_dtype(value)
    import jax.numpy as jnp
    if not explicit and dtype == jnp.float32:
        return None
    return dtype


class Preprocessor:
    """obs → flat/typed numpy (parity: `rllib/models/preprocessors.py`).

    Kept deliberately thin: images pass through as uint8 (normalized
    on-device in the network, so host→device stays 1 byte/pixel), Discrete
    becomes one-hot, Box passes through.
    """

    def __init__(self, obs_space):
        self.obs_space = obs_space
        if isinstance(obs_space, Discrete):
            self.shape = (obs_space.n,)
            self.dtype = np.float32
        else:
            self.shape = obs_space.shape
            self.dtype = obs_space.dtype if hasattr(obs_space, "dtype") \
                else np.float32

    def transform(self, obs):
        if isinstance(self.obs_space, Discrete):
            out = np.zeros(self.obs_space.n, dtype=np.float32)
            out[int(obs)] = 1.0
            return out
        return np.asarray(obs, dtype=self.dtype)

    @property
    def is_identity(self) -> bool:
        return not isinstance(self.obs_space, Discrete)

    def transform_batch(self, obs):
        """Vectorized transform for a [num_envs, ...] stack of raw obs."""
        if isinstance(self.obs_space, Discrete):
            idx = np.asarray(obs, dtype=np.int64)
            return np.eye(self.obs_space.n, dtype=np.float32)[idx]
        return np.asarray(obs, dtype=self.dtype)


def get_preprocessor(obs_space) -> Preprocessor:
    return Preprocessor(obs_space)


def is_image_space(obs_space) -> bool:
    return isinstance(obs_space, Box) and len(obs_space.shape) == 3


def get_model(obs_space, num_outputs: int, model_config: dict = None):
    """Build the flax module for this observation space.

    Returns a module whose __call__(obs) -> (dist_inputs, value).
    """
    cfg = dict(MODEL_DEFAULTS)
    cfg.update(model_config or {})
    if cfg["custom_model"]:
        if cfg["custom_model"] not in CUSTOM_MODELS:
            raise ValueError(
                f"unknown custom_model {cfg['custom_model']!r}; known: "
                f"{sorted(CUSTOM_MODELS)}")
        return CUSTOM_MODELS[cfg["custom_model"]](
            obs_space, num_outputs, dict(cfg["custom_model_config"] or {}),
            _resolve_compute_dtype(cfg))
    if cfg["use_lstm"]:
        # Recurrent trunk: JaxPolicy drives it through the recurrent path
        # (state threading in the sampler + sequence-major training,
        # parity: `rllib/policy/rnn_sequencing.py` + `lstm_v1.py`).
        return LSTMNetwork(
            num_outputs=num_outputs,
            cell_size=cfg["lstm_cell_size"],
            hiddens=tuple(cfg["fcnet_hiddens"]),
            activation=cfg["fcnet_activation"])
    dtype = _resolve_compute_dtype(cfg)
    if is_image_space(obs_space):
        filters = cfg["conv_filters"] or ((32, 8, 4), (64, 4, 2), (64, 3, 1))
        kwargs = {} if dtype is None else {"compute_dtype": dtype}
        return VisionNetwork(
            num_outputs=num_outputs,
            conv_filters=tuple(tuple(f) for f in filters), **kwargs)
    kwargs = {} if dtype is None else {"compute_dtype": dtype}
    return FullyConnectedNetwork(
        num_outputs=num_outputs,
        hiddens=tuple(cfg["fcnet_hiddens"]),
        activation=cfg["fcnet_activation"],
        vf_share_layers=cfg["vf_share_layers"],
        free_log_std=cfg["free_log_std"], **kwargs)


def observation_shape(obs_space) -> Tuple[int, ...]:
    if isinstance(obs_space, Discrete):
        return (obs_space.n,)
    return tuple(obs_space.shape)
