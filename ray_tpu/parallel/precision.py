"""Learner compute precision: the `compute_dtype` knob and its cast.

Selection is per-trainer (`compute_dtype` config key) with the
`RAY_TPU_COMPUTE_DTYPE` registry knob as the `auto` fallback. bf16 casts
the parameters at the loss boundary only: master weights, gradients and
optax state stay f32, and bf16's f32-equal exponent range needs no loss
scaling.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

COMPUTE_DTYPES = {
    "f32": jnp.float32, "float32": jnp.float32,
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
}


def resolve_compute_dtype(value: Any = "auto"):
    """Resolve a `compute_dtype` config value to a jnp dtype."""
    if value in (None, "auto"):
        from .._private import config as config_mod
        value = config_mod.get("RAY_TPU_COMPUTE_DTYPE")
    if isinstance(value, str):
        key = value.lower()
        if key not in COMPUTE_DTYPES:
            raise ValueError(
                f"unknown compute dtype {value!r}; known: "
                f"{sorted(COMPUTE_DTYPES)}")
        return COMPUTE_DTYPES[key]
    return jnp.dtype(value).type


def cast_float_tree(tree, dtype):
    """Cast float leaves to `dtype`, leaving integer leaves alone.

    The bf16-compute entry point: params cast at the loss boundary so the
    f32 masters (and optax state initialized from them) never change
    dtype, while autodiff transposes the cast and returns f32 gradients.
    """
    def cast(x):
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            return jnp.asarray(x).astype(dtype)
        return x
    return jax.tree.map(cast, tree)


__all__ = ["COMPUTE_DTYPES", "resolve_compute_dtype", "cast_float_tree"]
