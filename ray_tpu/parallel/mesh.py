"""Device-mesh helpers: the TPU replacement for the reference's device
placement machinery.

Where the reference pinned TF towers to `/gpu:N` and averaged gradients
in-graph (`rllib/optimizers/multi_gpu_impl.py:83-93,310`), here the learner
is ONE jitted program over a `jax.sharding.Mesh`: parameters replicated,
batches sharded along the `dp` axis, and XLA inserts the gradient psum over
ICI. The same program runs on 1 chip (trivial mesh) or a pod slice.

Axis vocabulary (used by the policies and `sgd.JaxTrainer`):
- "dp": data parallel (batch dim)
- "mp": model/tensor parallel (large dense layers, optional)
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# The directory is part of every cache key, so it must be the same in
# every process and on every run: a fixed, git-ignored path in the
# checkout — never a temp dir, a pid or the session directory.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compile_cache() -> str:
    """Point XLA's persistent compile cache somewhere that stays put.

    Every device owner builds its mesh through `make_mesh`, which calls
    this first. Where JAX_COMPILATION_CACHE_DIR is set jax has already
    taken it (spawned workers inherit the variable) and nothing is set
    here; otherwise the cache goes to `COMPILE_CACHE_DIR`. Returns the
    directory in use."""
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir


def get_devices(platform: Optional[str] = None):
    devs = jax.devices()
    if platform:
        devs = [d for d in devs if d.platform == platform]
    return devs


def make_mesh(num_devices: Optional[int] = None,
              axis_names: Sequence[str] = ("dp",),
              shape: Optional[Sequence[int]] = None,
              devices=None) -> Mesh:
    """Build a mesh over the local devices.

    With only `num_devices`, makes a 1-D "dp" mesh. With `shape`,
    reshapes devices to that topology (e.g. (4, 2) for ("dp", "mp")).
    """
    place_compile_cache()
    devs = list(devices if devices is not None else jax.devices())
    if num_devices is not None:
        devs = devs[:num_devices]
    if shape is None:
        shape = (len(devs),) if len(axis_names) == 1 else None
        if shape is None:
            raise ValueError("shape required for multi-axis meshes")
    arr = np.array(devs).reshape(tuple(shape))
    return Mesh(arr, tuple(axis_names))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    return NamedSharding(mesh, P(axis))


def refuse_allreduce_codec(config: dict) -> None:
    """A learner's config may not name a gradient exchange: the only one
    is the psum XLA inserts from `batch_sharded`. `deep_merge` takes
    unknown keys silently, so the key the q8 exchange was selected by is
    refused by name where a learner reads its config."""
    if "allreduce_codec" in config:
        raise ValueError(
            f"config key 'allreduce_codec' "
            f"({config['allreduce_codec']!r}) is no longer an option: the "
            "gradient exchange is XLA's psum, inserted from the batch's "
            "sharding; remove the key")


def put_replicated(tree, mesh: Mesh):
    sharding = replicated(mesh)
    return jax.device_put(tree, sharding)


def put_batch(tree, mesh: Mesh, axis: str = "dp"):
    sharding = batch_sharded(mesh, axis)
    return jax.device_put(tree, sharding)


def pad_to_multiple(batch_size: int, n: int) -> int:
    """Smallest multiple of n >= batch_size (batches must divide the dp
    axis evenly for even sharding)."""
    return ((batch_size + n - 1) // n) * n
