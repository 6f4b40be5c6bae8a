"""In-mesh collective plane: the quantized gradient all-reduce.

PR 7 built q8 block quantization for the HOST wire (weight-sync deltas,
`_private/serialization.py`); this module moves the same arithmetic
INSIDE the jitted update step. On a multi-device mesh the learner's
gradient exchange is, by default, the implicit fp32 psum XLA inserts
from batch sharding. Selecting the `q8` codec replaces it with an
explicit EQuARX-style quantized all-reduce ("EQuARX: Efficient Quantized
AllReduce in XLA", PAPERS.md):

- each sender block-quantizes its local gradient (+ carried error
  residual) to int8 with one f32 scale per `Q8_BLOCK` elements — the
  exact `q8_quantize` arithmetic, mirrored here in jnp (bit-identical:
  same amax/127 scale, same `Q8_SCALE_EPS` clamp, same round-half-even);
- the int8 payload + scales are exchanged over the mesh axis
  (`lax.all_gather` — what actually travels is the quantized wire
  image, 1 byte/elem + 4/Q8_BLOCK amortized scale bytes ≈ 3.9× smaller
  than fp32) and summed in f32 after per-sender dequantize;
- sender-side error feedback: the residual (local value − its own
  dequantized wire image) is carried to the next step and added before
  quantizing, so the quantization error telescopes instead of
  accumulating and learning curves stay on the fp32 trajectory.

Codec selection is per-trainer (`allreduce_codec` config key) with the
`RAY_TPU_ALLREDUCE_CODEC` registry knob as the `auto` fallback; bf16
compute (`RAY_TPU_COMPUTE_DTYPE`) resolves through the same pattern.
The q8 path requires replicated parameters (each sender quantizes a
full local gradient); callers fall back to fp32 — with a warning — on
sharded (fsdp) layouts and trivially on single-device meshes.
"""

from __future__ import annotations

import time
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .._private.serialization import Q8_BLOCK, Q8_SCALE_EPS

CODECS = ("fp32", "q8")
COMPUTE_DTYPES = {
    "f32": jnp.float32, "float32": jnp.float32,
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
}


# ---------------------------------------------------------------------
# knob resolution (config-key value "auto" -> registry env fallback)
# ---------------------------------------------------------------------
def resolve_codec(value: Any = "auto") -> str:
    """Resolve an `allreduce_codec` config value to "fp32" | "q8"."""
    if value in (None, "auto"):
        from .._private import config as config_mod
        value = config_mod.get("RAY_TPU_ALLREDUCE_CODEC")
    value = str(value).lower()
    if value not in CODECS:
        raise ValueError(
            f"unknown allreduce codec {value!r}; known: {CODECS}")
    return value


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


# ---------------------------------------------------------------------
# jnp q8 block quantizer — mirrors serialization.q8_quantize bit-for-bit
# ---------------------------------------------------------------------
def q8_encode(vec) -> Tuple[jax.Array, jax.Array]:
    """f32[...] -> (q int8[nb, Q8_BLOCK], scales f32[nb]).

    Same arithmetic as the numpy `q8_quantize` (amax/127 per-block scale
    clamped to Q8_SCALE_EPS, round-half-even, clip to ±127); the padded
    block layout is kept — `q8_decode` trims back to the original shape.
    """
    flat = jnp.asarray(vec, jnp.float32).reshape(-1)
    n = flat.size
    nb = max(1, -(-n // Q8_BLOCK))
    padded = jnp.pad(flat, (0, nb * Q8_BLOCK - n))
    blocks = padded.reshape(nb, Q8_BLOCK)
    scales = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1) / 127.0,
                         Q8_SCALE_EPS).astype(jnp.float32)
    q = jnp.clip(jnp.round(blocks / scales[:, None]), -127, 127) \
        .astype(jnp.int8)
    return q, scales


def q8_decode(q, scales, shape) -> jax.Array:
    """Inverse of q8_encode, trimmed back to `shape` (f32 multiply —
    the same reconstruction the numpy path and every receiver uses)."""
    out = q.astype(jnp.float32) * scales[:, None]
    n = int(np.prod(shape)) if shape else 1
    return out.reshape(-1)[:n].reshape(shape)


def _leaf_allreduce_q8(g, err, axis_name):
    """One leaf of the quantized all-reduce, per replica (inside
    shard_map): returns (summed f32 gradient, new error residual)."""
    v = g.astype(jnp.float32) + err
    q, scales = q8_encode(v)
    # The sender's own wire image; the residual it failed to transmit is
    # carried to the next step (error feedback).
    sent = q8_decode(q, scales, v.shape)
    new_err = v - sent
    # Exchange the quantized payload over the mesh axis. all_gather of
    # (int8 q, f32 scales) is the on-wire image the byte accounting
    # (payload_bytes) measures; each receiver dequantizes every sender's
    # contribution and sums in f32.
    all_q = jax.lax.all_gather(q, axis_name)          # [ndev, nb, B]
    all_s = jax.lax.all_gather(scales, axis_name)     # [ndev, nb]
    total = jnp.sum(all_q.astype(jnp.float32) * all_s[:, :, None],
                    axis=0)
    n = g.size
    return total.reshape(-1)[:n].reshape(g.shape), new_err


def psum_quantized(grads, ef, axis_name: str):
    """Quantized psum over `axis_name` for a gradient pytree.

    `ef` is the per-replica error-feedback residual tree (same structure
    and shapes as `grads`, f32, zeros at step 0). Returns (summed grads,
    updated residuals). Call inside shard_map/pmap only.
    """
    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = treedef.flatten_up_to(ef)
    out_g, out_e = [], []
    for g, e in zip(flat_g, flat_e):
        s, ne = _leaf_allreduce_q8(g, e, axis_name)
        out_g.append(s)
        out_e.append(ne)
    return (jax.tree_util.tree_unflatten(treedef, out_g),
            jax.tree_util.tree_unflatten(treedef, out_e))


def pmean_quantized(grads, ef, axis_name: str, ndev: int):
    """psum_quantized / ndev — the drop-in for `lax.pmean` on grads."""
    summed, ef = psum_quantized(grads, ef, axis_name)
    return jax.tree.map(lambda g: g / ndev, summed), ef


# ---------------------------------------------------------------------
# error-feedback state
# ---------------------------------------------------------------------
def ef_zeros(tree, mesh: Mesh, axis: str = "dp"):
    """Initial error-feedback residuals for `tree`: one f32 zero copy
    per mesh device, stacked on a leading axis sharded over `axis` (so
    each replica owns exactly its own residual; shard_map peels the
    leading unit dim off per replica)."""
    ndev = int(mesh.shape[axis])
    sh = ef_sharding(mesh, axis)
    return jax.device_put(
        jax.tree.map(
            lambda p: np.zeros((ndev,) + tuple(np.shape(p)), np.float32),
            tree),
        jax.tree.map(lambda _: sh, tree))


def ef_sharding(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    """Sharding of the stacked residual tree: leading dim over `axis`."""
    return NamedSharding(mesh, P(axis))


# ---------------------------------------------------------------------
# byte accounting (analytic: what one all-reduce puts on the wire)
# ---------------------------------------------------------------------
def payload_bytes(tree, codec: str) -> int:
    """Per-sender payload bytes for ONE all-reduce of `tree`.

    fp32: 4 bytes/element. q8: 1 byte/element + one f32 scale per
    Q8_BLOCK elements per leaf (each leaf quantizes independently).
    """
    total = 0
    for leaf in jax.tree.leaves(tree):
        n = int(np.prod(np.shape(leaf))) if np.shape(leaf) else 1
        if codec == "q8":
            total += n + 4 * max(1, -(-n // Q8_BLOCK))
        else:
            total += 4 * n
    return total


# ---------------------------------------------------------------------
# timed standalone probe — collectives fused into the update program
# cannot be timed from the host, so allreduce_ms is estimated once from
# a standalone jitted program of just the exchange on grad-shaped zeros.
# ---------------------------------------------------------------------
def allreduce_probe_s(tree, mesh: Mesh, codec: str, axis: str = "dp",
                      iters: int = 3) -> float:
    """Median wall seconds of one standalone all-reduce of `tree`."""
    zeros = jax.device_put(
        jax.tree.map(
            lambda p: np.zeros(np.shape(p), np.float32), tree),
        NamedSharding(mesh, P()))

    if codec == "q8":
        ef0 = ef_zeros(tree, mesh, axis)

        def step(t, ef):
            def per_replica(t, ef):
                ef = jax.tree.map(lambda e: e[0], ef)
                out, ef = psum_quantized(t, ef, axis)
                return out, jax.tree.map(lambda e: e[None], ef)
            # check_vma=False: replication of the summed output can't be
            # statically inferred through all_gather + sum (it IS
            # replicated — every replica sums the same gathered payload).
            return jax.shard_map(
                per_replica, mesh=mesh,
                in_specs=(P(), P(axis)), out_specs=(P(), P(axis)),
                check_vma=False)(t, ef)

        fn = jax.jit(step)
        args = (zeros, ef0)
    else:
        def step(t):
            def per_replica(t):
                return jax.lax.psum(t, axis)
            return jax.shard_map(per_replica, mesh=mesh,
                             in_specs=(P(),), out_specs=P())(t)

        fn = jax.jit(step)
        args = (zeros,)

    jax.block_until_ready(fn(*args))  # compile outside the timed window
    times = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def account(codec: str, nbytes: int, n_updates: int = 1,
            probe_s: float = None) -> None:
    """Record one (or n) gradient all-reduces in the metrics plane:
    `allreduce_bytes` / `allreduce_ms` counters and the codec-labeled
    `learner_allreduce_s.<codec>` histogram."""
    from .._private import metrics
    metrics.inc("allreduce_bytes", float(nbytes) * n_updates)
    if probe_s is not None:
        metrics.inc("allreduce_ms", probe_s * 1e3 * n_updates)
        for _ in range(n_updates):
            metrics.observe(f"learner_allreduce_s.{codec}", probe_s)


__all__ = [
    "CODECS", "Q8_BLOCK", "Q8_SCALE_EPS",
    "resolve_codec", "resolve_compute_dtype", "cast_float_tree",
    "q8_encode", "q8_decode", "psum_quantized", "pmean_quantized",
    "ef_zeros", "ef_sharding", "payload_bytes", "allreduce_probe_s",
    "account",
]
