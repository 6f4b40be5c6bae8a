"""A decode step of the gated delta rule's matrix states, in place.

    S' = Diag(exp g) S;  u = beta (v - S'^T k);  o = S'^T q + (k . q) u
    S = S' + k u^T

for a row's state S [d_k, d_v] a head, float32 (`transformer.kda_step` is
the plain form, and has the algebra), the states of the rows that `reset`
zeroed first. The decay in the shape the model publishes: a channel of the
key's, g [.., heads, d_k] (Kimi Delta Attention), or ONE a head, g [..,
heads] (Gated DeltaNet: Diag(exp g) is then exp(g) I). Two forms:

* the plain form after a select, as XLA compiles it: a pass over every
  state for the two sums, then one that reads and writes them all.
* `kda_kernel` (Pallas, TPU): a grid step fetches the tiles of `heads` heads
  of one row into VMEM, everything the step reads of them and writes to them
  happens there, and they go back to where they came from
  (`input_output_aliases`: the program holds no second copy of a state). S is
  read once and written once. The select that zeroes a row is inside (outside
  a `pallas_call` it is a pass of its own over every state). Float32
  throughout, the sums on the vector unit, in `kda_step`'s order: on a v5e
  the results are the plain form's bit for bit.

The vectors of a row arrive as they lie, [.., heads, d] with d on the lanes.
Those that multiply the ROWS of a tile (exp g, k, q) are turned inside the
kernel, all the heads of a grid step by one transpose of a [128, 128] tile
whose column c is then one head's vector down the sublanes. No [.., d_k, 1]
array exists in HBM (it would pad 128-fold, to the size of the states).
A decay of one number a head is not turned and not broadcast to a head's
channels anywhere: a grid step's decays arrive as one row [1, heads], as
its betas do, the tile that is turned holds k and q alone, and a head's
state is multiplied by its one number. On a v5e, three layers' states [32,
32, 128, 128] carried by a scan, ms a step for the three (my chip run, PR
52; PERF.md section 5): XLA's passes 0.873 under either decay; this kernel
under a decay a channel 0.731, under one a head 0.697, and 0.730 where the
one is first widened to a head's channels and handed over as a decay a
channel.

`in_place(kernel, plain)` is the kernel with the plain form's derivative: a
`pallas_call` with aliased operands has no JVP, and a decode step is
differentiated where a learner takes its bootstrap value through one.

Mamba-2's step (`transformer.ssd_step`) has no kernel here: XLA's fusions
already read its states once and write them once, at 638 GB/s, and a copy
through VMEM of the same bytes reads 648 on the same chip (reads and writes
do not overlap there: 715 GB/s read alone, 630 written alone), so there is
nothing for a kernel to take (PERF.md section 5, PR 46).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The most heads of one row a grid step holds. On a v5e at Kimi-Linear's
# shape (32 rows x 32 heads of [128, 128], four layers' states carried by a
# scan that feeds them a step's vectors; ms a step for the four; PERF.md
# section 5 has the sweep): XLA's passes 1.386; the kernel at 8 heads a
# step 0.906, 16 0.862, 32 0.861. The kernel's own time is a copy's: the
# same grid that only multiplies the tile by a constant reads 0.861 / 0.858
# / 0.856, four copies in flight instead of two the same, and XLA's own
# `S * 1.5` 0.846. A grid step costs ~0.35 us; 16 heads are 1 MB a buffer
# (two buffers each way: 4 MB of the 16 the compiler allows a kernel).
HEADS = 16
LANES = 128


def heads_a_step(heads: int) -> int:
    """The heads of a row a grid step holds: the largest divisor of `heads`
    up to `HEADS`."""
    return max(h for h in range(1, min(heads, HEADS) + 1) if heads % h == 0)


def whole_tiles(heads: int, d_k: int, d_v: int) -> bool:
    """Whether states [.., heads, d_k, d_v] can take the kernel: a function
    of the static shape alone. Whole (8, 128) float32 tiles of the last two
    axes, the turned axis within one lane tile, and a grid step's heads
    whole sublane tiles of the vectors' blocks (or all the heads)."""
    step = heads_a_step(heads)
    return (d_v % LANES == 0 and d_k % 8 == 0 and d_k <= LANES
            and (step % 8 == 0 or step == heads))


def _turned(rows):
    """[128, 128]: column c is row c of `rows` ([n, d_k] float32, n and d_k
    at most 128) down the sublanes."""
    n, d_k = rows.shape
    return jnp.pad(rows, ((0, LANES - n), (0, LANES - d_k))).T


def _body(reset_ref, beta_ref, q_ref, k_ref, g_ref, v_ref, s_ref, o_ref,
          out_ref):
    """One grid step (b, j): block j of row b's heads."""
    heads, d_k, d_v = s_ref.shape
    q, k, beta = q_ref[...], k_ref[...], beta_ref[...]
    decays = jnp.exp(g_ref[...])
    # A decay a channel goes down the sublanes with k and q; one a head
    # stays the row [1, heads] it came as.
    by_channel = decays.shape == k.shape
    down = ([decays] if by_channel else []) + [k, q]
    turned = _turned(jnp.concatenate(down, axis=0))
    # [heads, 1]
    kq = jnp.sum(k * q, axis=-1, keepdims=True)
    dropped = jnp.full((d_k, d_v), reset_ref[pl.program_id(0)]) > 0
    for h in range(heads):
        *decay, k_down, q_down = (
            turned[:d_k, n * heads + h:n * heads + h + 1]
            for n in range(len(down)))
        decay = decay[0] if by_channel else decays[:, h:h + 1]
        # Selected, not multiplied: what a row that begins held may be
        # anything. (Hidden behind the copies, as the rest is.)
        decayed = jnp.where(dropped, 0.0, decay * s_ref[h])
        from_k = jnp.sum(decayed * k_down, axis=0, keepdims=True)
        from_q = jnp.sum(decayed * q_down, axis=0, keepdims=True)
        u = beta[:, h:h + 1] * (v_ref[h:h + 1, :] - from_k)
        o_ref[h:h + 1, :] = from_q + kq[h:h + 1, :] * u
        out_ref[h] = decayed + k_down * u


def kda_kernel(S, q, k, v, g, beta, reset, *, heads=None, interpret=False):
    """`transformer.kda_step` of the states S [B, H, d_k, d_v] zeroed where
    `reset` [B] > 0, as the kernel: (o [B, H, d_v] float32, the states after
    the position, in S's buffer); g [B, H, d_k], or [B, H] for one decay a
    head. `heads` heads a grid step (`heads_a_step`); `interpret` runs it
    by the Pallas interpreter (a test on a CPU)."""
    B, H, d_k, d_v = S.shape
    heads = heads or heads_a_step(H)
    if H % heads or 3 * heads > LANES:
        raise ValueError(
            f"{H} heads are not whole steps of {heads}, or three vectors a "
            f"head are more than one tile's {LANES} columns")
    f32 = jnp.float32
    by_channel = g.ndim == 3

    def a_row():
        """A step's own numbers a head as one row, [1, heads]."""
        return pl.BlockSpec((None, None, 1, heads), lambda b, j: (b, j, 0, 0))

    def vectors(d):
        return pl.BlockSpec((None, heads, d), lambda b, j: (b, j, 0))

    def states():
        return pl.BlockSpec((None, heads, d_k, d_v),
                            lambda b, j: (b, j, 0, 0))
    return pl.pallas_call(
        _body,
        grid=(B, H // heads),
        in_specs=[
            # A row's flag is read as a scalar.
            pl.BlockSpec(memory_space=pltpu.SMEM),
            a_row(),
            vectors(d_k), vectors(d_k),
            vectors(d_k) if by_channel else a_row(), vectors(d_v),
            states()],
        out_specs=[vectors(d_v), states()],
        out_shape=[jax.ShapeDtypeStruct((B, H, d_v), f32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype)],
        input_output_aliases={6: 1},
        # What XLA's scheduler takes the call to cost. Without it the call
        # is free in its eyes: it moved two layers' states into VMEM ahead
        # of their calls, the step's weights then waited for their own
        # copies, and of 1.6 s a call that the states' row lost, the cell
        # kept 0.5 (PERF.md section 5, PR 46).
        cost_estimate=pl.CostEstimate(
            flops=8 * S.size, transcendentals=g.size,
            bytes_accessed=4 * (2 * S.size + g.size
                                + B * H * (2 * d_k + 2 * d_v))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="kda_state_step",
        interpret=interpret,
    )(reset.astype(jnp.int32),
      beta.astype(f32).reshape(B, H // heads, 1, heads),
      q.astype(f32), k.astype(f32),
      g.astype(f32) if by_channel else g.astype(f32).reshape(
          B, H // heads, 1, heads),
      v.astype(f32), S)


def in_place(kernel, plain):
    """`kernel(S, *vectors, reset)`, differentiable: the pullback is that
    of `plain` of the same operands, the same step as XLA's fusions."""
    @jax.custom_vjp
    def step(*operands):
        return kernel(*operands)

    def forward(*operands):
        return step(*operands), operands

    def backward(kept, g):
        *operands, reset = kept
        _, pullback = jax.vjp(lambda *a: plain(*a, reset), *operands)
        return (*pullback(g), None)

    step.defvjp(forward, backward)
    return step
