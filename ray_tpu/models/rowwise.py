"""A head's rows between a projection and the attention, touched once.

A learner's causal pass holds queries, keys and a head's output head-major,
[B, heads, T, d] in the compute dtype. Between the projection and the
attention a head's rows are rotated (`transformer.rope`) and the queries
scaled; between the attention and W_o they may be gated. Both are row-wise
float32 arithmetic on two-byte operands: a read and a write an element is
what they owe. As XLA compiles `rope` (a `concatenate` of two negated
slices along the lane axis, which ends its fusions) float32 copies of the
operand go to HBM and come back several times a pass, forward, recomputed
and differentiated: 27-30 B an element on a v5e (PERF.md section 5, PR 57).

`rotate_kernel` (Pallas, TPU) reads a tile of rows in the operand's dtype,
rotates the leading `rotated` values of each row in float32 registers
(rotate-half is a lane rotation by half of them, `pltpu.roll`, the sign on
the sine's table), multiplies everything by `scale` and writes the
operand's dtype: the float32 arithmetic `rope` does, in the order it does
it. Cos and sin are operands [B, T, d] float32 (`tables`: made once a call
from the positions, shared by every head: the grid walks a tile's heads
innermost, so a tile's tables are fetched once). `rotation` is the kernel
with its own derivative: rotate-half's transpose is its negative and both
halves of a row share an angle, so the pullback is the same pass with the
sine negated, and nothing float32 is kept for it.

`gate_kernel` is the other side of the attention: o * sigmoid(gate), a gate
a head (`gate` [B, T, heads]: positions on sublanes as o's are, so a head's
column broadcasts along a row's lanes) or a gate a value ([B, heads, T, d]);
`gating` is the pass with its derivative (the gate's gradient a row is a
sum over the row's lanes, taken in the same pass as o's).

`whole_tiles` says which static shapes the kernels take; every other shape,
and every program lowered for anything but a TPU, keeps the plain forms
(`transformer.TokenDecoder._rotate`, `._gated`).

On a v5e (my chip runs, PR 57; PERF.md section 6): in
`laguna_token_anakin_8k`'s call `anakin/learn|policy/rope` 1.108 -> 0.209 s
(33.0 G elements: 27.5 -> 5.2 B an element at 819 GB/s) and
`|policy/attention_gate` 0.561 -> 0.205 s; in `sdar_block_token_anakin_2k`'s
`|policy/rope` 0.989 -> 0.187 s. Alone, the rotation of `[1, 64, 8192, 128]`
is ~0.45 ms against ~3.4 ms (a host-timed call less the ~0.6 ms every call
carries), and a body that only copies its tile takes as long: the pass is at
what a pipelined copy through VMEM reaches, the lane rotation and a product
with a signed permutation on the MXU take the same time, and tiles of 256 to
2,048 positions by 2 to 16 heads differ by under 5 %. The calls state no
`cost_estimate`: with one (the bytes and four operations an element) the two
cells read 6,940 against 6,951 and 10,307 against 10,345 steps/s.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Positions a grid step takes: the fragment is whole tiles of this many
# (`transformer.CAUSAL_TILE`'s 512, so that a shape the fused attention
# takes is one these take).
ROWS = 512
# Heads a grid step takes: the most of these that divide a layer's and make
# a block of at most `LANES_A_STEP` values a position (1 MB of two-byte
# values: the gate's pullback holds five such blocks twice).
HEADS_A_STEP = (8, 7, 6, 4, 3, 2, 1)
LANES_A_STEP = 1024


def whole_tiles(T: int, d: int, rotated: int | None = None) -> bool:
    """Whether rows [.., T, d] (the leading `rotated` of d rotated) can
    take the kernels: a function of the static shape alone. Whole lane
    tiles of d, whole tiles of positions, halves that are whole values."""
    return (d % LANES == 0 and T % ROWS == 0
            and (rotated is None or (rotated % 2 == 0 and 0 < rotated <= d)))


def heads_a_step(heads: int, d: int) -> int:
    """The heads of `d` values a grid step takes beside its `ROWS`
    positions."""
    return next(h for h in HEADS_A_STEP
                if heads % h == 0 and (h * d <= LANES_A_STEP or h == 1))


def tables(positions, inv_freq, factor, d: int):
    """(cos, sin) [.., T, d] float32 of `positions` [.., T] for a rotation
    of a row's leading 2 * len(inv_freq) values at the angles
    positions * inv_freq (`transformer.rope_frequencies`), `factor` on
    both; the sine carries rotate-half's sign (- over the first half), and
    the values past the rotated ones read cos 1, sin 0."""
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    rest = angles.shape[:-1] + (d - 2 * angles.shape[-1],)
    return (jnp.concatenate([cos, cos, jnp.ones(rest, jnp.float32)], axis=-1),
            jnp.concatenate([-sin, sin, jnp.zeros(rest, jnp.float32)],
                            axis=-1))


def _rotate_body(x_ref, cos_ref, sin_ref, out_ref, *, rotated, scale, back):
    cos, sin = cos_ref[0], sin_ref[0]
    d = cos.shape[-1]
    half = rotated // 2
    if rotated < d:
        first = jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1) < half

    def a_head(h, carry):
        x = x_ref[0, h].astype(jnp.float32)
        # Rotate-half without its sign: value i + half at i over the first
        # half, value i - half at i over the second.
        turned = pltpu.roll(x, half, 1)
        if rotated < d:
            turned = jnp.where(first, pltpu.roll(x, d - half, 1), turned)
        out = x * cos - turned * sin if back else x * cos + turned * sin
        out_ref[0, h] = (out if scale == 1.0 else out * scale).astype(
            out_ref.dtype)
        return carry
    jax.lax.fori_loop(0, x_ref.shape[1], a_head, 0)


def rotate_kernel(x, cos, sin, *, rotated, scale=1.0, back=False,
                  interpret=False):
    """x [B, heads, T, d] rotated by `tables`' cos and sin [B, T, d]: a row
    x -> (x cos + half-turned(x) sin) scale over its leading `rotated`
    values, x scale over the rest, in float32, written in x's dtype; `back`:
    with the sine negated (the pullback). `interpret` runs it by the Pallas
    interpreter (a test on a CPU)."""
    B, heads, T, d = x.shape
    rows, a_step = ROWS, heads_a_step(heads, d)
    table = pl.BlockSpec((1, rows, d), lambda b, t, h: (b, t, 0))
    block = pl.BlockSpec((1, a_step, rows, d), lambda b, t, h: (b, h, t, 0))
    return pl.pallas_call(
        functools.partial(_rotate_body, rotated=rotated, scale=scale,
                          back=back),
        grid=(B, T // rows, heads // a_step),
        in_specs=[block, table, table],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="rotate_rows",
        interpret=interpret,
    )(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def rotation(x, cos, sin, rotated, scale):
    """`rotate_kernel` with its derivative in x (the tables come from
    positions and have none)."""
    return rotate_kernel(x, cos, sin, rotated=rotated, scale=scale)


def _rotation_forward(x, cos, sin, rotated, scale):
    return rotation(x, cos, sin, rotated, scale), (cos, sin)


def _rotation_backward(rotated, scale, kept, g):
    cos, sin = kept
    return (rotate_kernel(g, cos, sin, rotated=rotated, scale=scale,
                          back=True),
            jnp.zeros_like(cos), jnp.zeros_like(sin))


rotation.defvjp(_rotation_forward, _rotation_backward)


def _gate_body(o_ref, gate_ref, *refs, a_head, back):
    """o * sigmoid(gate) over a step's heads of a tile of rows; `back`:
    refs are (g, the pullbacks d_o and d_gate), else (out,)."""
    a_step = o_ref.shape[1]
    if a_head:
        # [rows, heads], whole over the tile's steps: a head's column, along
        # a row's lanes.
        sig = jax.nn.sigmoid(gate_ref[0].astype(jnp.float32))
        lane = jax.lax.broadcasted_iota(jnp.int32, sig.shape, 1)
        first = pl.program_id(2) * a_step

    def gate_of(h):
        if not a_head:
            return jax.nn.sigmoid(gate_ref[0, h].astype(jnp.float32))
        return jnp.sum(jnp.where(lane == first + h, sig, 0.0), axis=1,
                       keepdims=True)
    if not back:
        out_ref, = refs

        def forward(h, carry):
            out_ref[0, h] = (o_ref[0, h].astype(jnp.float32) * gate_of(h)
                             ).astype(out_ref.dtype)
            return carry
        jax.lax.fori_loop(0, a_step, forward, 0)
        return
    g_ref, d_o_ref, d_gate_ref = refs

    def backward(h, d_gate):
        g, s = g_ref[0, h].astype(jnp.float32), gate_of(h)
        d_o_ref[0, h] = (g * s).astype(d_o_ref.dtype)
        d_s = g * o_ref[0, h].astype(jnp.float32)
        if not a_head:
            d_gate_ref[0, h] = (d_s * s * (1.0 - s)).astype(d_gate_ref.dtype)
            return d_gate
        d_s = jnp.sum(d_s, axis=1, keepdims=True) * s * (1.0 - s)
        return jnp.where(lane == first + h, d_s, d_gate)
    if not a_head:
        jax.lax.fori_loop(0, a_step, backward, 0)
        return
    # The gate's pullback stays in VMEM over a tile's steps: each writes
    # its heads' columns.
    d_gate = jax.lax.fori_loop(
        0, a_step, backward,
        jnp.where(first == 0, 0.0, d_gate_ref[0].astype(jnp.float32)))
    d_gate_ref[0] = d_gate.astype(d_gate_ref.dtype)


def gate_kernel(o, gate, g=None, *, interpret=False):
    """o [B, heads, T, d] times sigmoid(gate) in float32, in o's dtype:
    `gate` [B, T, heads] (a gate a head) or [B, heads, T, d] (a gate a
    value). With the output's cotangent `g`, the pullbacks (d_o, d_gate)
    instead, in one pass over o and g."""
    B, heads, T, d = o.shape
    a_head = gate.ndim == 3
    rows, a_step = ROWS, heads_a_step(heads, d)
    block = pl.BlockSpec((1, a_step, rows, d), lambda b, t, h: (b, h, t, 0))
    gates = pl.BlockSpec((1, rows, heads), lambda b, t, h: (b, t, 0)) \
        if a_head else block
    back = g is not None
    like = jax.ShapeDtypeStruct(o.shape, o.dtype)
    return pl.pallas_call(
        functools.partial(_gate_body, a_head=a_head, back=back),
        grid=(B, T // rows, heads // a_step),
        in_specs=[block, gates] + [block] * back,
        out_specs=[block, gates] if back else block,
        out_shape=[like, jax.ShapeDtypeStruct(gate.shape, gate.dtype)]
        if back else like,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="gate_rows_back" if back else "gate_rows",
        interpret=interpret,
    )(o, gate, *([g] if back else []))


@jax.custom_vjp
def gating(o, gate):
    """`gate_kernel` with its derivative."""
    return gate_kernel(o, gate)


def _gating_forward(o, gate):
    return gating(o, gate), (o, gate)


def _gating_backward(kept, g):
    return tuple(gate_kernel(*kept, g))


gating.defvjp(_gating_forward, _gating_backward)
