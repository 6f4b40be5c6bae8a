"""A decode step's attention over the positions a row holds of its cache.

    o[b, g, r] = sum_s softmax_s(q[b, g, r] . k[b, g, s] * scale) v[b, g, s]
                 over s < lengths[b]

for one query a row and cached head: q [B, G, R, d_qk] (R query heads read
cached head g), k [B, G, S, d_qk], v [B, G, S, d_v]; or `v` None, and the
values are the first `value_dim` lanes of the same rows (a latent cache:
G = 1, every query head of a row against the row's [S, d_qk] latents).
`lengths` [B] int32, 1 <= lengths[b] <= S: a held position is
s < lengths[b], nothing else is masked. One sum, two forms:

* `whole_window`: the two matrix products over all S positions, the scores
  [B, G, R, S] one float32 array, masked, normalised, cast, multiplied.
  Every position of the cache is read, by each product.
* `prefix_kernel` (Pallas, TPU): an online softmax over blocks of `block`
  positions. A grid step holds one block of `rows` rows' cache in VMEM,
  fetched once, and both products read it there; the blocks beyond the
  furthest position that any of a step's rows holds are not fetched (their
  index map names the last block that is, and the pipeline issues no copy
  for a block it has). A row of a step that stops earlier than the step's
  furthest masks all of a later block.

Both multiply operands in q's dtype and accumulate in float32, take the
maximum, the exponentials and their sum in float32, cast the probabilities
to q's dtype where they enter the second product, and return q's dtype. They
differ in where the probabilities are rounded: normalised first
(`whole_window`), or each block's against the running maximum and the sum
divided out of the float32 result (`prefix_kernel`).

`decode_attention` is the kernel form with the other's derivative: a
`pallas_call` that prefetches scalars has no JVP, and a decode step is
differentiated where a learner takes its bootstrap value through one.

Grouped heads whose caches lie position-major, [B, S, groups, d], take the
same kernel with G = 1 (`grouped_kernel`): a position's cached heads are
one row of groups * d contiguous lanes, every query head of a row is
scored against that whole row with zeros outside its own cached head's
lanes, and one grid step covers a block of positions for all the heads,
where the head-major form above, grid (rows, G, blocks) with few query
rows a cached head, paid 2.25 ns a cached position and head whatever the
bytes (PERF.md section 7, PR 35). `attend_grouped` is its whole-cache form
and `grouped_decode_attention` the kernel with that form's derivative.
Where a position's row is wider than 512 lanes and a cached head is whole
lane tiles (8 x 128: `transformer.grouped_lanes`) the zeros of the
block-diagonal queries stop hiding behind the bytes, and the step takes
`lanes_kernel` (`lanes_decode_attention` with `attend_grouped`'s
derivative): the block step's blocking below without a fresh block, each
cached head's few query rows against that head's own lanes of a fetched row.

A step that generates a BLOCK of L positions a row (`transformer
.block_step`) reads the positions before the block from the caches and the
block itself, in both directions, from the pass's own keys and values,
which are operands and lie in no cache (`attend_block`, `block_kernel`,
`block_decode_attention`: plain form, kernel, kernel with the plain form's
derivative): s < lengths[b] of the caches, 0 <= lengths[b], and all L fresh
positions, one softmax. The kernel is `prefix_kernel`'s grid over flat
caches with the fresh block folded into the online softmax first (a row
that begins holds nothing cached, and its maximum is finite from there),
and it scores cached head g's queries, heads // groups x L rows of them,
against lanes [g d, (g + 1) d) of a fetched row alone: with L positions a
row the block-diagonal form's zeros are no longer hidden behind the bytes.
Only a commit pass writes (`write_block`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Positions in a block of the cache, and the most rows a grid step holds.
# On a v5e at the latent widths (20 query heads, 576 / 512 wide, 128 rows,
# a window of 1,024, bfloat16; one layer's attention in a scan that carries
# its cache, ms a step with the window filling from empty / held whole;
# PERF.md section 5 has the sweep): the two products over the whole window
# 0.526 / 0.526; the kernel at blocks of 512 x 8 rows 0.222 / 0.252, 256 x
# 16 0.188 / 0.252, 128 x 16 0.171 / 0.251, 128 x 32 0.169 / 0.253, 64 x 64
# 0.160 / 0.257. A window held whole streams at 600 GB/s whatever the
# block; a smaller block reads less of a window that fills from empty
# (1/2 + block / 2S of it on the mean), and a grid step costs ~0.35 us
# whether its block is fetched or not. Grouped heads' rows, 512 lanes each of
# K and V (64 rows of 32 query heads over 8 cached ones of 64, 4,096
# positions; XLA's two products 1.471 / 1.471): 128 x 8 0.436 / 0.756, 128 x
# 16 0.427 / 0.757 (709 GB/s), 128 x 32 0.423 / 0.759, 256 x 16 0.438 /
# 0.758, 512 x 16 0.465 / 0.760, 1,024 x 8 0.525 / 0.760: the same choice.
# The block entry (64 rows, 4 positions x 32 query heads over 4 cached ones
# of 128, 2,048 positions filling from empty; ms a layer-pass with the fold
# of the queries and of the output, my chip run, PR 49): a pass that
# scatters its block into the caches and reads it back through the
# block-diagonal form 0.401; the fresh block as operands, block-diagonal
# 0.325, a cached head against its own lanes 0.228 at 128 x 16 (0.237 at
# 128 x 8, 0.225 at 128 x 32, 0.243 at 256 x 16, 0.277 at 512 x 16), where
# the cache's bytes alone are 0.201 at the 709 GB/s above.
BLOCK = 128
ROWS = 16
# Two buffers of ROWS x BLOCK x 640 lanes x 2 bytes are 5.2 MB (grouped
# heads' K and V, 2 x 512 lanes: 8.4 MB), the float32
# scores and probabilities of a step 0.4 MB; the compiler's own limit is
# 16 MB of the chip's 128, and larger blocks were measured under this one.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def rows_a_step(B: int) -> int:
    """The rows of the batch a grid step holds: the largest divisor of `B`
    up to `ROWS`."""
    return max(r for r in range(1, min(B, ROWS) + 1) if B % r == 0)


def last_blocks(lengths, block: int, rows: int):
    """[B // rows] int32: the last block of positions that any row of a
    grid step's `rows` rows holds."""
    furthest = jnp.max(lengths.reshape(-1, rows), axis=1)
    return jnp.maximum(furthest - 1, 0) // block


def positions_fetched(lengths):
    """The positions of the blocks `prefix_kernel` fetches of a row's
    cache at its own block and rows a step, the mean over the rows."""
    last = last_blocks(lengths, BLOCK, rows_a_step(lengths.shape[0]))
    return jnp.mean((last + 1).astype(jnp.float32)) * BLOCK


def whole_window(q, k, v, lengths, scale, value_dim=None):
    """The sum as two matrix products over the whole window."""
    f32 = jnp.float32
    held = jnp.arange(k.shape[2])[None, :] < lengths[:, None]
    scores = jnp.einsum("bgrd,bgsd->bgrs", q, k,
                        preferred_element_type=f32) * scale
    scores = jnp.where(held[:, None, None, :], scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if v is not None:
        return jnp.einsum("bgrs,bgsd->bgrd", attn, v,
                          preferred_element_type=f32).astype(q.dtype)
    # Against the whole rows, the values cut out of the product (a product
    # against `k[..., :value_dim]` copies the rows).
    return jnp.einsum("bgrs,bgsd->bgrd", attn, k,
                      preferred_element_type=f32).astype(
                          q.dtype)[..., :value_dim]


def _kernel(last_ref, lengths_ref, q_ref, k_ref, *rest, scale, block,
            value_dim):
    """One grid step (i, g, j): block j of the cache of the rows of step
    i, cached head g, against those rows' queries; the running maximum,
    sum and weighted values of a row's heads stay in VMEM across j."""
    if value_dim is None:
        v_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        v_ref, (o_ref, m_ref, l_ref, acc_ref) = None, rest
    i, j = pl.program_id(0), pl.program_id(2)
    f32 = jnp.float32

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The steps beyond the last block held run nothing; their block index
    # is the last one's, which the pipeline has, so it copies nothing.
    @pl.when(j <= last_ref[i])
    def _():
        # [rows, 1, 1]
        lengths = lengths_ref[...]
        q, k = q_ref[...], k_ref[...]
        # [rows, R, block]
        s = jnp.einsum("trd,tsd->trs", q, k,
                       preferred_element_type=f32) * scale
        at = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(at < lengths, s, -jnp.inf)
        # A row always holds position 0, so after block 0 its maximum is
        # finite, and a later block it holds nothing of adds exp(-inf).
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_next
        v = k[:, :, :value_dim] if v_ref is None else v_ref[...]
        # What lies beyond a row's length is not the row's: 0 x NaN would
        # be NaN. (The select is hidden behind the products: the step
        # measured 0.2470 ms with it and 0.2474 without.)
        at = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (v.shape[0], block, 1), 1)
        v = jnp.where(at < lengths, v, jnp.zeros_like(v))
        acc_ref[...] = alpha * acc_ref[...] + jnp.einsum(
            "trs,tsd->trd", p.astype(q.dtype), v, preferred_element_type=f32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def prefix_kernel(q, k, v, lengths, scale, value_dim=None, *, block=None,
                  rows=None, interpret=False):
    """The sum as the kernel over the blocks held: `block` positions
    (`BLOCK`) of `rows` rows (`rows_a_step`) a grid step; `interpret` runs
    it by the Pallas interpreter (a test on a CPU)."""
    B, G, R, d_qk = q.shape
    S = k.shape[2]
    block = block or BLOCK
    rows = rows or rows_a_step(B)
    if S % block or B % rows:
        raise ValueError(
            f"{S} positions are not whole blocks of {block}, or {B} rows "
            f"not whole steps of {rows}")
    d_v = value_dim if v is None else v.shape[3]
    lengths = lengths.astype(jnp.int32)

    def held(i, g, j, last):
        return i, g, jnp.minimum(j, last[i]), 0

    def whole(i, g, j, last):
        return i, g, 0, 0

    cached = [pl.BlockSpec((rows, None, block, d_qk), held)]
    operands = [k]
    if v is not None:
        cached.append(pl.BlockSpec((rows, None, block, d_v), held))
        operands.append(v)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, block=block,
                          value_dim=None if v is not None else value_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # The index maps and the steps that run nothing read scalars;
            # the mask reads the lengths as a vector.
            num_scalar_prefetch=1,
            grid=(B // rows, G, S // block),
            in_specs=[pl.BlockSpec((rows, 1, 1), lambda i, g, j, last: (
                i, 0, 0)), pl.BlockSpec((rows, None, R, d_qk), whole)]
            + cached,
            out_specs=pl.BlockSpec((rows, None, R, d_v), whole),
            scratch_shapes=[pltpu.VMEM((rows, R, 1), jnp.float32),
                            pltpu.VMEM((rows, R, 1), jnp.float32),
                            pltpu.VMEM((rows, R, d_v), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, G, R, d_v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="decode_attention",
        interpret=interpret,
    )(last_blocks(lengths, block, rows), lengths.reshape(B, 1, 1), q,
      *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def decode_attention(q, k, v, lengths, scale, value_dim=None):
    """`prefix_kernel`, differentiable: the pullback is `whole_window`'s."""
    return prefix_kernel(q, k, v, lengths, scale, value_dim)


def _forward(q, k, v, lengths, scale, value_dim):
    return decode_attention(q, k, v, lengths, scale, value_dim), (
        q, k, v, lengths)


def _backward(scale, value_dim, kept, g):
    q, k, v, lengths = kept
    _, pullback = jax.vjp(
        lambda q, k, v: whole_window(q, k, v, lengths, scale, value_dim),
        q, k, v)
    return (*pullback(g), None)


decode_attention.defvjp(_forward, _backward)


# -- grouped heads: caches [B, S, groups, d], position-major ----------------
def attend_grouped(q, k, v, lengths, scale):
    """`heads // groups` query heads against each cached head (query head
    h against cached head h // their number), q [B, heads, d] over k, v
    [B, S, groups, d]: a group's queries as the rows of one matrix against
    that head's [S, d] keys, then its weights against the values, a cached
    row read once for all the queries of its group; every position of the
    cache is read, by each product."""
    f32 = jnp.float32
    B, heads, d = q.shape
    held = jnp.arange(k.shape[1])[None, :] < lengths[:, None]
    q = q.reshape(B, k.shape[2], -1, d)
    # [B, groups, heads a group, S]
    scores = jnp.einsum("bgrd,bsgd->bgrs", q, k,
                        preferred_element_type=f32) * scale
    scores = jnp.where(held[:, None, None, :], scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrs,bsgd->bgrd", attn, v,
                      preferred_element_type=f32).astype(
                          q.dtype).reshape(B, heads, -1)


def grouped_kernel(q, k, v, lengths, scale, **kernel):
    """`attend_grouped`'s sum by `prefix_kernel`, the caches where they
    lie: a position's `groups` cached heads are one row of groups * d
    contiguous lanes, [B, 1, S, groups * d]; the queries are made
    block-diagonal, [B, 1, heads, groups * d], head h zero outside the d
    lanes of its cached head, so that one product scores all the heads of
    a row against a position's whole row (the zeros are `groups` times the
    owed matrix FLOPs, where the step is bound by the cache's bytes). Of
    the output's row, head h keeps the d lanes of its own cached head; the
    others are other heads' values under h's weights: finite, dropped."""
    B, heads, d = q.shape
    S, groups = k.shape[1:3]
    # [1, heads, groups, 1]: whether cached head g is query head h's.
    own = (jnp.arange(heads)[:, None] // (heads // groups)
           == jnp.arange(groups)[None, :])[None, :, :, None]
    q = jnp.where(own, q[:, :, None, :], jnp.zeros((), q.dtype))
    o = prefix_kernel(
        q.reshape(B, 1, heads, groups * d), k.reshape(B, 1, S, groups * d),
        v.reshape(B, 1, S, groups * d), lengths, scale, **kernel)
    o = o.reshape(B, heads, groups, d)
    # One term a sum and zeros: exact.
    return jnp.sum(jnp.where(own, o, jnp.zeros((), o.dtype)), axis=2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_decode_attention(q, k, v, lengths, scale):
    """`grouped_kernel`, differentiable: the pullback is `attend_grouped`'s
    (the block-diagonal `whole_window`'s would pay `groups` times the
    products, over the caches in another view)."""
    return grouped_kernel(q, k, v, lengths, scale)


def _grouped_forward(q, k, v, lengths, scale):
    return grouped_decode_attention(q, k, v, lengths, scale), (
        q, k, v, lengths)


def _grouped_backward(scale, kept, g):
    q, k, v, lengths = kept
    _, pullback = jax.vjp(
        lambda q, k, v: attend_grouped(q, k, v, lengths, scale), q, k, v)
    return (*pullback(g), None)


grouped_decode_attention.defvjp(_grouped_forward, _grouped_backward)


# -- a block of fresh positions a row beside the caches ----------------------
def attend_block(q, k, v, k_new, v_new, lengths, scale):
    """A block step's sum, the plain form: q [B, G, R, d], the R rows of
    cached head g the queries of every position of the row's block
    (`heads // groups` heads x L positions, in any order); over the cached
    positions s < lengths[b] of k, v [B, S, G * d] (stored flat: a
    position's cached heads one row) and over the block's own L positions
    k_new, v_new [B, L, G * d], all of them (both directions), which no
    cache holds yet. 0 <= lengths[b] <= S: a row that begins holds nothing,
    and the block alone is its sum. One softmax over S + L scores; two
    products a side, every position of the cache read by each."""
    f32 = jnp.float32
    B, G, R, d = q.shape

    def by_head(a):
        return a.reshape(B, -1, G, d)
    held = jnp.arange(k.shape[1])[None, :] < lengths[:, None]
    cached = jnp.einsum("bgrd,bsgd->bgrs", q, by_head(k),
                        preferred_element_type=f32) * scale
    cached = jnp.where(held[:, None, None, :], cached, -jnp.inf)
    fresh = jnp.einsum("bgrd,bsgd->bgrs", q, by_head(k_new),
                       preferred_element_type=f32) * scale
    # The block's own scores are finite: so is the maximum.
    m = jax.lax.stop_gradient(jnp.maximum(
        jnp.max(cached, axis=-1, keepdims=True),
        jnp.max(fresh, axis=-1, keepdims=True)))
    cached, fresh = jnp.exp(cached - m), jnp.exp(fresh - m)
    total = (jnp.sum(cached, axis=-1, keepdims=True)
             + jnp.sum(fresh, axis=-1, keepdims=True))
    o = jnp.einsum("bgrs,bsgd->bgrd", (cached / total).astype(q.dtype),
                   by_head(v), preferred_element_type=f32)
    return (o + jnp.einsum(
        "bgrs,bsgd->bgrd", (fresh / total).astype(q.dtype), by_head(v_new),
        preferred_element_type=f32)).astype(q.dtype)


def _fold(s, v, m_ref, l_ref, acc_ref, g, dtype):
    """One block of scores s [rows, R, n] and values v [rows, n, d] into
    cached head g's running maximum, sum and weighted values (`_kernel`'s
    online softmax)."""
    m_prev = m_ref[:, g]
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next)
    l_ref[:, g] = alpha * l_ref[:, g] + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[:, g] = m_next
    acc_ref[:, g] = alpha * acc_ref[:, g] + jnp.einsum(
        "trs,tsd->trd", p.astype(dtype), v,
        preferred_element_type=jnp.float32)


def _fold_cached(j, lengths_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                 scale, block):
    """Block j of the flat caches of a grid step's rows into the running
    softmax, a cached head at a time against its own lanes of a fetched
    row: the positions at or beyond a row's length masked."""
    G, d = q_ref.shape[1], q_ref.shape[3]
    # [rows, 1, 1]
    lengths = lengths_ref[...]
    k, v = k_ref[...], v_ref[...]
    rows = k.shape[0]
    at = j * block + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1, block), 2)
    held = at < lengths
    # What lies beyond a row's length is not the row's: 0 x NaN would
    # be NaN.
    at = j * block + jax.lax.broadcasted_iota(
        jnp.int32, (rows, block, 1), 1)
    v = jnp.where(at < lengths, v, jnp.zeros_like(v))
    for g in range(G):
        q = q_ref[:, g]
        s = jnp.einsum("trd,tsd->trs", q, k[:, :, g * d:(g + 1) * d],
                       preferred_element_type=jnp.float32) * scale
        _fold(jnp.where(held, s, -jnp.inf), v[:, :, g * d:(g + 1) * d],
              m_ref, l_ref, acc_ref, g, q.dtype)


def _block_kernel(last_ref, lengths_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, acc_ref, *, scale, block):
    """One grid step (i, j): block j of the caches of the rows of step i
    against those rows' queries, a cached head at a time against its own
    lanes; before block 0, the rows' own fresh block, so that a row's
    maximum is finite whatever it holds of the cache."""
    i, j = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32
    G, d = q_ref.shape[1], q_ref.shape[3]

    def head(a, g):
        return a[:, :, g * d:(g + 1) * d]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        kn, vn = kn_ref[...], vn_ref[...]
        for g in range(G):
            q = q_ref[:, g]
            s = jnp.einsum("trd,tsd->trs", q, head(kn, g),
                           preferred_element_type=f32) * scale
            _fold(s, head(vn, g), m_ref, l_ref, acc_ref, g, q.dtype)

    # The steps beyond the last block held run nothing; their block index
    # is the last one's, which the pipeline has, so it copies nothing.
    @pl.when(j <= last_ref[i])
    def _():
        _fold_cached(j, lengths_ref, q_ref, k_ref, v_ref, m_ref, l_ref,
                     acc_ref, scale, block)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


# Under `jit`: a block step calls the entry 14 times at the same shapes, and
# `pallas_call` traces and lowers its kernel anew at every call (a block
# step's trace + lowering for a v5e took 4.7 + 2.2 s on the sandbox where
# the parent's took 2.6 + 1.4; under `jit`, traced and lowered once, ~1.8 +
# 0.9: set-up has a bound of its own). Every call keeps its caller's scopes
# in the compiled program's op names.
@functools.partial(jax.jit, static_argnames=("scale", "block", "rows",
                                              "interpret"))
def block_kernel(q, k, v, k_new, v_new, lengths, scale, *, block=None,
                 rows=None, interpret=False):
    """`attend_block`'s sum as a kernel: `prefix_kernel`'s grid over the
    blocks held of the caches where they lie ([B, S, G * d], fetched once
    for both products and all the cached heads), the rows' fresh block
    folded into the same online softmax first, and cached head g's R query
    rows scored against lanes [g d, (g + 1) d) of a fetched row alone: the
    owed products, no zeros. `d` is whole lane tiles (`block_fused`). The
    call states no `cost_estimate`, on purpose: with an honest one (the
    owed FLOPs, the mean blocks' bytes) XLA's scheduler held the next
    layer's weight prefetches back behind the call and the cell ran 2.0 %
    slower (9,220 against 9,415 steps/s at one seed; PERF.md section 6, PR
    49), the opposite of what `models/state_step.py`'s kernel found."""
    B, G, R, d = q.shape
    S, L = k.shape[1], k_new.shape[1]
    block = block or BLOCK
    rows = rows or rows_a_step(B)
    if S % block or B % rows:
        raise ValueError(
            f"{S} positions are not whole blocks of {block}, or {B} rows "
            f"not whole steps of {rows}")
    lengths = lengths.astype(jnp.int32)

    def held(i, j, last):
        return i, jnp.minimum(j, last[i]), 0

    def own(i, j, last):
        return i, 0, 0

    def whole(i, j, last):
        return i, 0, 0, 0
    return pl.pallas_call(
        functools.partial(_block_kernel, scale=scale, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // rows, S // block),
            in_specs=[
                pl.BlockSpec((rows, 1, 1), own),
                pl.BlockSpec((rows, G, R, d), whole),
                pl.BlockSpec((rows, L, G * d), own),
                pl.BlockSpec((rows, L, G * d), own),
                pl.BlockSpec((rows, block, G * d), held),
                pl.BlockSpec((rows, block, G * d), held)],
            out_specs=pl.BlockSpec((rows, G, R, d), whole),
            scratch_shapes=[pltpu.VMEM((rows, G, R, 1), jnp.float32),
                            pltpu.VMEM((rows, G, R, 1), jnp.float32),
                            pltpu.VMEM((rows, G, R, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="block_attention",
        interpret=interpret,
    )(last_blocks(lengths, block, rows), lengths.reshape(B, 1, 1), q, k_new,
      v_new, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def block_decode_attention(q, k, v, k_new, v_new, lengths, scale):
    """`block_kernel`, differentiable: the pullback is `attend_block`'s."""
    return block_kernel(q, k, v, k_new, v_new, lengths, scale)


def _block_forward(q, k, v, k_new, v_new, lengths, scale):
    return block_decode_attention(q, k, v, k_new, v_new, lengths, scale), (
        q, k, v, k_new, v_new, lengths)


def _block_backward(scale, kept, g):
    *operands, lengths = kept
    _, pullback = jax.vjp(
        lambda *a: attend_block(*a, lengths, scale), *operands)
    return (*pullback(g), None)


block_decode_attention.defvjp(_block_forward, _block_backward)


# -- one position a row, a cached head against its own lanes ------------------
def _lanes_kernel(last_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                  l_ref, acc_ref, *, scale, block):
    """One grid step (i, j) of `_block_kernel` without a fresh block: a row
    holds position 0 (1 <= lengths), so its maximum is finite after block
    0, as `_kernel`'s is."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j <= last_ref[i])
    def _():
        _fold_cached(j, lengths_ref, q_ref, k_ref, v_ref, m_ref, l_ref,
                     acc_ref, scale, block)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def lanes_kernel(q, k, v, lengths, scale, *, block=None, rows=None,
                 interpret=False):
    """`attend_grouped`'s sum, one query a row and head, by `block_kernel`'s
    blocking: q [B, heads, d] over the caches where they lie, k, v [B, S,
    groups, d] (a position's cached heads one row of groups * d contiguous
    lanes, fetched once for both products and all the heads), cached head
    g's `heads // groups` query rows scored against lanes [g d, (g + 1) d)
    of a fetched row alone: the owed products, where `grouped_kernel`'s
    block-diagonal queries multiply `groups` times as many. `d` is whole
    lane tiles."""
    B, heads, d = q.shape
    S, G = k.shape[1:3]
    R = heads // G
    block = block or BLOCK
    rows = rows or rows_a_step(B)
    if S % block or B % rows:
        raise ValueError(
            f"{S} positions are not whole blocks of {block}, or {B} rows "
            f"not whole steps of {rows}")
    lengths = lengths.astype(jnp.int32)

    def held(i, j, last):
        return i, jnp.minimum(j, last[i]), 0

    def whole(i, j, last):
        return i, 0, 0, 0
    return pl.pallas_call(
        functools.partial(_lanes_kernel, scale=scale, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // rows, S // block),
            in_specs=[
                pl.BlockSpec((rows, 1, 1), lambda i, j, last: (i, 0, 0)),
                pl.BlockSpec((rows, G, R, d), whole),
                pl.BlockSpec((rows, block, G * d), held),
                pl.BlockSpec((rows, block, G * d), held)],
            out_specs=pl.BlockSpec((rows, G, R, d), whole),
            scratch_shapes=[pltpu.VMEM((rows, G, R, 1), jnp.float32),
                            pltpu.VMEM((rows, G, R, 1), jnp.float32),
                            pltpu.VMEM((rows, G, R, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, G, R, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="lanes_attention",
        interpret=interpret,
    )(last_blocks(lengths, block, rows), lengths.reshape(B, 1, 1),
      q.reshape(B, G, R, d), k.reshape(B, S, G * d),
      v.reshape(B, S, G * d)).reshape(B, heads, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def lanes_decode_attention(q, k, v, lengths, scale):
    """`lanes_kernel`, differentiable: the pullback is `attend_grouped`'s."""
    return lanes_kernel(q, k, v, lengths, scale)


def _lanes_forward(q, k, v, lengths, scale):
    return lanes_decode_attention(q, k, v, lengths, scale), (
        q, k, v, lengths)


lanes_decode_attention.defvjp(_lanes_forward, _grouped_backward)


# -- a block's keys and values into the caches --------------------------------
def write_block(k, v, k_new, v_new, pos):
    """The caches k, v [B, S, W] with k_new, v_new [B, L, W] at positions
    [pos[b], pos[b] + L) of row b: XLA's scatter of B x L rows, in place in
    a scan's carry. The two forms that write a row's L positions as ONE
    slice were measured and lost (my chip runs, PR 49; PERF.md section 5):
    `dynamic_update_slice` under `vmap` becomes a loop over the rows on a
    TPU (0.469 ms for both caches of 64 rows where the scatter takes 0.065);
    a kernel that reads, changes and writes the 16-row tile around a block
    in place took 0.034 alone and cost the cell 0.25 % against the scatter
    (a fusion XLA schedules among the step's other ops)."""
    at = (jnp.arange(k.shape[0])[:, None],
          pos[:, None] + jnp.arange(k_new.shape[1]))
    return k.at[at].set(k_new), v.at[at].set(v_new)
