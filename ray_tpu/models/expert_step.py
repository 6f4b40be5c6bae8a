"""A decode step's routed experts, over the held experts that some row chose.

    out[m] = sum_e c[m, e] W_down,e (act(W_gate,e n[m]) * W_up,e n[m])

for a step's rows n [M, H], c [M, E] float32 the weight a row gives held
expert e (exactly 0 where it did not choose it) and the held experts'
matrices [E, H, W] / [E, W, H] in n's dtype; without a gate matrix W_down,e
act(W_up,e n[m]). `transformer.dropless_experts` has the sum's other two
forms. Its batched form multiplies every row by every held expert: a step of
few rows is bound by reading the matrices, and it reads all E experts'
whatever the router said. Where a step brings under two rows a held expert
many of them have no row (32 rows, 10 of 512 a row: 53 % under a uniform
router), and their bytes are read to be multiplied by 0.

`chosen_kernel` (Pallas, TPU) reads the others alone: a grid over the held
experts, the ids of those with a row first (a prefetched scalar row) and
their count; the three matrices of grid step i are fetched by `ids[i]`, and
the steps past the count name the block the last one fetched, for which the
pipeline issues no copy, and run nothing. A step multiplies all M rows by
its expert, as the batched form does (a row that did not choose it has c
exactly 0 there), rounds the products and the weighted hidden rows to n's
dtype, and adds the down product into a float32 [M, H] sum that stays in
VMEM over the grid. It rounds in two places fewer than the batched form,
which also rounds act(gate) and act(gate) * up to n's dtype: here both stay
float32 until the weighted rows are rounded, so the kernel's sum is the
nearer one to the float32 sum and equals the batched form's to two roundings
of n's dtype. An expert's width is one block: a width past `WIDTH` keeps the
batched form (`whole_tiles`).

On a v5e, one layer's experts alone in a scan that feeds seeded rows and
seeded routing, ms a step, the batched form's beside the kernel's at the
share of the held experts that had a row (my chip run, PR 53; PERF.md
section 5 has the table): 32 rows over 32 experts of 2,048 x 512 (10 of
512 a row) 0.271 against 0.141 at 0.47, 0.114 at 0.37, 0.074 at 0.22; 32
rows over 8 of 2,304 x 1,024 (8 of 256) 0.160 against 0.109 at 0.63, 0.074
at 0.40, 0.039 at 0.14; 16 rows over 16 of 2,560 x 768 (6 of 64) 0.255
against 0.208 at 0.79, 0.165 at 0.62, 0.116 at 0.42. A straight line in the
share read, 13-20 us at none, and the batched form's time at a share of
0.97-1.0: the kernel reads at the rate XLA's fusions do, and skips what it
does not read. The grouped form at the step's M k sorted pairs whole
(`ragged_dot`, or the library's `gmm` at tiles of 128 rows) took 0.245 /
0.226 / 0.305 where the kernel took 0.141 / 0.109 / 0.208: a sort, a gather,
three products and a scatter-add cost more than they skip.

No derivative: the rollout's step is the only caller (`dropless_experts`'
`chosen`), and nothing differentiates it; the learner's bootstrap step keeps
the batched form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# The widest expert a grid step holds whole, the widest a cell has run: tiles
# of 128 to 512 columns of the three cells' widths (512, 768, 1,024) were
# 0-5 % slower than the width whole, so the kernel has no tiles. A wider
# expert keeps the batched form: the wider ones were timed only at steps that
# leave no expert empty (1,536 to 1,856 wide, 64 to 128 rows: 1.04 to 1.96
# of the batched form's time; my chip run, PR 53, `micro2.json`).
WIDTH = 1024
# What the kernel may hold in VMEM: two buffers of three matrices [H, WIDTH]
# (28.3 MB at 2,304 x 1,024) beside the rows and the sum.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def whole_tiles(M: int, H: int, W: int, dtype=jnp.bfloat16) -> bool:
    """Whether `M` rows of hidden `H` through experts `W` wide can take the
    kernel: a function of the static shape alone. Operands of two bytes in
    whole (16, 128) tiles, an expert's width one block."""
    return (jnp.dtype(dtype).itemsize == 2 and M % 16 == 0
            and H % LANES == 0 and W % LANES == 0 and W <= WIDTH)


def _body(ids_ref, count_ref, n_ref, c_ref, *refs, act, gated):
    """One grid step: the i-th chosen held expert."""
    *gate_ref, up_ref, down_ref, out_ref = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < count_ref[0])
    def _():
        n = n_ref[...]

        def product(w_ref):
            return jnp.dot(n, w_ref[...], preferred_element_type=jnp.float32
                           ).astype(n.dtype).astype(jnp.float32)
        up = product(up_ref)
        hidden = act(product(gate_ref[0])) * up if gated else act(up)
        # Column ids[i] of c, [M, 1]: selected, the column's number is a
        # scalar.
        c = c_ref[...]
        here = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1) == ids_ref[i]
        weight = jnp.sum(jnp.where(here, c, 0.0), axis=1, keepdims=True)
        out_ref[...] += jnp.dot(
            (weight * hidden).astype(n.dtype), down_ref[...],
            preferred_element_type=jnp.float32)


def chosen_kernel(n, c, group_sizes, w_gate, w_up, w_down, act=jax.nn.silu,
                  *, interpret=False):
    """The sum over the held experts e with `group_sizes[e]` > 0, as the
    kernel: [M, H] float32. n [M, H]; c [M, E] float32;
    `group_sizes` [E], the rows that chose each held expert; `w_gate` (or
    None) and `w_up` [E, H, W], `w_down` [E, W, H], in n's dtype.
    `interpret` runs it by the Pallas interpreter (a test on a CPU). The
    call states no `cost_estimate`: with an honest one (the expected share's
    bytes and FLOPs) a call of `qwen3_next_token_anakin_4k` took 13.24 s
    against 13.18 (PERF.md section 6, PR 53), and alone it moved nothing."""
    M, H = n.shape
    E, _, W = w_up.shape
    gated = w_gate is not None
    chosen = group_sizes > 0
    count = jnp.sum(chosen.astype(jnp.int32))
    # The chosen experts' ids in turn, then the last of them again: a step
    # that names the block the step before it named fetches nothing.
    order = jnp.argsort(~chosen, stable=True).astype(jnp.int32)
    ids = order[jnp.minimum(jnp.arange(E), jnp.maximum(count - 1, 0))]

    def expert(i, ids, count):
        return ids[i], 0, 0

    def whole(i, ids, count):
        return 0, 0
    matrices = ([w_gate] if gated else []) + [w_up, w_down]
    return pl.pallas_call(
        functools.partial(_body, act=act, gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(E,),
            in_specs=[pl.BlockSpec((M, H), whole), pl.BlockSpec((M, E), whole)]
            + [pl.BlockSpec((None, H, W), expert)] * (len(matrices) - 1)
            + [pl.BlockSpec((None, W, H), expert)],
            out_specs=pl.BlockSpec((M, H), whole)),
        out_shape=jax.ShapeDtypeStruct((M, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="chosen_experts",
        interpret=interpret,
    )(ids, count.reshape(1), n, c.astype(jnp.float32), *matrices)
