"""A decode step's attention over a latent cache or grouped heads' caches
(`models/decode_attention.py`, `transformer.cached_attention`): the kernel,
run by the Pallas interpreter on the CPU, against the two products over the
whole window, over rows whose lengths differ and caches of both kinds; what
lies beyond a row's length; grouped heads as block-diagonal queries against
a position's whole row; the rules that choose the form and the platform
they follow; gradients through the kernel forms; a whole `TokenDecoder`
decode through it against the causal pass; and what the counters say.
(`kernel_here` is `conftest.py`'s: the grouped models' test files use it
too.)
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import reference_glm4_moe_lite as reference  # noqa: E402

from ray_tpu.models import (  # noqa: E402
    catalog, decode_attention, rowwise, transformer)
from ray_tpu.models.transformer import decode_fused, grouped_fused  # noqa: E402
from ray_tpu.rllib.agents.impala import IMPALATrainer  # noqa: E402

from conftest import KERNEL_BLOCK as BLOCK  # noqa: E402

WINDOW = 4 * BLOCK
# (cached heads, query heads a cached one, d_qk, d_v, values cached apart):
# the second token cell's latent rows, a rehearsal's, and grouped heads
# with values of their own.
LAYOUTS = {
    "latent_576_512": (1, 20, 576, 512, False),
    "latent_rehearsal": (1, 4, 24, 16, False),
    "grouped_4x7_128": (4, 7, 128, 128, True),
    "grouped_2x3_64_32": (2, 3, 64, 32, True),
}
# Rows two a grid step: a step whose rows both end in block 0; one with a
# row at a block's last position and one at the next block's first; a row
# that holds the window whole beside one of a single position; a block's
# edge exactly, beside the middle of the last block.
LENGTHS = [1, BLOCK - 1, BLOCK, BLOCK + 1, WINDOW, 1, 2 * BLOCK,
           WINDOW - 3]
# float32: the two forms are one sum to rounding. bfloat16: they round the
# probabilities in different places (the chip read 0.008 at the cell's
# widths and outputs of 2.9).
LIMITS = {"f32": 1e-5, "bf16": 0.03}


def operands(layout, dtype, key=0):
    G, R, d_qk, d_v, apart = LAYOUTS[layout]
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
    B = len(LENGTHS)
    keys = jax.random.split(jax.random.PRNGKey(key), 3)
    q = jax.random.normal(keys[0], (B, G, R, d_qk), dtype)
    k = jax.random.normal(keys[1], (B, G, WINDOW, d_qk), dtype)
    v = jax.random.normal(keys[2], (B, G, WINDOW, d_v), dtype) if apart \
        else None
    return q, k, v, jnp.asarray(LENGTHS, jnp.int32), d_qk ** -0.5, (
        None if apart else d_v)


def kernel(q, k, v, lengths, scale, value_dim, rows=2):
    return decode_attention.prefix_kernel(
        q, k, v, lengths, scale, value_dim, block=BLOCK, rows=rows,
        interpret=True)


def f32(a):
    return np.asarray(a, np.float32)


# -- the two forms of the one sum ------------------------------------------
@pytest.mark.parametrize("rows", [1, 2, 8])
@pytest.mark.parametrize("dtype", LIMITS)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_is_the_whole_window_form(layout, dtype, rows):
    args = operands(layout, dtype)
    got = kernel(*args, rows=rows)
    want = decode_attention.whole_window(*args)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.max(np.abs(f32(got) - f32(want))) <= LIMITS[dtype]
    # A row of one position attends to it alone.
    q, k, v, *_ = args
    alone = (k[..., :want.shape[-1]] if v is None else v)[0, :, :1]
    np.testing.assert_allclose(
        f32(got[0]), np.broadcast_to(f32(alone), got[0].shape),
        atol=LIMITS[dtype])


@pytest.mark.parametrize("layout", LAYOUTS)
def test_what_lies_beyond_a_row_s_length_changes_nothing(layout):
    """NaN in every position a row does not hold, in the block its length
    ends in and in those beyond, which are not fetched."""
    q, k, v, lengths, scale, value_dim = operands(layout, "bf16")
    beyond = (jnp.arange(WINDOW)[None, :] >= lengths[:, None])[
        :, None, :, None]
    spoiled = [None if a is None else jnp.where(beyond, jnp.nan, a)
               for a in (k, v)]
    got = kernel(q, *spoiled, lengths, scale, value_dim)
    assert np.isfinite(f32(got)).all()
    np.testing.assert_array_equal(
        f32(got), f32(kernel(q, k, v, lengths, scale, value_dim)))


def test_the_limit_refuses_a_length_off_by_one():
    args = list(operands("latent_576_512", "f32"))
    want = f32(decode_attention.whole_window(*args))
    args[3] = jnp.minimum(args[3] + 1, WINDOW)
    got = f32(kernel(*args))
    moved = np.max(np.abs(got - want), axis=(1, 2, 3))
    # Every row but the one that holds the whole window.
    assert (moved[np.asarray(LENGTHS) < WINDOW] > 1e3 * LIMITS["f32"]).all()
    assert moved[LENGTHS.index(WINDOW)] <= LIMITS["f32"]


def test_blocks_beyond_the_furthest_row_of_a_step_are_not_fetched():
    """`last_blocks` is what the index maps clamp to and what the read
    counter counts; rows two a step."""
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    np.testing.assert_array_equal(
        decode_attention.last_blocks(lengths, BLOCK, 2), [0, 1, 3, 3])
    np.testing.assert_array_equal(
        decode_attention.last_blocks(lengths, BLOCK, 8), [3])
    assert decode_attention.rows_a_step(128) == decode_attention.ROWS
    assert decode_attention.rows_a_step(8) == 8
    assert decode_attention.rows_a_step(3) == 3
    assert decode_attention.rows_a_step(34) == 2


def test_whole_blocks_and_whole_steps_or_an_error():
    q, k, v, lengths, scale, value_dim = operands("latent_rehearsal", "f32")
    with pytest.raises(ValueError, match="whole blocks"):
        decode_attention.prefix_kernel(
            q, k, v, lengths, scale, value_dim, block=5, rows=2,
            interpret=True)
    with pytest.raises(ValueError, match="whole steps"):
        decode_attention.prefix_kernel(
            q, k, v, lengths, scale, value_dim, block=BLOCK, rows=3,
            interpret=True)


# -- grouped heads: block-diagonal queries against a position's whole row ----
# (cached heads, query heads a cached one, d): the fourth token cell's heads
# and the third's.
GROUPED = {"8x4_64": (8, 4, 64), "4x7_128": (4, 7, 128)}


def grouped_operands(layout, dtype, key=3):
    G, R, d = GROUPED[layout]
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
    B = len(LENGTHS)
    keys = jax.random.split(jax.random.PRNGKey(key), 3)
    q = jax.random.normal(keys[0], (B, G * R, d), dtype)
    k = jax.random.normal(keys[1], (B, WINDOW, G, d), dtype)
    v = jax.random.normal(keys[2], (B, WINDOW, G, d), dtype)
    return q, k, v, jnp.asarray(LENGTHS, jnp.int32), d ** -0.5


def grouped(q, k, v, lengths, scale, rows=2):
    return decode_attention.grouped_kernel(
        q, k, v, lengths, scale, block=BLOCK, rows=rows, interpret=True)


@pytest.mark.parametrize("rows", [1, 2, 8])
@pytest.mark.parametrize("dtype", LIMITS)
@pytest.mark.parametrize("layout", GROUPED)
def test_block_diagonal_form_is_attend_grouped(layout, dtype, rows):
    """Rows at unequal lengths, one of a single position, one that holds
    the window whole."""
    args = grouped_operands(layout, dtype)
    got = grouped(*args, rows=rows)
    want = decode_attention.attend_grouped(*args)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.max(np.abs(f32(got) - f32(want))) <= LIMITS[dtype]
    # A row of one position attends to it alone: each head reads the
    # values of its own cached head.
    q, k, v, *_ = args
    G, R, d = GROUPED[layout]
    np.testing.assert_allclose(
        f32(got[0]).reshape(G, R, d),
        np.broadcast_to(f32(v[0, 0])[:, None], (G, R, d)),
        atol=LIMITS[dtype])


@pytest.mark.parametrize("layout", GROUPED)
def test_lanes_of_other_groups_never_leak(layout):
    """Every other cached head's values poisoned: a group's heads read
    what they read of clean ones; and NaN beyond a row's length, in keys
    and values alike, changes nothing."""
    q, k, v, lengths, scale = grouped_operands(layout, "bf16")
    G, R, d = GROUPED[layout]
    clean = f32(grouped(q, k, v, lengths, scale)).reshape(-1, G, R, d)
    for g in range(G):
        others = (jnp.arange(G) != g)[None, None, :, None]
        got = f32(grouped(q, k, jnp.where(others, jnp.nan, v), lengths,
                          scale)).reshape(-1, G, R, d)
        np.testing.assert_array_equal(got[:, g], clean[:, g])
    beyond = (jnp.arange(WINDOW)[None, :] >= lengths[:, None])[
        :, :, None, None]
    got = grouped(q, jnp.where(beyond, jnp.nan, k),
                  jnp.where(beyond, jnp.nan, v), lengths, scale)
    np.testing.assert_array_equal(f32(got).reshape(clean.shape), clean)


# -- a block step: a block's fresh keys and values beside the caches ----------
# Rows two a grid step: a row that begins (nothing cached: the block alone),
# one block in; a block that ends at a kernel block's edge beside the one
# that begins there; a fresh block that straddles a kernel block's edge (the
# entry takes any position; only the write wants multiples of L); the
# cache's last block beside a row in the middle.
BLOCK_POS = [0, 4, BLOCK - 4, BLOCK, BLOCK - 2, 2 * BLOCK + 2, WINDOW - 4,
             WINDOW // 2]


def block_operands(L, groups, dtype, key=7, d=128, per_group=2):
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
    B = len(BLOCK_POS)
    keys = jax.random.split(jax.random.PRNGKey(key), 5)
    q = jax.random.normal(keys[0], (B, groups, per_group * L, d), dtype)
    k, v = (jax.random.normal(key, (B, WINDOW, groups * d), dtype)
            for key in keys[1:3])
    k_new, v_new = (jax.random.normal(key, (B, L, groups * d), dtype)
                    for key in keys[3:])
    return q, k, v, k_new, v_new, jnp.asarray(BLOCK_POS, jnp.int32), d ** -0.5


def block_entry(*args, rows=2):
    return decode_attention.block_kernel(
        *args, block=BLOCK, rows=rows, interpret=True)


def written_then_read(q, k, v, k_new, v_new, pos, scale):
    """The sum as a step that writes where it reads has it: the block
    scattered into the caches, `attend_grouped` up to the block's end."""
    B, G, R, d = q.shape
    L = k_new.shape[1]
    at = (jnp.arange(B)[:, None], pos[:, None] + jnp.arange(L))
    k, v = k.at[at].set(k_new), v.at[at].set(v_new)
    return decode_attention.attend_grouped(
        q.reshape(B, G * R, d), k.reshape(B, -1, G, d),
        v.reshape(B, -1, G, d), pos + L, scale).reshape(q.shape)


@pytest.mark.parametrize("dtype", LIMITS)
@pytest.mark.parametrize("groups", [2, 4])
@pytest.mark.parametrize("L", [2, 4])
def test_block_entry_is_the_sum_over_a_cache_with_the_block_written_in(
        L, groups, dtype):
    args = block_operands(L, groups, dtype)
    want = written_then_read(*args)
    for got in (block_entry(*args), decode_attention.attend_block(*args)):
        assert got.shape == want.shape and got.dtype == want.dtype
        assert np.max(np.abs(f32(got) - f32(want))) <= LIMITS[dtype]
    # A row that begins reads its own block alone: blind to the cache.
    q, k, v, k_new, v_new, pos, scale = args
    blind = block_entry(q, k * jnp.nan, v * jnp.nan, k_new, v_new,
                        jnp.zeros_like(pos), scale)
    alone = decode_attention.attend_block(
        q, k, v, k_new, v_new, jnp.zeros_like(pos), scale)
    assert np.max(np.abs(f32(blind) - f32(alone))) <= LIMITS[dtype]


@pytest.mark.parametrize("rows", [1, 2, 8])
def test_block_entry_at_any_rows_a_grid_step(rows):
    args = block_operands(4, 4, "f32")
    assert np.max(np.abs(f32(block_entry(*args, rows=rows))
                         - f32(written_then_read(*args)))) <= LIMITS["f32"]


@pytest.mark.parametrize("groups", [2, 4])
def test_lanes_of_other_groups_never_leak_into_a_block_s_heads(groups):
    """Every other cached head poisoned, keys and values, cached and
    fresh: a group's heads read what they read of clean ones; and NaN at
    and beyond a row's position in the caches changes nothing."""
    q, k, v, k_new, v_new, pos, scale = block_operands(4, groups, "bf16")
    d = q.shape[-1]
    clean = f32(block_entry(q, k, v, k_new, v_new, pos, scale))
    for g in range(groups):
        others = (jnp.arange(groups * d) // d != g)[None, None, :]
        got = f32(block_entry(q, *(jnp.where(others, jnp.nan, a) for a in (
            k, v, k_new, v_new)), pos, scale))
        np.testing.assert_array_equal(got[:, g], clean[:, g])
    beyond = (jnp.arange(WINDOW)[None, :] >= pos[:, None])[:, :, None]
    got = block_entry(q, jnp.where(beyond, jnp.nan, k),
                      jnp.where(beyond, jnp.nan, v), k_new, v_new, pos, scale)
    np.testing.assert_array_equal(f32(got), clean)


@pytest.mark.parametrize("dtype", LIMITS)
@pytest.mark.parametrize("L", [2, 4, 16])
def test_a_block_s_write_changes_its_own_rows_alone(L, dtype):
    """Rows at the cache's first and last block and across the batch:
    [pos, pos + L) takes the block bit for bit, every other position is
    what it was."""
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
    S, W = 64, 256
    pos = jnp.asarray([0, S - L, 16 - L, 16, 32, 48 - L], jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    k, v = (jax.random.normal(key, (6, S, W), dtype) for key in keys[:2])
    k_new, v_new = (jax.random.normal(key, (6, L, W), dtype)
                    for key in keys[2:])
    own = ((jnp.arange(S)[None, :] >= pos[:, None])
           & (jnp.arange(S)[None, :] < pos[:, None] + L))
    for got, old, new in zip(
            decode_attention.write_block(k, v, k_new, v_new, pos), (k, v),
            (k_new, v_new)):
        assert got.shape == old.shape and got.dtype == old.dtype
        np.testing.assert_array_equal(
            f32(got)[np.asarray(own)].reshape(6, L, W), f32(new))
        np.testing.assert_array_equal(
            f32(got)[~np.asarray(own)], f32(old)[~np.asarray(own)])


@pytest.mark.parametrize("S,d,fused", [
    (2048, 128, True),     # the seventh token cell's block step
    (2048, 256, True),
    (2048, 64, False),     # half a lane tile a cached head: no static cut
    (128, 128, False),     # one block
    (2000, 128, False),    # not whole blocks
])
def test_block_fused_is_a_rule_of_the_static_shape(S, d, fused):
    assert transformer.block_fused(S, d) is fused


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_gradients_through_the_block_entry_are_the_plain_form_s(
        kernel_here, dtype):
    """`block_attention` under a gradient, kernel form against plain form:
    the same pullback, evaluated at the same operands."""
    q, k, v, k_new, v_new, pos, scale = block_operands(4, 2, dtype)
    weights = jax.random.normal(jax.random.PRNGKey(5), q.shape)

    def loss(form):
        def f(q, k, v, k_new, v_new):
            o = form(q, k, v, k_new, v_new, pos, scale)
            o = o[0] if isinstance(o, tuple) else o
            return jnp.sum(o.astype(jnp.float32) * weights)
        return jax.grad(f, argnums=(0, 1, 2, 3, 4))(q, k, v, k_new, v_new)
    got = loss(transformer.block_attention)
    want = loss(decode_attention.attend_block)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(f32(a), f32(b), atol=1e-6)
    o, read = transformer.block_attention(q, k, v, k_new, v_new, pos, scale)
    assert np.max(np.abs(f32(o) - f32(written_then_read(
        q, k, v, k_new, v_new, pos, scale)))) <= LIMITS[dtype]
    # Two rows a grid step, the blocks up to the further one's last cached
    # position: a row that begins still has block 0 fetched.
    assert float(read) == BLOCK * np.mean([1, 1, 3, 4])


# -- the rule ----------------------------------------------------------------
@pytest.mark.parametrize("S,R,d_qk,value_dim,fused", [
    (1024, 20, 576, 512, True),    # the second token cell's decode step
    (16, 4, 24, 16, False),        # its rehearsal's, and a test's
    (128, 20, 576, 512, False),    # one block
    (256, 20, 576, 512, True),     # two
    (1000, 20, 576, 512, False),   # no whole blocks
    (1024, 20, 576, 500, False),   # values of no whole lane tiles
    (1024, 20, 544, 512, False),   # a rotary key of no whole half tile
    (1024, 20, 512, 512, False),   # no rotary key: no latent layout
    (4096, 128, 576, 512, True),   # DeepSeek-V2's heads
    (2048, 16, 320, 256, True),
])
def test_decode_fused_is_a_rule_of_the_static_shape(S, R, d_qk, value_dim,
                                                    fused):
    assert decode_attention.BLOCK == 128
    assert decode_fused(S, R, d_qk, value_dim) == fused


@pytest.mark.parametrize("S,groups,heads,d,fused", [
    (4096, 8, 32, 64, True),     # the fourth token cell's one cache
    (8192, 4, 28, 128, True),    # the third's full cache
    (4096, 4, 28, 128, True),    # and its rings
    (24, 2, 8, 16, False),       # a rehearsal's, and a test's
    (128, 8, 32, 64, False),     # one block
    (256, 8, 32, 64, True),      # two
    (4000, 8, 32, 64, False),    # no whole blocks
    (4096, 3, 24, 64, False),    # a position's heads no whole lane tiles
    (4096, 16, 32, 32, False),   # heads under half a tile
    (4096, 4, 8, 96, False),     # heads of no whole half tiles
    (4096, 8, 30, 64, False),    # no whole groups of query heads
    (4096, 2, 64, 64, True),     # one lane tile a position
    (32768, 8, 64, 128, True),
    (4096, 2, 16, 256, True),    # the eighth token cell's: heads of 256
    (8192, 8, 48, 128, True),    # the ninth's full caches, 1,024 lanes
    (512, 8, 64, 128, True),     # and its rings, four blocks
])
def test_grouped_fused_is_a_rule_of_the_static_shape(S, groups, heads, d,
                                                     fused):
    assert decode_attention.BLOCK == 128
    assert grouped_fused(S, groups, heads, d) == fused


@pytest.mark.parametrize("groups,d,lanes", [
    (8, 128, True),     # the ninth token cell's row of 1,024 lanes
    (4, 128, False),    # the third's and the seventh's 512: block-diagonal
    (8, 64, False),     # the fourth's: heads of half a tile cannot be cut
    (2, 256, False),    # the eighth's 512
    (16, 64, False),    # 1,024 lanes of half tiles
    (4, 256, True),
])
def test_grouped_lanes_is_a_rule_of_the_static_shape(groups, d, lanes):
    """Which of the kernel's two forms grouped caches take: a cached head
    against its own lanes where a position's row is wider than the 512
    lanes the block-diagonal form was measured at and a head is whole
    tiles; the four older cells keep the form they have."""
    assert transformer.grouped_lanes(groups, d) == lanes


@pytest.mark.parametrize("platform,S,kernel_there", [
    ("cpu", 1024, False), ("tpu", 1024, True), ("tpu", 1000, False)])
def test_the_form_follows_the_platform_the_program_is_lowered_for(
        platform, S, kernel_there):
    """Lowered for a TPU, whole blocks: the kernel, and no product against
    the [B, S, 576] window. Anywhere else the two products and no kernel."""
    q = jax.ShapeDtypeStruct((8, 20, 576), jnp.bfloat16)
    cache = jax.ShapeDtypeStruct((8, S, 576), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((8,), jnp.int32)
    lowered = jax.jit(functools.partial(
        transformer.cached_attention, v_cache=None, scale=576 ** -0.5,
        value_dim=512)).trace(q, cache, pos=pos).lower(
            lowering_platforms=(platform,)).as_text()
    assert ("tpu_custom_call" in lowered) == kernel_there
    assert ("dot_general" in lowered) != kernel_there


@pytest.mark.parametrize("kind", ["heads_of_their_own", "grouped"])
def test_the_other_kinds_of_cache_take_no_kernel(kind):
    """A head's own keys and values take the `switch`; grouped heads took
    no kernel until PR 39 and take it since, lowered for a TPU and nowhere
    else, with no product against the caches left."""
    heads, groups = (16, 16) if kind == "heads_of_their_own" else (28, 4)
    q = jax.ShapeDtypeStruct((8, heads, 128), jnp.bfloat16)
    cache = jax.ShapeDtypeStruct((8, 1024, groups, 128), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((8,), jnp.int32)
    traced = jax.jit(transformer.cached_attention).trace(q, cache, cache, pos)
    lowered = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert ("tpu_custom_call" in lowered) == (kind == "grouped")
    if kind == "grouped":
        assert "dot_general" not in lowered
        off = traced.lower(lowering_platforms=("cpu",)).as_text()
        assert "tpu_custom_call" not in off and "dot_general" in off


# -- through `cached_attention`, on this CPU ----------------------------------
def latent(dtype, B=4):
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(keys[0], (B, 4, 24), dtype)
    cache = jax.random.normal(keys[1], (B, WINDOW, 24), dtype)
    weight = jax.random.normal(keys[2], (B, 4, 16), jnp.float32)
    pos = jnp.asarray([0, BLOCK - 1, BLOCK, WINDOW - 1][:B], jnp.int32)
    return q, cache, pos, weight


def test_cached_attention_reads_the_blocks_held(kernel_here, monkeypatch):
    q, cache, pos, _ = latent(jnp.float32)
    got, read = transformer.cached_attention(
        q, cache, None, pos, scale=0.25, value_dim=16)
    # Rows two a step: blocks [0, 0] and [1, 3] are the last held.
    assert float(read) == (1 + 4) / 2 * BLOCK
    monkeypatch.undo()
    want, whole = transformer.cached_attention(
        q, cache, None, pos, scale=0.25, value_dim=16)
    assert float(whole) == WINDOW
    np.testing.assert_allclose(f32(got), f32(want), atol=1e-5)


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_gradients_through_the_kernel_form_are_the_plain_form_s(
        dtype, kernel_here, monkeypatch):
    """The bootstrap step's `value_and_grad`: the kernel's output with the
    two products' pullback."""
    q, cache, pos, weight = latent(
        {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype])

    def run():
        def loss(q, cache):
            out, _ = transformer.cached_attention(
                q, cache, None, pos, scale=0.25, value_dim=16)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(q, cache)
        return [f32(a) for a in (out,) + grads]
    fused = run()
    monkeypatch.undo()
    plain = run()
    assert np.max(np.abs(fused[0] - plain[0])) <= LIMITS[dtype]
    for got, want in zip(fused[1:], plain[1:]):
        np.testing.assert_array_equal(got, want)
    # Nothing flows to what a row does not hold.
    assert not fused[2][0, 1:].any() and fused[2][0, 0].any()


def grouped_caches(dtype, layout="8x4_64", B=4):
    """Caches [B, S, G, d] of a ring of `WINDOW` slots: a row at its first
    position, one at a block's edge, one at the ring's last slot, and one
    whose position has passed the ring's length and holds every slot."""
    G, R, d = GROUPED[layout]
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(keys[0], (B, G * R, d), dtype)
    k = jax.random.normal(keys[1], (B, WINDOW, G, d), dtype)
    v = jax.random.normal(keys[2], (B, WINDOW, G, d), dtype)
    weight = jax.random.normal(keys[3], (B, G * R, d), jnp.float32)
    pos = jnp.asarray([0, BLOCK, WINDOW - 1, 3 * WINDOW + 5][:B], jnp.int32)
    return q, k, v, pos, weight


@pytest.mark.parametrize("layout", GROUPED)
def test_cached_attention_reads_a_grouped_ring_s_blocks_held(
        layout, kernel_here, monkeypatch):
    q, k, v, pos, _ = grouped_caches(jnp.float32, layout)
    got, read = transformer.cached_attention(q, k, v, pos)
    # Rows two a step: blocks [0, 1] and [3, 3 (every slot)] the last held.
    assert float(read) == (2 + 4) / 2 * BLOCK
    monkeypatch.undo()
    want, whole = transformer.cached_attention(q, k, v, pos)
    assert float(whole) == WINDOW
    np.testing.assert_allclose(f32(got), f32(want), atol=1e-5)


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_gradients_through_the_grouped_kernel_form_are_attend_grouped_s(
        dtype, kernel_here, monkeypatch):
    """The kernel's output with the pullback of the two products over the
    caches by head, not of the block-diagonal ones."""
    q, k, v, pos, weight = grouped_caches(
        {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype])

    def run():
        def loss(q, k, v):
            out, _ = transformer.cached_attention(q, k, v, pos)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return [f32(a) for a in (out,) + grads]
    fused = run()
    monkeypatch.undo()
    plain = run()
    assert np.max(np.abs(fused[0] - plain[0])) <= LIMITS[dtype]
    for got, want in zip(fused[1:], plain[1:]):
        np.testing.assert_array_equal(got, want)
    # Nothing flows to the values a row does not hold; every slot of a
    # ring that has turned is held.
    assert not fused[3][0, 1:].any() and fused[3][0, 0].any()
    assert fused[3][3].any(axis=(1, 2)).all()


# -- a whole decode ------------------------------------------------------------
NET = dict(vocab_size=96, hidden_size=64, num_attention_heads=4,
           num_key_value_heads=4, num_hidden_layers=3, q_lora_rank=24,
           kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8,
           v_head_dim=16, first_k_dense_replace=1, intermediate_size=96,
           n_routed_experts=8, experts_held=2, first_expert_held=0,
           num_experts_per_tok=2, moe_intermediate_size=32,
           n_shared_experts=1, topk_method="noaux_tc", n_group=1,
           topk_group=1, norm_topk_prob=True, routed_scaling_factor=1.8,
           num_nextn_predict_layers=1, max_position_embeddings=WINDOW,
           rope_theta=1e6, rms_norm_eps=1e-5)


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_a_decode_through_the_kernel_form_is_the_causal_pass(
        dtype, kernel_here):
    """Every position of a rehearsal-sized glm4_moe_lite decoded one token
    at a time through the kernel form, against the causal pass's
    decompressed keys and values; the share of the window each step read."""
    rows = 4
    model = catalog.get_model(None, NET["vocab_size"], {
        "custom_model": "glm4_moe_lite", "custom_model_config": NET,
        "compute_dtype": dtype})
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (rows, WINDOW), 0, NET["vocab_size"])
    variables = model.init(jax.random.PRNGKey(0), tokens[:, :1],
                           model.initial_state(rows), jnp.zeros((rows, 1)))
    logits, values, _ = model.apply(
        variables, tokens, None, jnp.zeros((rows, WINDOW)))

    @jax.jit
    def decode(token, state):
        return model.apply(variables, token, state, jnp.zeros(rows),
                           method="decode", mutable=["counters"])
    state, got_l, got_v, read = model.initial_state(rows), [], [], []
    for t in range(WINDOW):
        (step_l, step_v, state), kept = decode(tokens[:, t], state)
        got_l.append(step_l)
        got_v.append(step_v)
        read.append(float(kept["counters"]["decode_cache_read_share"][-1]))
    assert read == [(t // BLOCK + 1) * BLOCK / WINDOW for t in range(WINDOW)]
    got_l, got_v = jnp.stack(got_l, 1), jnp.stack(got_v, 1)
    if dtype == "f32":
        assert reference.relative_error(got_l, logits) <= 1e-5
        assert reference.relative_error(got_v, values) <= 1e-5
        return
    # bfloat16: where a token's experts tie within the rounding, one of
    # them changes and that position's logits with it (the two products
    # over the whole window read the same here: 8 of 128 positions).
    by_position = np.max(np.abs(f32(got_l) - f32(logits)), axis=-1) / np.max(
        np.abs(f32(logits)))
    assert np.median(by_position) <= reference.TOLERANCE / 3
    assert np.mean(by_position > reference.TOLERANCE) <= 0.15


# -- the counters ---------------------------------------------------------------
def test_static_counters_name_the_kernel_and_its_block():
    cell = dict(NET, vocab_size=19360, hidden_size=2048,
                num_attention_heads=20, num_key_value_heads=20,
                num_hidden_layers=5, q_lora_rank=768, kv_lora_rank=512,
                qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
                intermediate_size=10240, n_routed_experts=64, experts_held=8,
                num_experts_per_tok=4, moe_intermediate_size=1536,
                max_position_embeddings=1024)
    for net, platform, kernel_there, block in [
            (cell, "tpu", 1.0, decode_attention.BLOCK),
            (cell, "cpu", 0.0, 1024), (NET, "tpu", 0.0, WINDOW)]:
        model = catalog.get_model(None, net["vocab_size"], {
            "custom_model": "glm4_moe_lite", "custom_model_config": net})
        counters = model.static_counters(128, 1024, platform)
        assert counters["decode_attention_kernel"] == kernel_there
        assert counters["decode_cache_block"] == block
        assert counters["latent_cache_bytes_per_token"] == (
            net["num_hidden_layers"]
            * (net["kv_lora_rank"] + net["qk_rope_head_dim"]) * 2)


def test_learner_stats_report_what_the_kernel_read(kernel_here):
    """The trainer on the fused Anakin path with the kernel form in its
    rollout and under its learner's bootstrap step: a window that fills
    from empty reads 1/2 + block / 2S of itself over a rollout."""
    trainer = IMPALATrainer(config=dict(
        env="TokenBigram-v0",
        env_config={"vocab_size": NET["vocab_size"], "episode_len": WINDOW},
        anakin=True, num_workers=0, num_envs_per_worker=4,
        rollout_fragment_length=WINDOW, train_batch_size=4 * WINDOW,
        sgd_minibatch_size=2 * WINDOW, num_sgd_iter=1,
        anakin_updates_per_call=1, min_iter_time_s=0, lr=6e-4, seed=3,
        model={"custom_model": "glm4_moe_lite", "custom_model_config": NET,
               "compute_dtype": "f32"}))
    try:
        result = trainer.train()
        assert np.isfinite(result["info"]["learner"]["total_loss"])
        kept = trainer.optimizer.learner_stats
        assert kept["decode_cache_read_share"] == pytest.approx(
            0.5 + BLOCK / (2 * WINDOW))
        # The host's counters are of the platform the trainer runs on.
        assert kept["decode_attention_kernel"] == 0.0
    finally:
        trainer.stop()


# -- the chip's compiler --------------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def shaped(sharding, *shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("rows", [128, 8])
def test_the_kernel_compiles_for_a_v5e_at_the_cell_s_widths(rows, one_chip):
    """Mosaic takes the contraction of 576, the value slice of 512 and the
    block as they stand (the rollout's 128 rows, the bootstrap step's 8),
    and the cache enters as it lies: no copy of the window."""
    compiled = jax.jit(functools.partial(
        transformer.cached_attention, v_cache=None, scale=576 ** -0.5,
        value_dim=512)).trace(
            shaped(one_chip, rows, 20, 576), shaped(one_chip, rows, 1024, 576),
            pos=shaped(one_chip, rows, dtype=jnp.int32)).lower(
                lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in compiled
    assert not [line for line in compiled.splitlines()
                if " copy(" in line and f"bf16[{rows},1,1024,576]" in
                line.split(" copy(")[0]]


@pytest.mark.parametrize("rows,heads,groups,d,S", [
    (64, 32, 8, 64, 4096),     # the fourth token cell's rollout
    (2, 32, 8, 64, 4096),      # and its bootstrap step
    (16, 28, 4, 128, 8192),    # the third's full cache
    (16, 28, 4, 128, 4096),    # its rings
    (1, 28, 4, 128, 8192),     # its bootstrap step
    (64, 4 * 32, 4, 128, 2048),  # the seventh's block step: a block's 4
                                 # positions folded into the 32 heads' rows
    (32, 16, 2, 256, 4096),    # the eighth's rollout: 8 query heads a
    (2, 16, 2, 256, 4096),     # cached head of 256; its bootstrap step
    (32, 48, 8, 128, 8192),    # the ninth's full layers: 6 query heads a
    (32, 64, 8, 128, 512),     # cached head of 128; its rings: 8 a head
    (1, 64, 8, 128, 512),      # its bootstrap step (a cached head against
                               # its own lanes: `lanes_attention`)
])
def test_the_grouped_form_compiles_for_a_v5e_at_the_cells_widths(
        rows, heads, groups, d, S, one_chip):
    """Mosaic takes 32 and 28 query rows against rows of 512 lanes, and a
    cache stored flat enters as it lies: its view by head and the kernel's
    view of that are bitcasts, no copy of the cache."""
    def step(q, k_cache, v_cache, pos):
        by_head = (rows, S, groups, d)
        return transformer.cached_attention(
            q, k_cache.reshape(by_head), v_cache.reshape(by_head), pos)
    flat = shaped(one_chip, rows, S, groups * d)
    compiled = jax.jit(step).trace(
        shaped(one_chip, rows, heads, d), flat, flat,
        shaped(one_chip, rows, dtype=jnp.int32)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in compiled
    assert ("lanes_attention" in compiled) == transformer.grouped_lanes(
        groups, d)
    assert not cache_copies(compiled, rows, S)


@pytest.mark.parametrize("queries", [3, 2])
def test_the_stream_mask_compiles_inside_the_fused_causal_form(
        queries, one_chip):
    """Mosaic takes `block_stream_allowed`'s integer divisions inside the
    splash kernel and its backward kernel at the seventh cell's learner
    shape (a clean and two noisy streams of 2,048 positions, 32 query heads
    over 4 key/value heads of 128), square (every stream's queries: the
    first four layers) and rectangular (the noisy streams' queries against
    every stream's keys: the last layer), and no score matrix is written."""
    n, rows = 3 * 2048, queries * 2048

    def loss(q, k, v, episode):
        return jnp.sum(transformer.block_stream_attention(
            q, k, v, episode, 1.0, block=4, streams=3).astype(jnp.float32))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        shaped(one_chip, 1, 32, rows, 128), shaped(one_chip, 1, 4, n, 128),
        shaped(one_chip, 1, 4, n, 128),
        shaped(one_chip, 1, n, dtype=jnp.int32)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    assert compiled.count("tpu_custom_call") >= 2
    assert f"f32[1,32,{rows},{n}]" not in compiled


def cache_copies(compiled, rows, S):
    """The lines of a compiled program that lay a [rows, S, ..] or
    [rows, 1, S, ..] bfloat16 array out anew."""
    def result(line):
        return line.split(" = ", 1)[1][:60] if " = " in line else ""
    return [line for line in compiled.splitlines()
            if any(f" {op}(" in line for op in (
                "copy", "copy-start", "reshape", "transpose"))
            and any(shape in result(line) for shape in (
                f"bf16[{rows},{S},", f"bf16[{rows},1,{S},"))]


@pytest.mark.parametrize("rows", [64, 2])
def test_the_block_entry_compiles_for_a_v5e_at_the_cell_s_widths(
        rows, one_chip):
    """Mosaic takes the seventh cell's block step as it stands: 32 query
    rows a cached head (8 heads x 4 positions) against the head's own 128
    lanes of a fetched row, four fresh positions (a quarter of a bfloat16
    tile) as the products' short side, and the caches where they lie."""
    S, G, R, d, L = 2048, 4, 32, 128, 4
    cache, fresh = shaped(one_chip, rows, S, G * d), shaped(
        one_chip, rows, L, G * d)
    compiled = jax.jit(functools.partial(
        transformer.block_attention, scale=d ** -0.5)).trace(
            shaped(one_chip, rows, G, R, d), cache, cache, fresh, fresh,
            shaped(one_chip, rows, dtype=jnp.int32)).lower(
                lowering_platforms=("tpu",)).compile().as_text()
    assert "block_attention" in compiled and "tpu_custom_call" in compiled
    assert not cache_copies(compiled, rows, S)


def test_the_compiled_block_step_holds_no_copy_of_a_cache(one_chip):
    """The seventh cell's whole block step at its real widths (shapes
    alone), the state donated as the rollout's scan carries it: 14 calls of
    the block entry (3 passes x 5 layers less the commit pass's last), ten
    scatters (the commit pass's, a cache each: none of a denoising pass),
    each cache aliased through; no cache laid out anew."""
    import json
    with open(os.path.join(BENCH, "configs/impala_sdar_30b_a3b.json")) as f:
        network = {k: v for k, v in json.load(f)["network"].items()
                   if k != "param_count"}
    model = catalog.get_model(None, network["vocab_size"] - 1, {
        "custom_model": "sdar_moe", "custom_model_config": network,
        "compute_dtype": "bf16"})
    rows, S = 64, network["max_position_embeddings"]

    def there(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    variables = there(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
        model.initial_state(1), jnp.zeros((1, 1)))))

    def step(variables, obs, state, reset, rng):
        return model.apply(variables, obs, state, reset, rng,
                           method="block_step", mutable=["routing"])[0]
    compiled = jax.jit(step, donate_argnums=(2,)).trace(
        variables, shaped(one_chip, rows, dtype=jnp.int32),
        there(jax.eval_shape(lambda: model.initial_state(rows))),
        shaped(one_chip, rows, dtype=jnp.float32),
        there(jax.eval_shape(lambda: jax.random.PRNGKey(0)))).lower(
            lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if " custom-call(" in line]
    assert sum("block_attention" in line.split(" = ")[0]
               for line in calls) == 14
    assert not cache_copies(text, rows, S)
    assert len([line for line in text.splitlines() if " scatter(" in line
                and f"bf16[{rows},{S}," in line.split(" scatter(")[0]]) == 10
    # Ten caches in, the same ten buffers out.
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 10 * rows * S * 512 * 2
    assert memory.temp_size_in_bytes < rows * S * 512 * 2


def test_a_cache_stored_by_head_would_be_copied_every_step(one_chip):
    """Why grouped caches are stored flat: [B, S, 8, 64] is tiled over its
    last two axes, and the kernel's view of it is another layout."""
    rows, heads, groups, d, S = 64, 32, 8, 64, 4096
    by_head = shaped(one_chip, rows, S, groups, d)
    compiled = jax.jit(transformer.cached_attention).trace(
        shaped(one_chip, rows, heads, d), by_head, by_head,
        shaped(one_chip, rows, dtype=jnp.int32)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in compiled
    assert cache_copies(compiled, rows, S)


@pytest.mark.parametrize("decay", ["a_channel", "a_head"])
@pytest.mark.parametrize("rows", [32, 2])
def test_the_state_step_compiles_for_a_v5e_at_the_cell_s_widths(
        rows, decay, one_chip):
    """The other kernel file's (`models/state_step.py`; here because this is
    the one file that loads the chip's compiler): Mosaic takes the fifth
    cell's 32 heads of [128, 128] (the rollout's 32 rows, the bootstrap
    step's 2), the turned vectors and the one-lane slices as they stand, and
    a scan's carried states go through it where they lie: no copy of one.
    The eighth cell's states are the same 32 heads under ONE decay a head,
    which arrives as a row a grid step and multiplies a head's whole tile."""
    def steps(S, q, k, v, g, beta, reset):
        def one(S, _):
            o, S = transformer.kda_decode_step(S, q, k, v, g, beta, reset)
            return S, jnp.sum(o)
        return jax.lax.scan(one, S, None, length=4)
    f32 = jnp.float32
    vector = shaped(one_chip, rows, 32, 128)
    compiled = jax.jit(steps, donate_argnums=(0,)).trace(
        shaped(one_chip, rows, 32, 128, 128, dtype=f32), vector, vector,
        vector, shaped(one_chip, rows, 32, 128, dtype=f32)
        if decay == "a_channel" else shaped(one_chip, rows, 32, dtype=f32),
        shaped(one_chip, rows, 32, dtype=f32),
        shaped(one_chip, rows, dtype=jnp.int32)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    assert "kda_state_step" in compiled and "tpu_custom_call" in compiled
    assert not [line for line in compiled.splitlines()
                if " copy(" in line and f"f32[{rows},32,128,128]" in
                line.split(" copy(")[0]]


@pytest.mark.parametrize("cell, rows, H, W, k, E, held, act", [
    ("qwen3_next", 32, 2048, 512, 10, 512, 32, "silu"),
    ("kimi_linear", 32, 2304, 1024, 8, 256, 8, "silu"),
    ("smallthinker", 16, 2560, 768, 6, 64, 16, "relu"),
])
def test_the_chosen_experts_compile_for_a_v5e_at_the_cells_widths(
        cell, rows, H, W, k, E, held, act, one_chip):
    """The third kernel file's (`models/expert_step.py`; here because this is
    the one file that loads the chip's compiler): a rollout's step of the
    three cells whose steps leave held experts without a row is the kernel
    (the matrices' blocks by a prefetched id, an expert a grid step), and
    the same shapes without `rollout` (a learner's bootstrap step says
    nothing) keep XLA's batched products and no kernel."""
    def experts(rollout):
        def run(n, top_p, top_i, w_gate, w_up, w_down):
            return transformer.dropless_experts(
                n, top_p, top_i, w_gate, w_up, w_down, 0, E,
                transformer.ACTIVATIONS[act], rollout)[0]
        return jax.jit(run).trace(
            shaped(one_chip, rows, H),
            shaped(one_chip, rows, k, dtype=jnp.float32),
            shaped(one_chip, rows, k, dtype=jnp.int32),
            shaped(one_chip, held, H, W), shaped(one_chip, held, H, W),
            shaped(one_chip, held, W, H)).lower(
                lowering_platforms=("tpu",)).compile().as_text()
    assert transformer.experts_sparse(rows, k, E, H, W)
    compiled = experts(True)
    assert "chosen_experts" in compiled and "tpu_custom_call" in compiled
    assert "tpu_custom_call" not in experts(False)


YARN = (32.0, 4096, 64.0, 1.0, 1.3)


@pytest.mark.parametrize("cell, B, heads, T, d, share, scaling", [
    ("laguna, window queries", 1, 64, 8192, 128, 1.0, ()),
    ("laguna, full queries", 1, 48, 8192, 128, 0.5, YARN),
    ("laguna, keys", 1, 8, 8192, 128, 1.0, ()),
    ("sdar, three streams' queries", 4, 32, 6144, 128, 1.0, ()),
    ("sdar, the last layer's queries", 4, 32, 4096, 128, 1.0, ()),
    ("sdar, keys", 4, 4, 6144, 128, 1.0, ()),
    ("smallthinker, queries", 1, 28, 8192, 128, 1.0, ()),
    ("qwen3_next, queries", 2, 16, 4096, 256, 0.25, ()),
    ("qwen3_next, keys", 2, 2, 4096, 256, 0.25, ()),
])
def test_the_rotation_compiles_for_a_v5e_at_the_cells_widths(
        cell, B, heads, T, d, share, scaling, one_chip):
    """The fourth kernel file's (`models/rowwise.py`; here because this is
    the one file that loads the chip's compiler): a learner's head-major
    rotation and its pullback are two calls of the one kernel; the
    rollout's form (rows by head) keeps `rope`."""
    rotation = transformer.Rotation(10000.0, share, scaling)

    def rotated(head_major):
        def loss(x, positions, w):
            return jnp.sum(transformer.TokenDecoder._rotate(
                None, x, positions, rotation, d ** -0.5, head_major) * w)
        shape = (B, heads, T, d) if head_major else (B, T, heads, d)
        return jax.jit(jax.value_and_grad(loss)).trace(
            shaped(one_chip, *shape),
            shaped(one_chip, B, T, dtype=jnp.int32),
            shaped(one_chip, *shape)).lower(
                lowering_platforms=("tpu",)).compile().as_text()
    assert rowwise.whole_tiles(T, d, int(d * share))
    compiled = rotated(True)
    assert compiled.count("tpu_custom_call") == 2
    assert "rotate_rows" in compiled
    assert "tpu_custom_call" not in rotated(False)


@pytest.mark.parametrize("cell, B, heads, T, d, a_head", [
    ("laguna, window layers", 1, 64, 8192, 128, True),
    ("laguna, full layers", 1, 48, 8192, 128, True),
    ("qwen3_next", 2, 16, 4096, 256, False),
])
def test_the_gate_compiles_for_a_v5e_at_the_cells_widths(
        cell, B, heads, T, d, a_head, one_chip):
    """The gate a head (its column out of a [positions, heads] tile) and
    the gate a value, forward and pullback: two kernels, and the gate a
    head never lies [B, heads, T, 1]."""
    import types
    me = types.SimpleNamespace(compute_dtype=jnp.bfloat16)

    def loss(o, gate, w):
        return jnp.sum(transformer.TokenDecoder._gated(me, o, gate, True) * w)
    whole = shaped(one_chip, B, heads, T, d)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(
        whole, shaped(one_chip, B, T, heads) if a_head else whole,
        whole).lower(lowering_platforms=("tpu",)).compile().as_text()
    assert compiled.count("tpu_custom_call") == 2
    assert "gate_rows_back" in compiled
    assert f"[{B},{heads},{T},1]" not in compiled
