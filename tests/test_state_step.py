"""A decode step's matrix states in place (`models/state_step.py`): the
kernel, run by the Pallas interpreter on the CPU at the published tile
shape, is the plain step (`transformer.kda_step` after the select that
zeroes the rows that reset) to float32 reassociation; its state comes back in
the buffer it came in; its derivative is the plain form's; a shape of part
tiles keeps XLA's fusions, and so does Mamba-2's step at any shape; and a
program lowered off a TPU says it runs no kernel. The decay a channel of the
key (Kimi Delta Attention) and ONE decay a head (Gated DeltaNet) go through
the same kernel and the same plain step: the second is the first fed the
head's number on every channel, bit for bit."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import catalog, state_step, transformer

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")

plain = transformer._kda_reset_step
interpreted = functools.partial(state_step.kda_kernel, interpret=True)


def operands(B, heads, d_k, d_v, seed=0):
    """(S, q, k, v, g, beta, reset) as a decode step meets them: unit q and
    k and the values in bfloat16, float32 log decays <= 0, beta in (0, 1),
    every second row beginning an episode."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf16 = jnp.bfloat16

    def unit(key):
        a = jax.random.normal(key, (B, heads, d_k))
        return (a / jnp.linalg.norm(a, axis=-1, keepdims=True)).astype(bf16)
    return (jax.random.normal(ks[0], (B, heads, d_k, d_v)), unit(ks[1]),
            unit(ks[2]), jax.random.normal(ks[3], (B, heads, d_v)).astype(
                bf16), -jnp.abs(jax.random.normal(ks[4], (B, heads, d_k))),
            jax.nn.sigmoid(jax.random.normal(ks[5], (B, heads))),
            jnp.arange(B) % 2)


def one_decay(given):
    """The same operands under ONE decay a head: g [B, heads], each head's
    first channel's."""
    S, q, k, v, g, beta, reset = given
    return S, q, k, v, g[..., 0], beta, reset


def widened(given):
    """One decay a head as the decay a channel that says the same."""
    S, q, k, v, g, beta, reset = given
    return S, q, k, v, jnp.broadcast_to(g[..., None], k.shape), beta, reset


def close(got, want):
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype == jnp.float32
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


# The published tile (Kimi-Linear's 32 heads of [128, 128]) at the module's
# heads a step and at the fewest a block of vectors holds; a narrower key
# axis under wider values, every head of a row in one step.
@pytest.mark.parametrize("shape, heads", [
    ((2, 32, 128, 128), None), ((2, 32, 128, 128), 8),
    ((3, 4, 64, 256), None)])
def test_the_kernel_is_the_plain_step(shape, heads):
    given = operands(*shape)
    want = plain(*given)
    close(interpreted(*given, heads=heads), want)
    # A row that reset keeps nothing of the state it had, whatever it was.
    S = given[0].at[1].set(jnp.nan)
    got = interpreted(S, *given[1:], heads=heads)
    close([a[1] for a in got], [a[1] for a in want])
    assert not np.isnan(got[0][1]).any()


@pytest.mark.parametrize("shape, heads", [
    ((2, 32, 128, 128), None), ((2, 32, 128, 128), 8),
    ((3, 4, 64, 256), None)])
def test_one_decay_a_head_is_the_same_kernel_and_the_same_step(shape, heads):
    """The scalar form of both (g [B, heads]) against the per-channel form
    fed the same number on every channel of a head: the plain steps agree
    bit for bit (exp(g) times a row is the same product either way), the
    kernel with the plain step to float32 reassociation; and no operand of
    the scalar call is as wide as a decay a channel would be."""
    given = one_decay(operands(*shape))
    want = plain(*widened(given))
    for got, w in zip(plain(*given), want):
        np.testing.assert_array_equal(got, w)
    close(interpreted(*given, heads=heads), want)
    close(interpreted(*widened(given), heads=heads), want)
    S = given[0].at[1].set(jnp.nan)
    got = interpreted(S, *given[1:], heads=heads)
    assert not np.isnan(got[0][1]).any()
    jaxpr = jax.make_jaxpr(functools.partial(interpreted, heads=heads))(
        *given)
    call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    B, H, d_k, d_v = shape
    # reset, beta, q, k, g, v, S: g is as small as beta.
    assert [v.aval.size for v in call.invars] == [
        B, B * H, B * H * d_k, B * H * d_k, B * H, B * H * d_v,
        B * H * d_k * d_v]


def test_the_state_comes_back_in_the_buffer_it_came_in():
    """The call's one aliased pair is (S, the state after the step), S is
    its last operand, and no other operand is as large as a [.., d_k, 1]
    array would be (a sixty-fourth of the states at most)."""
    given = operands(2, 32, 128, 128)
    S = given[0]
    jaxpr = jax.make_jaxpr(interpreted)(*given)
    call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["input_output_aliases"] == (
        (len(call.invars) - 1, 1),)
    assert call.invars[-1].aval.shape == call.outvars[1].aval.shape == S.shape
    assert all(v.aval.size <= S.size // 64 for v in call.invars[:-1])
    want = plain(*given)
    close(jax.jit(interpreted, donate_argnums=(0,))(*given), want)


@pytest.mark.parametrize("decay", ["a_channel", "a_head"])
def test_the_kernel_form_has_the_plain_form_s_derivative(decay):
    given = operands(2, 32, 128, 128)
    S, *vectors, reset = one_decay(given) if decay == "a_head" else given
    fused = state_step.in_place(interpreted, plain)
    weights = [jax.random.normal(jax.random.PRNGKey(n), a.shape)
               for n, a in enumerate(jax.eval_shape(plain, S, *vectors,
                                                    reset))]

    def loss(step, S, *vectors):
        # Linear in the outputs: both forms pull the same cotangents back.
        return sum(jnp.sum(a * w) for a, w in zip(
            step(S, *vectors, reset), weights))
    argnums = tuple(range(len(vectors) + 1))
    got = jax.grad(functools.partial(loss, fused), argnums)(S, *vectors)
    want = jax.grad(functools.partial(loss, plain), argnums)(S, *vectors)
    assert got[4].shape == vectors[3].shape  # the decay's: its own shape
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


@pytest.mark.parametrize("heads, d_k, d_v, whole", [
    (32, 128, 128, True), (16, 8, 256, True), (4, 128, 128, True),
    (4, 16, 16, False), (32, 128, 64, False), (32, 60, 128, False),
    (32, 256, 128, False), (36, 128, 128, False)])
def test_whole_tiles_are_a_matter_of_the_static_shape(heads, d_k, d_v,
                                                      whole):
    assert state_step.whole_tiles(heads, d_k, d_v) == whole


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """A program lowered here takes the branch a TPU's would, its kernel
    run by the Pallas interpreter."""
    monkeypatch.setattr(
        jax.lax, "platform_dependent",
        lambda *args, tpu, default: tpu(*args))
    monkeypatch.setattr(transformer, "_kda_in_place",
                        state_step.in_place(interpreted, plain))


def kernels_in(step, *given):
    return str(jax.make_jaxpr(step)(*given)).count("pallas_call")


def test_part_tiles_keep_the_plain_form_and_whole_ones_take_the_kernel(
        as_on_a_tpu):
    small = operands(2, 4, 16, 16)
    assert kernels_in(transformer.kda_decode_step, *small) == 0
    for g, w in zip(transformer.kda_decode_step(*small), plain(*small)):
        np.testing.assert_array_equal(g, w)
    whole = operands(2, 8, 128, 128)
    assert kernels_in(transformer.kda_decode_step, *whole) == 1
    close(transformer.kda_decode_step(*whole), plain(*whole))
    scalar = one_decay(whole)
    assert kernels_in(transformer.kda_decode_step, *scalar) == 1
    close(transformer.kda_decode_step(*scalar), plain(*scalar))


def test_off_a_tpu_whole_tiles_take_the_plain_form_too():
    whole = operands(2, 8, 128, 128)
    step = jax.jit(transformer.kda_decode_step)
    assert "tpu_custom_call" not in step.lower(*whole).as_text()
    close(step(*whole), plain(*whole))


@pytest.mark.parametrize("config, custom_model, rows, fragment, on_a_tpu", [
    ("impala_kimi_linear_48b_a3b", "kimi_linear", 32, 4096, 1.0),
    # Mamba-2's states keep XLA's fusions on every platform.
    ("impala_nemotron_twotower_30b_a3b", "nemotron_h", 128, 2048, 0.0),
    # Gated DeltaNet's take the kernel under one decay a head.
    ("impala_qwen3_next_80b_a3b", "qwen3_next", 32, 4096, 1.0)])
def test_a_cell_says_whether_its_states_take_the_kernel(
        config, custom_model, rows, fragment, on_a_tpu):
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        net = {k: v for k, v in json.load(f)["network"].items()
               if k != "param_count"}
    model = catalog.get_model(None, net["vocab_size"], {
        "custom_model": custom_model, "custom_model_config": net})
    assert model.static_counters(rows, fragment, "cpu")[
        "state_step_kernel"] == 0.0
    assert model.static_counters(rows, fragment, "tpu")[
        "state_step_kernel"] == on_a_tpu
