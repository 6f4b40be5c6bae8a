"""The grouped experts' products (`transformer.grouped_product`): the
library's TPU grouped-matmul kernels run by the Pallas interpreter on the
CPU against `jax.lax.ragged_dot`, the product and both its transposes; the
grouped form of `dropless_experts` on the kernels against the same form on
`ragged_dot`, gated and un-gated, over a held share; and the rule that
chooses the form and the tiles from the static shape.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import catalog, transformer
from ray_tpu.models.transformer import (
    dispatch_rows, dropless_experts, experts_batched, experts_fused,
    grouped_product, grouped_tiles)

# Rows in a tile of the kernels under `kernels_here`.
ROWS_TILE = 16


@pytest.fixture
def interpreted(monkeypatch):
    """The library's kernels run by the Pallas interpreter."""
    for kernel in ("_gmm", "_tgmm"):
        monkeypatch.setattr(transformer, kernel, functools.partial(
            getattr(transformer, kernel), interpret=True))


@pytest.fixture
def kernels_here(interpreted, monkeypatch):
    """A program lowered for this CPU takes the branch a TPU's would, in
    tiles of a test's size; every operand dtype has tiles."""
    def tiles(R, K, N, dtype=None):
        if R % ROWS_TILE:
            return None
        wide = 2 * ROWS_TILE
        return ((ROWS_TILE, K, 128), (ROWS_TILE, N, 128),
                (ROWS_TILE if R % wide else wide, 128, 128))
    monkeypatch.setattr(transformer, "grouped_tiles", tiles)
    monkeypatch.setattr(
        jax.lax, "platform_dependent",
        lambda *args, tpu, default: tpu(*args))


def product_and_transposes(product, rows, w, sizes, weight):
    out, pull = jax.vjp(lambda rows, w: product(rows, w, sizes), rows, w)
    return [np.asarray(a, np.float32) for a in (out, *pull(weight))]


# (R, K, N, the rows of each group): whole tiles; a group without rows at
# each end and in the middle; rows past the last group (a size of the
# ladder that holds more than landed); widths that are no whole tiles
# (nemotron_h's 1,856 is 14.5 lane tiles: here 3.625 and 1.8); one row a
# group; a family at its rehearsal's widths (nemotron_h: hidden 64, width
# 32, 2 of 8 experts held).
PRODUCTS = {
    "whole_tiles": (64, 256, 128, (16, 32, 16)),
    "groups_across_tiles": (64, 128, 256, (5, 30, 22, 7)),
    "empty_groups": (64, 128, 128, (0, 23, 0, 0, 41, 0)),
    "rows_past_the_last_group": (96, 128, 256, (11, 3, 27, 9)),
    "nothing_landed": (32, 128, 128, (0, 0, 0)),
    "widths_of_part_tiles": (64, 464, 232, (20, 0, 31, 6)),
    "a_row_a_group": (16, 128, 128, (1,) * 8),
    "nemotron_h_rehearsal": (32, 64, 32, (9, 17)),
}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", PRODUCTS)
def test_the_kernels_product_and_transposes_are_ragged_dots(
        case, dtype, kernels_here):
    """Over the rows of a group: the product, the rows' gradient; and the
    matrices' gradient whole, zeros for a group without rows. The rows
    past the last group are finite garbage on the way in and move
    nothing."""
    R, K, N, sizes = PRODUCTS[case]
    landed, dtype = sum(sizes), jnp.dtype(dtype)
    keys = jax.random.split(jax.random.PRNGKey(R + N), 3)
    rows = jax.random.normal(keys[0], (R, K), dtype)
    rows = rows.at[landed:].set(1e4)
    w = jax.random.normal(keys[1], (len(sizes), K, N), dtype) * K ** -0.5
    weight = jax.random.normal(keys[2], (R, N), dtype)
    weight = weight.at[landed:].set(-1e4)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = product_and_transposes(grouped_product, rows, w, sizes, weight)
    want = product_and_transposes(
        jax.lax.ragged_dot, rows.at[landed:].set(0), w, sizes,
        weight.at[landed:].set(0))
    limit = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for g, h in ((got[0][:landed], want[0][:landed]),
                 (got[1][:landed], want[1][:landed]), (got[2], want[2])):
        assert np.isfinite(g).all()
        assert np.max(np.abs(g - h), initial=0.0) <= limit * (
            np.max(np.abs(h), initial=0.0) + 1e-6)
    assert got[2].dtype == want[2].dtype and got[2].shape == w.shape
    empty = np.asarray(sizes) == 0
    assert not got[2][empty].any()


def test_a_shape_without_tiles_is_a_ragged_dot(interpreted, monkeypatch):
    """Where the rule gives no tiles no `platform_dependent` is traced:
    the program is `ragged_dot`'s, as it was."""
    def never(*args, **kwargs):
        raise AssertionError("a platform's branch was asked for")
    monkeypatch.setattr(jax.lax, "platform_dependent", never)
    rows = jnp.ones((24, 128), jnp.bfloat16)
    w = jnp.ones((2, 128, 128), jnp.bfloat16)
    sizes = jnp.asarray([10, 14], jnp.int32)
    assert grouped_tiles(24, 128, 128) is None
    text = jax.jit(grouped_product).lower(rows, w, sizes).as_text()
    assert text == jax.jit(jax.lax.ragged_dot).lower(
        rows, w, sizes).as_text().replace("ragged_dot", "grouped_product", 1)


# -- the layer on the kernels ------------------------------------------------
# (M, k, num_experts, held, first, H, W, gated, activation).
LAYERS = {
    "gated_share": (64, 2, 8, 4, 2, 128, 256, True, "silu"),
    "ungated_share": (64, 2, 8, 4, 0, 128, 232, False, "relu2"),
    "gated_all_here": (32, 2, 4, 4, 0, 128, 128, True, "relu"),
    "nemotron_h_rehearsal": (128, 2, 8, 2, 0, 64, 32, False, "relu2"),
}


def a_layer(case, dtype=jnp.float32):
    M, k, E, held, first, H, W, gated, act = LAYERS[case]
    keys = jax.random.split(jax.random.PRNGKey(M + W), 6)
    n = jax.random.normal(keys[0], (M, H), dtype)
    top_i = jnp.argsort(jax.random.uniform(keys[1], (M, E)))[:, :k]
    top_p = jax.nn.softmax(jax.random.normal(keys[2], (M, k)))
    w_gate, w_up = (
        jax.random.normal(key, (held, H, W), dtype) * H ** -0.5
        for key in keys[3:5])
    w_down = jax.random.normal(keys[5], (held, W, H), dtype) * W ** -0.5

    def layer(n, top_p, *weights):
        gate = weights[0] if gated else None
        out, sizes, R = dropless_experts(
            n, top_p, top_i, gate, *weights[-2:], first=first,
            num_experts=E, act=transformer.ACTIVATIONS[act])
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), (out, sizes, R)
    weights = (w_gate, w_up, w_down) if gated else (w_up, w_down)
    return layer, (n, top_p) + weights


def run(layer, operands):
    (_, (out, sizes, R)), grads = jax.value_and_grad(
        layer, argnums=tuple(range(len(operands))), has_aux=True)(*operands)
    return [out, *grads], np.asarray(sizes), int(R)


@pytest.fixture
def grouped_at_a_tests_size(monkeypatch):
    monkeypatch.setattr(transformer, "GROUP_COST_ROWS", 0)
    monkeypatch.setattr(transformer, "GROUPED_ROW_COST", 0.0)
    monkeypatch.setattr(transformer, "DISPATCH_TILE", ROWS_TILE)


@pytest.mark.parametrize("case", LAYERS)
def test_the_grouped_form_on_the_kernels_is_the_form_on_ragged_dot(
        case, grouped_at_a_tests_size, monkeypatch, request):
    """The sum and every gradient, where the kernels leave garbage in the
    rows of no group as a TPU's memory does: with a share held the size
    taken is one of the ladder's short ones, and `landed` keeps what
    stands past the count out of both passes."""
    layer, operands = a_layer(case)
    want, sizes, R = run(layer, operands)
    request.getfixturevalue("kernels_here")
    gmm, calls = transformer._gmm, []

    def as_a_tpu_leaves_it(lhs, rhs, group_sizes, *args, **kwargs):
        calls.append(lhs.shape)
        out = gmm(lhs, rhs, group_sizes, *args, **kwargs)
        past = jnp.arange(out.shape[0]) >= jnp.sum(group_sizes)
        return jnp.where(past[:, None], 3e4, out)
    monkeypatch.setattr(transformer, "_gmm", as_a_tpu_leaves_it)
    got, sizes_here, R_here = run(layer, operands)
    assert (sizes_here == sizes).all() and R_here == R
    M, k, E, held = LAYERS[case][:4]
    # The products of the forward pass and the rows' gradients: at the
    # ladder's first size.
    assert len(calls) >= 2 * (3 if LAYERS[case][7] else 2)
    assert {shape[0] for shape in calls} == {R}
    if held < E:
        assert sizes.sum() < R < M * k
    for g, w in zip(got, want):
        scale = float(jnp.max(jnp.abs(w))) + 1e-8
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * scale


def test_a_later_size_of_the_ladder_keeps_ragged_dot(
        grouped_at_a_tests_size, kernels_here, monkeypatch):
    """More pairs land than the first size holds (every row chooses two of
    the four held experts): the branch taken is `ragged_dot`'s, on the
    width padded inside it, and no kernel runs; the sum and the gradients
    are those of the form without kernels."""
    layer, (n, top_p, *weights) = a_layer("ungated_share")
    M, k, E, held, first = LAYERS["ungated_share"][:5]
    top_i = first + jnp.argsort(jax.random.uniform(
        jax.random.PRNGKey(5), (M, held)))[:, :k]

    def run_here():
        def loss(n, top_p, *weights):
            out, sizes, R = dropless_experts(
                n, top_p, top_i, None, *weights, first=first, num_experts=E,
                act=transformer.ACTIVATIONS["relu2"])
            return jnp.sum(jnp.sin(out.astype(jnp.float32))), (out, R)
        (_, (out, R)), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True)(n, top_p, *weights)
        return [out, *grads], int(R)

    # Every branch is traced, the first size's kernels too: stand-ins
    # whose zeros would show in the sum had that branch run.
    traced = []

    def stand_in(lhs, rhs, sizes, tiles, transpose_rhs=False):
        traced.append(lhs.shape[0])
        return jnp.zeros((lhs.shape[0], rhs.shape[1 if transpose_rhs else 2]),
                         lhs.dtype)
    monkeypatch.setattr(transformer, "_gmm", stand_in)
    monkeypatch.setattr(
        transformer, "_tgmm", lambda lhs, rhs, sizes, tiles: jnp.zeros(
            (sizes.shape[0], lhs.shape[1], rhs.shape[1]), lhs.dtype))
    got, R = run_here()
    sizes = dispatch_rows(M, k, held, E)
    assert R == M * k == sizes[-1] and set(traced) == {sizes[0]}
    monkeypatch.setattr(transformer, "grouped_tiles", lambda *a: None)
    want, _ = run_here()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_an_expert_without_rows_gets_a_zero_gradient(
        grouped_at_a_tests_size, kernels_here):
    """No row chooses the layer's last held expert: `tgmm` visits the
    empty group and writes zeros."""
    layer, (n, top_p, *weights) = a_layer("gated_share")
    M, k, E, held, first = LAYERS["gated_share"][:5]
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    top_i = jnp.argsort(jax.random.uniform(keys[0], (M, E - 1)))[:, :k]
    top_i = jnp.where(top_i >= first + held - 1, top_i + 1, top_i)

    def loss(*weights):
        out, sizes, _ = dropless_experts(
            n, top_p, top_i, *weights, first=first, num_experts=E)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), sizes
    grads, sizes = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(*weights)
    assert int(sizes[-1]) == 0 and int(sizes[0]) > 0
    for g in grads:
        assert not np.asarray(g[-1]).any() and np.asarray(g[0]).any()


# -- the rule ----------------------------------------------------------------
BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
# A cell of the benchmark: its learner's dispatch sizes over a minibatch of
# 8,192 tokens, and the three kernels' tiles of its up (and gate) product
# and of its down product at each of them, as the sweep on the chip chose
# them (PERF.md section 5: no cell's shape kept `ragged_dot`).
CELLS = {
    "olmoe_token_anakin": ((65536,), (
        ((256, 2048, 1024), (256, 1024, 1024), (256, 1024, 1024)),
        ((256, 1024, 1024), (256, 2048, 1024), (256, 1024, 1024)))),
    "glm47_flash_token_anakin": ((5120, 8192, 16384, 32768), (
        ((256, 2048, 768), (256, 1536, 1024), (256, 1024, 768)),
        ((256, 1536, 1024), (256, 2048, 768), (256, 768, 1024)))),
    "smallthinker_token_anakin_8k": ((15360, 24576, 49152), (
        ((256, 2560, 768), (256, 768, 640), (256, 640, 768)),
        ((256, 768, 640), (256, 2560, 768), (256, 768, 640)))),
    "lfm2_token_anakin_4k": ((10240, 16384, 32768), (
        ((256, 2048, 896), (256, 1792, 1024), (256, 1024, 896)),
        ((256, 1792, 1024), (256, 2048, 896), (256, 896, 1024)))),
    "kimi_linear_token_anakin_4k": ((2560, 4096, 8192, 65536), (
        ((256, 2304, 1024), (256, 1024, 768), (256, 768, 1024)),
        ((256, 1024, 768), (256, 2304, 1024), (256, 1024, 768)))),
    # 1,856 = 14.5 lane tiles: the contraction whole, its column tiles of
    # 1,024 with the last part-filled; 2,688 wide a tile of 1,024 columns
    # leaves room for 128 rows.
    "nemotron_h_token_anakin_2k": ((3840, 6144, 12288, 49152), (
        ((128, 2688, 1024), (256, 1856, 896), (256, 896, 1024)),
        ((256, 1856, 896), (128, 2688, 1024), (256, 1024, 896)))),
}


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_s_learner_takes_the_kernels_in_the_tiles_measured(cell):
    """From the cell's own files: the published widths, the minibatch, the
    share held. Every size of the ladder has tiles, those the sweep chose
    (the first size, the one the expected load takes, is the one that
    runs the kernels on a TPU); `learner_stats` says so, and says 0.0 of
    any other platform."""
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        workload = json.load(f)
    with open(os.path.join(BENCH, "configs",
                           workload["config"] + ".json")) as f:
        config = json.load(f)
    network = dict(config["network"])
    network.pop("param_count", None)
    model = catalog.get_model(None, network["vocab_size"], {
        "custom_model": config["trainer_config"]["model"]["custom_model"],
        "custom_model_config": network})
    trainer = workload["trainer_config"]
    rows, fragment, M = (trainer["num_envs_per_worker"],
                         trainer["rollout_fragment_length"],
                         trainer["sgd_minibatch_size"])
    k, E, H, W = (model.experts_per_token, model.num_experts,
                  model.hidden_size, model.expert_width)
    sizes, (up, down) = CELLS[cell]
    assert not experts_batched(M, k, E)
    assert dispatch_rows(M, k, model.held, E) == sizes
    for R in sizes:
        assert grouped_tiles(R, H, W) == up
        assert grouped_tiles(R, W, H) == down
        assert experts_fused(R, H, W, model.compute_dtype)
    assert model.static_counters(rows, fragment, "tpu", M)[
        "experts_grouped_kernel"] == 1.0
    assert model.static_counters(rows, fragment, "cpu", M)[
        "experts_grouped_kernel"] == 0.0
    # Not said where the learner's minibatch is not.
    assert "experts_grouped_kernel" not in model.static_counters(
        rows, fragment, "tpu")


@pytest.mark.parametrize("R,K,N,dtype,why", [
    (10240 + 64, 2048, 1792, "bfloat16", "no whole tiles of rows"),
    (10240, 2048, 1792, "float32", "operands of four bytes"),
    (256, 64, 32, "bfloat16", "a rehearsal's widths"),
    (10240, 2048, 1000, "bfloat16", "a width of no whole half tiles"),
    (10240, 8192, 2048, "bfloat16", "a contraction no tile holds whole"),
])
def test_a_shape_the_kernels_cannot_take_keeps_ragged_dot(R, K, N, dtype, why):
    assert grouped_tiles(R, K, N, dtype) is None, why
    assert not experts_fused(R, K, N, dtype)
    assert not experts_fused(R, N, K, dtype)


def test_rows_of_no_256_take_tiles_of_128():
    assert grouped_tiles(10240 + 128, 2048, 1792) == (
        (128, 2048, 896), (128, 1792, 1024), (128, 1024, 896))


def test_a_decode_step_and_a_rehearsal_say_no_kernel():
    """The decode takes the batched form and a rehearsal's minibatch too:
    neither runs a grouped product, whatever the platform."""
    model = catalog.get_model(None, 96, {
        "custom_model": "olmoe", "custom_model_config": dict(
            vocab_size=96, hidden_size=64, num_attention_heads=4,
            num_hidden_layers=2, num_experts=4, num_experts_per_tok=2,
            intermediate_size=32, max_position_embeddings=16)})
    assert model.static_counters(2, 16, "tpu", 32)[
        "experts_grouped_kernel"] == 0.0
