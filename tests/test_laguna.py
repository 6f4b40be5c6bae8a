"""The `laguna` token policy at a tiny size on the CPU: the family's row, the
checks it shares with the other families (`tests/token_families.py`: the model
against the plain reference `benchmark/lib/reference_laguna.py` in its causal
form and decoded through two full caches and three rings, each named wrong
mathematics refused by the cell's limits, the grouped form of the expert
product, the cell's program from its shapes, the builder's refusals, the tuned
example) and what is its own: an attention whose GEOMETRY is a layer kind's (a
full layer of 4 query heads under YaRN over half a head beside window layers
of 6 under the default rotation over the whole head, all over 2 cached heads,
a gate a head), fragments longer than three windows and than the preset's
original positions, so that the rings turn and the scaled rotation leaves its
trained range; the decode kernel's two forms at the cell's cached row; YaRN's
frequencies at the PUBLISHED parameters against hand-computed values; the
expert layer that holds a share against the uncut layer; the published
parameter count. The loss and the loop: `tests/test_laguna_update.py`.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from token_families import (  # noqa: F401: pytest collects what is named
    Family, build, causal_routed, configuration, decode_routed, shapes_of,
    share_of,
    test_a_causal_pass_over_the_landed_rows_is_the_batched_pass,
    test_causal_pass_matches_reference,
    test_custom_model_config_without_a_part_is_refused,
    test_decode_through_every_kind_of_state_matches_reference
    as test_decode_through_two_full_caches_and_three_rings_matches_reference,
    test_limits_refuse_wrong_mathematics,
    test_the_cell_s_program_is_known_from_its_static_shapes,
    test_the_tuned_example_is_the_benchmark_s_cell)

from lib import reference_laguna as reference

from ray_tpu.models import decode_attention, transformer
from ray_tpu.models.transformer import dropless_experts

# The published layers 0-4 in small: full + dense, three window layers and a
# full one with experts; 4 / 6 query heads over 2 cached heads of 16; 4 of 16
# experts held, 3 a token, beside a shared one; a window of 8 under
# fragments of 32 (a ring turns four times) and YaRN trained on 16 positions
# (the fragment's second half lies beyond them), its ramp over all four of
# the rotated half's frequencies.
WINDOW, S, B = 8, 32, 3
ROPE = {
    "full_attention": {
        "rope_theta": 100, "rope_type": "yarn", "factor": 8,
        "original_max_position_embeddings": 16, "beta_slow": 0.05,
        "beta_fast": 1, "attention_factor": 1.2079441541679836,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1},
    "original_max_position_embeddings": 16}
KINDS = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
NET = dict(vocab_size=96, hidden_size=64, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, num_hidden_layers=5,
           sliding_window=WINDOW, intermediate_size=128, num_experts=16,
           experts_held=4, first_expert_held=0, num_experts_per_tok=3,
           moe_intermediate_size=32, shared_expert_intermediate_size=32,
           moe_routed_scaling_factor=2.5, max_position_embeddings=S,
           rms_norm_eps=1e-6, partial_rotary_factor=0.5, layer_types=KINDS,
           mlp_layer_types=["dense"] + ["sparse"] * 4,
           num_attention_heads_per_layer=[4, 6, 6, 6, 4],
           rope_parameters=ROPE)
# Grouped heads' caches are stored flat: 2 cached heads of 16 a row.
CACHES = [(S, 32)] + [(WINDOW, 32)] * 3 + [(S, 32)]
PUBLISHED_YARN = transformer.LAGUNA_PUBLISHED[
    "rope_parameters"]["full_attention"]

FAMILY = Family(
    name="laguna", net=NET, reference=reference, B=B, S=S,
    # What a pass hands a decode: the context's positions of a full layer,
    # a ring of the window of each window layer.
    state_shapes=lambda positions: (
        [(positions, 32)] * 2 + [(WINDOW, 32)] * 6 + [(positions, 32)] * 2,),
    state_layers={"kv": [2, 2, 2, 2, 2]},
    # Four of the five layers route.
    expert_layers=4, experts_per_token=3,
    sharp_keys=("wq", "wk", "wg"), limits_build=dict(sharp=4.0),
    # One block a cache at this size: a full layer reads its 32 positions,
    # a ring its 8 of the context's 32.
    decode_counters={
        "decode_cache_read_share": pytest.approx((2 + 3 / 4) / 5),
        "decode_cache_read_share_full": 1.0,
        "decode_cache_read_share_window": pytest.approx(1 / 4)},
    wrong_updates={
        "no_gate_in_the_gradient": dict(mutate="no_attention_gate"),
        "full_layers_rotated_as_window_layers": dict(
            mutate="default_rope_on_the_full_layer"),
        "vf_coeff_doubled": dict(cfg={"vf_loss_coeff": 1.0},
                                 by="loss_error"),
        "no_clip": dict(cfg={"grad_clip": None}, by="update_error"),
        "ten_times_the_lr": dict(cfg={"lr": 6e-3}, by="update_error")},
    refused=(
        ({"n_routed_experts": 8}, "not laguna's"),
        ({"rope_theta": 10000}, "not laguna's"),
        ({"gating": False}, "gating"),
        ({"attention_bias": True}, "attention_bias"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"moe_apply_router_weight_on_input": True},
         "router_weight_on_input"),
        ({"model_type": "qwen3_moe"}, "model_type"),
        ({"num_key_value_heads": 4}, "groups"),
        ({"num_attention_heads_per_layer": [4, 6]}, "names each"),
        ({"layer_types": ["full_attention", "linear_attention"] * 3},
         "names each"),
        ({"mlp_layer_types": ["dense", "sparse", "dense", "sparse",
                              "sparse"]}, "leading"),
        ({"rope_parameters": dict(ROPE, full_attention=dict(
            ROPE["full_attention"], rope_type="llama3"))}, "rope_type"),
        ({"rope_parameters": dict(ROPE, sliding_attention=dict(
            ROPE["sliding_attention"], factor=4))}, "rope_parameters"),
        ({"partial_rotary_factor": 0.25}, "partial_rotary_factor"),
        ({"experts_held": 12, "first_expert_held": 8}, "not among")),
    example="laguna-token-impala.yaml", cell="laguna_token_anakin_8k",
    config="impala_laguna_xs2_33b_a3b",
    # What the benchmark's cell is, from shapes alone: 32 rows, five layers
    # of the published widths, 32 of 256 experts held; a TPU's program
    # takes the decode kernel in every layer (a ring of 512 is four
    # blocks), the chosen experts' kernel in the rollout, the fused causal
    # form whose window layers visit two tiles a row of tiles; the caches
    # count a ring as 512 positions and a full cache as the episode.
    program=dict(
        rows=32, fragment=8192, minibatch=(8192,),
        on_tpu={
            "decode_rows_per_expert": 1.0, "decode_experts_batched": 0.0,
            "decode_experts_sparse": 1.0,
            "decode_cache_block": decode_attention.BLOCK,
            "decode_attention_kernel": 1.0, "causal_attention_fused": 1.0,
            "rotation_fused_layers": 5.0, "experts_grouped_kernel": 1.0,
            "kv_cache_bytes_per_token": (
                2 * 8 * 128 * 2 * (2 * 8192 + 3 * 512) / 8192),
            "window_layers": 3, "kv_groups": 6,
            # 16 tiles a side: 136 causal, 16 + 15 within a window of one
            # tile.
            "causal_window_tiles_kept": 31 / 136},
        off_tpu={
            "decode_experts_batched": 1.0, "decode_experts_sparse": 0.0,
            "decode_experts_read_share": 1.0, "decode_cache_block": 8192,
            "decode_attention_kernel": 0.0, "causal_attention_fused": 0.0,
            "rotation_fused_layers": 0.0, "experts_grouped_kernel": 0.0,
            "causal_window_tiles_kept": 1.0},
        state={"kv": [((32, 8192, 1024), "bfloat16")] * 2
               + [((32, 512, 1024), "bfloat16")] * 6
               + [((32, 8192, 1024), "bfloat16")] * 2},
        # The trainer's own count is the configuration's.
        parameters=691_625_985))


# -- the decode: the kernel's forms, a prefill --------------------------------
@pytest.fixture
def lanes_here(kernel_here, monkeypatch):
    """`conftest.kernel_here`, the grouped caches through the form that
    scores a cached head against its own lanes, whatever their width."""
    monkeypatch.setattr(transformer, "grouped_lanes", lambda *shape: True)


@pytest.mark.parametrize("form", ["diagonal", "lanes"])
def test_a_decode_through_either_kernel_form_is_the_causal_pass(
        form, kernel_here, request):
    """The grouped caches through the kernel forms (`conftest.kernel_here`:
    blocks of 8 positions, interpreted): a window of 16 under 32
    positions, so a full cache is four blocks and a ring two, which turns
    at position 16 and holds every slot from then on. A step reads the
    blocks its rows hold; the logits are the causal pass's, which keeps
    every position and masks the window."""
    if form == "lanes":
        request.getfixturevalue("lanes_here")
    net = dict(NET, sliding_window=2 * WINDOW)
    built = build(FAMILY, "f32", net, fresh=True)
    _, variables, tokens = built
    system, state, counted = decode_routed(built, variables, tokens)
    for t, step in enumerate(counted):
        held = 8 * (t // 8 + 1)
        assert step["decode_cache_read_share_full"] == pytest.approx(
            held / S)
        assert step["decode_cache_read_share_window"] == pytest.approx(
            min(held, 2 * WINDOW) / S)
    causal, _, _ = causal_routed(built, variables, tokens)
    assert reference.relative_error(system[0], causal[0]) < 1e-5
    assert reference.relative_error(system[1], causal[1]) < 1e-5
    assert np.array_equal(system[2], causal[2])


@pytest.mark.parametrize("per", [6, 8])
@pytest.mark.parametrize("form", ["diagonal", "lanes"])
def test_the_decode_kernel_forms_at_the_cell_s_cached_row(form, per):
    """8 cached heads of 128 (1,024 lanes a position) under 6 or 8 query
    heads each, the cell's two layer kinds: both kernel forms by the
    interpreter against XLA's two products, rows that hold one position, a
    part of a block, whole blocks and the whole cache."""
    rows, S_, G, d = 4, 32, 8, 128
    keys = jax.random.split(jax.random.PRNGKey(per), 3)
    q = jax.random.normal(keys[0], (rows, G * per, d), jnp.bfloat16)
    k, v = (jax.random.normal(key, (rows, S_, G, d), jnp.bfloat16)
            for key in keys[1:])
    lengths = jnp.array([1, 7, 16, 32])
    kernel = {"diagonal": decode_attention.grouped_kernel,
              "lanes": decode_attention.lanes_kernel}[form]
    want = decode_attention.attend_grouped(q, k, v, lengths, d ** -0.5)
    got = kernel(q, k, v, lengths, d ** -0.5, block=8, rows=2,
                 interpret=True)
    # bfloat16 outputs of sums of a few unit normals: one rounding apart.
    assert reference.relative_error(
        got.astype(jnp.float32), want.astype(jnp.float32)) < 0.01


def test_the_lanes_form_differentiates_as_the_two_products(monkeypatch):
    """A learner's bootstrap step differentiates through the decode: the
    kernel form's pullback is the two products' over the caches by head."""
    import functools
    monkeypatch.setattr(decode_attention, "lanes_kernel", functools.partial(
        decode_attention.lanes_kernel, block=8, rows=2, interpret=True))
    rows, S_, G, d = 4, 16, 2, 128
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (rows, G * 3, d))
    k, v = (jax.random.normal(key, (rows, S_, G, d)) for key in keys[1:3])
    w = jax.random.normal(keys[3], (rows, G * 3, d))
    lengths = jnp.array([1, 7, 9, 16])

    def grads(attend):
        return jax.grad(lambda *a: jnp.sum(
            attend(*a, lengths, d ** -0.5) * w), argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(grads(decode_attention.lanes_decode_attention),
                         grads(decode_attention.attend_grouped)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_prefill_then_ring_decode_and_a_reset_inside_a_fragment():
    """A fragment whose rows start a new episode at different steps, then
    a decode that goes on from the state it hands over: the logits of the
    episode's own positions, whatever came before it in the fragment."""
    built = build(FAMILY, "f32")
    _, variables, tokens = built
    half = S // 2
    reset = jnp.zeros((B, S)).at[:, half].set(1.0)
    (both, _, _), state, _ = causal_routed(built, variables, tokens, reset)
    (alone, _, _), _, _ = causal_routed(built, variables, tokens[:, half:])
    assert reference.relative_error(both[:, half:], alone) < 1e-5
    # The state is the second episode's: a decode goes on from position 16.
    more = jax.random.randint(jax.random.PRNGKey(2), (B, 4), 0, 96)
    (want, _, _), _, _ = causal_routed(
        built, variables, jnp.concatenate([tokens[:, half:], more], axis=1))
    for t in range(4):
        step, _, state = built.decode(
            variables, more[:, t:t + 1], state, jnp.zeros((B, 1)))
        assert reference.relative_error(step[:, 0], want[:, half + t]) < 1e-5


# -- the geometry by layer kind: the parts, one at a time ----------------
def test_two_head_counts_and_two_rotations_stand_in_one_stack():
    """A layer's W_q, W_o and gate have its own kind's heads; its rotation
    is its kind's: the full layers YaRN over half a head, the window layers
    the default over all of it."""
    model, variables, _ = build(FAMILY, "f32")
    shapes = jax.tree.map(lambda a: a.shape, variables["params"])
    for i, heads in enumerate([4, 6, 6, 6, 4]):
        layer = shapes[f"layer_{i}"]
        assert (layer["wq"], layer["wo"], layer["wg"], layer["wk"]) == (
            (64, heads * 16), (heads * 16, 64), (64, heads), (64, 32)), i
        assert "q_norm" not in layer and "router_bias" not in layer
    assert "dense_gate" in shapes["layer_0"]
    assert "router" not in shapes["layer_0"]
    assert shapes["layer_1"]["shared_up"] == (64, 32)
    assert set(variables) == {"params"}  # no selection bias: no constants
    kinds = [model.layer_kind(i) for i in range(5)]
    yarn = (8, 16, 1, 0.05, 1.2079441541679836)
    assert kinds[0] == kinds[4] == transformer.AttentionKind(
        0, True, 4, transformer.Rotation(100, 0.5, yarn))
    assert kinds[1] == kinds[2] == kinds[3] == transformer.AttentionKind(
        WINDOW, True, 6, transformer.Rotation(10000, 1, ()))
    assert [model.cache_len(i) for i in range(5)] == [S, 8, 8, 8, S]
    # The rotations differ where they are applied: a full layer's query at
    # position 20 under its own rotation and under the window layers'.
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 16))
    at = jnp.array([20])
    own = model._rotate(x, at, kinds[0].rotation)
    other = model._rotate(x, at, kinds[1].rotation)
    assert np.array_equal(own[..., 8:], x[..., 8:])  # the half that passes
    assert not np.allclose(own[..., :8], other[..., :8], atol=1e-2)


def test_yarn_frequencies_at_the_published_parameters():
    """Laguna-XS.2's full layers: 64 of a head's 128 values rotated, theta
    500,000, factor 64 over 4,096 positions, beta 64 / 1. By hand: c(64) =
    64 ln(4096 / (128 pi)) / (2 ln 500000) = 5.66, c(1) = 15.80, so the
    ramp runs from 5 to 16; frequency 0 is untouched (1), frequency 31
    is theta^(-62/64) / 64; the factor is 0.1 ln 64 + 1."""
    p = PUBLISHED_YARN
    scaling = (p["factor"], p["original_max_position_embeddings"],
               p["beta_fast"], p["beta_slow"], p["attention_factor"])
    inv_freq, factor = transformer.rope_frequencies(
        64, p["rope_theta"], scaling)
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    c = lambda r: 64 * np.log(4096 / (2 * np.pi * r)) / (2 * np.log(5e5))
    assert (int(np.floor(c(64))), int(np.ceil(c(1)))) == (5, 16)
    want = plain.copy()
    for i in range(32):
        ramp = min(max((i - 5) / 11.0, 0.0), 1.0)
        want[i] = plain[i] * (1 - ramp) + plain[i] / 64 * ramp
    np.testing.assert_allclose(inv_freq, want, rtol=2e-6)
    assert inv_freq[0] == 1.0
    np.testing.assert_allclose(inv_freq[5], 500000.0 ** (-10 / 64),
                               rtol=2e-6)
    np.testing.assert_allclose(inv_freq[16], 500000.0 ** (-0.5) / 64,
                               rtol=2e-6)
    np.testing.assert_allclose(inv_freq[31], 500000.0 ** (-62 / 64) / 64,
                               rtol=2e-6)
    assert factor == pytest.approx(0.1 * np.log(64) + 1, abs=1e-12)
    assert factor == 1.4158883083359672
    # The reference writes the formulas out on its own.
    ref_freq, ref_factor, rotated = reference.rope_frequencies(p, 128)
    assert rotated == 64 and ref_factor == factor
    np.testing.assert_allclose(ref_freq, want, rtol=2e-6)
    # The default rotation is what it was.
    np.testing.assert_allclose(
        transformer.rope_frequencies(128, 10000.0)[0],
        10000.0 ** (-np.arange(64) / 64.0), rtol=2e-6)


# -- the expert layer that holds a share ---------------------------------
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight shares of 2 of 16 experts: their parts, with the shared
    expert counted ONCE, add up to what the uncut reference gives for the
    whole layer (the reference's shares, and the system's in both forms of
    its product)."""
    rng = np.random.default_rng(0)
    H, W, E, k, held = 64, 32, 16, 3, 2
    net = dict(NET, num_experts=E)

    def normal(*shape, over=8):
        return rng.normal(size=shape).astype(np.float32) / over
    lp = jax.tree.map(jnp.asarray, {
        "attn_norm": np.ones(H, np.float32),
        "mlp_norm": np.ones(H, np.float32),
        "wq": normal(H, 96), "wk": normal(H, 32), "wv": normal(H, 32),
        "wg": normal(H, 6), "wo": normal(96, H),
        "router": normal(H, E, over=4),
        "w_gate": normal(E, H, W), "w_up": normal(E, H, W),
        "w_down": normal(E, W, H, over=6),
        "shared_gate": normal(H, W), "shared_up": normal(H, W),
        "shared_down": normal(W, H, over=6)})
    x = jnp.asarray(rng.normal(size=(2, 12, H)), jnp.float32)

    @functools.partial(jax.jit, static_argnums=(1,))
    def layer(first, size, **other):
        with jax.default_matmul_precision("highest"):
            return reference._layer(
                dict(share_of(lp, first, size), **other), x,
                dict(net, experts_held=size, first_expert_held=first), 1,
                lambda a: a, None, None)
    whole, chosen, _ = layer(0, E)
    silent = {"w_down": jnp.zeros_like(lp["w_down"])}
    # h alone, x + attention: experts and a shared expert that give nothing.
    no_expert, _, _ = layer(
        0, E, **silent, shared_down=jnp.zeros_like(lp["shared_down"]))
    shared_alone, _, _ = layer(0, E, **silent)
    parts = sum(layer(first, held)[0] - shared_alone
                for first in range(0, E, held))
    assert reference.relative_error(
        parts + (shared_alone - no_expert), whole - no_expert) < 1e-5
    # Counting the shared expert with every share would be eight of it.
    assert reference.relative_error(
        sum(layer(first, held)[0] - no_expert
            for first in range(0, E, held)), whole - no_expert) > 0.1

    # The system's shares of the same routing, in the form each shape
    # takes (24 rows batched, 64 times as many grouped).
    m = transformer.rms_norm(no_expert.reshape(-1, H), lp["mlp_norm"], 1e-6,
                             jnp.float32)
    top_p, top_i = transformer.route(m, lp["router"], k, True, scale=2.5,
                                     sigmoid=True)
    assert np.array_equal(np.sort(top_i, -1),
                          np.sort(chosen.reshape(-1, k), -1))
    np.testing.assert_allclose(jnp.sum(top_p, -1), 2.5, rtol=1e-6)
    routed_whole = (whole - shared_alone).reshape(-1, H)
    for reps in (1, 64):
        rows, p, i = (jnp.tile(a, (reps, 1)) for a in (m, top_p, top_i))
        routed, landed = jnp.zeros_like(rows), 0
        for first in range(0, E, held):
            s = share_of(lp, first, held)
            part, sizes, _ = dropless_experts(
                rows, p, i, s["w_gate"], s["w_up"], s["w_down"], first, E,
                jax.nn.silu)
            routed, landed = routed + part, landed + int(jnp.sum(sizes))
        assert landed == rows.shape[0] * k
        assert reference.relative_error(
            routed[:m.shape[0]], routed_whole) < 1e-4
    assert transformer.experts_batched(m.shape[0], k, E)
    assert not transformer.experts_batched(64 * m.shape[0], k, E)



def test_the_published_config_counts_the_published_parameters():
    """`jax.eval_shape` of Laguna-XS.2 whole (40 layers, 256 experts,
    100,352 ids): 33,442,596,864 parameters, the catalog's 33.4 B, beside
    the repo's value head of 2,049. An elementwise gate (heads x 128 more
    columns a layer) would count 34.07 B: the gate is a head's."""
    model = transformer.laguna_from_config(100352, {})
    assert model.num_layers == 40 and model.dense_layers == 1
    assert [model.layer_kind(i).heads for i in range(5)] == [
        48, 64, 64, 64, 48]
    assert model.layer_kind(0).rotation.scaling[0] == 64
    assert (model.cache_len(0), model.cache_len(1)) == (262144, 512)
    variables = shapes_of(model)
    assert set(variables) == {"params"}
    count = sum(int(np.prod(a.shape))
                for a in jax.tree.leaves(variables["params"]))
    assert count == 33_442_596_864 + 2049
    full = 2048 * 6144 * 2 + 2 * 2048 * 1024 + 2048 * 48
    sliding = 2048 * 8192 * 2 + 2 * 2048 * 1024 + 2048 * 64
    sparse = 2048 * 256 + 257 * 3 * 2048 * 512
    assert count == (10 * full + 30 * sliding + 3 * 2048 * 8192
                     + 39 * sparse + 80 * 2048 + 2 * 100352 * 2048 + 2048
                     + 2049)
    elementwise = 10 * 2048 * 48 * 127 + 30 * 2048 * 64 * 127
    assert round((count + elementwise) / 1e9, 2) == 34.07


def test_the_configuration_s_file_holds_its_source_s_published_numbers():
    """Every published number of the source (the builder's copy of the
    catalog's row) but the ones the file lists as reduced; the lists stay
    whole and are read by their head."""
    _, _, config, network = configuration(FAMILY)
    published = dict(transformer.LAGUNA_PUBLISHED, **transformer.LAGUNA_FIXED)
    assert set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings", "env"}
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"], config["max_position_embeddings"]) == (
                5, 32, 12544, 8192)
    assert network["num_experts"] == 256 and network["experts_held"] == 32
    for key in ("gate", "router", "qk_norm", "yarn", "rope", "value_head"):
        assert any(key in name for name in config["assumed"]), key
    assert isinstance(config["weights_seed"], int)
    assert config["weights_seed_why"]
