"""The `lfm2_moe` token policy at a tiny size on the CPU: the family's row,
the checks it shares with the other families (`tests/token_families.py`: the
model against the plain reference `benchmark/lib/reference_lfm2_moe.py` in its
causal form and decoded through two kinds of state, a convolution layer's last
two gated inputs and an attention layer's cache; a decode that continues a
causal pass from the state it handed over; each named wrong mathematics
refused by the cell's limits; the grouped form of the expert product; the
cell's program from its shapes; the builder's refusals; the tuned example) and
what is its own: a reset inside a fragment and an episode one token long
against separate passes; the convolution against the sum written out; the
renormalisation's epsilon; the expert layer that holds a share against the
uncut layer. The loss and the loop: `tests/test_lfm2_moe_update.py`.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from token_families import (  # noqa: F401: pytest collects what is named
    Family, build, causal_routed, configuration, count, decode_routed,
    held_to_reference, seeded_norms, shapes_of, share_of, state_shapes,
    test_a_causal_pass_over_the_landed_rows_is_the_batched_pass,
    test_a_decode_continues_a_causal_pass_from_the_state_it_hands_over,
    test_causal_pass_matches_reference,
    test_custom_model_config_without_a_part_is_refused,
    test_decode_through_every_kind_of_state_matches_reference
    as test_decode_through_both_kinds_of_state_matches_reference,
    test_limits_refuse_wrong_mathematics,
    test_the_cell_s_program_is_known_from_its_static_shapes,
    test_the_tuned_example_is_the_benchmark_s_cell)

from lib import reference_lfm2_moe as reference

from ray_tpu.models import catalog, transformer
from ray_tpu.models.transformer import dropless_experts

# The cell's five layers: a dense convolution layer, then one period of
# expert layers, an attention and three convolutions; 8 query heads in 2
# groups; 2 of 8 experts held.
S, B = 24, 3
TYPES = ["conv", "full_attention", "conv", "conv", "conv"]
NET = dict(vocab_size=96, hidden_size=64, num_attention_heads=8,
           num_key_value_heads=2, num_hidden_layers=5, layer_types=TYPES,
           conv_L_cache=3, conv_bias=False, num_dense_layers=1,
           intermediate_size=96, num_experts=8, experts_held=2,
           first_expert_held=0, num_experts_per_tok=2,
           moe_intermediate_size=32, norm_topk_prob=True,
           routed_scaling_factor=1, use_expert_bias=True,
           max_position_embeddings=S, rope_theta=1e6, norm_eps=1e-5)
# Grouped heads' caches are stored flat: 2 cached heads of 8 a row.
CONV_STATE, CACHE = (2, 64), (S, 2 * 8)
# A reset inside the fragment, and an episode one token long after it.
RESET = jnp.zeros((B, S)).at[:, 11].set(1.0).at[:, 12].set(1.0)
# The cell's parameters at the published widths, by hand: the embedding
# counted once (the head is tied to it).
CONV = 4 * 2048 * 2048 + 2048 * 3
ATTENTION = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
EXPERTS = 2048 * 32 + 8 * 3 * 2048 * 1792
PARAMETERS = (16384 * 2048 + CONV + 3 * 2048 * 7168 + ATTENTION + EXPERTS
              + 3 * (CONV + EXPERTS) + 5 * 2 * 2048 + 2048 + 2048 + 1)

FAMILY = Family(
    name="lfm2_moe", net=NET, reference=reference, B=B, S=S,
    # What a pass hands a decode: the one attention layer's K and V, and
    # two rows of every convolution layer, under a key of their own.
    state_kinds=("kv", "conv"),
    state_shapes=lambda positions: (
        [(positions, 2 * 8)] * 2, [CONV_STATE] * 4),
    state_layers={"kv": [0, 2, 0, 0, 0], "conv": [1, 0, 1, 1, 1]},
    collections=frozenset({"params", "constants"}),
    expert_layers=4, experts_per_token=2,  # the expert layers
    seeded=seeded_norms(), limits_build=dict(bias_scale=0.2),
    # The head's alone.
    refused_by={"untied_head": lambda verdicts:
                verdicts["outputs"]["errors"]["value"] == 0.0},
    reset=RESET, handed_atol=1e-5,
    # Prefixes shorter than the taps, as long, and longer.
    prefixes=(2, 3, 5, 13),
    # The attention layer alone reads a cache: grouped, so all of it.
    decode_counters={"decode_cache_read_share": 1.0},
    wrong_updates={
        "taps_reversed_in_the_gradient": dict(mutate="taps_reversed"),
        "qk_norm_after_rope": dict(mutate="qk_norm_after_rope"),
        "an_untied_head": dict(mutate="untied_head"),
        "vf_coeff_doubled": dict(cfg={"vf_loss_coeff": 1.0},
                                 by="loss_error"),
        "no_clip": dict(cfg={"grad_clip": None}, by="update_error"),
        "ten_times_the_lr": dict(cfg={"lr": 6e-3}, by="update_error")},
    refused=(
        ({"n_routed_experts": 8}, "not lfm2_moe's"),
        ({"head_dim": 16}, "not lfm2_moe's"),
        ({"conv_bias": True}, "conv_bias"),
        ({"use_expert_bias": False}, "use_expert_bias"),
        ({"tie_embedding": False}, "tie_embedding"),
        ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
        ({"num_key_value_heads": 3}, "groups"),
        ({"layer_types": ["conv", "full_attention"]}, "layer_types has 2"),
        ({"layer_types": ["conv", "sliding_attention"] + TYPES[2:]},
         "sliding_attention"),
        ({"experts_held": 6, "first_expert_held": 4}, "not among")),
    example="lfm2-token-impala.yaml", cell="lfm2_token_anakin_4k",
    config="impala_lfm2_8b_a1b",
    # At the published widths: 507.8 M parameters, the embedding counted
    # once; one cache of 4,096 positions, 2,048 bytes a position, and four
    # states of two rows, 32,768 bytes a sequence whatever its length;
    # heads of 64 take the fused causal form.
    program=dict(
        rows=64, fragment=4096,
        on_tpu={
            "decode_rows_per_expert": 8.0, "decode_experts_batched": 1.0,
            "decode_experts_sparse": 0.0, "decode_experts_read_share": 1.0,
            "decode_cache_block": 128, "decode_attention_kernel": 1.0,
            "causal_attention_fused": 1.0,
            "kv_cache_bytes_per_token": 2048.0,
            # Heads of 64 are half a lane tile: the learner's rotation
            # keeps `rope` (`rowwise.whole_tiles`).
            "rotation_fused_layers": 0.0,
            "kv_groups": 4, "conv_layers": 4,
            "conv_state_bytes_per_row": 32768},
        # Off a TPU the cache is read whole, by XLA's products.
        off_tpu={"causal_attention_fused": 0.0, "decode_cache_block": 4096,
                 "decode_attention_kernel": 0.0},
        state={"kv": [((64, 4096, 8 * 64), "bfloat16")] * 2,
               "conv": [((64, 2, 2048), "bfloat16")] * 4},
        # 507,822,209 trained parameters and four routers' 32 biases.
        parameters=PARAMETERS + 4 * 32))


# -- the decode and the resets ----------------------------------------------
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_a_decode_through_the_kernel_form_is_the_causal_pass(
        dtype, kernel_here):
    """The grouped cache through the kernel form (`conftest.kernel_here`:
    three blocks of 8 positions, interpreted), a reset and an episode one
    token long inside the fragment: a step reads the blocks up to the
    furthest position a row holds of its own episode, and the logits are
    the causal pass's over the same resets."""
    built = build(FAMILY, dtype, fresh=True)
    _, variables, tokens = built
    system, state, counted = decode_routed(built, variables, tokens, RESET)
    # Episodes start at 0, 11 and 12: a row's position within its own.
    place = [t if t < 11 else 0 if t == 11 else t - 12 for t in range(S)]
    assert [step["decode_cache_read_share"] for step in counted] == [
        pytest.approx(8 * (at // 8 + 1) / S) for at in place]
    assert state_shapes(FAMILY, state) == ([CACHE] * 2, [CONV_STATE] * 4)
    if dtype == "f32":
        causal, _, _ = causal_routed(built, variables, tokens, RESET)
        assert reference.relative_error(system[0], causal[0]) < 1e-5
        assert reference.relative_error(system[1], causal[1]) < 1e-5
        assert np.array_equal(system[2], causal[2])
    else:
        held_to_reference(FAMILY, dtype, system, variables, tokens,
                          starts=RESET)


def test_a_reset_inside_a_fragment_and_an_episode_one_token_long():
    """Three episodes in a fragment, the second one token long: what
    separate passes give, in both forms and in the reference; the state
    handed over is the last episode's alone."""
    built = build(FAMILY, "f32")
    model, variables, tokens = built
    both, state, _ = causal_routed(built, variables, tokens, RESET)
    parts = [causal_routed(built, variables, tokens[:, a:b])
             for a, b in ((0, 11), (12, S))]
    # A causal pass takes two tokens or more: the lone token as a decode
    # step from empty state.
    lone, _, _ = built.decode(variables, tokens[:, 11:12],
                              model.initial_state(B), jnp.ones((B, 1)))
    separate = jnp.concatenate(
        [parts[0][0][0], lone, parts[1][0][0]], axis=1)
    assert reference.relative_error(both[0], separate) < 1e-5
    for got, want in zip(jax.tree.leaves(state["conv"]),
                         jax.tree.leaves(parts[1][1]["conv"])):
        np.testing.assert_allclose(got, want, atol=1e-5)
    assert np.all(np.asarray(state["pos"]) == S - 12)
    held_to_reference(FAMILY, "f32", both, variables, tokens, starts=RESET)
    stepped, _, _ = decode_routed(built, variables, tokens, RESET)
    assert reference.relative_error(stepped[0], both[0]) < 1e-5
    # A fragment that ends one token into an episode hands over one
    # gated input and a zero row.
    _, short, _ = causal_routed(built, variables, tokens[:, :13],
                                RESET[:, :13])
    for held in jax.tree.leaves(short["conv"]):
        assert not np.any(np.asarray(held[:, 0]))
        assert np.any(np.asarray(held[:, 1]))


def test_the_convolution_is_the_sum_written_out():
    """`_conv_causal` against v_t = sum_j w[:, j] g_{t - 2 + j} written
    as a loop over positions and taps, an episode boundary in the middle."""
    model, variables, tokens = build(FAMILY, "f32")
    lp = variables["params"]["layer_2"]
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, 64))
    positions = jnp.broadcast_to(
        jnp.where(jnp.arange(S) < 9, jnp.arange(S), jnp.arange(S) - 9),
        (B, S))
    h, state = model.apply(variables, lp, x, positions,
                           method="_conv_causal")
    n = np.asarray(transformer.rms_norm(x, lp["attn_norm"], 1e-5,
                                        jnp.float32))
    b, c, u = np.split(n @ np.asarray(lp["conv_in"]), 3, axis=-1)
    g, w = b * u, np.asarray(lp["conv_w"])
    v = np.zeros_like(g)
    for t in range(S):
        for j in range(3):
            s = t - 2 + j
            if s >= 0 and (t < 9) == (s < 9):
                v[:, t] += w[:, j] * g[:, s]
    want = np.asarray(x) + (c * v) @ np.asarray(lp["conv_out"])
    np.testing.assert_allclose(h, want, atol=2e-5)
    np.testing.assert_allclose(state, g[:, -2:], atol=1e-6)


# -- the router's division ------------------------------------------------
def test_the_renormalisation_s_epsilon_is_the_description_s():
    """`route` divides the chosen scores by their sum, plus the epsilon the
    description has: lfm2_moe's 1e-6, none anywhere else. It shows where
    the chosen scores are small beside it; without it the second
    configuration's numbers are what they were, bit for bit."""
    rng = np.random.default_rng(0)
    n = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 8)) / 8, jnp.float32)
    bias = jnp.asarray(rng.normal(size=8) * 0.02, jnp.float32)
    # Scores near 1e-7: every logit 16 lower.
    shifted = jnp.concatenate([n, jnp.ones((16, 1))], axis=1)
    faint = jnp.concatenate([router, jnp.full((1, 8), -16.0)], axis=0)
    for rows, w in ((n, router), (shifted, faint)):
        scores = jax.nn.sigmoid(jnp.dot(
            rows, w, precision=jax.lax.Precision.HIGHEST))
        p, i = transformer.route(rows, w, 2, True, bias, 1.0, 1e-6)
        chosen = jnp.take_along_axis(scores, i, axis=-1)
        want = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(p, want, rtol=1e-6)
        plain, same = transformer.route(rows, w, 2, True, bias, 1.0)
        assert np.array_equal(i, same)
        np.testing.assert_array_equal(
            plain, chosen / jnp.sum(chosen, axis=-1, keepdims=True))
    # Faint scores: the weights no longer add up to one.
    assert float(jnp.max(jnp.sum(p, axis=-1))) < 0.5
    model, _, _ = build(FAMILY, "f32")
    assert model.topk_eps == reference.TOPK_EPS == 1e-6
    for name, cfg in (("glm4_moe_lite", dict(
            q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8)), ("olmoe", {}),
            ("smallthinker", {})):
        other = catalog.get_model(None, 96, {
            "custom_model": name, "custom_model_config": cfg})
        assert other.topk_eps == 0.0 and not other.tie_embeddings


# -- the expert layer that holds a share ---------------------------------
def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four shares of 2 of the 8 experts: their parts add up to what the
    uncut reference gives for the whole layer (the reference's shares, and
    the system's in both forms of its product)."""
    rng = np.random.default_rng(0)
    H, W, E, k, held = 64, 32, 8, 2, 2
    lp = jax.tree.map(jnp.asarray, {
        "router": rng.normal(size=(H, E)).astype(np.float32) / 4,
        "w_gate": rng.normal(size=(E, H, W)).astype(np.float32) / 8,
        "w_up": rng.normal(size=(E, H, W)).astype(np.float32) / 8,
        "w_down": rng.normal(size=(E, W, H)).astype(np.float32) / 6})
    bias = jnp.asarray(rng.normal(size=E) * 0.05, jnp.float32)
    h = jnp.asarray(rng.normal(size=(2, 12, H)), jnp.float32)
    m = transformer.rms_norm(h, jnp.ones(H), 1e-5, jnp.float32)

    @functools.partial(jax.jit, static_argnums=(1,))
    def layer(first, size):
        net = dict(NET, experts_held=size, first_expert_held=first)
        with jax.default_matmul_precision("highest"):
            return reference._moe(share_of(lp, first, size), bias, h, m, net,
                                  lambda a: a, None, None)
    whole, chosen, _ = layer(0, E)
    parts = sum(layer(first, held)[0] - h for first in range(0, E, held))
    assert reference.relative_error(parts, whole - h) < 1e-5

    # The system's shares of the same routing, in the form each shape
    # takes (24 rows batched, 64 times as many grouped).
    rows = m.reshape(-1, H)
    top_p, top_i = transformer.route(rows, lp["router"], k, True, bias,
                                     1.0, 1e-6)
    assert np.array_equal(np.sort(top_i, -1),
                          np.sort(chosen.reshape(-1, k), -1))
    for reps in (1, 64):
        n, p, i = (jnp.tile(a, (reps, 1)) for a in (rows, top_p, top_i))
        routed, landed = jnp.zeros_like(n), 0
        for first in range(0, E, held):
            s = share_of(lp, first, held)
            part, sizes, _ = dropless_experts(
                n, p, i, s["w_gate"], s["w_up"], s["w_down"], first, E)
            routed, landed = routed + part, landed + int(jnp.sum(sizes))
        assert landed == n.shape[0] * k
        assert reference.relative_error(
            routed[:rows.shape[0]], (whole - h).reshape(-1, H)) < 1e-4
    assert transformer.experts_batched(rows.shape[0], k, E)
    assert not transformer.experts_batched(64 * rows.shape[0], k, E)


def test_the_accepted_descriptions_keep_their_state_and_counters():
    """A model whose layers are all attention has no "conv" key in its
    state, and every model with caches of a head's own now says what they
    hold a position."""
    olmoe = catalog.get_model(None, 96, {
        "custom_model": "olmoe", "custom_model_config": dict(
            vocab_size=96, hidden_size=64, num_attention_heads=4,
            num_hidden_layers=2, num_experts=4, num_experts_per_tok=2,
            intermediate_size=32, max_position_embeddings=16)})
    assert set(olmoe.initial_state(2)) == {"kv", "pos"}
    counted = olmoe.static_counters(2, 16, "cpu")
    # K and V, 4 heads of 16 in bfloat16, two layers.
    assert counted["kv_cache_bytes_per_token"] == 2 * 2 * 4 * 16 * 2
    assert "conv_layers" not in counted and "window_layers" not in counted


def test_the_published_cut_s_parameters_are_the_hand_count_s():
    """The embedding counted once: no head beside it; a norm a head."""
    _, _, _, network = configuration(FAMILY)
    model = transformer.lfm2_moe_from_config(16384, network)
    variables = shapes_of(model)
    assert "head" not in variables["params"]
    assert variables["params"]["layer_1"]["q_norm"].shape == (64,)
    assert count(variables["params"]) == PARAMETERS == 507_822_209
    assert count(variables["constants"]) == 4 * 32


def test_a_tied_head_gives_as_many_logits_as_the_vocabulary_has_ids():
    with pytest.raises(ValueError, match="tied to the embedding"):
        model = transformer.lfm2_moe_from_config(50, NET)
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
                   model.initial_state(1), jnp.zeros((1, 1)))


def test_keys_left_out_have_the_published_model_s_values():
    """An empty description is LFM2-8B-A1B itself: 24 layers, 18 of them
    convolutions, the first two dense, 8.3 B parameters."""
    model = transformer.lfm2_moe_from_config(65536, {})
    kinds = [model.layer_kind(i) for i in range(24)]
    assert kinds.count("conv") == 18 and model.attention_layers == (
        2, 6, 10, 14, 18, 21)
    assert (model.dense_layers, model.num_experts, model.held,
            model.experts_per_token, model.head_width) == (2, 32, 32, 4, 64)
    variables = shapes_of(model)
    count = sum(int(np.prod(v.shape))
                for v in jax.tree.leaves(variables["params"]))
    assert 8.2e9 < count < 8.5e9


def test_the_configuration_s_file_holds_its_source_s_published_numbers():
    """Every published number of the source (the catalog's row) but the
    ones the file lists as reduced."""
    _, _, config, network = configuration(FAMILY)
    # The source's config (the catalog's row), the reduced keys apart.
    published = dict(transformer.LFM2_MOE_PUBLISHED, conv_bias=False,
                     use_expert_bias=True, model_type="lfm2_moe")
    reduced = {"num_hidden_layers": (24, 5), "num_dense_layers": (2, 1),
               "num_experts": (32, 8), "vocab_size": (65536, 16384),
               "max_position_embeddings": (128000, 4096)}
    for key, value in published.items():
        if key in reduced:
            assert (config["published"][key], config[key]) == reduced[key]
        else:
            assert config[key] == value, key
            if key in network and key != "layer_types":
                assert network[key] == value, key
    # The published layers 1-5.
    assert network["layer_types"] == config["layer_types"][1:6] == TYPES
    assert (network["num_experts"], network["experts_held"]) == (32, 8)
    assert config["reduced"] == list(reduced) + ["env"]
    assert set(config["reduced"]) == set(config["reduced_why"])
    assert config["network"]["param_count"] == PARAMETERS + 4 * 32
    model = transformer.lfm2_moe_from_config(16384, network)
    assert (model.hidden_size, model.num_heads, model.kv_heads,
            model.head_width, model.conv_taps, model.dense_width,
            model.expert_width, model.experts_per_token, model.rope_theta,
            model.rms_eps) == (2048, 32, 8, 64, 3, 7168, 1792, 4, 1000000,
                               1e-5)
