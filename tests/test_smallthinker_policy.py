"""The `smallthinker` token policy at a tiny size on the CPU: the family's
row, the checks it shares with the other families (`tests/token_families.py`:
the model against the plain reference `benchmark/lib/reference_smallthinker.py`
in its causal form and decoded through a full cache and three rings, each
named wrong mathematics refused by the cell's limits, the grouped form of the
expert product, the cell's program from its shapes, the builder's refusals,
the tuned example) and what is its own: fragments LONGER than the window, so
that the window layers' rings turn; grouped key/value heads in both forms of
the attention; a decode through the kernel form; the expert layer that holds
a share against the uncut layer. The loss and the loop:
`tests/test_smallthinker_update.py`.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from token_families import (  # noqa: F401: pytest collects what is named
    Family, build, causal_routed, configuration, decode_routed,
    held_to_reference, share_of,
    test_a_causal_pass_over_the_landed_rows_is_the_batched_pass,
    test_causal_pass_matches_reference,
    test_custom_model_config_without_a_part_is_refused,
    test_decode_through_every_kind_of_state_matches_reference
    as test_decode_through_a_full_cache_and_three_rings_matches_reference,
    test_limits_refuse_wrong_mathematics,
    test_the_cell_s_program_is_known_from_its_static_shapes,
    test_the_tuned_example_is_the_benchmark_s_cell)

from lib import reference_smallthinker as reference

from ray_tpu.models import catalog, transformer
from ray_tpu.models.transformer import dropless_experts

# One period: a full, position-free layer and three windowed rotary ones;
# 8 query heads in 2 groups, of a width that is not hidden / heads; 2 of 8
# experts held; a window of 8 under fragments of 24, so a ring turns twice.
WINDOW, S, B = 8, 24, 3
NET = dict(vocab_size=96, hidden_size=64, num_attention_heads=8,
           num_key_value_heads=2, head_dim=16, num_hidden_layers=4,
           sliding_window_size=WINDOW, sliding_window_layout=[0, 1, 1, 1],
           rope_layout=[0, 1, 1, 1], moe_num_primary_experts=8,
           experts_held=2, first_expert_held=0,
           moe_num_active_primary_experts=2, moe_ffn_hidden_size=32,
           norm_topk_prob=True, max_position_embeddings=S,
           rope_theta=1.5e6, rms_norm_eps=1e-6)
# Grouped heads' caches are stored flat: 2 cached heads of 16 a row.
CACHES = [(S, 2 * 16)] + [(WINDOW, 2 * 16)] * 3
# The cell's parameters at the published widths, by hand.
ATTENTION = 2 * 2560 * 28 * 128 + 2 * 2560 * 4 * 128
LAYER = ATTENTION + 2560 * 64 + 16 * 3 * 2560 * 768 + 2 * 2560

FAMILY = Family(
    name="smallthinker", net=NET, reference=reference, B=B, S=S,
    # What a pass hands a decode: the context's positions of the full
    # layer, a ring of the window of each window layer.
    state_shapes=lambda positions: (
        [(positions, 32)] * 2 + [(WINDOW, 32)] * 6,),
    state_layers={"kv": [2, 2, 2, 2]},
    expert_layers=4, experts_per_token=2,
    sharp_keys=("wq", "wk"), limits_build=dict(sharp=4.0),
    refused_by={"router_reads_post_attention_norm":
                lambda verdicts: not verdicts["routing"]["ok"]},
    # One block a cache at this size: the full layer reads its 24
    # positions, a ring its 8 of the context's 24.
    decode_counters={
        "decode_cache_read_share": pytest.approx((1 + 3 / 3) / 4),
        "decode_cache_read_share_full": 1.0,
        "decode_cache_read_share_window": pytest.approx(1 / 3)},
    wrong_updates={
        "silu_in_the_gradient": dict(mutate="silu_for_relu"),
        "window_layers_without_rope": dict(
            mutate="no_rope_on_a_window_layer"),
        "vf_coeff_doubled": dict(cfg={"vf_loss_coeff": 1.0},
                                 by="loss_error"),
        "no_clip": dict(cfg={"grad_clip": None}, by="update_error"),
        "ten_times_the_lr": dict(cfg={"lr": 6e-3}, by="update_error")},
    refused=(
        ({"num_experts": 8}, "not smallthinker's"),
        ({"intermediate_size": 96}, "not smallthinker's"),
        ({"hidden_act": "silu"}, "hidden_act"),
        ({"moe_primary_router_apply_softmax": False}, "apply_softmax"),
        ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
        ({"num_key_value_heads": 3}, "groups"),
        ({"sliding_window_layout": [0, 1]}, "window layout"),
        ({"rope_layout": [1]}, "rope layout"),
        ({"experts_held": 6, "first_expert_held": 4}, "not among")),
    example="smallthinker-token-impala.yaml",
    cell="smallthinker_token_anakin_8k",
    config="impala_smallthinker_21b_a3b",
    # At the published widths: 656.5 M parameters; a full cache of 8,192
    # positions and three rings of 4,096, 5,120 bytes a position of the
    # context where caches that kept every position would hold 8,192; 108
    # of a window layer's 136 causal tiles visited.
    program=dict(
        rows=16, fragment=8192,
        on_tpu={
            # Under two rows a held expert: a rollout's step reads the
            # chosen ones' matrices alone, and counts their share itself.
            "decode_rows_per_expert": 1.5, "decode_experts_batched": 0.0,
            "decode_experts_sparse": 1.0,
            "decode_cache_block": 128, "decode_attention_kernel": 1.0,
            "causal_attention_fused": 1.0, "window_layers": 3,
            "kv_groups": 7,
            # The three window layers rotate, the full one is
            # position-free.
            "rotation_fused_layers": 3.0,
            "kv_cache_bytes_per_token": 5120.0,
            "causal_window_tiles_kept": 108 / 136},
        # Off a TPU the caches are read whole, by XLA's products, and every
        # held expert's matrices.
        off_tpu={
            "decode_experts_batched": 1.0, "decode_experts_sparse": 0.0,
            "decode_experts_read_share": 1.0, "decode_cache_block": 8192,
            "decode_attention_kernel": 0.0, "causal_attention_fused": 0.0,
            "rotation_fused_layers": 0.0, "causal_window_tiles_kept": 1.0},
        state={"kv": [((16, 8192, 4 * 128), "bfloat16")] * 2
               + [((16, 4096, 4 * 128), "bfloat16")] * 6},
        parameters=4 * LAYER + 2 * 37984 * 2560 + 2560 + 2560 + 1))


# -- the decode: the kernel form, blocks, a prefill ---------------------------
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_a_decode_through_the_kernel_form_is_the_causal_pass(
        dtype, kernel_here):
    """The grouped caches through the kernel form (`conftest.kernel_here`:
    blocks of 8 positions, interpreted): a window of 16 under 24
    positions, so the full cache is three blocks and a ring two, which
    turns at position 16 and holds every slot from then on. A step reads
    the blocks its rows hold; the logits are the causal pass's, which
    keeps every position and masks the window."""
    net = dict(NET, sliding_window_size=2 * WINDOW)
    built = build(FAMILY, dtype, net, fresh=True)
    _, variables, tokens = built
    system, state, counted = decode_routed(built, variables, tokens)
    for t, step in enumerate(counted):
        held = 8 * (t // 8 + 1)
        assert step["decode_cache_read_share_full"] == pytest.approx(
            held / S)
        assert step["decode_cache_read_share_window"] == pytest.approx(
            min(held, 2 * WINDOW) / S)
    assert [c.shape[1:] for c in jax.tree.leaves(state["kv"])] == (
        [(S, 32)] * 2 + [(2 * WINDOW, 32)] * 6)
    if dtype == "f32":
        causal, _, _ = causal_routed(built, variables, tokens)
        assert reference.relative_error(system[0], causal[0]) < 1e-5
        assert reference.relative_error(system[1], causal[1]) < 1e-5
        assert np.array_equal(system[2], causal[2])
    else:
        held_to_reference(FAMILY, dtype, system, variables, tokens, net)


def test_grouped_caches_are_read_whole_and_ungrouped_rings_in_blocks(
        monkeypatch):
    """Blocks of 4 positions. Grouped heads take one whole-cache branch,
    whatever the block; with as many cached heads as query heads a ring of
    8 is two blocks, read whole once any row holds 4 positions and whole
    ever after, while the full cache of 24 goes on growing. Either way
    the causal pass's logits."""
    monkeypatch.setattr(transformer, "DECODE_CACHE_BLOCK", 4)
    for net in (NET, dict(NET, num_key_value_heads=8)):
        built = build(FAMILY, "f32", net, fresh=True)
        _, variables, tokens = built
        system, _, counted = decode_routed(built, variables, tokens)
        causal, _, _ = causal_routed(built, variables, tokens)
        assert reference.relative_error(system[0], causal[0]) < 1e-5
        grouped = net["num_key_value_heads"] != net["num_attention_heads"]
        for t, step in enumerate(counted):
            read = S if grouped else 4 * (t // 4 + 1)
            assert step["decode_cache_read_share_full"] == pytest.approx(
                read / S)
            assert step["decode_cache_read_share_window"] == pytest.approx(
                min(read, WINDOW) / S)


def test_prefill_then_ring_decode_and_a_reset_inside_a_fragment():
    """The causal pass returns rings a decode continues from, whether the
    prefix is shorter than the window, longer, or a multiple of it; and a
    reset inside a fragment starts a fresh episode in both forms."""
    built = build(FAMILY, "f32")
    model, variables, tokens = built

    def causal(tokens, reset=None):
        (logits, _, _), state, _ = causal_routed(
            built, variables, tokens, reset)
        return logits, state
    full, _ = causal(tokens)
    for prefix in (5, WINDOW, 13, 2 * WINDOW):
        _, state = causal(tokens[:, :prefix])
        for t in range(prefix, S):
            step, _, state = built.decode(
                variables, tokens[:, t:t + 1], state, jnp.zeros((B, 1)))
            assert reference.relative_error(
                step[:, 0], full[:, t]) < 1e-5, (prefix, t)
    reset = jnp.zeros((B, S)).at[:, 11].set(1.0)
    both, state = causal(tokens, reset)
    second, _ = causal(tokens[:, 11:])
    assert reference.relative_error(both[:, 11:], second) < 1e-5
    assert reference.relative_error(both[:, :11], full[:, :11]) < 1e-5
    assert np.all(np.asarray(state["pos"]) == S - 11)
    state = model.initial_state(B)
    for t in range(S):
        step, _, state = built.decode(
            variables, tokens[:, t:t + 1], state, reset[:, t:t + 1])
        assert reference.relative_error(step[:, 0], both[:, t]) < 1e-5, t


# -- grouped heads, a kind a layer: the parts, one at a time -------------
@pytest.mark.parametrize("groups", [1, 2, 8])
@pytest.mark.parametrize("window", [0, 5])
def test_cached_attention_over_grouped_heads_is_the_plain_sum(groups, window):
    """`cached_attention` with 8 query heads over `groups` cached heads,
    rows at different positions, against the sum written out; with a
    window, the cache a ring of it that has turned."""
    heads, d, rows = 8, 16, 4
    length = window or 12
    keys = jax.random.split(jax.random.PRNGKey(groups), 3)
    q = jax.random.normal(keys[0], (rows, heads, d))
    k_all, v_all = (jax.random.normal(key, (rows, 12, groups, d))
                    for key in keys[1:])
    pos = jnp.asarray([0, 3, 7, 11])
    k_cache, v_cache = (jnp.zeros((rows, length, groups, d)),) * 2
    for t in range(12):  # written as a decode writes: slot t mod length
        live = (t <= pos)[:, None, None]
        k_cache = k_cache.at[:, t % length].set(
            jnp.where(live, k_all[:, t], k_cache[:, t % length]))
        v_cache = v_cache.at[:, t % length].set(
            jnp.where(live, v_all[:, t], v_cache[:, t % length]))
    got, read = transformer.cached_attention(q, k_cache, v_cache, pos)
    assert int(read) == length
    for b in range(rows):
        first = max(0, int(pos[b]) - window + 1) if window else 0
        span = slice(first, int(pos[b]) + 1)
        for h in range(heads):
            g = h // (heads // groups)
            a = jax.nn.softmax(k_all[b, span, g] @ q[b, h] / 4.0)
            np.testing.assert_allclose(
                got[b, h], a @ v_all[b, span, g], atol=2e-6)


def test_causal_window_tiles_are_counted_by_distance():
    # The cell: 16 tiles a side, 136 under the diagonal; a window of 8
    # tiles' positions reaches the tiles up to 8 apart: 16 + 15 + .. + 8.
    assert transformer.causal_window_tiles(8192, 4096) == (108, 136)
    assert transformer.causal_window_tiles(8192, 0) == (136, 136)
    assert transformer.causal_window_tiles(1024, 1) == (2, 3)
    assert transformer.causal_window_tiles(1024, 2) == (3, 3)
    assert transformer.causal_window_tiles(2048, 513) == (7, 10)
    assert transformer.causal_window_tiles(2048, 514) == (9, 10)


# -- the expert layer that holds a share ---------------------------------
def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four shares of 2 of the 8 experts: their parts add up to what the
    uncut reference gives for the whole layer (the reference's shares, and
    the system's in both forms of its product, ReLU in the gate)."""
    rng = np.random.default_rng(0)
    H, W, E, k, held = 64, 32, 8, 2, 2
    lp = jax.tree.map(jnp.asarray, {
        "attn_norm": np.ones(H, np.float32),
        "mlp_norm": np.ones(H, np.float32),
        "wq": rng.normal(size=(H, 128)).astype(np.float32) / 8,
        "wk": rng.normal(size=(H, 32)).astype(np.float32) / 8,
        "wv": rng.normal(size=(H, 32)).astype(np.float32) / 8,
        "wo": rng.normal(size=(128, H)).astype(np.float32) / 8,
        "router": rng.normal(size=(H, E)).astype(np.float32) / 4,
        "w_gate": rng.normal(size=(E, H, W)).astype(np.float32) / 8,
        "w_up": rng.normal(size=(E, H, W)).astype(np.float32) / 8,
        "w_down": rng.normal(size=(E, W, H)).astype(np.float32) / 6})
    x = jnp.asarray(rng.normal(size=(2, 12, H)), jnp.float32)

    @functools.partial(jax.jit, static_argnums=(1,))
    def layer(first, size, **other):
        net = dict(NET, experts_held=size, first_expert_held=first)
        with jax.default_matmul_precision("highest"):
            return reference._layer(
                dict(share_of(lp, first, size), **other), x, net, 1,
                lambda a: a, None, None)
    whole, chosen, _ = layer(0, E)
    # h alone, x + attention: experts that give nothing.
    no_expert, _, _ = layer(0, E, w_down=jnp.zeros_like(lp["w_down"]))
    parts = sum(layer(first, held)[0] - no_expert
                for first in range(0, E, held))
    assert reference.relative_error(parts, whole - no_expert) < 1e-5

    # The system's shares of the same routing, in the form each shape
    # takes (24 rows batched, 64 times as many grouped).
    n = transformer.rms_norm(x.reshape(-1, H), lp["attn_norm"], 1e-6,
                             jnp.float32)
    top_p, top_i = transformer.route(n, lp["router"], k, True)
    assert np.array_equal(np.sort(top_i, -1),
                          np.sort(chosen.reshape(-1, k), -1))
    m = transformer.rms_norm(no_expert.reshape(-1, H), lp["mlp_norm"], 1e-6,
                             jnp.float32)
    for reps in (1, 64):
        rows, p, i = (jnp.tile(a, (reps, 1)) for a in (m, top_p, top_i))
        routed, landed = jnp.zeros_like(rows), 0
        for first in range(0, E, held):
            s = share_of(lp, first, held)
            part, sizes, _ = dropless_experts(
                rows, p, i, s["w_gate"], s["w_up"], s["w_down"], first, E,
                jax.nn.relu)
            routed, landed = routed + part, landed + int(jnp.sum(sizes))
        assert landed == rows.shape[0] * k
        assert reference.relative_error(
            routed[:m.shape[0]], (whole - no_expert).reshape(-1, H)) < 1e-4
    assert transformer.experts_batched(m.shape[0], k, E)
    assert not transformer.experts_batched(64 * m.shape[0], k, E)


# -- the builder and the configuration ---------------------------------------
def test_a_layout_longer_than_the_depth_is_read_from_its_head():
    """A cut in depth keeps the leading layers' kinds: the published
    layouts of 52 entries under four layers."""
    model = catalog.get_model(None, 96, {
        "custom_model": "smallthinker", "custom_model_config": dict(
            NET, sliding_window_layout=[0, 1, 1, 1] * 13,
            rope_layout=[0, 1, 1, 1] * 13)})
    assert [model.layer_kind(i)[:2] for i in range(4)] == [
        (0, False), (WINDOW, True), (WINDOW, True), (WINDOW, True)]
    assert [model.cache_len(i) for i in range(4)] == [S, 8, 8, 8]


def test_the_configuration_s_file_holds_its_source_s_published_numbers():
    """Every published number of the source but the ones the file lists
    as reduced (the catalog's row)."""
    _, _, config, network = configuration(FAMILY)
    published = {
        "head_dim": 128, "hidden_size": 2560, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "norm_topk_prob": True,
        "moe_primary_router_apply_softmax": True,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-6, "rope_theta": 1500000,
        "sliding_window_size": 4096, "rope_scaling": None,
        "tie_word_embeddings": False,
        "rope_layout": [0, 1, 1, 1] * 13,
        "sliding_window_layout": [0, 1, 1, 1] * 13}
    for key, value in published.items():
        assert config[key] == value, key
        if key in network:
            # The network's layouts are the four leading entries.
            assert network[key] == (
                value[:4] if key.endswith("layout") else value), key
    assert config["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "vocab_size": 151936, "max_position_embeddings": 16384}
    assert (config["moe_num_primary_experts"],
            network["moe_num_primary_experts"],
            network["experts_held"]) == (16, 64, 16)
    assert config["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size",
        "max_position_embeddings", "env"]
    assert config["network"]["param_count"] == 656_532_481
