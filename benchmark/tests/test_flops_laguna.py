"""Shape-derived FLOPs, cache bytes and parameters of the `laguna` token
policy against a hand count at the published widths (the cell's share: the
published layers 0-4, 32 of 256 experts held, 12,544 ids, episodes of 8,192
tokens under a window of 512) and, as a cross-check, against XLA's cost
analysis of the plain reference at a small size on the CPU.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from lib import flops_laguna as flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def network():
    with open(os.path.join(
            BENCH, "configs", "impala_laguna_xs2_33b_a3b.json")) as f:
        return dict(json.load(f)["network"], sequence_length=8192)


def test_a_window_layer_is_owed_its_window_and_a_full_layer_the_episode(
        network):
    # A full layer: a token at position t meets t + 1 keys, 4,096.5 on the
    # mean over 8,192. A window layer: t + 1 up to 512 keys, then 512:
    # (512 x 513 / 2 + 7680 x 512) / 8192 = 496.03.
    assert [flops.mean_keys(network, i) for i in (0, 4)] == [4096.5] * 2
    assert [flops.mean_keys(network, i) for i in (1, 2, 3)] == [
        (512 * 513 / 2 + 7680 * 512) / 8192] * 3
    assert round(flops.mean_keys(network, 1), 2) == 496.03
    # Episodes no longer than the window: a window layer is a full one.
    short = dict(network, sequence_length=256)
    assert flops.mean_keys(short, 1) == flops.mean_keys(short, 0) == 128.5


def test_layer_macs_by_hand(network):
    # A full layer: W_q and W_o 2048 x 48 x 128, W_k and W_v 2048 x 8 x
    # 128, the gate 2048 x 48; 48 heads x 128 a key, score and value.
    assert flops.attention_macs(network, 0) == {
        "projections": 2 * 12_582_912 + 2 * 2_097_152 + 98_304,
        "attention": 2 * 6144 * 4096.5}
    # A window layer: 64 heads.
    assert flops.attention_macs(network, 2) == {
        "projections": 2 * 16_777_216 + 4_194_304 + 131_072,
        "attention": 2 * 8192 * flops.mean_keys(network, 2)}
    assert flops.feed_forward_macs(network, 0) == {"dense": 50_331_648}
    # The router's 256 outputs; 8 experts a token of which 32/256 are held
    # here on the mean: one of three 2048 x 512 products; the shared one.
    assert flops.feed_forward_macs(network, 1) == {
        "router": 2048 * 256, "experts": 1.0 * 3 * 2048 * 512,
        "shared": 3 * 2048 * 512}
    assert flops.head_macs(network) == 2048 * 12544 + 2048


def test_forward_is_802_mflop_a_token(network):
    attention = 2 * 2 * 6144 * 4096.5 + 3 * 2 * 8192 * flops.mean_keys(
        network, 1)
    projections = 2 * 29_458_432 + 3 * 37_879_808
    feeds = 50_331_648 + 4 * (524_288 + 2 * 3_145_728)
    trunk = attention + projections + feeds + 25_692_160
    assert flops.trunk_macs(network) == trunk
    forward = flops.forward_flops_per_token(network)
    assert forward == 2 * trunk and round(forward / 1e6) == 802
    # Attention's scores and values: 31 % of a pass's matrix FLOPs, the two
    # full layers 81 % of that.
    assert round(100 * attention / trunk) == 31
    assert round(100 * 2 * 2 * 6144 * 4096.5 / attention) == 81
    assert round(1000 * flops.head_share_of_a_pass(network)) == 64
    assert flops.train_flops_per_token(network) == 3 * forward
    assert flops.device_flops_per_step(
        network, {"inference": 1, "train": 1}) == 4 * forward


def test_a_decode_step_owes_the_positions_its_rows_hold(network):
    # A position's K and V: 2 x 8 x 128 x 2 B = 4,096 B. 32 rows x (2 x
    # 4,096.5 + 3 x 496.03 positions) x 4,096 B = 1.27 GB a step.
    owed = flops.attention_step_bytes(network, 32)
    assert owed == 32 * 4096 * (2 * 4096.5 + 3 * flops.mean_keys(network, 1))
    assert round(owed / 1e9, 2) == 1.27
    # Whatever the blocks fetched: a ring counts what it holds, never more
    # than its window.
    longer = dict(network, sequence_length=16384)
    assert flops.mean_keys(longer, 1) < 512


def test_param_count_by_hand(network):
    full, sliding = 29_458_432, 37_879_808
    sparse = 524_288 + 33 * 3_145_728
    layers = [4096 + full + 50_331_648] + [
        4096 + sliding + sparse] * 3 + [4096 + full + sparse]
    assert layers == [79_794_176, 142_217_216, 142_217_216, 142_217_216,
                      133_795_840]
    total = sum(layers) + 2 * 12544 * 2048 + 2048 + 2049
    assert flops.param_count(network) == total == 691_625_985
    assert network["param_count"] == total
    # All 256 experts in every layer, the whole vocabulary, 40 layers: the
    # published model's 33.44 B beside the value head.
    with open(os.path.join(
            BENCH, "configs", "impala_laguna_xs2_33b_a3b.json")) as f:
        config = json.load(f)
    whole = dict(network, experts_held=256, vocab_size=100352,
                 num_hidden_layers=40, **{key: config[key] for key in (
                     "layer_types", "mlp_layer_types",
                     "num_attention_heads_per_layer")})
    assert flops.param_count(whole) == 33_442_596_864 + 2049


def test_against_xla_cost_analysis():
    """XLA counts what the plain reference computes: the full [S, S]
    score matrix in every layer (where the mean keys of its kind are
    owed) of ONE cached head's query heads (the reference's loop over the
    cached heads is a `lax.map`, whose body XLA counts once), a held
    expert on every token (where k x held / routed of a token are owed;
    the loop over the held experts is a `scan`, likewise: one expert), and
    element-wise work. The shape count, with those parts scaled to what
    XLA sees of the reference, must sit below XLA's and within 10 %."""
    import jax
    import numpy as np
    from lib import reference_laguna as reference

    heads = [8, 12, 12, 12, 8]
    net = dict(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_attention_heads=8, num_key_value_heads=2, head_dim=32,
        num_hidden_layers=5, sliding_window=16,
        num_attention_heads_per_layer=heads,
        layer_types=["full_attention"] + ["sliding_attention"] * 3
        + ["full_attention"],
        mlp_layer_types=["dense"] + ["sparse"] * 4, num_experts=8,
        experts_held=2, first_expert_held=0, num_experts_per_tok=2,
        moe_intermediate_size=64, shared_expert_intermediate_size=64,
        moe_routed_scaling_factor=2.5, rms_norm_eps=1e-6,
        rope_parameters={
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
                "original_max_position_embeddings": 32, "beta_slow": 1,
                "beta_fast": 4, "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000}},
        sequence_length=64)
    H, E, W = 128, 2, 64
    rng = np.random.default_rng(0)

    def w(*shape):
        return rng.normal(size=shape).astype(np.float32) * 0.05

    def layer(i):
        q = heads[i] * 32
        lp = {"attn_norm": w(H), "mlp_norm": w(H), "wq": w(H, q),
              "wk": w(H, 64), "wv": w(H, 64), "wg": w(H, heads[i]),
              "wo": w(q, H)}
        if i == 0:
            return dict(lp, dense_gate=w(H, 256), dense_up=w(H, 256),
                        dense_down=w(256, H))
        return dict(lp, router=w(H, 8), w_gate=w(E, H, W), w_up=w(E, H, W),
                    w_down=w(E, W, H), shared_gate=w(H, W),
                    shared_up=w(H, W), shared_down=w(W, H))
    params = {"embed": w(512, H), "final_norm": w(H), "head": w(H, 512),
              "value_w": w(H), "value_b": w(),
              **{f"layer_{i}": layer(i) for i in range(5)}}
    B, S = 2, net["sequence_length"]
    tokens = rng.integers(0, 512, size=(B, S))

    def fwd(v):
        out = reference.forward(v, tokens, net)
        return out["logits"], out["values"]

    analysis = jax.jit(fwd).lower({"params": params}).compile(
        ).cost_analysis()
    if isinstance(analysis, list):
        analysis = analysis[0]
    xla = analysis["flops"] / (B * S)

    ours = 0.0
    for i in range(5):
        attention = flops.attention_macs(net, i)
        attention["attention"] *= S / flops.mean_keys(net, i) / 2
        feed = flops.feed_forward_macs(net, i)
        if "experts" in feed:
            # k x held / routed experts a token -> one.
            feed["experts"] = 3 * H * W
        ours += sum(attention.values()) + sum(feed.values())
    ours = 2.0 * (ours + flops.head_macs(net))
    assert 0.90 * xla <= ours <= xla, (ours, xla)
