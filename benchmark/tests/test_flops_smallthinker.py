"""Shape-derived FLOPs and parameters of the `smallthinker` token policy
against a hand count at the published widths (the cell's share: four layers,
16 of 64 experts held, 37,984 ids, episodes of 8,192 tokens under a window
of 4,096) and, as a cross-check, against XLA's cost analysis of the plain
reference at a small size on the CPU.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from lib import flops_smallthinker as flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def network():
    with open(os.path.join(
            BENCH, "configs", "impala_smallthinker_21b_a3b.json")) as f:
        return dict(json.load(f)["network"], sequence_length=8192)


def test_a_window_layer_is_owed_its_mean_keys_not_its_window(network):
    # A full layer: a token at position t meets t + 1 keys, 4,096.5 on the
    # mean over 8,192. A window layer: t + 1 up to 4,096 keys, then 4,096:
    # (4096 x 4097 / 2 + 4096 x 4096) / 8192 = 3,072.25.
    assert flops.mean_keys(network, 0) == 4096.5
    assert [flops.mean_keys(network, i) for i in (1, 2, 3)] == [3072.25] * 3
    # Episodes no longer than the window: a window layer is a full one.
    short = dict(network, sequence_length=1024)
    assert flops.mean_keys(short, 1) == flops.mean_keys(short, 0) == 512.5


def test_layer_macs_by_hand(network):
    # W_q and W_o 2560 x 28 x 128, W_k and W_v 2560 x 4 x 128; 28 heads x
    # 128 a key for the score and as much for the value.
    assert flops.attention_macs(network, 0) == {
        "projections": 2 * 9_175_040 + 2 * 1_310_720,
        "attention": 2 * 3584 * 4096.5}
    assert flops.attention_macs(network, 2) == {
        "projections": 20_971_520, "attention": 2 * 3584 * 3072.25}
    # The router's 64 outputs; 6 experts a token of which 16/64 are held
    # here on the mean: one and a half of three 2560 x 768 products.
    assert flops.expert_layer_macs(network) == {
        "router": 2560 * 64, "experts": 1.5 * 3 * 2560 * 768}
    assert flops.head_macs(network) == 2560 * 37984 + 2560


def test_forward_is_625_mflop_a_token_and_the_head_is_31_pct(network):
    layers = 4 * (20_971_520 + 163_840 + 8_847_360)
    attention = 7168 * 4096.5 + 3 * 7168 * 3072.25
    trunk = layers + attention + 97_241_600
    assert flops.trunk_macs(network) == trunk == 312_601_856
    forward = flops.forward_flops_per_token(network)
    assert forward == 2 * trunk and round(forward / 1e6) == 625
    assert round(100 * 2 * flops.head_macs(network) / forward) == 31
    # Attention's scores and values: 30.5 % of a pass's matrix FLOPs.
    assert round(1000 * attention / trunk) == 305
    assert flops.train_flops_per_token(network) == 3 * forward
    assert flops.device_flops_per_step(
        network, {"inference": 1, "train": 1}) == 4 * forward
    assert flops.device_flops_per_step(
        network, {"inference": 2, "train": 0}) == 2 * forward


def test_param_count_by_hand(network):
    layer = 2 * 2560 + 20_971_520 + 163_840 + 16 * 5_898_240
    assert layer == 115_512_320
    total = 4 * layer + 2 * 37984 * 2560 + 2560 + 2560 + 1
    assert flops.param_count(network) == total == 656_532_481
    assert network["param_count"] == total
    # All 64 experts in every layer, the whole vocabulary, 52 layers: the
    # published model's 21 B.
    full = dict(network, experts_held=64, vocab_size=151936,
                num_hidden_layers=52, sliding_window_layout=[0, 1, 1, 1] * 13)
    assert 20.5e9 < flops.param_count(full) < 22.5e9


def test_against_xla_cost_analysis():
    """XLA counts what the plain reference computes: the full [S, S]
    score matrix in every layer (where the mean keys of its kind are
    owed), a held expert on every token (where k x held / routed of a
    token are owed; the reference's loop over the held experts is a
    `scan`, whose body XLA counts ONCE: one expert), and element-wise
    work. The shape count, with those two parts scaled to what XLA sees
    of the reference, must sit below XLA's and within 10 %."""
    import jax
    import numpy as np
    from lib import reference_smallthinker as reference

    net = dict(vocab_size=512, hidden_size=128, num_attention_heads=8,
               num_key_value_heads=2, head_dim=32, num_hidden_layers=4,
               sliding_window_size=16, sliding_window_layout=[0, 1, 1, 1],
               rope_layout=[0, 1, 1, 1], moe_num_primary_experts=8,
               experts_held=2, first_expert_held=0,
               moe_num_active_primary_experts=2, moe_ffn_hidden_size=64,
               norm_topk_prob=True, rope_theta=1.5e6, rms_norm_eps=1e-6,
               sequence_length=64)
    H, E, W = 128, 2, 64
    rng = np.random.default_rng(0)

    def w(*shape):
        return rng.normal(size=shape).astype(np.float32) * 0.05

    def layer():
        return {"attn_norm": w(H), "mlp_norm": w(H), "wq": w(H, 256),
                "wk": w(H, 64), "wv": w(H, 64), "wo": w(256, H),
                "router": w(H, 8), "w_gate": w(E, H, W), "w_up": w(E, H, W),
                "w_down": w(E, W, H)}
    params = {"embed": w(512, H), "final_norm": w(H), "head": w(H, 512),
              "value_w": w(H), "value_b": w(),
              **{f"layer_{i}": layer() for i in range(4)}}
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

    experts = flops.expert_layer_macs(net)
    # k x held / routed experts a token -> one.
    experts["experts"] = 3 * H * W
    ours = 0.0
    for i in range(4):
        attention = flops.attention_macs(net, i)
        attention["attention"] *= S / flops.mean_keys(net, i)
        ours += sum(attention.values()) + sum(experts.values())
    ours = 2.0 * (ours + flops.head_macs(net))
    assert 0.90 * xla <= ours <= xla, (ours, xla)
