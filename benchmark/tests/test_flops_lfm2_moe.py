"""Shape-derived FLOPs and parameters of the `lfm2_moe` token policy against
a hand count at the published widths (the cell's share: five layers, four of
them convolutions, 8 of 32 experts held, 16,384 ids, episodes of 4,096
tokens) and, as a cross-check, against XLA's cost analysis of the plain
reference at a small size on the CPU.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from lib import flops_lfm2_moe as flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def network():
    with open(os.path.join(
            BENCH, "configs", "impala_lfm2_8b_a1b.json")) as f:
        return dict(json.load(f)["network"], sequence_length=4096)


def test_layer_macs_by_hand(network):
    # A convolution's operator: W_in 2048 x 6144 and W_out 2048 x 2048; its
    # three taps a channel are elementwise work and owed nothing.
    assert flops.operator_macs(network, 0) == {
        "conv_projections": 12_582_912 + 4_194_304}
    assert flops.operator_macs(network, 2) == flops.operator_macs(network, 4)
    # The attention: W_q and W_o 2048 x 32 x 64, W_k and W_v 2048 x 8 x 64;
    # 32 heads x 64 a key for the score and as much for the value, over
    # the 2,048.5 keys a query of 4,096 positions meets on the mean.
    assert flops.head_dim(network) == 64
    assert flops.operator_macs(network, 1) == {
        "projections": 2 * 4_194_304 + 2 * 1_048_576,
        "attention": 2 * 2048 * 2048.5}
    assert flops.feed_forward_macs(network, 0) == {"dense": 3 * 2048 * 7168}
    # The router's 32 outputs; 4 experts a token of which 8/32 are held
    # here on the mean: one expert's three 2048 x 1792 products.
    for layer in (1, 2, 3, 4):
        assert flops.feed_forward_macs(network, layer) == {
            "router": 2048 * 32, "experts": 3 * 2048 * 1792}
    assert flops.head_macs(network) == 2048 * 16384 + 2048


def test_forward_is_416_mflop_a_token_and_the_head_is_16_pct(network):
    conv, experts = 16_777_216, 65_536 + 11_010_048
    trunk = ((conv + 44_040_192) + (10_485_760 + 8_390_656 + experts)
             + 3 * (conv + experts) + 33_556_480)
    assert flops.trunk_macs(network) == trunk == 207_884_288
    forward = flops.forward_flops_per_token(network)
    assert forward == 2 * trunk and round(forward / 1e6) == 416
    assert round(100 * 2 * flops.head_macs(network) / forward) == 16
    # The convolutions' projections: 32 % of a pass's matrix FLOPs; the
    # attention's scores and values 4 %.
    assert round(100 * 4 * conv / trunk) == 32
    assert round(100 * 8_390_656 / trunk) == 4
    assert flops.train_flops_per_token(network) == 3 * forward
    assert flops.device_flops_per_step(
        network, {"inference": 1, "train": 1}) == 4 * forward
    assert flops.device_flops_per_step(
        network, {"inference": 2, "train": 0}) == 2 * forward


def test_param_count_by_hand(network):
    conv = 16_777_216 + 2048 * 3
    attention = 10_485_760 + 2 * 64
    # router and its bias (a constant), the 8 held experts
    experts = 65_536 + 32 + 8 * 11_010_048
    dense_layer = 2 * 2048 + conv + 44_040_192
    assert dense_layer == 60_827_648
    assert 2 * 2048 + attention + experts == 98_635_936
    assert 2 * 2048 + conv + experts == 104_933_408
    total = (dense_layer + 98_635_936 + 3 * 104_933_408
             + 16384 * 2048 + 2048 + 2048 + 1)
    assert flops.param_count(network) == total == 507_822_337
    assert network["param_count"] == total
    # All 32 experts in every expert layer, the whole vocabulary, the 24
    # published layers with their two dense ones: the published 8.3 B.
    full = dict(
        network, experts_held=32, vocab_size=65536, num_hidden_layers=24,
        num_dense_layers=2,
        layer_types=["conv", "conv", "full_attention", "conv"] * 5 + [
            "conv", "full_attention", "conv", "conv"])
    assert 8.2e9 < flops.param_count(full) < 8.5e9


def test_against_xla_cost_analysis():
    """XLA counts what the plain reference computes: the full [S, S]
    score matrix in the attention layer (where the mean keys are owed),
    every held expert on every token (where k x held / routed of a token
    are owed), and element-wise work, the taps among it. The shape count,
    with those two parts scaled to what XLA sees of the reference, must
    sit below XLA's and within 10 %."""
    import jax
    import numpy as np
    from lib import reference_lfm2_moe as reference

    types = ["conv", "full_attention", "conv", "conv", "conv"]
    net = dict(vocab_size=512, hidden_size=128, num_attention_heads=8,
               num_key_value_heads=2, num_hidden_layers=5, layer_types=types,
               conv_L_cache=3, num_dense_layers=1, intermediate_size=256,
               num_experts=8, experts_held=2, first_expert_held=0,
               num_experts_per_tok=2, moe_intermediate_size=64,
               norm_topk_prob=True, routed_scaling_factor=1,
               rope_theta=1e6, norm_eps=1e-5, sequence_length=64)
    H, E, W = 128, 2, 64
    rng = np.random.default_rng(0)

    def w(*shape):
        return rng.normal(size=shape).astype(np.float32) * 0.05

    def layer(i):
        lp = {"attn_norm": w(H), "mlp_norm": w(H)}
        if types[i] == "conv":
            lp.update(conv_in=w(H, 3 * H), conv_w=w(H, 3), conv_out=w(H, H))
        else:
            lp.update(wq=w(H, 128), wk=w(H, 32), wv=w(H, 32), wo=w(128, H),
                      q_norm=w(16), k_norm=w(16))
        if i == 0:
            lp.update(dense_gate=w(H, 256), dense_up=w(H, 256),
                      dense_down=w(256, H))
        else:
            lp.update(router=w(H, 8), w_gate=w(E, H, W), w_up=w(E, H, W),
                      w_down=w(E, W, H))
        return lp
    variables = {
        "params": {"embed": w(512, H), "final_norm": w(H), "value_w": w(H),
                   "value_b": w(), **{f"layer_{i}": layer(i)
                                      for i in range(5)}},
        "constants": {f"layer_{i}": {"router_bias": w(8)}
                      for i in range(1, 5)}}
    B, S = 2, net["sequence_length"]
    tokens = rng.integers(0, 512, size=(B, S))

    def fwd(v):
        out = reference.forward(v, tokens, net)
        return out["logits"], out["values"]

    analysis = jax.jit(fwd).lower(variables).compile().cost_analysis()
    if isinstance(analysis, list):
        analysis = analysis[0]
    xla = analysis["flops"] / (B * S)

    ours = 0.0
    for i in range(5):
        operator = flops.operator_macs(net, i)
        if "attention" in operator:
            operator["attention"] *= S / ((S + 1) / 2.0)
        feed_forward = flops.feed_forward_macs(net, i)
        if "experts" in feed_forward:
            # k x held / routed experts a token -> every held one.
            feed_forward["experts"] = E * 3 * H * W
        ours += sum(operator.values()) + sum(feed_forward.values())
    ours = 2.0 * (ours + flops.head_macs(net))
    assert 0.90 * xla <= ours <= xla, (ours, xla)
