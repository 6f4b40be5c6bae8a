"""Shape-derived FLOPs, bytes and parameters of the `kimi_linear` token policy
against a hand count at the published widths (the cell's share: five layers,
four of them KDA, 8 of 256 experts held, 20,480 ids, episodes of 4,096
tokens), the owed bytes of a decode step against the matrix states' own
`nbytes`, and, as a cross-check, against XLA's cost analysis of the plain
reference at a small size on the CPU.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from lib import flops_kimi_linear as flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def network():
    with open(os.path.join(
            BENCH, "configs", "impala_kimi_linear_48b_a3b.json")) as f:
        return dict(json.load(f)["network"], sequence_length=4096)


def test_layer_macs_by_hand(network):
    assert [flops.is_kda(network, i) for i in range(5)] == [
        True, True, True, False, True]
    assert flops.kda_layers(network) == 4
    # A KDA operator: W_q, W_k, W_v 2304 x 4096 each; the decay's and the
    # gate's pairs 2304 x 128 and 128 x 4096; beta's 2304 x 32; W_out 4096
    # x 2304. The state's three products a head: 32 x 128 x 128 each.
    assert flops.operator_macs(network, 0) == {
        "kda_projections": (3 * 9_437_184 + 2 * (294_912 + 524_288) + 73_728
                            + 9_437_184),
        "kda_state": 3 * 524_288}
    assert flops.operator_macs(network, 4) == flops.operator_macs(network, 1)
    # The latent layer: W_q 2304 x 32 x 192 straight from the input, W_kva
    # 2304 x 576, W_o 4096 x 2304; W_kvb 512 x 32 x 256; decompressed 32 x
    # (192 + 128) a key is cheaper than absorbed 32 x (576 + 512), over
    # the 2,048.5 keys a query of 4,096 positions meets on the mean.
    assert flops.operator_macs(network, 3) == {
        "projections": 14_155_776 + 1_327_104 + 9_437_184,
        "kv_up": 4_194_304,
        "attention": 32 * 320 * 2048.5}
    assert flops.feed_forward_macs(network, 0) == {"dense": 3 * 2304 * 9216}
    # The router's 256 outputs; 8 experts a token of which 8/256 are held
    # here on the mean: a quarter of one expert's three 2304 x 1024
    # products; the shared expert whole.
    for layer in (1, 2, 3, 4):
        assert flops.feed_forward_macs(network, layer) == {
            "router": 2304 * 256, "experts": 0.25 * 3 * 2304 * 1024,
            "shared": 3 * 2304 * 1024}
    assert flops.head_macs(network) == 2304 * 20480 + 2304


def test_forward_is_726_mflop_a_token_and_the_head_is_13_pct(network):
    kda, experts = 39_460_864 + 1_572_864, 589_824 + 1_769_472 + 7_077_888
    latent = 24_920_064 + 4_194_304 + 20_976_640
    trunk = ((kda + 63_700_992) + 3 * (kda + experts) + (latent + experts)
             + 47_188_224)
    assert flops.trunk_macs(network) == trunk == 362_863_872
    forward = flops.forward_flops_per_token(network)
    assert forward == 2 * trunk and round(forward / 1e6) == 726
    assert round(100 * 2 * flops.head_macs(network) / forward) == 13
    # The KDA layers' projections: 43 % of a pass's matrix FLOPs; the
    # state's products 1.7 %; the latent layer's scores and values 5.8 %.
    assert round(100 * 4 * 39_460_864 / trunk) == 43
    assert round(1000 * 4 * 1_572_864 / trunk) == 17
    assert round(1000 * 20_976_640 / trunk) == 58
    assert flops.train_flops_per_token(network) == 3 * forward
    assert flops.device_flops_per_step(
        network, {"inference": 1, "train": 1}) == 4 * forward
    assert flops.device_flops_per_step(
        network, {"inference": 2, "train": 0}) == 2 * forward


def test_a_decode_step_owes_the_matrix_states_once_each_way(network):
    """`kda_step_bytes` against the state's own `nbytes`: the leaves the
    model makes under the policy state's "kda" key, read once and written
    once."""
    import sys
    sys.path.insert(0, os.path.dirname(BENCH))
    import jax

    from ray_tpu.models import transformer
    model = transformer.kimi_linear_from_config(20480, {
        k: v for k, v in network.items()
        if k not in ("param_count", "sequence_length")})
    for rows in (1, 32):
        state = jax.eval_shape(lambda: model.initial_state(rows))
        held = sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(state["kda"]))
        assert flops.kda_step_bytes(network, rows) == 2 * held
    assert flops.kda_step_bytes(network, 1) == 2 * 8_388_608
    assert flops.kda_step_bytes(network, 32) == 536_870_912


def test_param_count_by_hand(network):
    kda = (28_311_552 + 49_152 + 819_200 + 32 + 4096 + 73_728 + 819_200
           + 128 + 9_437_184)
    assert kda == 39_514_272
    attention = 14_155_776 + 1_327_104 + 512 + 4_194_304 + 9_437_184
    assert attention == 29_114_880
    # router and its bias (a constant), the 8 held experts, the shared one
    experts = 589_824 + 256 + 9 * 7_077_888
    dense_layer = 2 * 2304 + kda + 63_700_992
    assert dense_layer == 103_219_872
    assert 2 * 2304 + kda + experts == 103_809_952
    assert 2 * 2304 + attention + experts == 93_410_560
    total = (dense_layer + 3 * 103_809_952 + 93_410_560
             + 2 * 20480 * 2304 + 2304 + 2304 + 1)
    assert flops.param_count(network) == total == 602_436_737
    assert network["param_count"] == total
    # All 256 experts in every expert layer, the whole vocabulary, the 27
    # published layers: the published 48 B.
    full = dict(
        network, experts_held=256, vocab_size=163840, num_hidden_layers=27,
        linear_attn_config=dict(
            network["linear_attn_config"],
            kda_layers=[i for i in range(1, 27) if i % 4],
            full_attn_layers=[4, 8, 12, 16, 20, 24, 27]))
    assert 48e9 < flops.param_count(full) < 50e9


def test_against_xla_cost_analysis():
    """XLA counts what the plain reference computes: the full [S, S]
    score matrix in the latent layer (where the mean keys are owed), every
    held expert on every token (where k x held / routed of a token are
    owed), the recurrence's products a position (what is owed), and
    element-wise work, the taps and the decay of S by rows among it. The
    shape count, with the first two parts scaled to what XLA sees of the
    reference, must sit below XLA's and within 10 %."""
    import jax
    import numpy as np
    from lib import reference_kimi_linear as reference

    linear = dict(kda_layers=[1, 2, 3, 5], full_attn_layers=[4], head_dim=32,
                  num_heads=4, short_conv_kernel_size=4)
    net = dict(vocab_size=512, hidden_size=128, num_attention_heads=4,
               num_hidden_layers=5, linear_attn_config=linear,
               kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
               v_head_dim=32, first_k_dense_replace=1, intermediate_size=256,
               num_experts=8, experts_held=2, first_expert_held=0,
               num_experts_per_token=2, moe_intermediate_size=64,
               num_shared_experts=1, moe_renormalize=True,
               routed_scaling_factor=2.446, rope_theta=10000,
               rms_norm_eps=1e-5, sequence_length=64)
    H, E, W, P, d = 128, 2, 64, 128, 32
    rng = np.random.default_rng(0)

    def w(*shape):
        return rng.normal(size=shape).astype(np.float32) * 0.05

    def layer(i):
        lp = {"attn_norm": w(H), "mlp_norm": w(H)}
        if flops.is_kda(net, i):
            lp.update(kda_qkv=w(H, 3 * P), kda_conv=w(3 * P, 4),
                      kda_fa=w(H, d), kda_fb=w(d, P), kda_a_log=w(4),
                      kda_dt_bias=w(P), kda_b=w(H, 4), kda_ga=w(H, d),
                      kda_gb=w(d, P), kda_o_norm=w(d), kda_out=w(P, H))
        else:
            lp.update(wq=w(H, 4 * 48), wkv_a=w(H, 48), kv_a_norm=w(32),
                      wkv_b=w(32, 4 * 64), wo=w(4 * 32, H))
        if i == 0:
            lp.update(dense_gate=w(H, 256), dense_up=w(H, 256),
                      dense_down=w(256, H))
        else:
            lp.update(router=w(H, 8), w_gate=w(E, H, W), w_up=w(E, H, W),
                      w_down=w(E, W, H), shared_gate=w(H, W),
                      shared_up=w(H, W), shared_down=w(W, H))
        return lp
    variables = {
        "params": {"embed": w(512, H), "final_norm": w(H),
                   "head": w(H, 512), "value_w": w(H), "value_b": w(),
                   **{f"layer_{i}": layer(i) for i in range(5)}},
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
