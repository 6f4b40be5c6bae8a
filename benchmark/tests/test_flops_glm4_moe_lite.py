"""Shape-derived FLOPs and parameters of the `glm4_moe_lite` token policy
against a hand count at the published widths (the cell's share: five
layers, 8 of 64 experts held, 19,360 ids, episodes of 1,024 tokens) and, as
a cross-check, against XLA's cost analysis of the plain reference at a
small size on the CPU.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from lib import flops_glm4_moe_lite as flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def network():
    with open(os.path.join(
            BENCH, "configs", "impala_glm_4_7_flash.json")) as f:
        return dict(json.load(f)["network"], sequence_length=1024)


def test_layer_macs_by_hand(network):
    # W_qa 2048 x 768, W_qb 768 x 20 x (192 + 64), W_kva 2048 x (512 + 64),
    # W_o 20 x 256 x 2048. W_kvb 512 x 20 x (192 + 256), paid once a token
    # in either form. A token at position t meets t + 1 keys: 512.5 on the
    # mean over 1,024; decompressed, 20 heads x (256 a key + 256 a value);
    # absorbed 20 x (576 + 512), the dearer, not owed.
    assert flops.attention_macs(network) == {
        "projections": 1_572_864 + 3_932_160 + 1_179_648 + 10_485_760,
        "kv_up": 4_587_520,
        "attention": 20 * 512 * 512.5,
    }
    assert 20 * 512 * 512.5 < 20 * (576 + 512) * 512.5
    # The router's 64 outputs; 4 experts a token of which 8/64 are held
    # here on the mean: half an expert of three 2048 x 1536 products; the
    # shared expert, every token.
    assert flops.expert_layer_macs(network) == {
        "router": 2048 * 64,
        "experts": 0.5 * 3 * 2048 * 1536,
        "shared": 3 * 2048 * 1536,
    }
    assert flops.dense_layer_macs(network) == 3 * 2048 * 10240
    assert flops.head_macs(network) == 2048 * 19360 + 2048


def test_forward_is_589_mflop_a_token_and_the_head_is_13_pct(network):
    attention = 17_170_432 + 4_587_520 + 5_248_000
    expert_layer = 131_072 + 4_718_592 + 9_437_184
    trunk = 5 * attention + 62_914_560 + 4 * expert_layer + 39_651_328
    assert flops.trunk_macs(network) == trunk == 294_743_040
    forward = flops.forward_flops_per_token(network)
    assert forward == 2 * trunk and round(forward / 1e6) == 589
    assert round(100 * 2 * flops.head_macs(network) / forward) == 13


def test_module_is_owed_in_the_learner_alone(network):
    # One more attention + expert layer; W_eh 4096 x 2048 and the trunk's
    # head a second time, each with ONE backward product (its inputs, or
    # its weights, take no gradient).
    module = flops.module_macs(network)
    assert module == {"block": 27_005_952 + 14_286_848,
                      "edges": 2 * 2048 * 2048 + 2048 * 19360}
    train = flops.train_flops_per_token(network)
    assert train == 2 * (3 * (294_743_040 + 41_292_800) + 2 * 48_037_888)
    assert round(train / 1e6) == 2208
    forward = flops.forward_flops_per_token(network)
    assert flops.device_flops_per_step(
        network, {"inference": 1, "train": 1}) == forward + train
    assert flops.device_flops_per_step(
        network, {"inference": 2, "train": 0}) == 2 * forward
    without = dict(network, num_nextn_predict_layers=0)
    assert flops.train_flops_per_token(without) == 3 * forward


def test_param_count_by_hand(network):
    attention = (2 * 2048 + 1_572_864 + 768 + 3_932_160 + 1_179_648 + 512
                 + 4_587_520 + 10_485_760)
    assert attention == 21_763_328
    dense = attention + 62_914_560
    # router and its bias, 8 held experts, the shared one
    expert = attention + 131_072 + 64 + 8 * 9_437_184 + 9_437_184
    module = expert + 2 * 2048 * 2048 + 3 * 2048
    total = (dense + 4 * expert + module + 2 * 19360 * 2048
             + 2048 + 2048 + 1)
    assert flops.param_count(network) == total == 706_520_897
    assert network["param_count"] == total
    # All 64 experts in every layer, the whole vocabulary, 47 layers:
    # the published model's 30 B.
    full = dict(network, experts_held=64, vocab_size=154880,
                num_hidden_layers=47)
    assert 29.5e9 < flops.param_count(full) < 31.5e9


def test_against_xla_cost_analysis():
    """XLA counts what the plain reference computes: the full [S, S]
    score matrix (twice the causal mean), every held expert on every token
    (held rows where k x held / routed are owed), and element-wise work.
    The shape count, with those two parts scaled to what the reference
    does, must sit below XLA's and within 10 %."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lib import reference_glm4_moe_lite as reference

    net = dict(vocab_size=512, hidden_size=128, num_attention_heads=4,
               num_hidden_layers=3, q_lora_rank=48, kv_lora_rank=32,
               qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32,
               first_k_dense_replace=1, intermediate_size=384,
               n_routed_experts=8, experts_held=2, first_expert_held=0,
               num_experts_per_tok=2, moe_intermediate_size=64,
               n_shared_experts=1, norm_topk_prob=True,
               routed_scaling_factor=1.8, num_nextn_predict_layers=1,
               rope_theta=1e6, rms_norm_eps=1e-5, sequence_length=64)
    H, E, W, D = 128, 2, 64, 384
    rng = np.random.default_rng(0)

    def w(*shape):
        return rng.normal(size=shape).astype(np.float32) * 0.05

    def layer(dense):
        lp = {"attn_norm": w(H), "mlp_norm": w(H), "wq_a": w(H, 48),
              "q_a_norm": w(48), "wq_b": w(48, 4 * 32), "wkv_a": w(H, 40),
              "kv_a_norm": w(32), "wkv_b": w(32, 4 * 56),
              "wo": w(4 * 32, H)}
        if dense:
            lp.update(dense_gate=w(H, D), dense_up=w(H, D),
                      dense_down=w(D, H))
        else:
            lp.update(router=w(H, 8), w_gate=w(E, H, W), w_up=w(E, H, W),
                      w_down=w(E, W, H), shared_gate=w(H, W),
                      shared_up=w(H, W), shared_down=w(W, H))
        return lp
    params = {"embed": w(512, H), "final_norm": w(H), "head": w(H, 512),
              "value_w": w(H), "value_b": w(), "layer_0": layer(True),
              "layer_1": layer(False), "layer_2": layer(False),
              "nextn_0": dict(layer(False), hnorm=w(H), enorm=w(H),
                              eh_proj=w(2 * H, H), final_norm=w(H))}
    variables = {"params": params, "constants": {
        name: {"router_bias": w(8)}
        for name in ("layer_1", "layer_2", "nextn_0")}}
    B, S = 2, net["sequence_length"]
    tokens = rng.integers(0, 512, size=(B, S))

    def fwd(v):
        out = reference.forward(v, tokens, net)
        return out["logits"], out["values"], out["nextn_nll"]

    analysis = jax.jit(fwd).lower(variables).compile().cost_analysis()
    if isinstance(analysis, list):
        analysis = analysis[0]
    xla = analysis["flops"] / (B * S)

    attention = flops.attention_macs(net)
    mean_keys = (S + 1) / 2.0
    attention["attention"] *= S / mean_keys
    experts = flops.expert_layer_macs(net)
    experts["experts"] *= net["n_routed_experts"] / net["num_experts_per_tok"]
    block = sum(attention.values()) + sum(experts.values())
    ours = 2.0 * (
        3 * sum(attention.values()) + flops.dense_layer_macs(net)
        + 2 * sum(experts.values()) + flops.head_macs(net)
        + block + flops.module_macs(net)["edges"])
    assert 0.90 * xla <= ours <= xla, (ours, xla)
