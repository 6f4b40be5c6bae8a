"""Shape-derived FLOPs, bytes and parameters of the `qwen3_next` token policy
against a hand count at the published widths (the cell's share: one period of
four layers, three of them Gated DeltaNet, 32 of 512 experts held, 18,992
ids, episodes of 4,096 tokens), the owed bytes of a decode step against the
matrix states' own `nbytes`, the parameter count against what the trainer
builds (abstractly: shapes, no allocation), and, as a cross-check, against
XLA's cost analysis of the plain reference at a small size on the CPU.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from lib import flops_qwen3_next as flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def network():
    with open(os.path.join(
            BENCH, "configs", "impala_qwen3_next_80b_a3b.json")) as f:
        return dict(json.load(f)["network"], sequence_length=4096)


def test_layer_macs_by_hand(network):
    assert [flops.is_attention(network, i) for i in range(4)] == [
        False, False, False, True]
    assert flops.gdn_layers(network) == 3
    # A Gated DeltaNet operator: W_qkvz 2048 x 12288, W_ba 2048 x 64, W_out
    # 4096 x 2048. The state's three products a value head: 32 x 128 x 128
    # each.
    assert flops.operator_macs(network, 0) == {
        "gdn_projections": 25_165_824 + 131_072 + 8_388_608,
        "gdn_state": 3 * 524_288}
    assert flops.operator_macs(network, 2) == flops.operator_macs(network, 0)
    # The gated attention: W_q 2048 x 16 x 512 (query and gate), W_k and W_v
    # 2048 x 512 each, W_o 4096 x 2048; scores and values 16 x 256 each a
    # key, over the 2,048.5 keys a query of 4,096 positions meets on the
    # mean.
    assert flops.operator_macs(network, 3) == {
        "projections": 16_777_216 + 2 * 1_048_576 + 8_388_608,
        "attention": 2 * 16 * 256 * 2048.5}
    # The router's 512 outputs; 10 experts a token of which 32/512 are held
    # here on the mean: 0.625 of one expert's three 2048 x 512 products;
    # the shared expert whole, and its gate.
    for layer in range(4):
        assert flops.feed_forward_macs(network, layer) == {
            "router": 2048 * 512, "experts": 0.625 * 3 * 2048 * 512,
            "shared": 3 * 2048 * 512 + 2048}
    assert flops.head_macs(network) == 2048 * 18992 + 2048


def test_forward_is_427_mflop_a_token_and_the_head_is_18_pct(network):
    gdn, experts = 33_685_504 + 1_572_864, 1_048_576 + 1_966_080 + 3_147_776
    attention = 27_262_976 + 16_781_312
    trunk = 3 * (gdn + experts) + (attention + experts) + 38_897_664
    assert flops.trunk_macs(network) == trunk == 213_366_784
    forward = flops.forward_flops_per_token(network)
    assert forward == 2 * trunk and round(forward / 1e6) == 427
    assert round(1000 * flops.head_share_of_a_pass(network)) == 182
    # At the full depth (48 layers, the same slice of the head) the head is
    # 1.8 % of a pass: what the cut in depth distorts.
    assert round(1000 * flops.head_share_of_a_pass(
        dict(network, num_hidden_layers=48))) == 18
    # Gated DeltaNet's projections: 47 % of a pass's matrix FLOPs; the
    # state's products 2.2 %; the attention's scores and values 7.9 %.
    assert round(100 * 3 * 33_685_504 / trunk) == 47
    assert round(1000 * 3 * 1_572_864 / trunk) == 22
    assert round(1000 * 16_781_312 / trunk) == 79
    assert flops.train_flops_per_token(network) == 3 * forward
    assert flops.device_flops_per_step(
        network, {"inference": 1, "train": 1}) == 4 * forward
    assert flops.device_flops_per_step(
        network, {"inference": 2, "train": 0}) == 2 * forward


def model_of(network):
    import sys
    sys.path.insert(0, os.path.dirname(BENCH))
    from ray_tpu.models import transformer
    return transformer.qwen3_next_from_config(network["vocab_size"], {
        k: v for k, v in network.items()
        if k not in ("param_count", "sequence_length")})


def test_a_decode_step_owes_the_matrix_states_once_each_way(network):
    """`gdn_step_bytes` against the state's own `nbytes`: the leaves the
    model makes under the policy state's "gdn" key, read once and written
    once."""
    import jax
    model = model_of(network)
    for rows in (1, 32):
        state = jax.eval_shape(lambda: model.initial_state(rows))
        held = sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(state["gdn"]))
        assert flops.gdn_step_bytes(network, rows) == 2 * held
    assert flops.gdn_step_bytes(network, 1) == 2 * 6_291_456
    assert flops.gdn_step_bytes(network, 32) == 402_653_184


def test_param_count_by_hand_and_by_the_trainer(network):
    gdn = 25_165_824 + 131_072 + 32_768 + 32 + 32 + 128 + 8_388_608
    assert gdn == 33_718_464
    attention = 16_777_216 + 2 * 1_048_576 + 8_388_608 + 2 * 256
    assert attention == 27_263_488
    # router, the 32 held experts, the shared one and its gate
    experts = 1_048_576 + 33 * 3_145_728 + 2048
    assert experts == 104_859_648
    assert 2 * 2048 + gdn + experts == 138_582_208
    assert 2 * 2048 + attention + experts == 132_127_232
    total = (3 * 138_582_208 + 132_127_232 + 2 * 18992 * 2048 + 2048 + 2048
             + 1)
    assert flops.param_count(network) == total == 625_669_185
    assert network["param_count"] == total
    assert round(16 * total / 1e9, 2) == 10.01
    # What the trainer's model builds, from shapes alone.
    import jax
    import jax.numpy as jnp
    import numpy as np
    model = model_of(network)
    variables = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
        jax.eval_shape(lambda: model.initial_state(1)),
        jax.ShapeDtypeStruct((1, 1), jnp.float32))
    assert set(variables) == {"params"}
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(variables)) == total
    # 64 experts held (eight chips a layer) or a second period: what did
    # not fit (ISSUE 52).
    assert round(16 * flops.param_count(
        dict(network, experts_held=64)) / 1e9, 1) == 16.5
    assert round(16 * flops.param_count(
        dict(network, num_hidden_layers=8)) / 1e9, 1) == 18.8
    assert round(16 * flops.param_count(
        dict(network, experts_held=16)) / 1e9, 1) == 6.8
    # All 512 experts in every layer, the whole vocabulary, the 48
    # published layers: the published 80 B.
    full = dict(network, experts_held=512, vocab_size=151936,
                num_hidden_layers=48)
    assert 79e9 < flops.param_count(full) < 81e9


def test_against_xla_cost_analysis():
    """XLA counts what the plain reference computes: the full [S, S] score
    matrix in the attention layer (where the mean keys are owed), every held
    expert on every token (where k x held / routed of a token are owed), the
    recurrence's products a position (what is owed), and element-wise work,
    the taps and the decay of S among it. The shape count, with the first
    two parts scaled to what XLA sees of the reference, must sit below
    XLA's and within 10 %."""
    import jax
    import numpy as np
    from lib import reference_qwen3_next as reference

    net = dict(vocab_size=512, hidden_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, num_hidden_layers=4,
               full_attention_interval=4, partial_rotary_factor=0.25,
               linear_num_key_heads=2, linear_num_value_heads=4,
               linear_key_head_dim=32, linear_value_head_dim=32,
               linear_conv_kernel_dim=4, num_experts=8, experts_held=2,
               first_expert_held=0, num_experts_per_tok=2,
               moe_intermediate_size=64, shared_expert_intermediate_size=64,
               norm_topk_prob=True, rope_theta=10000000, rms_norm_eps=1e-6,
               sequence_length=64)
    H, E, W, K, V, d = 128, 2, 64, 64, 128, 32
    rng = np.random.default_rng(0)

    def w(*shape):
        return rng.normal(size=shape).astype(np.float32) * 0.05

    def layer(i):
        lp = {"attn_norm": w(H), "mlp_norm": w(H), "router": w(H, 8),
              "w_gate": w(E, H, W), "w_up": w(E, H, W), "w_down": w(E, W, H),
              "shared_gate": w(H, W), "shared_up": w(H, W),
              "shared_down": w(W, H), "shared_scale": w(H, 1)}
        if flops.is_attention(net, i):
            lp.update(wq=w(H, 4 * 2 * d), wk=w(H, 2 * d), wv=w(H, 2 * d),
                      wo=w(4 * d, H), q_norm=w(d), k_norm=w(d))
        else:
            lp.update(gdn_qkvz=w(H, 2 * K + 2 * V), gdn_ba=w(H, 8),
                      gdn_conv=w(2 * K + V, 4), gdn_a_log=w(4),
                      gdn_dt_bias=w(4), gdn_o_norm=w(d), gdn_out=w(V, H))
        return lp
    variables = {"params": {
        "embed": w(512, H), "final_norm": w(H), "head": w(H, 512),
        "value_w": w(H), "value_b": w(),
        **{f"layer_{i}": layer(i) for i in range(4)}}}
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
    for i in range(4):
        operator = flops.operator_macs(net, i)
        if "attention" in operator:
            operator["attention"] *= S / ((S + 1) / 2.0)
        feed_forward = flops.feed_forward_macs(net, i)
        # k x held / routed experts a token -> every held one.
        feed_forward["experts"] = E * 3 * H * W
        ours += sum(operator.values()) + sum(feed_forward.values())
    ours = 2.0 * (ours + flops.head_macs(net))
    assert 0.90 * xla <= ours <= xla, (ours, xla)
