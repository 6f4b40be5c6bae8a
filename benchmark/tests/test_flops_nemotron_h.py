"""Shape-derived FLOPs, bytes and parameters of the `nemotron_h` token policy
against a hand count at the published widths (the cell's share: seven
one-function layers M E M E M * E, 8 of 128 experts held, 16,384 ids,
episodes of 2,048 tokens), the owed bytes of a decode step against the
matrix states' own `nbytes`, and, as a cross-check, against XLA's cost
analysis of the plain reference at a small size on the CPU.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from lib import flops_nemotron_h as flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def network():
    with open(os.path.join(
            BENCH, "configs", "impala_nemotron_twotower_30b_a3b.json")) as f:
        return dict(json.load(f)["network"], sequence_length=2048)


def test_layer_macs_by_hand(network):
    assert flops.letters(network) == "MEMEM*E"
    assert flops.ssm_layers(network) == 3
    # A Mamba-2 layer: W_in 2688 x (4096 + 6144 + 64), W_out 4096 x 2688;
    # the state's two products a head: 64 x 64 x 128 each.
    for layer in (0, 2, 4):
        assert flops.layer_macs(network, layer) == {
            "ssm_projections": 27_697_152 + 11_010_048,
            "ssm_state": 2 * 524_288}
    # The attention layer: W_q and W_o 2688 x 4096, W_k and W_v 2688 x 256;
    # 32 heads x (128 + 128) a key over the 1,024.5 keys a query of 2,048
    # positions meets on the mean.
    assert flops.layer_macs(network, 5) == {
        "projections": 2 * 11_010_048 + 2 * 688_128,
        "attention": 32 * 256 * 1024.5}
    # The router's 128 outputs; 6 experts a token of which 8/128 are held
    # here on the mean: 0.375 of one expert's TWO 2688 x 1856 products; the
    # shared expert's two 2688 x 3712.
    for layer in (1, 3, 6):
        assert flops.layer_macs(network, layer) == {
            "router": 2688 * 128, "experts": 0.375 * 2 * 2688 * 1856,
            "shared": 2 * 2688 * 3712}
    assert flops.head_macs(network) == 2688 * 16384 + 2688


def test_forward_is_534_mflop_a_token_and_the_head_is_16_pct(network):
    ssm = 38_707_200 + 1_048_576
    attention = 23_396_352 + 8_392_704
    experts = 344_064 + 3_741_696 + 19_955_712
    trunk = 3 * ssm + attention + 3 * experts + 44_042_880
    assert flops.trunk_macs(network) == trunk == 267_223_680
    forward = flops.forward_flops_per_token(network)
    assert forward == 2 * trunk and round(forward / 1e6) == 534
    assert round(100 * 2 * flops.head_macs(network) / forward) == 16
    # The Mamba-2 layers' projections: 43 % of a pass's matrix FLOPs; the
    # state's products 1.2 %; the attention's scores and values 3.1 %; the
    # held experts 4.2 % beside the shared ones' 22 %.
    assert round(100 * 3 * 38_707_200 / trunk) == 43
    assert round(1000 * 3 * 1_048_576 / trunk) == 12
    assert round(1000 * 8_392_704 / trunk) == 31
    assert round(1000 * 3 * 3_741_696 / trunk) == 42
    assert round(100 * 3 * 19_955_712 / trunk) == 22
    assert flops.train_flops_per_token(network) == 3 * forward
    assert flops.device_flops_per_step(
        network, {"inference": 1, "train": 1}) == 4 * forward
    assert flops.device_flops_per_step(
        network, {"inference": 2, "train": 0}) == 2 * forward


def test_a_decode_step_owes_the_matrix_states_once_each_way(network):
    """`ssm_step_bytes` against the state's own `nbytes`: the leaves the
    model makes under the policy state's "ssm" key, read once and written
    once."""
    import sys
    sys.path.insert(0, os.path.dirname(BENCH))
    import jax

    from ray_tpu.models import transformer
    model = transformer.nemotron_h_from_config(16384, {
        k: v for k, v in network.items()
        if k not in ("param_count", "sequence_length")})
    for rows in (1, 128):
        state = jax.eval_shape(lambda: model.initial_state(rows))
        held = sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(state["ssm"]))
        assert flops.ssm_step_bytes(network, rows) == 2 * held
    assert flops.ssm_step_bytes(network, 1) == 2 * 6_291_456
    assert flops.ssm_step_bytes(network, 128) == 1_610_612_736


def test_param_count_by_hand(network):
    ssm = (27_697_152 + 24_576 + 6_144 + 192 + 4_096 + 11_010_048)
    assert 2688 + ssm == 38_744_896
    attention = 2 * 11_010_048 + 2 * 688_128
    assert 2688 + attention == 23_399_040
    # router and its bias (a constant), the 8 held experts' two matrices,
    # the shared one's two
    experts = 344_064 + 128 + 16 * 4_988_928 + 2 * 9_977_856
    assert 2688 + experts == 100_125_440
    total = (3 * 38_744_896 + 23_399_040 + 3 * 100_125_440
             + 2 * 16384 * 2688 + 2688 + 2688 + 1)
    assert flops.param_count(network) == total == 528_095_809
    assert network["param_count"] == total
    # All 128 experts in every expert layer, the whole vocabulary, the 52
    # published layers: the published 30 B (of the one tower).
    with open(os.path.join(
            BENCH, "configs", "impala_nemotron_twotower_30b_a3b.json")) as f:
        published = json.load(f)["published"]
    full = dict(network, experts_held=128, vocab_size=131072,
                num_hidden_layers=52,
                hybrid_override_pattern=published["hybrid_override_pattern"])
    assert 30e9 < flops.param_count(full) < 33e9


def test_against_xla_cost_analysis():
    """XLA counts what the plain reference computes: the full [S, S] score
    matrix in the attention layer (where the mean keys are owed), every
    held expert on every token (where k x held / routed of a token are
    owed), the recurrence's products a position (what is owed), and
    element-wise work, the taps and the decay of S among it. The shape
    count, with the first two parts scaled to what XLA sees of the
    reference, must sit below XLA's and within 10 %."""
    import jax
    import numpy as np
    from lib import reference_nemotron_h as reference

    net = dict(vocab_size=512, hidden_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, num_hidden_layers=7,
               hybrid_override_pattern="MEMEM*E", mamba_num_heads=8,
               mamba_head_dim=16, n_groups=2, ssm_state_size=32,
               conv_kernel=4, chunk_size=16, n_routed_experts=8,
               experts_held=2, first_expert_held=0, num_experts_per_tok=2,
               moe_intermediate_size=64,
               moe_shared_expert_intermediate_size=128, n_shared_experts=1,
               norm_topk_prob=True, routed_scaling_factor=2.5,
               rope_theta=10000, layer_norm_epsilon=1e-5, sequence_length=64)
    H, E, W, SW, inner, conv, heads = 128, 2, 64, 128, 128, 256, 8
    rng = np.random.default_rng(0)

    def w(*shape):
        return rng.normal(size=shape).astype(np.float32) * 0.05

    def layer(letter):
        if letter == "M":
            return dict(attn_norm=w(H), ssm_in=w(H, inner + conv + heads),
                        ssm_conv=w(conv, 4), ssm_conv_bias=w(conv),
                        ssm_a_log=w(heads), ssm_dt_bias=w(heads),
                        ssm_d=w(heads), ssm_norm=w(inner),
                        ssm_out=w(inner, H))
        if letter == "*":
            return dict(attn_norm=w(H), wq=w(H, 128), wk=w(H, 64),
                        wv=w(H, 64), wo=w(128, H))
        return dict(mlp_norm=w(H), router=w(H, 8), w_up=w(E, H, W),
                    w_down=w(E, W, H), shared_up=w(H, SW),
                    shared_down=w(SW, H))
    pattern = net["hybrid_override_pattern"]
    variables = {
        "params": {"embed": w(512, H), "final_norm": w(H),
                   "head": w(H, 512), "value_w": w(H), "value_b": w(),
                   **{f"layer_{i}": layer(c) for i, c in enumerate(pattern)}},
        "constants": {f"layer_{i}": {"router_bias": w(8)}
                      for i, c in enumerate(pattern) if c == "E"}}
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
    for i in range(7):
        macs = flops.layer_macs(net, i)
        if "attention" in macs:
            macs["attention"] *= S / ((S + 1) / 2.0)
        if "experts" in macs:
            # k x held / routed experts a token -> every held one.
            macs["experts"] = E * 2 * H * W
        ours += sum(macs.values())
    ours = 2.0 * (ours + flops.head_macs(net))
    assert 0.90 * xla <= ours <= xla, (ours, xla)
