"""Shape-derived FLOPs and parameters of the `sdar_moe` token policy, which
generates by diffusion over blocks, against a hand count at the published
widths (the cell's share: five layers, 16 of 128 experts held, 18,992 ids,
episodes of 2,048 positions in blocks of 4, 2 denoising passes and a commit
pass a block in the rollout, a clean and 2 noisy streams in the learner),
and, as a cross-check, against XLA's cost analysis of the plain reference at
a small size on the CPU.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from lib import flops_sdar_moe as flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def network():
    with open(os.path.join(BENCH, "configs", "impala_sdar_30b_a3b.json")) as f:
        return dict(json.load(f)["network"], sequence_length=2048)


def test_layer_macs_by_hand(network):
    # W_q and W_o 2048 x 4096; W_k and W_v 2048 x 512; 32 heads x (128 + 128)
    # a key over the (2,048 + 4) / 2 keys a query of a block meets on the
    # mean (its own block whole and the blocks before it); the router's 128
    # outputs; 8 experts a token of which 16/128 are held here on the mean:
    # one expert's three 2048 x 768 products.
    assert flops.mean_keys(network) == 1026.0
    assert flops.layer_macs(network) == {
        "queries_and_output": 2 * 8_388_608,
        "keys_and_values": 2 * 1_048_576,
        "attention": 32 * 256 * 1026.0,
        "router": 2048 * 128,
        "experts": 1.0 * 3 * 2048 * 768}
    assert flops.head_macs(network) == 2048 * 18992


def test_passes_and_streams_by_hand(network):
    layer = 16_777_216 + 2_097_152 + 8_404_992 + 262_144 + 4_718_592
    assert sum(flops.layer_macs(network).values()) == layer == 32_260_096
    # 2 noisy passes (or streams) through 5 layers, the clean one through 4
    # and the last layer's keys and values.
    streams = 2 * 5 * layer + 4 * layer + 2_097_152
    assert flops.streams_macs(network) == streams == 453_738_496
    # The rollout's head over the rows still masked: 4 + 2 of a block of 4,
    # 1.5 a position; the value head once a block.
    rollout = streams + 1.5 * 38_895_616 + 2048 / 4
    assert flops.rollout_macs_per_position(network) == rollout
    learner = streams + 38_895_616 + 2048 / 4
    assert flops.learner_macs_per_position(network) == learner
    # A step is an action: 2,047 of an episode's 2,048 positions.
    per_step = 2048 / 2047
    assert flops.forward_flops_per_token(network) == 2 * rollout * per_step
    assert round(flops.forward_flops_per_token(network) / 1e6) == 1025
    assert flops.train_flops_per_token(network) == 6 * learner * per_step
    assert round(flops.train_flops_per_token(network) / 1e6) == 2957
    assert flops.device_flops_per_step(
        network, {"inference": 1, "train": 1}) == (
            2 * rollout + 6 * learner) * per_step
    assert flops.device_flops_per_step(
        network, {"inference": 2, "train": 0}) == 4 * rollout * per_step
    # A call of the cell: 64 x 2,047 steps, 522 TF.
    call = 64 * 2047 * flops.device_flops_per_step(
        network, {"inference": 1, "train": 1})
    assert round(call / 1e12) == 522


def test_the_cut_in_depth_makes_the_head_19_pct_of_a_pass(network):
    assert round(1000 * flops.head_share_of_a_pass(network)) == 194
    assert round(1000 * flops.head_share_of_a_pass(
        dict(network, num_hidden_layers=48))) == 25


def test_param_count(network):
    # A layer: two norms of 2,048, two of 128, W_q and W_o, W_k and W_v, the
    # router, 16 experts of 3 x 2,048 x 768.
    layer = (4096 + 256 + 2 * 8_388_608 + 2 * 1_048_576 + 262_144
             + 16 * 4_718_592)
    assert layer == 94_638_336
    total = 5 * layer + 2 * 18992 * 2048 + 2048 + 2048 + 1
    assert flops.param_count(network) == total == 550_987_009
    assert network["param_count"] == total
    # All 128 experts in every layer, the whole vocabulary, the 48 published
    # layers: the published 30 B.
    with open(os.path.join(BENCH, "configs", "impala_sdar_30b_a3b.json")) as f:
        published = json.load(f)["published"]
    full = dict(network, experts_held=published["num_experts"],
                vocab_size=published["vocab_size"],
                num_hidden_layers=published["num_hidden_layers"])
    assert 30e9 < flops.param_count(full) < 31e9


def test_against_xla_cost_analysis():
    """XLA counts what the plain reference computes: for each of the S
    passes the clean and the noisy stream as one sequence of 2T positions
    (the clean stream S times over, every layer whole), the full [2T, 2T]
    score matrix (where the mean keys are owed), a held expert on every row
    (the loop over them counted once; k x held / routed of a row are owed),
    the head over the noisy
    half, and element-wise work. The shape count, with those parts scaled to
    what XLA sees of the reference, must sit below XLA's and within 10 %."""
    import jax
    import numpy as np
    from lib import reference_sdar_moe as reference

    net = dict(vocab_size=512, hidden_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, num_hidden_layers=3,
               num_experts=8, experts_held=2, first_expert_held=0,
               num_experts_per_tok=2, moe_intermediate_size=64,
               norm_topk_prob=True, rope_theta=1000000, rms_norm_eps=1e-6,
               block_length=4, denoise_steps=2, sequence_length=64)
    H, E, W, d = 128, 2, 64, 32
    rng = np.random.default_rng(0)

    def w(*shape):
        return rng.normal(size=shape).astype(np.float32) * 0.05
    layer = lambda: dict(  # noqa: E731
        attn_norm=w(H), mlp_norm=w(H), q_norm=w(d), k_norm=w(d),
        wq=w(H, 128), wk=w(H, 64), wv=w(H, 64), wo=w(128, H),
        router=w(H, 8), w_gate=w(E, H, W), w_up=w(E, H, W),
        w_down=w(E, W, H))
    variables = {"params": {
        "embed": w(512, H), "final_norm": w(H), "head": w(H, 512),
        "value_w": w(H), "value_b": w(),
        **{f"layer_{i}": layer() for i in range(3)}}}
    B, T, S = 2, net["sequence_length"], net["denoise_steps"]
    tokens = rng.integers(0, 511, size=(B, T))
    steps = rng.integers(0, S, size=(B, T))

    def fwd(v):
        out = reference.forward(v, tokens, steps, net)
        return out["logits"], out["values"]

    analysis = jax.jit(fwd).lower(variables).compile().cost_analysis()
    if isinstance(analysis, list):
        analysis = analysis[0]
    xla = analysis["flops"] / (B * T)

    macs = flops.layer_macs(net)
    macs["attention"] *= 2 * T / flops.mean_keys(net)
    # k x held / routed experts a row -> ONE whole expert: the reference
    # puts every row through every held expert in a loop, and XLA's analysis
    # counts a loop's body once.
    macs["experts"] = 3 * H * W
    # A position: S passes of 2 rows through every layer, the head S times.
    ours = 2.0 * (S * 2 * 3 * sum(macs.values())
                  + S * (flops.head_macs(net) + H))
    assert 0.90 * xla <= ours <= xla, (ours, xla)
