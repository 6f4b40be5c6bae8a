"""Shape-derived FLOPs and parameters of the OLMoE token policy against a
hand count at the published widths (one layer, episodes of 1,024 tokens).

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from lib import flops_olmoe

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def network():
    with open(os.path.join(BENCH, "configs", "impala_olmoe_1b_7b.json")) as f:
        return dict(json.load(f)["network"], sequence_length=1024)


def test_layer_macs_by_hand(network):
    # q, k, v, o: 4 x 2048^2. A token at position t (0-based) meets t + 1
    # keys: 512.5 on the mean over 1,024; 16 heads x 128 a key, for the
    # scores and again for the values. Router 2048 x 64. Eight experts of
    # three 2048 x 1024 products.
    assert flops_olmoe.layer_macs(network) == {
        "projections": 4 * 2048 * 2048,
        "attention": 2 * 16 * 128 * 512.5,
        "router": 2048 * 64,
        "experts": 8 * 3 * 2048 * 1024,
    }
    assert flops_olmoe.head_macs(network) == 2048 * 50304 + 2048


def test_forward_is_345_mflop_a_token_and_the_head_is_60_pct(network):
    forward = flops_olmoe.forward_flops_per_token(network)
    assert forward == 2 * (16_777_216 + 2_099_200 + 131_072 + 50_331_648
                           + 103_024_640)
    assert round(forward / 1e6) == 345
    assert round(100 * 2 * 103_024_640 / forward) == 60


def test_train_and_device_passes(network):
    forward = flops_olmoe.forward_flops_per_token(network)
    assert flops_olmoe.train_flops_per_token(network) == 3 * forward
    assert flops_olmoe.device_flops_per_step(
        network, {"inference": 1, "train": 1}) == 4 * forward


def test_sixteen_layers_put_the_head_at_8_pct(network):
    full = dict(network, num_hidden_layers=16, sequence_length=4096)
    forward = flops_olmoe.forward_flops_per_token(full)
    assert 7 <= 100 * 2 * flops_olmoe.head_macs(full) / forward <= 9


def test_param_count_by_hand(network):
    layer = 4 * 2048 * 2048 + 4 * 2048 + 2048 * 64 + 3 * 64 * 2048 * 1024
    assert layer == 419_569_664
    total = layer + 2 * 50304 * 2048 + 2048 + 2048 + 1
    assert flops_olmoe.param_count(network) == total == 625_618_945
    assert network["param_count"] == total
