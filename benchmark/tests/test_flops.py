"""Shape-derived FLOPs of the Nature-CNN against the hand count and, as a
cross-check, against XLA's cost analysis of the plain reference on the CPU.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from lib import flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def network():
    with open(os.path.join(BENCH, "configs", "impala_nature_cnn.json")) as f:
        return json.load(f)["network"]


def test_layer_macs_by_hand(network):
    # conv 8x8x4->32 /4 on 84x84: 20x20 outputs; 4x4x32->64 /2: 9x9;
    # 3x3x64->64 /1: 7x7; fc 3136->512; heads 512->(6+1).
    assert dict(flops.layer_macs(network)) == {
        "conv_0": 20 * 20 * 32 * 8 * 8 * 4,
        "conv_1": 9 * 9 * 64 * 4 * 4 * 32,
        "conv_2": 7 * 7 * 64 * 3 * 3 * 64,
        "fc": 3136 * 512,
        "heads": 512 * 7,
    }


def test_forward_is_18_7_mflop_a_row(network):
    assert flops.forward_flops_per_row(network) == 18_693_120
    assert round(flops.forward_flops_per_row(network) / 1e6, 1) == 18.7


def test_train_step_by_hand(network):
    # forward + weight gradients + input gradients, no input gradient for
    # the first layer: 3 x 9,346,560 - 3,276,800 MACs.
    assert flops.train_flops_per_row(network) == 2 * (
        3 * 9_346_560 - 3_276_800)


def test_param_count(network):
    assert flops.param_count(network) == network["param_count"] == 1_687_719


def test_device_passes(network):
    fwd = flops.forward_flops_per_row(network)
    train = flops.train_flops_per_row(network)
    assert flops.device_flops_per_step(
        network, {"inference": 1, "train": 1}) == fwd + train
    assert flops.device_flops_per_step(
        network, {"inference": 0, "train": 4}) == 4 * train


def test_against_xla_cost_analysis(network):
    """XLA counts element-wise work too, so it reads higher; PERF.md has
    19.02 MF/row forward and 50.82 MF/row for a train step of the system's
    program. The shape count must sit below XLA's and within 6 %."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lib import reference

    rng = np.random.default_rng(0)
    shapes = {"conv_0": (8, 8, 4, 32), "conv_1": (4, 4, 32, 64),
              "conv_2": (3, 3, 64, 64), "fc": (3136, 512),
              "logits": (512, 6), "value": (512, 1)}
    params = {k: {"kernel": rng.normal(size=s).astype(np.float32) * 0.05,
                  "bias": np.zeros(s[-1], np.float32)}
              for k, s in shapes.items()}
    rows = 32
    obs = rng.integers(0, 256, size=(rows, 84, 84, 4), dtype=np.uint8)

    def fwd(p, o):
        return reference.forward(p, o, (4, 2, 1))

    def loss(p, o):
        logits, value = fwd(p, o)
        return jnp.sum(logits) + jnp.sum(value)

    def cost(fn):
        analysis = jax.jit(fn).lower(params, obs).compile().cost_analysis()
        if isinstance(analysis, list):
            analysis = analysis[0]
        return analysis["flops"] / rows

    xla_fwd, xla_train = cost(fwd), cost(jax.grad(loss))
    ours_fwd = flops.forward_flops_per_row(network)
    ours_train = flops.train_flops_per_row(network)
    assert 0.94 * xla_fwd <= ours_fwd <= xla_fwd, (ours_fwd, xla_fwd)
    assert 0.94 * xla_train <= ours_train <= xla_train, (ours_train,
                                                         xla_train)
