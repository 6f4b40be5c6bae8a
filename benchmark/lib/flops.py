"""FLOPs from shapes. A later PR can change the program, not this count.

A multiply-accumulate is 2 FLOPs. Only the matrix work is counted
(convolutions and dense layers); element-wise work, the softmax, V-trace
and GAE recursions and the optimizer's update are left out, so every share
built on these counts is a slight under-count, never an over-count.

`network` is the `network` block of a configuration file:
  {"obs_shape": [H, W, C], "conv_filters": [[out, kernel, stride], ...],
   "hidden": n, "num_actions": a}
with VALID padding, as `ray_tpu/models/networks.py` VisionNetwork runs it.
"""


def conv_out(size: int, kernel: int, stride: int) -> int:
    return (size - kernel) // stride + 1


def layer_macs(network: dict) -> list:
    """[(name, multiply-accumulates per row)] of one forward pass."""
    h, w, c = network["obs_shape"]
    out = []
    for i, (ch, k, s) in enumerate(network["conv_filters"]):
        h, w = conv_out(h, k, s), conv_out(w, k, s)
        out.append((f"conv_{i}", h * w * ch * k * k * c))
        c = ch
    flat = h * w * c
    out.append(("fc", flat * network["hidden"]))
    # Policy logits and the value head read the same hidden vector.
    out.append(("heads", network["hidden"] * (network["num_actions"] + 1)))
    return out


def forward_flops_per_row(network: dict) -> float:
    return 2.0 * sum(m for _, m in layer_macs(network))


def train_flops_per_row(network: dict) -> float:
    """Forward + backward of one row. Backward is a weight-gradient and an
    input-gradient product per layer, each the size of the forward one;
    the first layer needs no input gradient (observations take none)."""
    macs = layer_macs(network)
    total = sum(m for _, m in macs)
    return 2.0 * (3 * total - macs[0][1])


def param_count(network: dict) -> int:
    h, w, c = network["obs_shape"]
    n = 0
    for ch, k, s in network["conv_filters"]:
        h, w = conv_out(h, k, s), conv_out(w, k, s)
        n += k * k * c * ch + ch
        c = ch
    flat = h * w * c
    n += flat * network["hidden"] + network["hidden"]
    n += network["hidden"] * (network["num_actions"] + 1) \
        + network["num_actions"] + 1
    return n


def device_flops_per_step(network: dict, passes: dict) -> float:
    """FLOPs the device owes for one trained env step.

    `passes` is the cell's `device_passes`: how many inference (forward)
    passes and how many training (forward + backward) passes of each step
    run on the device. Inference on CPU rollout workers counts 0."""
    return (passes["inference"] * forward_flops_per_row(network)
            + passes["train"] * train_flops_per_row(network))
