"""Matrix FLOPs of the `qwen3_next` token policy from shapes, and the bytes a
decode step owes for its matrix states. A later PR can change the program,
not these counts.

A multiply-accumulate is 2 FLOPs. Counted, a token. A Gated DeltaNet layer's
operator: its projections (W_qkvz [hidden, 2 K + 2 V], K = key heads x d_k, V
= value heads x d_v; W_ba [hidden, 2 x value heads]; W_out [V, hidden]) and
THE STATE'S PRODUCTS in the cheaper of the two forms, which is the step's:
S^T k, the outer product k u^T and S^T q, value heads x d_k x d_v each (the
chunked form owes more a token at chunks of 64; a pass in the dearer form
earns no share by it). The decay of S, the convolution's four taps a
channel, the normalisations and the gates are elementwise and NOT counted.
The attention layer's: its projections (W_q [hidden, heads x 2 d], the query
and the gate; W_k, W_v [hidden, groups x d]; W_o [heads x d, hidden]);
scores and weighted values over the MEAN number of keys a query of an
episode meets, (S + 1) / 2, heads x d each. Every layer's feed-forward: the
router over all its outputs, the experts at the EXPECTED share of a token's
k that the held experts take (k x held / routed, three products each), the
shared expert (three products of hidden x its width) and its gate (hidden).
The output head and the value head.

Left out: the embedding gather, norms, softmax, the rotation, the
elementwise work named above, the sort and un-sort of the dispatch, V-trace
and the optimizer's update, and everything the program computes beyond the
algorithm's need (masked parts of a tile or of a chunk's triangle, experts'
products on rows that are not theirs, the backward pass's recomputation of
each block); so a share built on these counts is an under-count, never an
over-count.

`network` is the `network` block of the configuration: the published
`config.json` keys, `experts_held` (the experts this chip holds), and
`sequence_length` (positions an episode).
"""

STATE_BYTES = 4  # a matrix state's element: float32


def is_attention(network: dict, layer: int) -> bool:
    """Whether the 0-indexed `layer` is the gated attention."""
    return (layer + 1) % network["full_attention_interval"] == 0


def gdn_layers(network: dict) -> int:
    return sum(not is_attention(network, i)
               for i in range(network["num_hidden_layers"]))


def _gdn(network: dict) -> tuple:
    """(K, V, value heads, d_k, d_v) of a Gated DeltaNet layer."""
    d_k, d_v = (network["linear_key_head_dim"],
                network["linear_value_head_dim"])
    value_heads = network["linear_num_value_heads"]
    return (network["linear_num_key_heads"] * d_k, value_heads * d_v,
            value_heads, d_k, d_v)


def operator_macs(network: dict, layer: int) -> dict:
    """Multiply-accumulates a token of `layer`'s operator, by part."""
    h = network["hidden_size"]
    if not is_attention(network, layer):
        K, V, value_heads, d_k, d_v = _gdn(network)
        return {
            "gdn_projections": (h * (2 * K + 2 * V) + h * 2 * value_heads
                                + V * h),
            # S^T k, k u^T, S^T q: the step's three products a value head.
            "gdn_state": 3 * value_heads * d_k * d_v,
        }
    heads, groups, d = (network["num_attention_heads"],
                        network["num_key_value_heads"], network["head_dim"])
    mean_keys = (network["sequence_length"] + 1) / 2.0
    return {
        "projections": (h * heads * 2 * d + 2 * h * groups * d
                        + heads * d * h),
        "attention": 2 * heads * d * mean_keys,
    }


def feed_forward_macs(network: dict, layer: int) -> dict:
    """Multiply-accumulates a token of `layer`'s feed-forward."""
    h, w = network["hidden_size"], network["moe_intermediate_size"]
    routed = network["num_experts"]
    held = network.get("experts_held") or routed
    return {
        "router": h * routed,
        "experts": (network["num_experts_per_tok"] * held / routed
                    * 3 * h * w),
        "shared": 3 * h * network["shared_expert_intermediate_size"] + h,
    }


def head_macs(network: dict) -> int:
    return network["hidden_size"] * (network["vocab_size"] + 1)


def trunk_macs(network: dict) -> float:
    return (sum(sum(operator_macs(network, i).values())
                + sum(feed_forward_macs(network, i).values())
                for i in range(network["num_hidden_layers"]))
            + head_macs(network))


def forward_flops_per_token(network: dict) -> float:
    return 2.0 * trunk_macs(network)


def train_flops_per_token(network: dict) -> float:
    """Forward + backward. Backward is a weight-gradient and an
    input-gradient product per forward product (scores, values and the
    state's products: one a side), each the size of the forward one; the
    first layer's input gradient is owed too, because it reaches the
    embedding."""
    return 3.0 * forward_flops_per_token(network)


def device_flops_per_step(network: dict, passes: dict) -> float:
    """FLOPs the device owes for one trained env step (= one generated and
    learned token): `passes["inference"]` decode forwards and
    `passes["train"]` learner passes (the cell's `device_passes`)."""
    return (passes["inference"] * forward_flops_per_token(network)
            + passes["train"] * train_flops_per_token(network))


def head_share_of_a_pass(network: dict) -> float:
    """The share of a token's forward matrix FLOPs that the output head's
    slice (and the value head) takes: what a cut in depth distorts."""
    return head_macs(network) / trunk_macs(network)


def gdn_step_bytes(network: dict, rows: int) -> int:
    """Bytes a decode step of `rows` sequences owes for the matrix states:
    every Gated DeltaNet layer's S [value heads, d_k, d_v] float32 read
    once and written once a row, whatever computes the step."""
    _, _, value_heads, d_k, d_v = _gdn(network)
    return (rows * gdn_layers(network) * 2 * value_heads * d_k * d_v
            * STATE_BYTES)


def param_count(network: dict) -> int:
    """What the trainer builds: the trained parameters (the model has no
    constants)."""
    h, w = network["hidden_size"], network["moe_intermediate_size"]
    sw = network["shared_expert_intermediate_size"]
    heads, groups, d = (network["num_attention_heads"],
                        network["num_key_value_heads"], network["head_dim"])
    K, V, value_heads, _, d_v = _gdn(network)
    taps = network["linear_conv_kernel_dim"]
    routed = network["num_experts"]
    held = network.get("experts_held") or routed
    total = 0
    for i in range(network["num_hidden_layers"]):
        total += 2 * h  # the operator's norm, the feed-forward's
        if is_attention(network, i):
            # W_q (query and gate); W_k, W_v; W_o; the q and k norms
            total += (h * heads * 2 * d + 2 * h * groups * d + heads * d * h
                      + 2 * d)
        else:
            # W_qkvz; W_ba; the taps over q, k, v; A_log and dt_bias; the
            # output norm; W_out
            total += (h * (2 * K + 2 * V) + h * 2 * value_heads
                      + (2 * K + V) * taps + 2 * value_heads + d_v + V * h)
        # router, the held experts, the shared one and its gate
        total += h * routed + 3 * held * h * w + 3 * h * sw + h
    # embedding, head, final norm, value head (weight and bias)
    return total + 2 * network["vocab_size"] * h + h + h + 1
