"""Matrix FLOPs of the `smallthinker` token policy from shapes. A later PR
can change the program, not this count.

A multiply-accumulate is 2 FLOPs. Counted, a token: the four attention
projections at their own widths (W_q and W_o heads x head_dim, W_k and W_v
key/value heads x head_dim); scores and weighted values, heads x head_dim a
key each, over the MEAN number of keys a query of an episode meets in that
layer: (S + 1) / 2 in a full layer, and in a window layer the mean over t of
min(t + 1, window), which at 8,192 positions and a window of 4,096 is 3,072
and not 4,096; the router over all its outputs; the experts at the EXPECTED
share of a token's k that the held experts take (k x held / routed, three
products each); the output head and the value head.

Left out: the embedding gather, norms, RoPE, softmax, the sort and un-sort
of the dispatch, V-trace and the optimizer's update, and everything the
program computes beyond the algorithm's need (scores against masked cache
positions or masked parts of a tile, experts' products on rows that are not
theirs, the backward pass's recomputation of each block); so a share built
on these counts is an under-count, never an over-count.

`network` is the `network` block of the configuration: the published
`config.json` keys, `experts_held` (the experts this chip holds), and
`sequence_length` (positions an episode).
"""


def mean_keys(network: dict, layer: int) -> float:
    """Keys a query meets in `layer`, on the mean over an episode."""
    S = network["sequence_length"]
    if not network["sliding_window_layout"][layer]:
        return (S + 1) / 2.0
    full = min(network["sliding_window_size"], S)  # positions t >= full - 1
    return (full * (full + 1) / 2.0 + (S - full) * full) / S


def attention_macs(network: dict, layer: int) -> dict:
    """Multiply-accumulates a token of `layer`'s attention."""
    h, d = network["hidden_size"], network["head_dim"]
    heads, groups = (network["num_attention_heads"],
                     network["num_key_value_heads"])
    return {
        "projections": 2 * h * heads * d + 2 * h * groups * d,
        # q.k and attn.v: heads x head_dim a key, twice.
        "attention": 2 * heads * d * mean_keys(network, layer),
    }


def expert_layer_macs(network: dict) -> dict:
    """Multiply-accumulates a token of one layer's feed-forward."""
    h, w = network["hidden_size"], network["moe_ffn_hidden_size"]
    routed = network["moe_num_primary_experts"]
    held = network.get("experts_held") or routed
    return {
        "router": h * routed,
        "experts": (network["moe_num_active_primary_experts"] * held / routed
                    * 3 * h * w),
    }


def head_macs(network: dict) -> int:
    return network["hidden_size"] * (network["vocab_size"] + 1)


def trunk_macs(network: dict) -> float:
    layers = network["num_hidden_layers"]
    return (sum(sum(attention_macs(network, i).values())
                for i in range(layers))
            + layers * sum(expert_layer_macs(network).values())
            + head_macs(network))


def forward_flops_per_token(network: dict) -> float:
    return 2.0 * trunk_macs(network)


def train_flops_per_token(network: dict) -> float:
    """Forward + backward. Backward is a weight-gradient and an
    input-gradient product per forward product (scores and values: one a
    side), each the size of the forward one; the first layer's input
    gradient is owed too, because it reaches the embedding."""
    return 3.0 * forward_flops_per_token(network)


def device_flops_per_step(network: dict, passes: dict) -> float:
    """FLOPs the device owes for one trained env step (= one generated and
    learned token): `passes["inference"]` decode forwards and
    `passes["train"]` learner passes (the cell's `device_passes`)."""
    return (passes["inference"] * forward_flops_per_token(network)
            + passes["train"] * train_flops_per_token(network))


def param_count(network: dict) -> int:
    h, d = network["hidden_size"], network["head_dim"]
    heads, groups = (network["num_attention_heads"],
                     network["num_key_value_heads"])
    routed = network["moe_num_primary_experts"]
    held = network.get("experts_held") or routed
    # attn_norm, mlp_norm; W_q, W_o; W_k, W_v; router; the held experts
    layer = (2 * h + 2 * h * heads * d + 2 * h * groups * d + h * routed
             + 3 * held * h * network["moe_ffn_hidden_size"])
    # embedding, head, final norm, value head (weight and bias)
    return (network["num_hidden_layers"] * layer
            + 2 * network["vocab_size"] * h + h + h + 1)
