"""Matrix FLOPs of the `lfm2_moe` token policy from shapes. A later PR can
change the program, not this count.

A multiply-accumulate is 2 FLOPs. Counted, a token. A convolution layer's
operator: its two projections, W_in [hidden, 3 hidden] and W_out [hidden,
hidden]; the three taps are NOT counted as matrix work (three multiplies a
channel: elementwise, 6,144 of a layer's 16.8 M multiply-accumulates). An
attention layer's: the four projections at their own widths (W_q and W_o
heads x head_dim, W_k and W_v key/value heads x head_dim), and scores and
weighted values, heads x head_dim a key each, over the MEAN number of keys a
query of an episode meets, (S + 1) / 2. A dense layer's feed-forward: three
products of hidden x intermediate_size. An expert layer's: the router over
all its outputs, and the experts at the EXPECTED share of a token's k that
the held experts take (k x held / routed, three products each). The output
head (the embedding, transposed: the same product) and the value head.

Left out: the embedding gather, norms, RoPE, softmax, the gates' and taps'
elementwise products, the sort and un-sort of the dispatch, V-trace and the
optimizer's update, and everything the program computes beyond the
algorithm's need (scores against masked cache positions or masked parts of a
tile, experts' products on rows that are not theirs, the backward pass's
recomputation of each block); so a share built on these counts is an
under-count, never an over-count.

`network` is the `network` block of the configuration: the published
`config.json` keys, `experts_held` (the experts this chip holds), and
`sequence_length` (positions an episode).
"""


def head_dim(network: dict) -> int:
    return network["hidden_size"] // network["num_attention_heads"]


def operator_macs(network: dict, layer: int) -> dict:
    """Multiply-accumulates a token of `layer`'s operator, by part."""
    h = network["hidden_size"]
    if network["layer_types"][layer] == "conv":
        return {"conv_projections": 3 * h * h + h * h}
    d = head_dim(network)
    heads, groups = (network["num_attention_heads"],
                     network["num_key_value_heads"])
    return {
        "projections": 2 * h * heads * d + 2 * h * groups * d,
        # q.k and attn.v: heads x head_dim a key, twice.
        "attention": 2 * heads * d * (network["sequence_length"] + 1) / 2.0,
    }


def feed_forward_macs(network: dict, layer: int) -> dict:
    """Multiply-accumulates a token of `layer`'s feed-forward."""
    h = network["hidden_size"]
    if layer < network["num_dense_layers"]:
        return {"dense": 3 * h * network["intermediate_size"]}
    routed = network["num_experts"]
    held = network.get("experts_held") or routed
    return {
        "router": h * routed,
        "experts": (network["num_experts_per_tok"] * held / routed
                    * 3 * h * network["moe_intermediate_size"]),
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
    input-gradient product per forward product (scores and values: one a
    side), each the size of the forward one; the first layer's input
    gradient is owed too, because it reaches the embedding (and the tied
    head's weight gradient is the embedding's)."""
    return 3.0 * forward_flops_per_token(network)


def device_flops_per_step(network: dict, passes: dict) -> float:
    """FLOPs the device owes for one trained env step (= one generated and
    learned token): `passes["inference"]` decode forwards and
    `passes["train"]` learner passes (the cell's `device_passes`)."""
    return (passes["inference"] * forward_flops_per_token(network)
            + passes["train"] * train_flops_per_token(network))


def param_count(network: dict) -> int:
    """What the trainer builds: the trained parameters and the routers'
    selection biases (constants: `num_experts` an expert layer)."""
    h, d = network["hidden_size"], head_dim(network)
    heads, groups = (network["num_attention_heads"],
                     network["num_key_value_heads"])
    routed = network["num_experts"]
    held = network.get("experts_held") or routed
    total = 0
    for i in range(network["num_hidden_layers"]):
        total += 2 * h  # the operator's norm, the feed-forward's
        if network["layer_types"][i] == "conv":
            total += 3 * h * h + h * h + h * network["conv_L_cache"]
        else:
            # W_q, W_o; W_k, W_v; the per-head q and k norms
            total += 2 * h * heads * d + 2 * h * groups * d + 2 * d
        if i < network["num_dense_layers"]:
            total += 3 * h * network["intermediate_size"]
        else:
            # router and its bias, the held experts
            total += h * routed + routed + 3 * held * h * network[
                "moe_intermediate_size"]
    # the tied embedding (once), final norm, value head (weight and bias)
    return total + network["vocab_size"] * h + h + h + 1
