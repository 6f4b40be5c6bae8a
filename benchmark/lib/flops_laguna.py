"""Matrix FLOPs of the `laguna` token policy from shapes, and the bytes a
decode step owes for its key/value caches. A later PR can change the
program, not these counts.

A multiply-accumulate is 2 FLOPs. Counted, a token. An attention layer at
ITS OWN kind's heads (`num_attention_heads_per_layer[l]`: W_q and W_o heads x
head_dim, the gate hidden x heads; W_k and W_v key/value heads x head_dim);
scores and weighted values, heads x head_dim a key each, over the MEAN number
of keys a query of an episode meets in that layer: (S + 1) / 2 in a full
layer, and in a sliding layer the mean over t of min(t + 1, window), so a
window layer is counted at its window (at 8,192 positions and a window of
512: 496.0). The feed-forward by `mlp_layer_types[l]`: a dense SwiGLU (three
products of hidden x `intermediate_size`), or the router over all its
outputs, the experts at the EXPECTED share of a token's k that the held
experts take (k x held / routed, three products each) and the shared expert
(three products of hidden x its width). The output head and the value head.

Left out: the embedding gather, norms, the rotations, softmax, the gates'
sigmoid, the sort and un-sort of the dispatch, V-trace and the optimizer's
update, and everything the program computes beyond the algorithm's need
(scores against masked cache positions or masked parts of a tile, experts'
products on rows that are not theirs, the backward pass's recomputation of
each block); so a share built on these counts is an under-count, never an
over-count.

`network` is the `network` block of the configuration: the published
`config.json` keys, `experts_held` (the experts this chip holds), and
`sequence_length` (positions an episode).
"""

CACHE_BYTES = 2  # a cached key's or value's element: bfloat16


def is_full(network: dict, layer: int) -> bool:
    return network["layer_types"][layer] == "full_attention"


def mean_keys(network: dict, layer: int) -> float:
    """Keys a query meets in `layer`, on the mean over an episode: the
    positions a decode step's row holds of that layer's cache."""
    S = network["sequence_length"]
    if is_full(network, layer):
        return (S + 1) / 2.0
    full = min(network["sliding_window"], S)  # positions t >= full - 1
    return (full * (full + 1) / 2.0 + (S - full) * full) / S


def attention_macs(network: dict, layer: int) -> dict:
    """Multiply-accumulates a token of `layer`'s attention."""
    h, d = network["hidden_size"], network["head_dim"]
    heads = network["num_attention_heads_per_layer"][layer]
    groups = network["num_key_value_heads"]
    return {
        # W_q, W_o; W_k, W_v; the gate a head
        "projections": 2 * h * heads * d + 2 * h * groups * d + h * heads,
        # q.k and attn.v: heads x head_dim a key, twice.
        "attention": 2 * heads * d * mean_keys(network, layer),
    }


def feed_forward_macs(network: dict, layer: int) -> dict:
    """Multiply-accumulates a token of `layer`'s feed-forward."""
    h = network["hidden_size"]
    if network["mlp_layer_types"][layer] == "dense":
        return {"dense": 3 * h * network["intermediate_size"]}
    routed = network["num_experts"]
    held = network.get("experts_held") or routed
    return {
        "router": h * routed,
        "experts": (network["num_experts_per_tok"] * held / routed
                    * 3 * h * network["moe_intermediate_size"]),
        "shared": 3 * h * network["shared_expert_intermediate_size"],
    }


def head_macs(network: dict) -> int:
    return network["hidden_size"] * (network["vocab_size"] + 1)


def trunk_macs(network: dict) -> float:
    return (sum(sum(attention_macs(network, i).values())
                + sum(feed_forward_macs(network, i).values())
                for i in range(network["num_hidden_layers"]))
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


def head_share_of_a_pass(network: dict) -> float:
    """The share of a token's forward matrix FLOPs that the output head's
    slice (and the value head) takes: what a cut in depth distorts."""
    return head_macs(network) / trunk_macs(network)


def attention_step_bytes(network: dict, rows: int) -> float:
    """Bytes a decode step of `rows` sequences owes for the caches, on the
    mean over an episode: every position a row HOLDS of every layer's K and
    V once (a full cache the positions so far, a ring at most its window),
    whatever computes the step and however many blocks it fetches."""
    row = 2 * network["num_key_value_heads"] * network["head_dim"] \
        * CACHE_BYTES
    return rows * row * sum(mean_keys(network, i)
                            for i in range(network["num_hidden_layers"]))


def param_count(network: dict) -> int:
    """What the trainer builds: the trained parameters (the model has no
    constants)."""
    h, d = network["hidden_size"], network["head_dim"]
    groups = network["num_key_value_heads"]
    routed = network["num_experts"]
    held = network.get("experts_held") or routed
    total = 0
    for i in range(network["num_hidden_layers"]):
        heads = network["num_attention_heads_per_layer"][i]
        # the two norms; W_q, W_o; W_k, W_v; the gate
        total += 2 * h + 2 * h * heads * d + 2 * h * groups * d + h * heads
        if network["mlp_layer_types"][i] == "dense":
            total += 3 * h * network["intermediate_size"]
        else:
            # router, the held experts, the shared one
            total += (h * routed
                      + 3 * held * h * network["moe_intermediate_size"]
                      + 3 * h * network["shared_expert_intermediate_size"])
    # embedding, head, final norm, value head (weight and bias)
    return total + 2 * network["vocab_size"] * h + h + h + 1
