"""Matrix FLOPs of the OLMoE token policy from shapes. A later PR can change
the program, not this count.

A multiply-accumulate is 2 FLOPs. Counted: the four attention projections,
scores and weighted values over the causal length (a token at position t
attends to t + 1 keys), the router, the `num_experts_per_tok` experts a token
is routed to (three products each), the output head and the value head.
Left out: the embedding gather (0 by definition), norms, RoPE, softmax, the
sort and un-sort of the dispatch, V-trace and the optimizer's update; so a
share built on these counts is an under-count, never an over-count. What the
program computes beyond what the algorithm needs (scores against cache
positions that are masked, the upper triangle of a causal pass) is not
counted either.

`network` is the `network` block of a token configuration: the published
`config.json` keys plus `sequence_length` (positions an episode).
"""


def layer_macs(network: dict) -> dict:
    """Multiply-accumulates a token of ONE layer's forward pass, by part,
    with the attention taken at the mean causal length of an episode."""
    h = network["hidden_size"]
    mean_keys = (network["sequence_length"] + 1) / 2.0
    return {
        "projections": 4 * h * h,
        # q.k and attn.v: heads x head_dim = h a key, twice.
        "attention": 2 * h * mean_keys,
        "router": h * network["num_experts"],
        "experts": (network["num_experts_per_tok"] * 3 * h
                    * network["intermediate_size"]),
    }


def head_macs(network: dict) -> int:
    return network["hidden_size"] * (network["vocab_size"] + 1)


def forward_flops_per_token(network: dict) -> float:
    per_layer = sum(layer_macs(network).values())
    return 2.0 * (network["num_hidden_layers"] * per_layer
                  + head_macs(network))


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
    h, e, w = (network["hidden_size"], network["num_experts"],
               network["intermediate_size"])
    layer = 4 * h * h + 4 * h + h * e + 3 * e * h * w
    # embedding, head, final norm, value head (weight and bias)
    return (network["num_hidden_layers"] * layer
            + 2 * network["vocab_size"] * h + h + h + 1)
