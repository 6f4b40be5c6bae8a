"""Matrix FLOPs of the `kimi_linear` token policy from shapes, and the bytes
a decode step owes for its matrix states. A later PR can change the program,
not these counts.

A multiply-accumulate is 2 FLOPs. Counted, a token. A KDA layer's operator:
its projections (W_q, W_k, W_v [hidden, P] each, P = heads x head_dim; the
decay's and the output gate's two low-rank pairs [hidden, head_dim] and
[head_dim, P]; beta's [hidden, heads]; W_out [P, hidden]) and THE STATE'S
PRODUCTS in the cheaper of the two forms, which is the step's: S^T k, the
outer product k u^T and S^T q, heads x d_k x d_v each (the chunked form owes
about 80,000 a head a token at chunks of 64, the step 49,152; a pass in the
dearer form earns no share by it). The decay of S by rows, the convolutions'
four taps a channel, the normalisations and the gates are elementwise and
NOT counted. The latent layer's: its projections (W_q straight from the
input, W_kva, W_o) and W_kvb, which both of its forms pay once a token;
scores and weighted values over the MEAN number of keys a query of an
episode meets, (S + 1) / 2, in the cheaper of the decompressed and the
absorbed form. A dense layer's feed-forward: three products of hidden x
intermediate_size. An expert layer's: the router over all its outputs, the
experts at the EXPECTED share of a token's k that the held experts take
(k x held / routed, three products each), and the shared expert. The output
head and the value head.

Left out: the embedding gather, norms, softmax, the elementwise work named
above, the sort and un-sort of the dispatch, V-trace and the optimizer's
update, and everything the program computes beyond the algorithm's need
(masked parts of a tile or of a chunk's triangle, the zeros that pad a
head's 192 to 256, experts' products on rows that are not theirs, the
backward pass's recomputation of each block); so a share built on these
counts is an under-count, never an over-count.

`network` is the `network` block of the configuration: the published
`config.json` keys (`linear_attn_config`'s lists name the layers 1-indexed),
`experts_held` (the experts this chip holds), and `sequence_length`
(positions an episode).
"""

STATE_BYTES = 4  # a matrix state's element: float32


def _kda(network: dict) -> tuple:
    linear = network["linear_attn_config"]
    return linear["num_heads"], linear["head_dim"]


def is_kda(network: dict, layer: int) -> bool:
    """Whether the 0-indexed `layer` is a KDA layer."""
    return layer + 1 in network["linear_attn_config"]["kda_layers"]


def kda_layers(network: dict) -> int:
    return sum(is_kda(network, i)
               for i in range(network["num_hidden_layers"]))


def operator_macs(network: dict, layer: int) -> dict:
    """Multiply-accumulates a token of `layer`'s operator, by part."""
    h = network["hidden_size"]
    if is_kda(network, layer):
        heads, d = _kda(network)
        p = heads * d
        return {
            "kda_projections": (3 * h * p + 2 * (h * d + d * p) + h * heads
                                + p * h),
            # S^T k, k u^T, S^T q: the step's three products a head.
            "kda_state": 3 * heads * d * d,
        }
    heads = network["num_attention_heads"]
    rkv = network["kv_lora_rank"]
    nope, rot, vd = (network["qk_nope_head_dim"], network["qk_rope_head_dim"],
                     network["v_head_dim"])
    mean_keys = (network["sequence_length"] + 1) / 2.0
    return {
        "projections": (h * heads * (nope + rot) + h * (rkv + rot)
                        + heads * vd * h),
        "kv_up": rkv * heads * (nope + vd),
        "attention": min(heads * (nope + rot + vd),
                         heads * (rkv + rot + rkv)) * mean_keys,
    }


def feed_forward_macs(network: dict, layer: int) -> dict:
    """Multiply-accumulates a token of `layer`'s feed-forward."""
    h, w = network["hidden_size"], network["moe_intermediate_size"]
    if layer < network["first_k_dense_replace"]:
        return {"dense": 3 * h * network["intermediate_size"]}
    routed = network["num_experts"]
    held = network.get("experts_held") or routed
    return {
        "router": h * routed,
        "experts": (network["num_experts_per_token"] * held / routed
                    * 3 * h * w),
        "shared": network["num_shared_experts"] * 3 * h * w,
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


def kda_step_bytes(network: dict, rows: int) -> int:
    """Bytes a decode step of `rows` sequences owes for the matrix states:
    every KDA layer's S [heads, d_k, d_v] float32 read once and written
    once a row, whatever computes the step."""
    heads, d = _kda(network)
    return rows * kda_layers(network) * 2 * heads * d * d * STATE_BYTES


def param_count(network: dict) -> int:
    """What the trainer builds: the trained parameters and the routers'
    selection biases (constants: `num_experts` an expert layer)."""
    h, w = network["hidden_size"], network["moe_intermediate_size"]
    heads = network["num_attention_heads"]
    rkv = network["kv_lora_rank"]
    nope, rot, vd = (network["qk_nope_head_dim"], network["qk_rope_head_dim"],
                     network["v_head_dim"])
    kda_heads, d = _kda(network)
    p = kda_heads * d
    taps = network["linear_attn_config"]["short_conv_kernel_size"]
    routed = network["num_experts"]
    held = network.get("experts_held") or routed
    total = 0
    for i in range(network["num_hidden_layers"]):
        total += 2 * h  # the operator's norm, the feed-forward's
        if is_kda(network, i):
            # W_q, W_k, W_v and their taps; the decay's pair, A_log and
            # dt_bias; beta's; the gate's pair; the output norm; W_out
            total += (3 * h * p + 3 * p * taps + h * d + d * p + kda_heads
                      + p + h * kda_heads + h * d + d * p + d + p * h)
        else:
            # W_q; W_kva and the latent's norm; W_kvb; W_o
            total += (h * heads * (nope + rot) + h * (rkv + rot) + rkv
                      + rkv * heads * (nope + vd) + heads * vd * h)
        if i < network["first_k_dense_replace"]:
            total += 3 * h * network["intermediate_size"]
        else:
            # router and its bias, the held experts, the shared one
            total += (h * routed + routed + 3 * held * h * w
                      + network["num_shared_experts"] * 3 * h * w)
    # embedding, head, final norm, value head (weight and bias)
    return total + 2 * network["vocab_size"] * h + h + h + 1
