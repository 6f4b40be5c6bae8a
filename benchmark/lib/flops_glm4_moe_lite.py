"""Matrix FLOPs of the `glm4_moe_lite` token policy from shapes. A later PR
can change the program, not this count.

A multiply-accumulate is 2 FLOPs. Counted, a token: latent attention's
projections (W_qa, W_qb, W_kva, W_o) and its W_kvb, which both of its forms
pay once a token (decompressed: the token's own keys and values; absorbed:
W_UK into its query and W_UV onto its weighted latents); scores and weighted
values over the mean causal length of an episode in the CHEAPER of the two
forms (decompressed: heads x (nope + rope) a key and heads x v_head_dim a
value; absorbed: heads x (kv_lora_rank + rope) and heads x kv_lora_rank),
whatever form the program runs, so that a pass in the dearer form earns no
share by it; the leading dense layers' SwiGLU; the router over all its
outputs; the routed experts at the EXPECTED share of a token's k that the
held experts take (k x held / routed, three products each), the shared
experts; the output head and the value head. The next-next-token module
(W_eh, one more expert layer, the head a second time) is owed in a learner
pass only: the rollout does not run it.

Left out: the embedding gather, norms, RoPE, softmax, the sort and un-sort of
the dispatch, V-trace and the optimizer's update, and everything the program
computes beyond the algorithm's need (scores against masked cache positions,
the upper triangle of a causal pass, experts' products on rows that are not
theirs, the backward pass's recomputation of each block); so a share built on
these counts is an under-count, never an over-count.

`network` is the `network` block of the configuration: the published
`config.json` keys, `experts_held` (the routed experts this chip holds), and
`sequence_length` (positions an episode).
"""


def attention_macs(network: dict) -> dict:
    """Multiply-accumulates a token of ONE layer's latent attention."""
    h, heads = network["hidden_size"], network["num_attention_heads"]
    rq, rkv = network["q_lora_rank"], network["kv_lora_rank"]
    nope, rot, vd = (network["qk_nope_head_dim"], network["qk_rope_head_dim"],
                     network["v_head_dim"])
    mean_keys = (network["sequence_length"] + 1) / 2.0
    decompressed = heads * (nope + rot + vd) * mean_keys
    absorbed = heads * (rkv + rot + rkv) * mean_keys
    return {
        "projections": (h * rq + rq * heads * (nope + rot) + h * (rkv + rot)
                        + heads * vd * h),
        "kv_up": rkv * heads * (nope + vd),
        "attention": min(decompressed, absorbed),
    }


def expert_layer_macs(network: dict) -> dict:
    """Multiply-accumulates a token of one expert layer's feed-forward."""
    h, w = network["hidden_size"], network["moe_intermediate_size"]
    routed = network["n_routed_experts"]
    held = network.get("experts_held") or routed
    return {
        "router": h * routed,
        "experts": network["num_experts_per_tok"] * held / routed * 3 * h * w,
        "shared": network["n_shared_experts"] * 3 * h * w,
    }


def dense_layer_macs(network: dict) -> int:
    return 3 * network["hidden_size"] * network["intermediate_size"]


def head_macs(network: dict) -> int:
    return network["hidden_size"] * (network["vocab_size"] + 1)


def trunk_macs(network: dict) -> float:
    """The policy's own forward: what a decode step owes a token."""
    layers, dense = (network["num_hidden_layers"],
                     network["first_k_dense_replace"])
    return (layers * sum(attention_macs(network).values())
            + dense * dense_layer_macs(network)
            + (layers - dense) * sum(expert_layer_macs(network).values())
            + head_macs(network))


def module_macs(network: dict) -> dict:
    """The next-next-token module's forward a token, in two parts: its
    expert layer, and its edges (W_eh in, the trunk's head out), whose
    inputs or weights the objective reads under `stop_gradient`."""
    h, n = network["hidden_size"], network["num_nextn_predict_layers"]
    return {
        "block": n * (sum(attention_macs(network).values())
                      + sum(expert_layer_macs(network).values())),
        "edges": n * (2 * h * h + h * network["vocab_size"]),
    }


def forward_flops_per_token(network: dict) -> float:
    return 2.0 * trunk_macs(network)


def train_flops_per_token(network: dict) -> float:
    """Forward + backward of the trunk and the module. Backward is a
    weight-gradient and an input-gradient product per forward product
    (scores and values: one a side), each the size of the forward one; the
    trunk's first layer's input gradient is owed too, because it reaches
    the embedding. The module's edges owe one backward product each, not
    two: W_eh's inputs (the trunk's hidden, the embedding) and the head's
    weights take no gradient from it."""
    module = module_macs(network)
    return 2.0 * (3.0 * (trunk_macs(network) + module["block"])
                  + 2.0 * module["edges"])


def device_flops_per_step(network: dict, passes: dict) -> float:
    """FLOPs the device owes for one trained env step (= one generated and
    learned token): `passes["inference"]` decode forwards and
    `passes["train"]` learner passes (the cell's `device_passes`)."""
    return (passes["inference"] * forward_flops_per_token(network)
            + passes["train"] * train_flops_per_token(network))


def param_count(network: dict) -> int:
    """Everything the policy holds: the parameters, and the routers'
    selection biases (constants: `n_routed_experts` an expert layer)."""
    h, heads = network["hidden_size"], network["num_attention_heads"]
    rq, rkv = network["q_lora_rank"], network["kv_lora_rank"]
    nope, rot, vd = (network["qk_nope_head_dim"], network["qk_rope_head_dim"],
                     network["v_head_dim"])
    w, routed = network["moe_intermediate_size"], network["n_routed_experts"]
    held = network.get("experts_held") or routed
    # attn_norm, mlp_norm; W_qa and its norm, W_qb; W_kva and the latent's
    # norm, W_kvb; W_o
    attention = (2 * h + h * rq + rq + rq * heads * (nope + rot)
                 + h * (rkv + rot) + rkv + rkv * heads * (nope + vd)
                 + heads * vd * h)
    dense = attention + 3 * h * network["intermediate_size"]
    expert = (attention + h * routed + routed + 3 * held * h * w
              + network["n_shared_experts"] * 3 * h * w)
    layers, first = (network["num_hidden_layers"],
                     network["first_k_dense_replace"])
    # W_eh, the module's three norms
    module = network["num_nextn_predict_layers"] * (
        expert + 2 * h * h + 3 * h)
    # embedding, head, final norm, value head (weight and bias)
    return (first * dense + (layers - first) * expert + module
            + 2 * network["vocab_size"] * h + h + h + 1)
