"""Matrix FLOPs of the `nemotron_h` token policy from shapes, and the bytes a
decode step owes for its matrix states. A later PR can change the program,
not these counts.

A multiply-accumulate is 2 FLOPs. Counted, a token, a layer by its letter in
`hybrid_override_pattern` (every layer is ONE function). `M`, a Mamba-2
layer: its projections (W_in [hidden, I + (I + 2 G N) + heads], I = heads x
head_dim; W_out [I, hidden]) and THE STATE'S PRODUCTS in the cheaper of the
two forms, which is the step's: the outer product (dt x) B^T and the read
S C, heads x P x N each (the chunked form owes the pair products C_t . B_s
and their sum against dt x over half a chunk, a chunk's state and the read
of the carried one: about 1.4 times as much at chunks of 128; a pass in the
dearer form earns no share by it). The decay of S, the convolution's four
taps a channel, the gate, the group norm and D x are elementwise and NOT
counted. `*`, the attention layer: W_q, W_k, W_v, W_o, and scores and
weighted values over the MEAN number of keys a query of an episode meets, (S
+ 1) / 2. `E`, an expert layer: the router over all its outputs, the experts
at the EXPECTED share of a token's k that the held experts take (k x held /
routed, TWO products each: there is no gate matrix), and the shared expert
(two products of its own width). The output head and the value head.

Left out: the embedding gather, norms, softmax, the elementwise work named
above, the sort and un-sort of the dispatch, V-trace and the optimizer's
update, and everything the program computes beyond the algorithm's need
(masked parts of a tile or of a chunk's triangle, experts' products on rows
that are not theirs, the backward pass's recomputation of each layer); so a
share built on these counts is an under-count, never an over-count.

`network` is the `network` block of the configuration: the published
`config.json` keys (the leading `num_hidden_layers` letters of
`hybrid_override_pattern` name the layers), `experts_held` (the experts this
chip holds), and `sequence_length` (positions an episode).
"""

STATE_BYTES = 4  # a matrix state's element: float32


def letters(network: dict) -> str:
    """The layers' letters: M, E or *."""
    return network["hybrid_override_pattern"][:network["num_hidden_layers"]]


def ssm_layers(network: dict) -> int:
    return letters(network).count("M")


def _ssm(network: dict) -> tuple:
    """(heads, P, G, N) of a Mamba-2 layer."""
    return (network["mamba_num_heads"], network["mamba_head_dim"],
            network["n_groups"], network["ssm_state_size"])


def layer_macs(network: dict, layer: int) -> dict:
    """Multiply-accumulates a token of `layer`'s one function, by part."""
    h = network["hidden_size"]
    letter = letters(network)[layer]
    if letter == "M":
        heads, p, g, n = _ssm(network)
        inner = heads * p
        return {
            "ssm_projections": (h * (2 * inner + 2 * g * n + heads)
                                + inner * h),
            # (dt x) B^T and S C: the step's two products a head.
            "ssm_state": 2 * heads * p * n,
        }
    if letter == "*":
        heads, kv = (network["num_attention_heads"],
                     network["num_key_value_heads"])
        d = network["head_dim"]
        mean_keys = (network["sequence_length"] + 1) / 2.0
        return {
            "projections": 2 * h * heads * d + 2 * h * kv * d,
            "attention": heads * 2 * d * mean_keys,
        }
    routed = network["n_routed_experts"]
    held = network.get("experts_held") or routed
    return {
        "router": h * routed,
        "experts": (network["num_experts_per_tok"] * held / routed
                    * 2 * h * network["moe_intermediate_size"]),
        "shared": (network["n_shared_experts"] * 2 * h
                   * network["moe_shared_expert_intermediate_size"]),
    }


def head_macs(network: dict) -> int:
    return network["hidden_size"] * (network["vocab_size"] + 1)


def trunk_macs(network: dict) -> float:
    return (sum(sum(layer_macs(network, i).values())
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


def ssm_step_bytes(network: dict, rows: int) -> int:
    """Bytes a decode step of `rows` sequences owes for the matrix states:
    every Mamba-2 layer's S [heads, P, N] float32 read once and written
    once a row, whatever computes the step."""
    heads, p, _, n = _ssm(network)
    return rows * ssm_layers(network) * 2 * heads * p * n * STATE_BYTES


def param_count(network: dict) -> int:
    """What the trainer builds: the trained parameters and the routers'
    selection biases (constants: `n_routed_experts` an expert layer)."""
    h = network["hidden_size"]
    heads, p, g, n = _ssm(network)
    inner, conv = heads * p, heads * p + 2 * g * n
    routed = network["n_routed_experts"]
    held = network.get("experts_held") or routed
    total = 0
    for letter in letters(network):
        total += h  # the layer's one norm
        if letter == "M":
            # W_in; the taps and their bias; A_log, dt_bias, D; the group
            # norm; W_out
            total += (h * (inner + conv + heads)
                      + conv * network["conv_kernel"] + conv + 3 * heads
                      + inner + inner * h)
        elif letter == "*":
            q = network["num_attention_heads"] * network["head_dim"]
            kv = network["num_key_value_heads"] * network["head_dim"]
            total += 2 * h * q + 2 * h * kv
        else:
            # router and its bias, the held experts, the shared one
            total += (h * routed + routed
                      + 2 * held * h * network["moe_intermediate_size"]
                      + network["n_shared_experts"] * 2 * h
                      * network["moe_shared_expert_intermediate_size"])
    # embedding, head, final norm, value head (weight and bias)
    return total + 2 * network["vocab_size"] * h + h + h + 1
