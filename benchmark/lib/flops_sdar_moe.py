"""Matrix FLOPs of the `sdar_moe` token policy, which generates by diffusion
over blocks, from shapes. A later PR can change the program, not this count.

A multiply-accumulate is 2 FLOPs. A ROW is one position in one pass (the
rollout) or in one stream (the learner). Counted, a row of a layer: the four
attention projections at their own widths (W_q and W_o heads x head_dim, W_k
and W_v key/value heads x head_dim); scores and weighted values, heads x
head_dim a key each, over the MEAN number of keys a query meets: a query of
block b reads the b blocks before it and its own, (b + 1) L positions,
whichever pass or stream it is in, (T + L) / 2 on the mean over an episode of
T positions; the router over all its outputs; the experts at the EXPECTED
share of a row's k that the held experts take (k x held / routed, three
products each).

What a generated block owes, S = `denoise_steps`, L = `block_length`:

* the rollout: S denoising passes of L rows through every layer, and a
  commit pass of L rows whose last layer stops at its keys and values (W_k
  and W_v alone: nothing reads the rest); the head over the rows that are
  still masked when a pass begins, L (S + 1) / 2 of them a block, and the
  value head once;
* the learner, forward: S noisy streams through every layer and the clean
  stream, whose last layer is owed its keys and values alone (since PR 51
  the program runs no more of it than that); the head over the L positions
  once, the value head once; backward twice that.

`steps` are ACTIONS: an episode of T positions has T - 1 of them (its first
position is given), so a step owes T / (T - 1) positions.

Left out: the embedding gather, norms, RoPE, softmax, the sampler, the sort
and un-sort of the dispatch, V-trace and the optimizer's update, and
everything the program computes beyond the algorithm's need (scores against
masked parts of a tile or of a cache block, logits of positions already
unmasked, the batched experts' products on rows that are not theirs, the
clean stream's last feed-forward, the backward pass's recomputation of each
layer); so a share built on these counts is an under-count, never an
over-count.

`network` is the `network` block of the configuration: the published
`config.json` keys, `experts_held`, `block_length`, `denoise_steps`, and
`sequence_length` (positions an episode).
"""


def mean_keys(network: dict) -> float:
    """Keys a query meets, on the mean over an episode's blocks."""
    return (network["sequence_length"] + network["block_length"]) / 2.0


def layer_macs(network: dict) -> dict:
    """Multiply-accumulates a row of one layer."""
    h, d = network["hidden_size"], network["head_dim"]
    heads, groups = (network["num_attention_heads"],
                     network["num_key_value_heads"])
    routed = network["num_experts"]
    held = network.get("experts_held") or routed
    return {
        "queries_and_output": 2 * h * heads * d,
        "keys_and_values": 2 * h * groups * d,
        # q.k and attn.v: heads x head_dim a key, twice.
        "attention": 2 * heads * d * mean_keys(network),
        "router": h * routed,
        "experts": (network["num_experts_per_tok"] * held / routed
                    * 3 * h * network["moe_intermediate_size"]),
    }


def head_macs(network: dict) -> int:
    """The output head over the vocabulary (a row)."""
    return network["hidden_size"] * network["vocab_size"]


def streams_macs(network: dict) -> float:
    """A position through S noisy passes (or streams) and the clean one:
    every layer S times, and once more but for what the clean last layer
    owes beyond its keys and values."""
    layer = layer_macs(network)
    whole = sum(layer.values())
    layers, S = network["num_hidden_layers"], network["denoise_steps"]
    return (S * layers * whole + (layers - 1) * whole
            + layer["keys_and_values"])


def rollout_macs_per_position(network: dict) -> float:
    S, L = network["denoise_steps"], network["block_length"]
    return (streams_macs(network) + (S + 1) / 2.0 * head_macs(network)
            + network["hidden_size"] / L)


def learner_macs_per_position(network: dict) -> float:
    return (streams_macs(network) + head_macs(network)
            + network["hidden_size"] / network["block_length"])


def positions_per_step(network: dict) -> float:
    T = network["sequence_length"]
    return T / (T - 1.0)


def forward_flops_per_token(network: dict) -> float:
    """The rollout's FLOPs a generated token (a step)."""
    return 2.0 * rollout_macs_per_position(network) * positions_per_step(
        network)


def train_flops_per_token(network: dict) -> float:
    """The learner's forward + backward a generated token. Backward is a
    weight-gradient and an input-gradient product per forward product
    (scores and values: one a side), each the size of the forward one."""
    return 3.0 * 2.0 * learner_macs_per_position(
        network) * positions_per_step(network)


def device_flops_per_step(network: dict, passes: dict) -> float:
    """FLOPs the device owes for one trained env step (= one generated and
    learned token): `passes["inference"]` rollouts and `passes["train"]`
    learner passes of it (the cell's `device_passes`)."""
    return (passes["inference"] * forward_flops_per_token(network)
            + passes["train"] * train_flops_per_token(network))


def head_share_of_a_pass(network: dict) -> float:
    """The head's share of one row's forward matrix FLOPs through every
    layer and the head: what a cut in depth distorts."""
    trunk = network["num_hidden_layers"] * sum(layer_macs(network).values())
    return head_macs(network) / (trunk + head_macs(network))


def param_count(network: dict) -> int:
    h, d = network["hidden_size"], network["head_dim"]
    heads, groups = (network["num_attention_heads"],
                     network["num_key_value_heads"])
    routed = network["num_experts"]
    held = network.get("experts_held") or routed
    # attn_norm, mlp_norm; q_norm, k_norm; W_q, W_o; W_k, W_v; router; the
    # held experts
    layer = (2 * h + 2 * d + 2 * h * heads * d + 2 * h * groups * d
             + h * routed + 3 * held * h * network["moe_intermediate_size"])
    # embedding, head, final norm, value head (weight and bias)
    return (network["num_hidden_layers"] * layer
            + 2 * network["vocab_size"] * h + h + h + 1)
