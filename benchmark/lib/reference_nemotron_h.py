"""Plain reference of the `nemotron_h` token policy: forward, V-trace's
loss, and the comparison that decides `correct` in its cells.

Straightforward `jax.numpy`, float32 throughout, matrix precision "highest",
no flax, no cache, no chunk, no kernel, no sort, no grouped or batched expert
product, nothing from `ray_tpu`. The equations are those of the source named
in `configs/impala_nemotron_twotower_30b_a3b.json` (`model_type: nemotron_h`;
the catalog's `config`; Mamba-2 in the state-space duality paper's form,
arXiv:2405.21060). Every layer is x + f(n), n = RMSNorm(x), with ONE f, by
the layer's letter in `hybrid_override_pattern`. For x [S, H]:

    `M` (Mamba-2; heads of P channels, I = heads x P; G groups of N values):
        [z | xBC | dt] = n W_in      widths I | I + 2 G N | heads, no bias
        xBC = silu(conv(xBC) + b)    ONE depthwise causal convolution over x,
              B and C together, `conv_kernel` taps as that many shifted
              products, w[:, L-1] on the current position; inputs before the
              episode's first position are 0
        x -> [heads, P];  B, C -> [G, N];  head h reads group h // (heads/G)
        dt_t = softplus(dt_t + dt_bias) a head;  a_t = exp(-exp(A_log) dt_t)
        THE RECURRENCE ITSELF, one position at a time (a `lax.scan` over the
        positions), S [P, N] a head, 0 where an episode begins:
            S <- a_t S + (dt_t x_t) B_t^T
            y_t = S C_t + D x_t
        out = x + GroupRMSNorm(y * silu(z)) W_out      the gate first, then
              the norm over each group's I / G channels, one weight [I]
    `*` (attention; heads query heads over num_key_value_heads of head_dim,
    no bias, NO rotation, no QK-norm):
        o_h = softmax_s(q_h . k_g,s / sqrt(head_dim)) v_g,s over s <= t of the
              same episode, g = h // (heads / groups);  out = x + o W_o
    `E` (experts; float32 router, one group: a plain top-k):
        s = sigmoid(n W_r);  S_t = the k largest of s + b (b a constant);
        w_e = s_e / sum_{e in S_t} s_e (norm_topk_prob), times
              routed_scaling_factor
        out = x + sum_{e in S_t, e HELD HERE} w_e W_down,e relu(W_up,e n)^2
              (NO gate matrix; a loop over the held experts, each on every
              token times its 0/1-masked weight; what the absent experts
              would add is left out)
              + W_down,sh relu(W_up,sh n)^2   the shared expert, every token
    after the last layer: RMSNorm;  logits = y W_head (untied);  a linear
    value head

An episode starts at position 0 and wherever `starts` says: the matrix
states are 0 there, a convolution's taps before it read 0, and attention
does not look back across it. The attention is a mask on the full score
matrix, a block of `QUERY_BLOCK` queries at a time so that 2,048 positions
fit a chip; the recurrence is scanned in blocks of `RECURRENCE_BLOCK`
positions; under a gradient each block and each layer is recomputed
(`jax.checkpoint`), which changes no number.

Departures from the source: a value head (an RL policy needs one); no
auxiliary router loss; the selection bias b is a constant (its balancing
update belongs to pre-training). THE SECOND TOWER IS ABSENT: what the
family's description adds to this config (a denoising tower with adaLN,
conditioning across the towers, bidirectional attention inside a block,
decoding by diffusion over blocks) has no key in `config.json`, and the
block length and noise schedule are not given; this is the config's one
52-layer tower as an autoregressive policy. `assumed` in the configuration's
file: attention without rotation (the family's modelling code applies none),
the weights' draws.

Tolerance. The system keeps parameters, router, final norm, heads, the time
steps, the decays and the matrix states in float32 and the blocks' other
activations in bfloat16; on the TPU its float32 products run as bf16 passes
at default precision. So it cannot agree with this reference to float32
accuracy. Measured and bounded, apart, as in the other token cells:

* the router's choice, A LAYER AT A TIME: this forward is held to the
  experts the system chose (`experts=`), and in each expert layer its own
  choice, from its own selection scores there, is compared with the system's
  (`router_flips`, `max_flip_gap`): `MAX_ROUTER_FLIPS`, `MAX_FLIP_GAP`.
* the arithmetic: logits and values against this reference held to the
  system's experts, each as the largest absolute difference over the largest
  absolute reference value: `TOLERANCE`.
* one update of the learner, by the trainer's own step (`compare_update`):
  `UPDATE_LOSS_TOLERANCE`, `UPDATE_TOLERANCE`.

Each limit of the forward lies between two readings at published widths on
the v5e (PERF.md section 4; my chip runs, PR 45): the system's largest over
its seeds, and this reference with its blocks rounded to float8_e4m3
(`round_to`, the nearest precision below the stated bfloat16) in the
system's place, which has to be refused. The readings stand beside the
constants below.
"""

import jax
import jax.numpy as jnp
import numpy as np

# The arithmetic the references share (float32 RMSNorm, the float8_e4m3
# rounding emulated in float32, the errors' measure; Adam's change, the
# global clip, a parameter's change against its float32 storage; the
# verdicts' arithmetic, judged here by this file's limits; the masked
# attention a block of queries at a time): one copy.
from lib import reference_glm4_moe_lite as _shared
from lib.reference_glm4_moe_lite import (  # noqa: F401
    adam_change, adam_update, change_error, clip_scale)
from lib.reference_kimi_linear import (  # noqa: F401
    output_scales, relative_error)
from lib.reference_lfm2_moe import _attention, _episodes, _rope
from lib.reference_olmoe import _rms_norm, _rounder

# Each limit beside the readings that set it (my chip runs, PR 45: the limits
# were set from the first three runs on three seeds; four sequences of 2,048
# positions each through the causal pass as one pass, the learner's shape,
# and through the decode as rows of the 128-row batch; "float8": this
# reference with its blocks rounded to float8_e4m3 in the system's place).
# Logits and values, seven bf16 half-blocks deep. The system: logits
# 1.02-1.10 %, values 1.51-1.78 %; float8: 19.2-20.5 %, 26.0-29.8 %. Near
# the geometric mean of the system's largest and float8's smallest.
TOLERANCE = 0.06
# (Token, expert layer) pairs whose six of 128 differ. The system: 5.5-5.8 %,
# rising with depth (3.9-4.3, 5.3-6.0, 6.7-7.2 % by layer); float8:
# 63.4-63.9 %. Near the two readings' geometric mean.
MAX_ROUTER_FLIPS = 0.19
# The largest gap of a flip over a pass's 24,576 pairs. The system:
# 0.57-0.82 %; float8: 16.2-18.0 %. Near their geometric mean.
MAX_FLIP_GAP = 0.035
# One update. Precision hardly moves the loss (a sum over 8,192 tokens): the
# accepted cells' limit, which leaves the first reading (0.059 %) fifteen
# times of room; then 0.005 and 0.023 %.
UPDATE_LOSS_TOLERANCE = 0.009
# The worst parameter's change, where 1 is what a state left unchanged
# reads, the reference held to the system's experts. First 0.139 % (the
# attention's W_o), then 0.183 and 0.170 % (W_o again; the routers 0.10-0.15
# %): near the geometric mean of the first reading and 1 (0.037), the more
# room above, since fresh seeds read higher.
UPDATE_TOLERANCE = 0.05

# Queries a block of the attention's score matrix (`_attention`'s, shared);
# positions a block of the recurrence's scan.
RECURRENCE_BLOCK = 64

MUTATIONS = (
    "decay_a_channel", "b_c_a_head", "conv_without_bias", "conv_over_x_alone",
    "taps_reversed", "conv_across_reset", "gate_after_norm",
    "norm_over_all_channels", "no_d", "dt_not_softplus", "relu_not_squared",
    "experts_gated", "no_scaling_factor", "attention_rotated",
    "state_one_step_stale", "no_carry_between_chunks", "bias_in_weights")


def _convolved(a, w, bias, positions, mutate):
    """silu-less: the depthwise causal convolution of a [B, S, C] with taps
    w [C, L] as L shifted products, a tap that would reach before its
    episode's first position reading 0, plus its bias."""
    L, S = w.shape[1], a.shape[1]
    if mutate == "taps_reversed":
        w = w[:, ::-1]
    out = jnp.zeros_like(a)
    for j in range(L):
        shift = L - 1 - j
        shifted = jnp.pad(a, ((0, 0), (shift, 0), (0, 0)))[:, :S]
        if mutate != "conv_across_reset":
            shifted = jnp.where((positions >= shift)[..., None], shifted, 0.0)
        out = out + w[:, j] * shifted
    return out if mutate == "conv_without_bias" else out + bias


def _recurrence(x, Bh, Ch, decay, starts, mutate):
    """The state-space recurrence one position at a time: x [B, S, heads,
    P] (dt x), Bh, Ch [B, S, heads, N] (each head's group's), decay [B, S,
    heads, P] (exp of the log decay: one value a head, the same for every
    channel unless mutated), `starts` [B, S] true where an episode begins.
    Returns (y [B, S, heads, P], S after the last position [B, heads, P,
    N])."""
    B, S, heads, P = x.shape

    def position(state, xs):
        x, Bh, Ch, decay, start = xs
        state = jnp.where(start[:, None, None, None], 0.0, state)
        stale = state
        state = decay[..., None] * state + x[..., None] * Bh[..., None, :]
        read = stale if mutate == "state_one_step_stale" else state
        return state, jnp.einsum("bhpn,bhn->bhp", read, Ch)

    def block(state, xs):
        return jax.lax.scan(position, state, xs)
    size = S if S % RECURRENCE_BLOCK else RECURRENCE_BLOCK
    xs = tuple(jnp.moveaxis(a, 1, 0).reshape(
        (S // size, size) + a.shape[:1] + a.shape[2:])
        for a in (x, Bh, Ch, decay, starts))
    state, y = jax.lax.scan(
        jax.checkpoint(block), jnp.zeros((B, heads, P, Bh.shape[-1])), xs)
    return jnp.moveaxis(y.reshape((S,) + y.shape[2:]), 0, 1), state


def _mamba2(lp, x, n, positions, net, r, mutate):
    """x + Mamba2(n); (out, the matrix states after the last position)."""
    heads, P = net["mamba_num_heads"], net["mamba_head_dim"]
    G, N = net["n_groups"], net["ssm_state_size"]
    inner = heads * P
    B, S, _ = x.shape
    mixed = r(n @ lp["ssm_in"])
    z, xBC, dt = (mixed[..., :inner], mixed[..., inner:-heads],
                  mixed[..., -heads:])
    conv = _convolved(xBC, lp["ssm_conv"], lp["ssm_conv_bias"], positions,
                      mutate)
    if mutate == "conv_over_x_alone":
        conv = jnp.concatenate([conv[..., :inner], xBC[..., inner:]], axis=-1)
    xBC = r(jax.nn.silu(conv))
    xs = xBC[..., :inner].reshape(B, S, heads, P)
    Bm, Cm = (a.reshape(B, S, G, N)
              for a in jnp.split(xBC[..., inner:], 2, axis=-1))
    of_head = (jnp.arange(heads) % G if mutate == "b_c_a_head"
               else jnp.arange(heads) // (heads // G))
    dt = dt + lp["ssm_dt_bias"]
    if mutate != "dt_not_softplus":
        dt = jax.nn.softplus(dt)
    rate = jnp.exp(lp["ssm_a_log"])
    if mutate == "decay_a_channel":
        # Channel p of head h decays at head (h P + p) mod heads' rate.
        rate = rate[(jnp.arange(heads)[:, None] * P + jnp.arange(P)) % heads]
        decay = jnp.exp(-rate * dt[..., None])
    else:
        decay = jnp.broadcast_to(jnp.exp(-rate * dt)[..., None],
                                 (B, S, heads, P))
    starts = positions == 0
    if mutate == "no_carry_between_chunks":
        starts = starts | (jnp.arange(S) % net["chunk_size"] == 0)[None]
    y, state = _recurrence(r(dt[..., None] * xs), Bm[:, :, of_head],
                           Cm[:, :, of_head], decay, starts, mutate)
    y = r(y)
    if mutate != "no_d":
        y = y + lp["ssm_d"][:, None] * xs
    y = y.reshape(B, S, inner)
    by_group = (B, S, G, inner // G)
    if mutate == "gate_after_norm":
        o = _rms_norm(y.reshape(by_group), lp["ssm_norm"].reshape(G, -1),
                      net["layer_norm_epsilon"]).reshape(B, S, inner)
        o = o * jax.nn.silu(z)
    elif mutate == "norm_over_all_channels":
        o = _rms_norm(y * jax.nn.silu(z), lp["ssm_norm"],
                      net["layer_norm_epsilon"])
    else:
        o = _rms_norm((y * jax.nn.silu(z)).reshape(by_group),
                      lp["ssm_norm"].reshape(G, -1),
                      net["layer_norm_epsilon"]).reshape(B, S, inner)
    return r(x + r(r(o) @ lp["ssm_out"])), state


def _grouped_attention(lp, x, n, episode, positions, net, r, mutate):
    heads, groups = net["num_attention_heads"], net["num_key_value_heads"]
    B, S, _ = x.shape
    q = r(n @ lp["wq"]).reshape(B, S, heads, -1)
    k = r(n @ lp["wk"]).reshape(B, S, groups, -1)
    v = r(n @ lp["wv"]).reshape(B, S, groups, -1)
    if mutate == "attention_rotated":
        q = r(_rope(q, positions, net["rope_theta"]))
        k = r(_rope(k, positions, net["rope_theta"]))
    # Query head h reads key/value head h // (heads / groups).
    of_head = jnp.arange(heads) // (heads // groups)
    o = _attention(q, k[:, :, of_head], v[:, :, of_head], episode, r)
    return r(x + r(o.reshape(B, S, -1) @ lp["wo"]))


def _relu2_mlp(m, w_up, w_down, r, mutate):
    """W_down relu(W_up m)^2: no gate matrix."""
    up = r(m @ w_up)
    if mutate == "relu_not_squared":
        hidden = jax.nn.relu(up)
    elif mutate == "experts_gated":
        hidden = jax.nn.silu(up) * up  # W_up as its own gate
    else:
        hidden = jnp.square(jax.nn.relu(up))
    return r(r(hidden) @ w_down)


def _moe(lp, bias, x, m, net, r, mutate, held_to):
    """x + MoE(m); (out, this layer's own choice [B, S, k], its selection
    scores [B, S, E]). `held_to` [B, S, k]: the experts every token is sent
    to instead, with the weights computed here for them."""
    k = net["num_experts_per_tok"]
    scores = jax.nn.sigmoid(m @ lp["router"])
    select = scores + bias
    _, own = jax.lax.top_k(select, k)
    top_i = own if held_to is None else jnp.asarray(held_to, jnp.int32)
    weigh = select if mutate == "bias_in_weights" else scores
    top_p = jnp.take_along_axis(weigh, top_i, axis=-1)
    if net.get("norm_topk_prob", True):
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if mutate != "no_scaling_factor":
        top_p = top_p * net.get("routed_scaling_factor", 1)
    moe = jnp.zeros_like(x)
    first = net.get("first_expert_held", 0)
    for e in range(lp["w_up"].shape[0]):  # the experts held here
        weight = jnp.sum(jnp.where(top_i == first + e, top_p, 0.0), axis=-1)
        moe = moe + weight[..., None] * _relu2_mlp(
            m, lp["w_up"][e], lp["w_down"][e], r, mutate)
    if net.get("n_shared_experts", 1):  # counted once, on every chip
        moe = moe + _relu2_mlp(m, lp["shared_up"], lp["shared_down"], r,
                               mutate)
    return r(x + r(moe)), own, select


def forward(variables: dict, tokens, net: dict, round_to=None, mutate=None,
            experts=None, starts=None) -> dict:
    """The model on int tokens [B, S], each sequence from position 0.

    `variables` is the system's own tree: `params` (`embed`, `layer_<i>`,
    `final_norm`, `head`, `value_w`, `value_b`) and `constants` (the
    routers' selection biases), cast to float32. `net` is the
    configuration's `network` block: the published keys (the leading
    `num_hidden_layers` letters of `hybrid_override_pattern` name the
    layers), and `experts_held` / `first_expert_held`, the share of the
    experts that the weights given are. `round_to` rounds the blocks'
    activations to that dtype ("float8_e4m3": emulated in float32; or a jnp
    dtype) where the system rounds to bfloat16 (never a time step, a decay
    or a matrix state, which the system keeps in float32); `mutate` (one of
    `MUTATIONS`) makes the named error: both exist to show that the limits
    refuse them. `experts` [expert layers, B, S, k], where given, are the
    experts every token is sent to; a layer's own choice is still returned,
    made from its own scores there. `starts` [B, S], where given, is 1
    where a new episode starts inside the sequence.

    Returns logits [B, S, V], values [B, S], experts [L, B, S, k] (each
    expert layer's own choice), select [L, B, S, E] (its selection scores
    s + b), ssm_states [Mamba-2 layers, B, heads, P, N] (each after the
    last position)."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                     variables["params"])
    biases = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                          variables["constants"])
    r = _rounder(round_to)
    eps = net["layer_norm_epsilon"]
    pattern = net["hybrid_override_pattern"][:net["num_hidden_layers"]]
    tokens = jnp.asarray(tokens, jnp.int32)
    episode, positions = _episodes(starts, tokens.shape)
    chosen, selects, states = [], [], []

    def layer(lp, bias, x, held_to, letter):
        norm = lp["mlp_norm"] if letter == "E" else lp["attn_norm"]
        n = r(_rms_norm(x, norm, eps))
        if letter == "M":
            return _mamba2(lp, x, n, positions, net, r, mutate)
        if letter == "*":
            return _grouped_attention(lp, x, n, episode, positions, net, r,
                                      mutate), None
        return _moe(lp, bias, x, n, net, r, mutate, held_to)

    with jax.default_matmul_precision("highest"):
        x = r(p["embed"][tokens])
        for i, letter in enumerate(pattern):
            name = f"layer_{i}"
            routed = letter == "E"
            held_to = experts[len(chosen)] if routed and experts is not None \
                else None
            bias = biases[name]["router_bias"] if routed else None
            x, *rest = jax.checkpoint(
                lambda lp, bias, x, held_to, letter=letter: layer(
                    lp, bias, x, held_to, letter))(p[name], bias, x, held_to)
            if routed:
                chosen.append(rest[0])
                selects.append(rest[1])
            elif letter == "M":
                states.append(rest[0])
        y = _rms_norm(x, p["final_norm"], eps)
        logits = y @ p["head"]
        values = y @ p["value_w"] + p["value_b"]
    return {"logits": logits, "values": values,
            "experts": jnp.stack(chosen), "select": jnp.stack(selects),
            "ssm_states": jnp.stack(states)}


def vtrace_loss(variables: dict, batch: dict, net: dict, cfg: dict,
                mutate=None):
    """IMPALA's loss of one minibatch of whole sequences, as
    `ray_tpu/rllib/agents/impala/vtrace_policy.py` describes it: sums over
    the minibatch of -logp * pg_advantage, 0.5 * (v - vs)^2 and the
    entropy. `batch`: tokens, actions [B, S] int, rewards, behaviour_logp
    [B, S], and every sequence ends its episode at its last step (so no
    bootstrap value is needed); with `experts` [expert layers, B, S, k] in
    it, the experts every token is sent to (`forward`'s). `mutate` is
    `forward`'s. Returns (total, parts)."""
    gamma, lam = cfg["gamma"], cfg.get("lambda", 1.0)
    out = forward(variables, batch["tokens"], net, mutate=mutate,
                  experts=batch.get("experts"))
    logits, values = out["logits"], out["values"]
    actions = jnp.asarray(batch["actions"], jnp.int32)
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    target_logp = jnp.take_along_axis(
        logp_all, actions[..., None], axis=-1)[..., 0]
    rhos = jnp.exp(target_logp - jnp.asarray(batch["behaviour_logp"]))
    discounts = jnp.full(actions.shape, gamma).at[:, -1].set(0.0)
    rewards = jnp.asarray(batch["rewards"], jnp.float32)
    clipped = jnp.minimum(cfg["vtrace_clip_rho_threshold"], rhos)
    cs = lam * jnp.minimum(1.0, rhos)
    next_values = jnp.concatenate(
        [values[:, 1:], jnp.zeros_like(values[:, :1])], axis=1)
    deltas = clipped * (rewards + discounts * next_values - values)

    def backward(acc, step):
        # vs_t - v_t = delta_t + discount_t c_t (vs_{t+1} - v_{t+1})
        delta, discount, c = step
        acc = delta + discount * c * acc
        return acc, acc
    _, vs_minus_v = jax.lax.scan(
        backward, jnp.zeros_like(values[:, 0]),
        (deltas.T, discounts.T, cs.T), reverse=True)
    vs = vs_minus_v.T + values
    next_vs = jnp.concatenate(
        [vs[:, 1:], jnp.zeros_like(vs[:, :1])], axis=1)
    pg_adv = jnp.minimum(cfg["vtrace_clip_pg_rho_threshold"], rhos) * (
        rewards + discounts * next_vs - values)
    vs, pg_adv = jax.lax.stop_gradient(vs), jax.lax.stop_gradient(pg_adv)
    pi_loss = -jnp.sum(target_logp * pg_adv)
    vf_loss = 0.5 * jnp.sum((values - vs) ** 2)
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all)
    total = (pi_loss + cfg["vf_loss_coeff"] * vf_loss
             - cfg["entropy_coeff"] * entropy)
    return total, {"policy_loss": pi_loss, "vf_loss": vf_loss,
                   "entropy": entropy}


def compare_update(loss, want_loss, errors: dict) -> dict:
    """One update of the learner against the reference's: the loss's
    relative error, and the worst (and named) of the parameters'
    `change_error`s, `errors` {name: error}; judged by this file's limits."""
    found = _shared.compare_update(loss, want_loss, errors)
    found["ok"] = bool(found["loss_error"] <= UPDATE_LOSS_TOLERANCE
                       and found["update_error"] <= UPDATE_TOLERANCE)
    return found


def compare(system_out, reference_out, scales=None) -> dict:
    """Per-output relative errors of (logits, values) and the verdict.
    `scales` are the outputs' scales where `reference_out` is a part of
    what was compared (an output's scale is that of all of it)."""
    scales = scales or output_scales(reference_out)
    errs = {name: relative_error(got, want, scale=scale)
            for name, got, want, scale in zip(
                ("logits", "value"), system_out, reference_out, scales)}
    return {"errors": errs, "tolerance": TOLERANCE,
            "ok": bool(max(errs.values()) <= TOLERANCE)}


def routing_verdict(system_experts, own_experts, select) -> dict:
    """The system's choice [L, B, S, k] against the reference's own choice
    in each expert layer, the reference held to the system's choice in the
    layers before it (`forward(experts=system_experts)` gives `own_experts`
    and `select` so). A flip's gap is how far below the reference's k-th
    selection score the reference puts the least likely expert the system
    chose, as a share of that k-th score: 0 is an exact tie. Judged by this
    file's limits."""
    found = _shared.routing_verdict(system_experts, own_experts, select)
    found["ok"] = bool(found["router_flips"] <= MAX_ROUTER_FLIPS
                       and found["max_flip_gap"] <= MAX_FLIP_GAP)
    return found
