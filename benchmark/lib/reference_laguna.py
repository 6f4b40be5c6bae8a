"""Plain reference of the `laguna` token policy: forward, V-trace's loss, and
the comparison that decides `correct` in its cells.

Straightforward `jax.numpy`, float32 throughout, matrix precision "highest",
no flax, no cache, no ring, no kernel, no sort, no grouped or batched expert
product, nothing from `ray_tpu`. The equations are those of the source named
in `configs/impala_laguna_xs2_33b_a3b.json` (`model_type: laguna`,
Laguna-XS.2's published `config.json`; what it leaves unsaid is listed under
`assumed` there). For x [S, H] and layer l, of kind `layer_types[l]`
("full_attention" | "sliding_attention") with H_l =
`num_attention_heads_per_layer[l]` query heads over G key/value heads of d:

    n   = RMSNorm_in(x)
    q   = n W_q -> H_l x d;  k = n W_k, v = n W_v -> G x d   (no bias, no
          QK-norm)
    q, k = R_kind(q), R_kind(k): rotate-half RoPE over the leading
          `partial_rotary_factor` of a head (the rest as it is), at the
          kind's `rope_parameters`:
            default:  inv_freq_i = theta^(-2i/D), D the rotated values
            yarn:     inv_freq_i = (1 - m_i) / (F theta^(2i/D))
                                   + m_i / theta^(2i/D),
                      m_i = 1 - clip((i - low) / (high - low), 0, 1),
                      low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
                      c(r) = D ln(P / (2 pi r)) / (2 ln theta), P the
                      original positions; cos and sin times
                      `attention_factor` (so the rotated values alone)
    o_h = softmax_s(q_h . k_{h // (H_l / G), s} / sqrt(d)) v_{..., s}
          over s <= t (full), and t - s < `sliding_window` (sliding: the
          token itself and the window - 1 before it)
    o_h = sigmoid(n . W_g[:, h]) o_h          one gate a head
    h   = x + [o_1 .. o_{H_l}] W_o
    m   = RMSNorm_post(h)
    `mlp_layer_types[l]` "dense":  y = h + W_down (silu(W_gate m) * W_up m)
    "sparse":  s = sigmoid(m W_r) [E];  S_t = the k largest of s;
          w_e = `moe_routed_scaling_factor` s_e / sum_{e' in S_t} s_e'
          y = h + sum_{e in S_t, e HELD HERE} w_e SwiGLU_e(m)
                + SwiGLU_shared(m)
          (a loop over the held experts, each on every token times its
          0/1-masked weight; what the absent experts would add is left out;
          the shared expert on every token, no gate of its own)
    after the last layer: RMSNorm, the untied head, a linear value head

The attention is a mask on the full score matrix, computed a block of
queries at a time so that 8,192 positions fit a chip
(`reference_smallthinker._attention`: a block's scores are [heads, block,
S]); under a gradient each block and each layer is recomputed
(`jax.checkpoint`), which changes no number.

Departures from the source: a value head (an RL policy needs one); no
auxiliary router loss.

Tolerance. The system keeps parameters, router, final norm and heads in
float32 and the blocks' activations in bfloat16 (8 bits of mantissa, ~0.4 %
a rounding); on the TPU its float32 products run as bf16 passes at default
precision. So it cannot agree with this reference to float32 accuracy.
Measured and bounded, apart, as in the other token cells: the router's
choice a layer at a time, this forward held to the experts the system chose
(`MAX_ROUTER_FLIPS`, `MAX_FLIP_GAP`); logits and values against this
reference held to the system's experts, each as the largest absolute
difference over the largest absolute reference value (`TOLERANCE`); one
update of the learner by the trainer's own step (`compare_update`:
`UPDATE_LOSS_TOLERANCE`, `UPDATE_TOLERANCE`). Each limit of the forward lies
between two readings at published widths on the v5e (PERF.md section 4): the
system's largest over its seeds, and this reference with its blocks rounded
to float8_e4m3 (`round_to`, the nearest precision below the stated
bfloat16) in the system's place, which has to be refused. The readings stand
beside the constants below.
"""

import jax
import jax.numpy as jnp
import numpy as np

# The arithmetic the references share (float32 RMSNorm, rotate-half, the
# float8_e4m3 rounding emulated in float32, the errors' measure; SwiGLU;
# Adam's change, the global clip, a parameter's change against its float32
# storage; the verdicts' arithmetic, judged here by this file's limits; the
# masked attention a block of queries at a time): one copy.
from lib import reference_glm4_moe_lite as _shared
from lib.reference_glm4_moe_lite import (  # noqa: F401
    _swiglu, adam_change, adam_update, change_error, clip_scale)
from lib.reference_olmoe import (  # noqa: F401
    _rms_norm, _rotate_half, _rounder, output_scales, relative_error)
from lib.reference_smallthinker import _attention as _masked_attention

# Each limit beside the readings that set it (my chip runs, PR 56; two
# sequences of 8,192 positions each through the causal pass, a sequence a
# pass, and through the decode as rows of the 32-row batch; "float8": this
# reference with its blocks rounded to float8_e4m3 in the system's place).
# The limits were set from the first run (seed 2147483659) and judged seven
# more on seven seeds, all `correct`; the ranges are those of the eight
# (PERF.md section 4). Each forward limit near the geometric mean of its two
# readings.
# Logits and values, five bf16 blocks and 8,192 positions deep. The system:
# logits 2.76-3.37 % (first run 2.94 causal / 3.04 decode), values
# 1.97-3.20 %; float8: 79.7-94.9 %, 61.1-86.5 %.
TOLERANCE = 0.12
# (Token, layer) pairs whose eight of 256 differ. The system: 16.2-16.7 %,
# rising with depth (12.0, 15.3, 18.0, 20.4 % by expert layer); float8:
# 99.4-99.5 %.
MAX_ROUTER_FLIPS = 0.4
# The largest gap of a flip over a pass's 32,768 pairs, a share of the k-th
# SCORE: a sigmoid near 1/2, not a probability near 1/256, so a logit's error
# of 0.05 is a gap of ~2.5 % where a softmax router's is 5 %. The system:
# 1.49-2.00 %; float8: 76.5-89.4 %.
MAX_FLIP_GAP = 0.12
# One update. Precision hardly moves the loss (a sum over 8,192 tokens): the
# accepted cells' limit, which leaves the first reading (0.127 %) seven times
# of room; eight runs 0.014-0.237 %.
UPDATE_LOSS_TOLERANCE = 0.009
# The worst parameter's change, where 1 is what a state left unchanged
# reads: 6.8 % first, then 5.5-6.7 % (layer 4's router every time; the
# routers and the last layers' experts lead), the reference held to the
# system's experts as the forward's comparison is. Between the first reading
# and 1, the more room above the reading (3.7 times; four below 1).
UPDATE_TOLERANCE = 0.25

MUTATIONS = (
    "default_rope_on_the_full_layer", "rope_whole_head_on_the_full_layer",
    "yarn_without_attention_factor", "yarn_ramp_reversed",
    "sliding_theta_on_the_full_layer", "window_one_too_long",
    "window_one_short", "window_on_the_full_layer",
    "key_head_h_mod_groups", "no_attention_gate", "attention_gate_silu",
    "softmax_router", "no_renormalisation", "no_routed_scaling",
    "no_shared_expert", "router_weight_on_input")


def rope_frequencies(rope: dict, head_dim: int, mutate=None):
    """(inv_freq [D / 2], the factor on cos and sin, D) of one kind's
    `rope_parameters`: D = `partial_rotary_factor` x head_dim values are
    rotated."""
    share = rope.get("partial_rotary_factor", 1.0)
    if mutate == "rope_whole_head_on_the_full_layer":
        share = 1.0
    D = int(head_dim * share)
    theta = float(rope["rope_theta"])
    i = np.arange(D // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / D)
    if rope.get("rope_type", "default") == "default" or mutate == \
            "default_rope_on_the_full_layer":
        return jnp.asarray(plain, jnp.float32), 1.0, D
    F = float(rope["factor"])
    P = float(rope["original_max_position_embeddings"])

    def c(turns):
        return D * np.log(P / (2 * np.pi * turns)) / (2 * np.log(theta))
    low = max(np.floor(c(rope["beta_fast"])), 0)
    high = min(np.ceil(c(rope["beta_slow"])), D - 1)
    m = 1.0 - np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    if mutate == "yarn_ramp_reversed":
        m = 1.0 - m
    inv_freq = (1.0 - m) * plain / F + m * plain
    factor = rope.get("attention_factor") or 0.1 * np.log(F) + 1.0
    if mutate == "yarn_without_attention_factor":
        factor = 1.0
    return jnp.asarray(inv_freq, jnp.float32), float(factor), D


def _rotate(x, rope: dict, mutate=None):
    """x [B, S, heads, d] at positions 0..S-1, by one kind's rotation."""
    inv_freq, factor, D = rope_frequencies(rope, x.shape[-1], mutate)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    turned = x[..., :D]
    turned = (turned * (jnp.cos(angles) * factor)
              + _rotate_half(turned) * (jnp.sin(angles) * factor))
    return jnp.concatenate([turned, x[..., D:]], axis=-1)


def _attention(q, k, v, window, r):
    """q [B, S, groups, per, d] against k, v [B, S, groups, d], a cached
    head's `per` query heads at a time (`reference_smallthinker._attention`
    of that group: the masked softmax over the full [S, S] scores, a block
    of queries at a time): [B, S, groups, per, d]. One group's scores are
    held at a time, and recomputed under a gradient, so that 64 heads of
    8,192 positions leave the check no more of the chip than the cell's own
    program takes."""
    per = q.shape[3]

    def of_group(group):
        q_g, k_g, v_g = group
        return _masked_attention(
            q_g, jnp.repeat(k_g[:, :, None], per, axis=2),
            jnp.repeat(v_g[:, :, None], per, axis=2), window, r)
    out = jax.lax.map(jax.checkpoint(of_group), (
        jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2)


def _moe(lp, h, m, net, r, mutate, held_to):
    """(h + experts + shared, the layer's own choice [B, S, k], its scores
    [B, S, E])."""
    k = net["num_experts_per_tok"]
    logits = m @ lp["router"]
    scores = jax.nn.softmax(logits, axis=-1) if mutate == "softmax_router" \
        else jax.nn.sigmoid(logits)
    _, own = jax.lax.top_k(scores, k)
    top_i = own if held_to is None else jnp.asarray(held_to, jnp.int32)
    top_p = jnp.take_along_axis(scores, top_i, axis=-1)
    if mutate != "no_renormalisation":
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if mutate != "no_routed_scaling":
        top_p = top_p * net["moe_routed_scaling_factor"]
    first = net.get("first_expert_held", 0)
    held = lp["w_gate"].shape[0]
    # weight[e, b, s] = w_e where the held expert first + e was chosen.
    weight = jnp.stack([
        jnp.sum(jnp.where(top_i == first + e, top_p, 0.0), axis=-1)
        for e in range(held)])

    @jax.checkpoint
    def of_expert(expert):
        # One held expert on every token, times its 0/1-masked weight
        # (recomputed under a gradient: its products are kept for no
        # other expert's sake).
        w_gate, w_up, w_down, w = expert
        if mutate == "router_weight_on_input":
            return _swiglu(w[..., None] * m, w_gate, w_up, w_down, r)
        return w[..., None] * _swiglu(m, w_gate, w_up, w_down, r)

    def add_expert(moe, expert):
        return moe + of_expert(expert), None
    moe, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h),
        (lp["w_gate"], lp["w_up"], lp["w_down"], weight))
    if mutate != "no_shared_expert":
        moe = moe + _swiglu(m, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"], r)
    return r(h + r(moe)), own, scores


def _layer(lp, x, net, i, r, mutate, held_to):
    """One block; (out, this layer's own choice [B, S, k] and its router's
    scores [B, S, E], or None twice of a dense layer)."""
    full = net["layer_types"][i] == "full_attention"
    heads = net["num_attention_heads_per_layer"][i]
    groups, eps = net["num_key_value_heads"], net["rms_norm_eps"]
    ropes = net["rope_parameters"]
    rope = ropes["full_attention" if full else "sliding_attention"]
    window = 0 if full else net["sliding_window"]
    if mutate == "sliding_theta_on_the_full_layer" and full:
        rope = dict(rope, rope_theta=ropes["sliding_attention"]["rope_theta"])
    if mutate == "window_on_the_full_layer" and full:
        window = net["sliding_window"]
    if mutate == "window_one_too_long" and not full:
        window += 1
    if mutate == "window_one_short" and not full:
        window -= 1
    B, S, _ = x.shape

    n = r(_rms_norm(x, lp["attn_norm"], eps))
    q = r(n @ lp["wq"]).reshape(B, S, heads, -1)
    kk = r(n @ lp["wk"]).reshape(B, S, groups, -1)
    v = r(n @ lp["wv"]).reshape(B, S, groups, -1)
    kind_mutation = mutate if full else None
    q, kk = r(_rotate(q, rope, kind_mutation)), r(_rotate(kk, rope,
                                                         kind_mutation))
    # Query head h reads key/value head h // (heads / groups): the heads
    # of a cached head lie together.
    per = heads // groups
    if mutate == "key_head_h_mod_groups":
        q = q.reshape(B, S, per, groups, -1).swapaxes(2, 3)
        o = _attention(q, kk, v, window, r).swapaxes(2, 3)
    else:
        o = _attention(q.reshape(B, S, groups, per, -1), kk, v, window, r)
    o = o.reshape(B, S, heads, -1)
    if mutate != "no_attention_gate":
        gate = n @ lp["wg"]  # [B, S, heads]
        gate = jax.nn.silu(gate) if mutate == "attention_gate_silu" \
            else jax.nn.sigmoid(gate)
        o = r(o * gate[..., None])
    h = r(x + r(o.reshape(B, S, -1) @ lp["wo"]))

    m = r(_rms_norm(h, lp["mlp_norm"], eps))
    if net["mlp_layer_types"][i] == "dense":
        return r(h + _swiglu(m, lp["dense_gate"], lp["dense_up"],
                             lp["dense_down"], r)), None, None
    return _moe(lp, h, m, net, r, mutate, held_to)


def expert_layers(net: dict) -> list:
    """The layers that route, in order: `experts`' leading axis."""
    return [i for i in range(net["num_hidden_layers"])
            if net["mlp_layer_types"][i] != "dense"]


def forward(variables: dict, tokens, net: dict, round_to=None, mutate=None,
            experts=None, hidden=False) -> dict:
    """The model on int tokens [B, S], each sequence from position 0.

    `variables` is the system's own tree, {"params": ...} (`embed`,
    `layer_<i>`, `final_norm`, `head`, `value_w`, `value_b`), cast to
    float32. `net` is the configuration's `network` block: the published
    keys, and `experts_held` / `first_expert_held`, the share of the
    experts that the weights given are. `round_to` rounds the blocks'
    activations to that dtype ("float8_e4m3": emulated in float32; or a
    jnp dtype) where the system rounds to bfloat16; `mutate` (one of
    `MUTATIONS`) makes the named error: both exist to show that the limits
    refuse them. `experts` [expert layers, B, S, k], where given, are the
    experts every token is sent to; a layer's own choice is still returned,
    made from its own scores there.

    Returns logits [B, S, V], values [B, S], experts [L, B, S, k] (each
    expert layer's own choice), select [L, B, S, E] (its router's
    scores); with `hidden`, the final normalised vectors [B, S, H] in the
    logits' place (`vtrace_loss` multiplies them by the head a block of
    positions at a time)."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                     variables["params"])
    r = _rounder(round_to)
    tokens = jnp.asarray(tokens, jnp.int32)
    chosen, selects = [], []
    routed = expert_layers(net)
    with jax.default_matmul_precision("highest"):
        x = r(p["embed"][tokens])
        for i in range(net["num_hidden_layers"]):
            held_to = None
            if experts is not None and i in routed:
                held_to = experts[routed.index(i)]
            x, own, scores = jax.checkpoint(
                lambda lp, x, held_to, i=i: _layer(
                    lp, x, net, i, r, mutate, held_to))(
                        p[f"layer_{i}"], x, held_to)
            if own is not None:
                chosen.append(own)
                selects.append(scores)
        y = _rms_norm(x, p["final_norm"], net["rms_norm_eps"])
        logits = y if hidden else y @ p["head"]
        values = y @ p["value_w"] + p["value_b"]
    return {"logits": logits, "values": values,
            "experts": jnp.stack(chosen), "select": jnp.stack(selects)}


# Positions a block of the loss's logits.
LOSS_BLOCK = 512


def _policy_terms(y, head, actions):
    """(log pi(a_t | s_t) [B, S], the sum over every position of the
    policy's entropy) from the final normalised vectors y [B, S, H]:
    log_softmax(y W_head), `LOSS_BLOCK` positions at a time and recomputed
    under a gradient, so that the [S, V] logits, their softmax and their
    cotangents are held a block at a time (with the whole of them the
    check's gradient took more of the chip than the cell's own program)."""
    B, S, H = y.shape
    block = min(LOSS_BLOCK, S)
    assert S % block == 0, (S, block)

    def by_block(a):
        return jnp.moveaxis(a.reshape((B, S // block, block) + a.shape[2:]),
                            1, 0)

    def of_block(part):
        y_b, a_b = part
        logp_all = jax.nn.log_softmax(y_b @ head, axis=-1)
        taken = jnp.take_along_axis(logp_all, a_b[..., None], axis=-1)
        return taken[..., 0], -jnp.sum(jnp.exp(logp_all) * logp_all)
    with jax.default_matmul_precision("highest"):
        logp, entropy = jax.lax.map(
            jax.checkpoint(of_block), (by_block(y), by_block(actions)))
    return jnp.moveaxis(logp, 0, 1).reshape(B, S), jnp.sum(entropy)


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
                  experts=batch.get("experts"), hidden=True)
    values = out["values"]
    actions = jnp.asarray(batch["actions"], jnp.int32)
    target_logp, entropy = _policy_terms(
        out["logits"], jnp.asarray(variables["params"]["head"], jnp.float32),
        actions)
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
    score the reference puts the lowest-scored expert the system chose, as
    a share of that k-th score: 0 is an exact tie. Judged by this file's
    limits."""
    found = _shared.routing_verdict(system_experts, own_experts, select)
    found["ok"] = bool(found["router_flips"] <= MAX_ROUTER_FLIPS
                       and found["max_flip_gap"] <= MAX_FLIP_GAP)
    return found
