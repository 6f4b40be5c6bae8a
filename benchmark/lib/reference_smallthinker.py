"""Plain reference of the `smallthinker` token policy: forward, V-trace's
loss, and the comparison that decides `correct` in its cells.

Straightforward `jax.numpy`, float32 throughout, matrix precision "highest",
no flax, no cache, no ring, no kernel, no sort, no grouped or batched expert
product, nothing from `ray_tpu`. The equations are those of the source named
in `configs/impala_smallthinker_21b_a3b.json` (`model_type: smallthinker`;
the family's paper, arXiv:2507.20984). For x [S, H] and layer l:

    n   = RMSNorm_in(x)
    r   = n W_r                       the router reads the ATTENTION's input
    S_t = the k largest of r;  w_e = softmax over r_e, e in S_t
          (= the k largest of softmax(r), over their sum: `norm_topk_prob`)
    q   = n W_q -> heads x d;  k = n W_k, v = n W_v -> groups x d
          (no bias, no QK-norm)
    rope_layout[l]:  q, k = RoPE(q), RoPE(k) (rotate-half);  else nothing:
          a layer without positions
    o_h = softmax_s(q_h . k_{h // (heads / groups), s} / sqrt(d)) v_{..., s}
          over s <= t, and t - s < window where sliding_window_layout[l]
          (the window holds the token itself and the window - 1 before it)
    h   = x + [o_1 .. o_heads] W_o
    m   = RMSNorm_post(h)
    y   = h + sum_{e in S_t, e HELD HERE} w_e W_down,e (relu(W_gate,e m)
          * W_up,e m)      (a loop over the held experts, each on every
          token times its 0/1-masked weight; what the absent experts would
          add is left out)
    after the last layer: RMSNorm, the untied head, a linear value head

The attention is a mask on the full score matrix, computed a block of
`QUERY_BLOCK` queries at a time so that 8,192 positions fit a chip (a block's
scores are [heads, block, S]); under a gradient each block and each layer is
recomputed (`jax.checkpoint`), which changes no number.

Departures from the source: a value head (an RL policy needs one); no
auxiliary router loss; only the primary experts the config has keys for.

Tolerance. The system keeps parameters, router, final norm and heads in
float32 and the blocks' activations in bfloat16 (8 bits of mantissa, ~0.4 %
a rounding); on the TPU its float32 products run as bf16 passes at default
precision. So it cannot agree with this reference to float32 accuracy.
Measured and bounded, apart, as in the other token cells:

* the router's choice, A LAYER AT A TIME: this forward is held to the
  experts the system chose (`experts=`), and in each layer its own choice,
  from its own probabilities there, is compared with the system's
  (`router_flips`, the share of (token, layer) pairs whose sets differ;
  `max_flip_gap`, the largest distance between this reference's k-th
  probability and the one it gives the least likely expert the system
  chose, as a share of the k-th): `MAX_ROUTER_FLIPS`, `MAX_FLIP_GAP`.
* the arithmetic: logits and values against this reference held to the
  system's experts, each as the largest absolute difference over the
  largest absolute reference value: `TOLERANCE`.
* one update of the learner, by the trainer's own step (`compare_update`):
  the minibatch's loss as the step reports it against `vtrace_loss` here,
  and the change of every parameter (`change_error`) against `adam_change`
  of this reference's gradients from the optimizer state the step began
  with: `UPDATE_LOSS_TOLERANCE`, `UPDATE_TOLERANCE`.

Each limit of the forward lies between two readings at published widths on
the v5e (PERF.md section 4; my chip runs, PR 34): the system's largest over
its seeds, and this reference with its blocks rounded to float8_e4m3
(`round_to`, the nearest precision below the stated bfloat16) in the
system's place, which has to be refused. The readings stand beside the
constants below.
"""

import jax
import jax.numpy as jnp
import numpy as np

# The arithmetic the references share (float32 RMSNorm, rotate-half RoPE
# over positions 0..S-1, the float8_e4m3 rounding emulated in float32, the
# errors' measure; Adam's change, the global clip, a parameter's change
# against its float32 storage): one copy.
from lib.reference_glm4_moe_lite import (  # noqa: F401
    adam_change, change_error, clip_scale)
from lib.reference_olmoe import (  # noqa: F401
    _rms_norm, _rope, _rounder, output_scales, relative_error)

# Each limit beside the readings that set it (my chip runs, PR 34: ten runs on
# seven seeds, two sequences of 8,192 positions each through the causal pass
# and through the decode; "float8": this reference with its blocks rounded to
# float8_e4m3 in the system's place).
# Logits and values, four bf16 blocks and 8,192 positions deep. The system:
# logits 0.84-1.08 %, values 0.70-1.43 %; float8: 122-137 %, 158-264 %.
TOLERANCE = 0.06
# (Token, layer) pairs whose six of 64 differ. The system: 3.7-4.3 % (layer
# 0, whose router reads embeddings alone, 1.1-1.6 %); float8: 76.5-77.6 %.
MAX_ROUTER_FLIPS = 0.15
# The largest gap of a flip over a pass's 65,536 pairs. The system: 3.4-6.8 %
# (the second token cell's 5 %, over 16,384 pairs, stood below the third
# reading here); float8: 98.9-99.6 %.
MAX_FLIP_GAP = 0.25
# One update. Precision hardly moves either (the loss is a sum over 8,192
# tokens; a new gradient is a tenth of Adam's first moment), so no float8
# reading stands beside them. The loss: the accepted cells' limit, which
# leaves the first reading (0.17 %; all: 9e-5 to 0.17 %) three times of
# room. The worst parameter's change (a router's, every run): 2.6-4.0 %,
# where 1 is what a state left unchanged reads.
UPDATE_LOSS_TOLERANCE = 0.009
UPDATE_TOLERANCE = 0.25

# Queries a block of the attention's score matrix.
QUERY_BLOCK = 512

MUTATIONS = (
    "rope_on_the_full_layer", "no_rope_on_a_window_layer",
    "window_one_too_long", "window_one_short",
    "key_head_h_mod_groups", "router_reads_post_attention_norm",
    "silu_for_relu", "no_renormalisation")


def _attention(q, k, v, window, r):
    """q [B, S, heads, d], k, v [B, S, groups, d] -> [B, S, heads, d]: the
    masked softmax over the full [S, S] scores, `QUERY_BLOCK` queries at a
    time. `window` 0: every s <= t."""
    B, S, heads, d = q.shape
    block = min(QUERY_BLOCK, S)
    assert S % block == 0, (S, block)
    keys = jnp.arange(S)

    def rows(start):
        t = start + jnp.arange(block)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk",
            jax.lax.dynamic_slice_in_dim(q, start, block, axis=1),
            k) / np.sqrt(d)
        allowed = keys[None, :] <= t[:, None]
        if window:
            allowed = allowed & (t[:, None] - keys[None, :] < window)
        scores = jnp.where(allowed[None, None], scores, -jnp.inf)
        attn = r(jax.nn.softmax(scores, axis=-1))
        return r(jnp.einsum("bhqk,bkhd->bqhd", attn, v))
    out = jax.lax.map(jax.checkpoint(rows), jnp.arange(0, S, block))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, heads, d)


def _layer(lp, x, net, i, r, mutate, held_to):
    """One block; (out, this layer's own choice [B, S, k], its router's
    probabilities [B, S, E]). `held_to` [B, S, k]: the experts every token
    is sent to instead, with the weights computed here for them."""
    heads, groups = net["num_attention_heads"], net["num_key_value_heads"]
    eps, k = net["rms_norm_eps"], net["moe_num_active_primary_experts"]
    theta = net["rope_theta"]
    windowed = bool(net["sliding_window_layout"][i])
    rotary = bool(net["rope_layout"][i])
    window = net["sliding_window_size"] if windowed else 0
    if mutate == "rope_on_the_full_layer" and not windowed:
        rotary = True
    if mutate == "no_rope_on_a_window_layer" and windowed:
        rotary = False
    if mutate == "window_one_too_long" and windowed:
        window += 1
    if mutate == "window_one_short" and windowed:
        # What a ring whose slot is overwritten a step early attends to.
        window -= 1
    B, S, _ = x.shape

    n = r(_rms_norm(x, lp["attn_norm"], eps))
    q = r(n @ lp["wq"]).reshape(B, S, heads, -1)
    kk = r(n @ lp["wk"]).reshape(B, S, groups, -1)
    v = r(n @ lp["wv"]).reshape(B, S, groups, -1)
    if rotary:
        q, kk = r(_rope(q, theta)), r(_rope(kk, theta))
    # Query head h reads key/value head h // (heads / groups).
    per = heads // groups
    if mutate == "key_head_h_mod_groups":
        of_head = jnp.arange(heads) % groups
    else:
        of_head = jnp.arange(heads) // per
    o = _attention(q, kk[:, :, of_head], v[:, :, of_head], window, r)
    h = r(x + r(o.reshape(B, S, -1) @ lp["wo"]))

    m = r(_rms_norm(h, lp["mlp_norm"], eps))
    routed_on = m if mutate == "router_reads_post_attention_norm" else n
    probs = jax.nn.softmax(routed_on @ lp["router"], axis=-1)
    _, own = jax.lax.top_k(probs, k)
    top_i = own if held_to is None else jnp.asarray(held_to, jnp.int32)
    top_p = jnp.take_along_axis(probs, top_i, axis=-1)
    if net.get("norm_topk_prob", True) and mutate != "no_renormalisation":
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    act = jax.nn.silu if mutate == "silu_for_relu" else jax.nn.relu
    first = net.get("first_expert_held", 0)
    held = lp["w_gate"].shape[0]
    # weight[e, b, s] = w_e where the held expert first + e was chosen.
    weight = jnp.stack([
        jnp.sum(jnp.where(top_i == first + e, top_p, 0.0), axis=-1)
        for e in range(held)])

    def add_expert(moe, expert):
        # One held expert on every token, times its 0/1-masked weight.
        w_gate, w_up, w_down, w = expert
        a = r(act(r(m @ w_gate)) * r(m @ w_up))
        return moe + w[..., None] * r(a @ w_down), None
    moe, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h),
        (lp["w_gate"], lp["w_up"], lp["w_down"], weight))
    return r(h + r(moe)), own, probs


def forward(variables: dict, tokens, net: dict, round_to=None, mutate=None,
            experts=None) -> dict:
    """The model on int tokens [B, S], each sequence from position 0.

    `variables` is the system's own tree, {"params": ...} (`embed`,
    `layer_<i>`, `final_norm`, `head`, `value_w`, `value_b`), cast to
    float32. `net` is the configuration's `network` block: the published
    keys, and `experts_held` / `first_expert_held`, the share of the
    experts that the weights given are. `round_to` rounds the blocks'
    activations to that dtype ("float8_e4m3": emulated in float32; or a
    jnp dtype) where the system rounds to bfloat16; `mutate` (one of
    `MUTATIONS`) makes the named error: both exist to show that the limits
    refuse them. `experts` [layers, B, S, k], where given, are the experts
    every token is sent to; a layer's own choice is still returned, made
    from its own probabilities there.

    Returns logits [B, S, V], values [B, S], experts [L, B, S, k] (each
    layer's own choice), select [L, B, S, E] (its router's
    probabilities)."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                     variables["params"])
    r = _rounder(round_to)
    tokens = jnp.asarray(tokens, jnp.int32)
    chosen, selects = [], []
    with jax.default_matmul_precision("highest"):
        x = r(p["embed"][tokens])
        for i in range(net["num_hidden_layers"]):
            held_to = None if experts is None else experts[i]
            x, own, probs = jax.checkpoint(
                lambda lp, x, held_to, i=i: _layer(
                    lp, x, net, i, r, mutate, held_to))(
                        p[f"layer_{i}"], x, held_to)
            chosen.append(own)
            selects.append(probs)
        y = _rms_norm(x, p["final_norm"], net["rms_norm_eps"])
        logits = y @ p["head"]
        values = y @ p["value_w"] + p["value_b"]
    return {"logits": logits, "values": values,
            "experts": jnp.stack(chosen), "select": jnp.stack(selects)}


def vtrace_loss(variables: dict, batch: dict, net: dict, cfg: dict,
                mutate=None):
    """IMPALA's loss of one minibatch of whole sequences, as
    `ray_tpu/rllib/agents/impala/vtrace_policy.py` describes it: sums over
    the minibatch of -logp * pg_advantage, 0.5 * (v - vs)^2 and the
    entropy. `batch`: tokens, actions [B, S] int, rewards, behaviour_logp
    [B, S], and every sequence ends its episode at its last step (so no
    bootstrap value is needed). `mutate` is `forward`'s. Returns (total,
    parts)."""
    gamma, lam = cfg["gamma"], cfg.get("lambda", 1.0)
    out = forward(variables, batch["tokens"], net, mutate=mutate)
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
    `change_error`s, `errors` {name: error}."""
    loss, want_loss = float(loss), float(want_loss)
    loss_error = abs(loss - want_loss) / abs(want_loss)
    worst = max(errors, key=errors.get)
    return {"loss": loss, "reference_loss": want_loss,
            "loss_error": loss_error, "update_error": float(errors[worst]),
            "worst_parameter": worst,
            "ok": bool(loss_error <= UPDATE_LOSS_TOLERANCE
                       and errors[worst] <= UPDATE_TOLERANCE)}


def compare(system_out, reference_out, scales=None) -> dict:
    """Per-output relative errors of (logits, values) and the verdict.
    `scales` are the outputs' scales where `reference_out` is a part of
    what was compared (an output's scale is that of all of it)."""
    errs, ok = {}, True
    scales = scales or output_scales(reference_out)
    for name, got, want, scale in zip(("logits", "value"), system_out,
                                      reference_out, scales):
        errs[name] = relative_error(got, want, scale=scale)
        ok = ok and errs[name] <= TOLERANCE
    return {"errors": errs, "tolerance": TOLERANCE, "ok": bool(ok)}


def routing_verdict(system_experts, own_experts, select) -> dict:
    """The system's choice [L, B, S, k] against the reference's own choice
    in each layer, the reference held to the system's choice in the layers
    before it (`forward(experts=system_experts)` gives `own_experts` and
    `select` so). A flip's gap is how far below the reference's k-th
    probability the reference puts the least likely expert the system
    chose, as a share of that k-th: 0 is an exact tie."""
    sys_e = np.asarray(system_experts)
    a = np.sort(sys_e, axis=-1)
    b = np.sort(np.asarray(own_experts), axis=-1)
    differ = np.any(a != b, axis=-1)  # [L, B, S]
    select = np.asarray(select, np.float64)
    chosen = np.take_along_axis(select, sys_e, axis=-1)
    kth = np.sort(select, axis=-1)[..., -sys_e.shape[-1]]
    gap = (kth - np.min(chosen, axis=-1)) / kth
    flips, gap = float(np.mean(differ)), float(np.max(gap, initial=0.0))
    return {"router_flips": flips, "max_flip_gap": gap,
            "flips_by_layer": [float(f) for f in
                               differ.reshape(len(differ), -1).mean(axis=1)],
            "ok": flips <= MAX_ROUTER_FLIPS and gap <= MAX_FLIP_GAP}
