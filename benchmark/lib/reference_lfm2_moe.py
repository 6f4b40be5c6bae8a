"""Plain reference of the `lfm2_moe` token policy: forward, V-trace's loss,
and the comparison that decides `correct` in its cells.

Straightforward `jax.numpy`, float32 throughout, matrix precision "highest",
no flax, no cache, no state, no kernel, no sort, no grouped or batched expert
product, nothing from `ray_tpu`. The equations are those of the source named
in `configs/impala_lfm2_8b_a1b.json` (`model_type: lfm2_moe`; the catalog's
`config` and `described_as`: "gated short convolution (L=3); GQA 32Q/8KV";
"32 experts, top-4, 0 shared; expert bias"). For x [S, H] and layer l:

    n   = RMSNorm_op(x)
    layer_types[l] == "conv":
        [b | c | u] = n W_in          thirds in that order, no bias
        g   = b * u
        v_t = sum_{j < L} w[:, j] * g_{t - (L-1) + j}      L = conv_L_cache;
              three shifted products; g before the episode's first position
              is 0; w[:, L-1] meets the current position
        h   = x + (c * v) W_out       no activation anywhere in the operator
    layer_types[l] == "full_attention":
        q = n W_q -> heads x d;  k = n W_k, v = n W_v -> groups x d
        q, k = RMSNorm over EACH HEAD's d values (one weight [d] for all
              heads), THEN rotate-half RoPE (the episode's own positions)
        o_h = softmax_s(q_h . k_{h // (heads / groups), s} / sqrt(d)) v_{.., s}
              over s <= t of the same episode
        h   = x + [o_1 .. o_heads] W_o
    m   = RMSNorm_ffn(h)
    l < num_dense_layers:  y = h + W_down (silu(W_gate m) * W_up m)
    else (float32 router): s = sigmoid(m W_r);  S_t = the k largest of s + b
              (b a constant);  w_e = s_e / (sum_{e in S_t} s_e + 1e-6), times
              routed_scaling_factor
        y   = h + sum_{e in S_t, e HELD HERE} w_e W_down,e (silu(W_gate,e m)
              * W_up,e m)    (a loop over the held experts, each on every
              token times its 0/1-masked weight; what the absent experts
              would add is left out)
    after the last layer: RMSNorm;  logits = y E^T (the head IS the
    embedding);  a linear value head

An episode starts at position 0 and wherever `starts` says: positions begin
again there, attention does not look back across it and a convolution's taps
before it read 0. The attention is a mask on the full score matrix, computed
a block of `QUERY_BLOCK` queries at a time so that 4,096 positions fit a
chip; under a gradient each block and each layer is recomputed
(`jax.checkpoint`), which changes no number.

Departures from the source: a value head (an RL policy needs one); no
auxiliary router loss; the selection bias b is a constant (its balancing
update belongs to pre-training); the tied head is `assumed` (the catalog's
row drops the key; the family's dense configs tie).

Tolerance. The system keeps parameters, router, final norm and heads in
float32 and the blocks' activations in bfloat16 (8 bits of mantissa, ~0.4 %
a rounding); on the TPU its float32 products run as bf16 passes at default
precision. So it cannot agree with this reference to float32 accuracy.
Measured and bounded, apart, as in the other token cells:

* the router's choice, A LAYER AT A TIME: this forward is held to the
  experts the system chose (`experts=`), and in each expert layer its own
  choice, from its own selection scores there, is compared with the system's
  (`router_flips`, the share of (token, layer) pairs whose sets differ;
  `max_flip_gap`, the largest distance between this reference's k-th
  selection score and the one it gives the least likely expert the system
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
the v5e (PERF.md section 4; my chip runs, PR 38): the system's largest over
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
# verdicts' arithmetic, judged here by this file's limits): one copy.
from lib import reference_glm4_moe_lite as _shared
from lib.reference_glm4_moe_lite import (  # noqa: F401
    _swiglu, adam_change, adam_update, change_error, clip_scale)
from lib.reference_olmoe import (  # noqa: F401
    _rms_norm, _rotate_half, _rounder, output_scales, relative_error)

# Each limit beside the readings that set it (my chip runs, PR 38: thirteen
# runs on thirteen seeds, two sequences of 4,096 positions each through the
# causal pass and through the decode as rows of the 64-row batch; "float8":
# this reference with its blocks rounded to float8_e4m3 in the system's
# place).
# Logits and values, five bf16 blocks deep. The system: logits 1.98-2.51 %,
# values 1.73-2.43 %; float8: 30.3-35.3 %, 26.1-37.4 %.
TOLERANCE = 0.06
# (Token, expert layer) pairs whose four of 32 differ. The system: 4.8-5.4 %,
# rising with depth (2.4-3.5, 4.0-4.8, 5.2-6.4, 6.3-7.5 % by layer); float8:
# 56.4-57.4 %.
MAX_ROUTER_FLIPS = 0.15
# The largest gap of a flip over a pass's 32,768 pairs. The system:
# 1.9-3.2 %; float8: 35.8-47.1 %. The limit near their geometric mean.
MAX_FLIP_GAP = 0.12
# One update. Precision hardly moves the loss (a sum over 8,192 tokens): the
# accepted cells' limit, which leaves the first reading (0.15 %; all: 0.04
# to 0.37 %) six times of room.
UPDATE_LOSS_TOLERANCE = 0.009
# The worst parameter's change, where 1 is what a state left unchanged
# reads: 13.8 % first, 13.8-16.9 % over the seeds, one of the last two expert
# layers' routers in every run (the routers by depth 9.5, 11.4, 12.8, 15.0 %
# in one of them). Above a tenth on every seed, and why: after 96 updates
# the new gradient is 0.46 of the kept moment's weight in the change, not a
# tenth; about half of the error (in quadrature) is the tokens whose fourth
# and fifth scores tie within bf16's rounding (2.4-7.5 % of a layer's tokens
# choose another expert than this reference, whose router is free here:
# held to the system's experts it reads 2.2, 5.1, 6.4, 9.6 % by depth); the
# rest is the blocks' bf16 rounding, growing with the depth a gradient
# crosses. In float32 the same step agrees to 1e-7 (the rehearsal;
# tests/test_lfm2_moe_policy.py).
UPDATE_TOLERANCE = 0.4

# Queries a block of the attention's score matrix.
QUERY_BLOCK = 512
# Beside the chosen scores' sum (the source's division).
TOPK_EPS = 1e-6

MUTATIONS = (
    "conv_across_reset", "taps_reversed", "b_and_c_exchanged",
    "state_one_step_stale", "activation_in_the_operator",
    "qk_norm_over_projection", "qk_norm_after_rope", "key_head_h_mod_groups",
    "bias_in_weights", "softmax_router", "no_renormalisation",
    "untied_head")


def _episodes(starts, shape):
    """(the episode a step belongs to, its position in it) [B, S] from
    `starts` [B, S] (true where an episode starts; position 0 always does;
    None: one episode a row)."""
    B, S = shape
    steps = jnp.broadcast_to(jnp.arange(S), (B, S))
    if starts is None:
        return jnp.zeros((B, S), jnp.int32), steps
    starts = (jnp.asarray(starts) > 0).at[:, 0].set(True)
    first = jax.lax.cummax(jnp.where(starts, steps, 0), axis=1)
    return jnp.cumsum(starts, axis=1), steps - first


def _rope(x, positions, theta):
    """Rotate-half RoPE of x [B, S, heads, d] at `positions` [B, S]."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]
    return x * jnp.cos(angles) + _rotate_half(x) * jnp.sin(angles)


def _attention(q, k, v, episode, r):
    """q, k, v [B, S, heads, d] -> [B, S, heads, d]: the masked softmax
    over the full [S, S] scores, `QUERY_BLOCK` queries at a time."""
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
        own = jax.lax.dynamic_slice_in_dim(episode, start, block, axis=1)
        allowed = (keys[None, None, :] <= t[None, :, None]) & (
            episode[:, None, :] == own[:, :, None])
        scores = jnp.where(allowed[:, None], scores, -jnp.inf)
        attn = r(jax.nn.softmax(scores, axis=-1))
        return r(jnp.einsum("bhqk,bkhd->bqhd", attn, v))
    out = jax.lax.map(jax.checkpoint(rows), jnp.arange(0, S, block))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, heads, d)


def _short_conv(lp, x, n, positions, net, r, mutate):
    """x + (c * conv(b * u)) W_out: the convolution as L shifted products."""
    L = net["conv_L_cache"]
    S = x.shape[1]
    b, c, u = jnp.split(r(n @ lp["conv_in"]), 3, axis=-1)
    if mutate == "b_and_c_exchanged":
        b, c = c, b
    if mutate == "activation_in_the_operator":
        b = jax.nn.silu(b)
    g = r(b * u)
    w = lp["conv_w"][:, ::-1] if mutate == "taps_reversed" else lp["conv_w"]
    v = jnp.zeros_like(g)
    for j in range(L):
        shift = L - 1 - j
        if mutate == "state_one_step_stale" and shift:
            # What a decode reads whose state lags a step: g_{t-2}, g_{t-3}.
            shift += 1
        shifted = jnp.pad(g, ((0, 0), (shift, 0), (0, 0)))[:, :S]
        if mutate != "conv_across_reset":
            shifted = jnp.where((positions >= shift)[..., None], shifted, 0.0)
        v = v + w[:, j] * shifted
    return r(x + r(r(c * r(v)) @ lp["conv_out"]))


def _grouped_attention(lp, x, n, episode, positions, net, r, mutate):
    heads, groups = net["num_attention_heads"], net["num_key_value_heads"]
    eps, theta = net["norm_eps"], net["rope_theta"]
    B, S, _ = x.shape
    q, k = r(n @ lp["wq"]), r(n @ lp["wk"])
    v = r(n @ lp["wv"]).reshape(B, S, groups, -1)
    if mutate == "qk_norm_over_projection":
        q = r(_rms_norm(q, jnp.tile(lp["q_norm"], heads), eps))
        k = r(_rms_norm(k, jnp.tile(lp["k_norm"], groups), eps))
    q, k = q.reshape(B, S, heads, -1), k.reshape(B, S, groups, -1)
    if mutate == "qk_norm_after_rope":
        q, k = r(_rope(q, positions, theta)), r(_rope(k, positions, theta))
    if mutate != "qk_norm_over_projection":
        q = r(_rms_norm(q, lp["q_norm"], eps))
        k = r(_rms_norm(k, lp["k_norm"], eps))
    if mutate != "qk_norm_after_rope":
        q, k = r(_rope(q, positions, theta)), r(_rope(k, positions, theta))
    # Query head h reads key/value head h // (heads / groups).
    if mutate == "key_head_h_mod_groups":
        of_head = jnp.arange(heads) % groups
    else:
        of_head = jnp.arange(heads) // (heads // groups)
    o = _attention(q, k[:, :, of_head], v[:, :, of_head], episode, r)
    return r(x + r(o.reshape(B, S, -1) @ lp["wo"]))


def _moe(lp, bias, h, m, net, r, mutate, held_to):
    """h + MoE(m); (out, this layer's own choice [B, S, k], its selection
    scores [B, S, E]). `held_to` [B, S, k]: the experts every token is sent
    to instead, with the weights computed here for them."""
    k = net["num_experts_per_tok"]
    logits = m @ lp["router"]
    if mutate == "softmax_router":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
    select = scores + bias
    _, own = jax.lax.top_k(select, k)
    top_i = own if held_to is None else jnp.asarray(held_to, jnp.int32)
    weigh = select if mutate == "bias_in_weights" else scores
    top_p = jnp.take_along_axis(weigh, top_i, axis=-1)
    if net.get("norm_topk_prob", True) and mutate != "no_renormalisation":
        top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + TOPK_EPS)
    top_p = top_p * net.get("routed_scaling_factor", 1)
    moe = jnp.zeros_like(h)
    first = net.get("first_expert_held", 0)
    for e in range(lp["w_gate"].shape[0]):  # the experts held here
        weight = jnp.sum(jnp.where(top_i == first + e, top_p, 0.0), axis=-1)
        moe = moe + weight[..., None] * _swiglu(
            m, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e], r)
    return r(h + r(moe)), own, select


def forward(variables: dict, tokens, net: dict, round_to=None, mutate=None,
            experts=None, starts=None) -> dict:
    """The model on int tokens [B, S], each sequence from position 0.

    `variables` is the system's own tree: `params` (`embed`, `layer_<i>`,
    `final_norm`, `value_w`, `value_b`; no `head`) and `constants` (the
    routers' selection biases), cast to float32. `net` is the
    configuration's `network` block: the published keys, and `experts_held`
    / `first_expert_held`, the share of the experts that the weights given
    are. `round_to` rounds the blocks' activations to that dtype
    ("float8_e4m3": emulated in float32; or a jnp dtype) where the system
    rounds to bfloat16; `mutate` (one of `MUTATIONS`) makes the named
    error: both exist to show that the limits refuse them. `experts`
    [expert layers, B, S, k], where given, are the experts every token is
    sent to; a layer's own choice is still returned, made from its own
    scores there. `starts` [B, S], where given, is 1 where a new episode
    starts inside the sequence.

    Returns logits [B, S, V], values [B, S], experts [L, B, S, k] (each
    expert layer's own choice), select [L, B, S, E] (its selection scores
    s + b)."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                     variables["params"])
    biases = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                          variables["constants"])
    r = _rounder(round_to)
    eps = net["norm_eps"]
    tokens = jnp.asarray(tokens, jnp.int32)
    episode, positions = _episodes(starts, tokens.shape)
    chosen, selects = [], []

    def layer(lp, bias, x, held_to, i):
        n = r(_rms_norm(x, lp["attn_norm"], eps))
        if net["layer_types"][i] == "conv":
            h = _short_conv(lp, x, n, positions, net, r, mutate)
        else:
            h = _grouped_attention(lp, x, n, episode, positions, net, r,
                                   mutate)
        m = r(_rms_norm(h, lp["mlp_norm"], eps))
        if i < net["num_dense_layers"]:
            return r(h + _swiglu(m, lp["dense_gate"], lp["dense_up"],
                                 lp["dense_down"], r)), None, None
        return _moe(lp, bias, h, m, net, r, mutate, held_to)

    with jax.default_matmul_precision("highest"):
        x = r(p["embed"][tokens])
        for i in range(net["num_hidden_layers"]):
            name = f"layer_{i}"
            dense = i < net["num_dense_layers"]
            held_to = None if experts is None or dense \
                else experts[len(chosen)]
            bias = None if dense else biases[name]["router_bias"]
            x, own, select = jax.checkpoint(
                lambda lp, bias, x, held_to, i=i: layer(
                    lp, bias, x, held_to, i))(p[name], bias, x, held_to)
            if not dense:
                chosen.append(own)
                selects.append(select)
        y = _rms_norm(x, p["final_norm"], eps)
        head = p["embed"]
        if mutate == "untied_head":
            head = 0.02 * jax.random.normal(jax.random.PRNGKey(0), head.shape)
        logits = y @ head.T
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
