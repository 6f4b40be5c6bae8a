"""Plain reference of the `kimi_linear` token policy: forward, V-trace's
loss, and the comparison that decides `correct` in its cells.

Straightforward `jax.numpy`, float32 throughout, matrix precision "highest",
no flax, no cache, no chunk, no triangular solve, no kernel, no sort, no
grouped or batched expert product, nothing from `ray_tpu`. The equations are
those of the source named in `configs/impala_kimi_linear_48b_a3b.json`
(`model_type: kimi_linear`; the catalog's `config` and `described_as`: "KDA
gated delta-rule linear (conv4); MLA NoPE global"; "256 experts, top-8, 1
shared"; the family's published form, arXiv:2510.26692). For x [S, H] and
the 1-indexed layer l:

    n   = RMSNorm_op(x)
    l in linear_attn_config.kda_layers (Kimi Delta Attention; heads of
    d_k = d_v = head_dim, P = heads x head_dim):
        q~, k~, v~ = n W_q, n W_k, n W_v       (the system keeps the three
              as one [H, 3 P] matrix and the taps as one [3 P, L]: thirds
              in that order)
        q', k', v' = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
              conv: depthwise causal, L = short_conv_kernel_size taps as L
              shifted products, w[:, L-1] on the current position; inputs
              before the episode's first position are 0
        q_t = q'_t / sqrt(|q'_t|^2 + 1e-6) a head, times d_k^-1/2;
        k_t = k'_t / sqrt(|k'_t|^2 + 1e-6) a head
        g_t = -exp(A_log[head]) * softplus((n_t W_fa) W_fb + dt_bias)
              the LOG decay a channel of the key, <= 0; a_t = exp(g_t)
        beta_t = sigmoid(n_t W_b), one a head
        THE RECURRENCE ITSELF, one position at a time (a `lax.scan` over
        the positions), S [d_k, d_v] a head, 0 where an episode begins:
            S <- a_t * S (by rows of d_k)
            u  = beta_t (v_t - S^T k_t)
            S <- S + k_t u^T
            o_t = S^T q_t
        h = x + (RMSNorm_head(o_t) * w_o * sigmoid((n_t W_ga) W_gb)) W_out
              the norm over each head's d_v values, one weight [d_v]
    l in linear_attn_config.full_attn_layers (latent attention,
    `mla_use_nope`: NO rotation anywhere; `q_lora_rank` null: no query
    latent):
        q = n W_q -> heads x (nope + rope)
        [c | k_r] = n W_kva;  c = RMSNorm(c);  k_r shared by the heads
        [k_nope | v] = c W_kvb -> heads x (nope | v_head_dim)
        o_h = softmax_s(q_h . [k_nope,h | k_r]_s / sqrt(nope + rope)) v_h,s
              over s <= t of the same episode
        h = x + [o_1 .. o_heads] W_o
    m   = RMSNorm_ffn(h)
    l <= first_k_dense_replace:  y = h + W_down (silu(W_gate m) * W_up m)
    else (float32 router; one group: a plain top-k): s = sigmoid(m W_r);
              S_t = the k largest of s + b (b a constant);
              w_e = s_e / (sum_{e in S_t} s_e + 1e-20) (moe_renormalize),
              times routed_scaling_factor
        y   = h + sum_{e in S_t, e HELD HERE} w_e SwiGLU_e(m) (a loop over
              the held experts, each on every token times its 0/1-masked
              weight; what the absent experts would add is left out)
              + SwiGLU_shared(m)
    after the last layer: RMSNorm;  logits = y W_head (untied);  a linear
    value head

An episode starts at position 0 and wherever `starts` says: the matrix
states are 0 there, a convolution's taps before it read 0, and attention
does not look back across it. The attention is a mask on the full score
matrix, computed a block of `QUERY_BLOCK` queries at a time so that 4,096
positions fit a chip; the recurrence is scanned in blocks of
`RECURRENCE_BLOCK` positions; under a gradient each block and each layer is
recomputed (`jax.checkpoint`), which changes no number.

Departures from the source: a value head (an RL policy needs one); no
auxiliary router loss; the selection bias b is a constant (its balancing
update belongs to pre-training). `assumed` in the configuration's file: the
rank of the decay's and the gate's projections (the head's 128), no bias on
either, the normalisation's and the renormalisation's epsilons.

Tolerance. The system keeps parameters, router, final norm, heads, the
decays and the matrix states in float32 and the blocks' other activations
in bfloat16 (8 bits of mantissa, ~0.4 % a rounding); on the TPU its float32
products run as bf16 passes at default precision. So it cannot agree with
this reference to float32 accuracy. Measured and bounded, apart, as in the
other token cells:

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
the v5e (PERF.md section 4; my chip runs, PR 41): the system's largest over
its seeds, and this reference with its blocks rounded to float8_e4m3
(`round_to`, the nearest precision below the stated bfloat16) in the
system's place, which has to be refused. The readings stand beside the
constants below.
"""

import jax
import jax.numpy as jnp
import numpy as np

# The arithmetic the references share (float32 RMSNorm, the float8_e4m3
# rounding emulated in float32, the errors' measure; SwiGLU; Adam's change,
# the global clip, a parameter's change against its float32 storage; the
# verdicts' arithmetic, judged here by this file's limits): one copy.
from lib import reference_glm4_moe_lite as _shared
from lib.reference_glm4_moe_lite import (  # noqa: F401
    _swiglu, adam_change, adam_update, change_error, clip_scale)
from lib.reference_lfm2_moe import _episodes
from lib.reference_olmoe import _rms_norm, _rotate_half, _rounder

# Each limit beside the readings that set it (my chip runs, PR 41: the limits
# were set from the first six runs on six seeds; the ranges are those of
# thirty-six runs on thirty-four seeds, all but the first six judged against
# the scale of the reference held to the causal pass's experts and not the
# free one's, which moves a reading by less than a tenth of itself; two
# sequences of 4,096 positions each through the causal pass as one pass, the
# learner's shape, and through the decode as rows of the 32-row batch;
# "float8": this reference with its blocks rounded to float8_e4m3 in the
# system's place).
# Logits and values, five bf16 blocks deep. The system: logits 1.60-2.52 %,
# values 1.52-2.85 %; float8: 39.8-50.4 %, 35.5-83.9 %.
TOLERANCE = 0.08
# (Token, expert layer) pairs whose eight of 256 differ. The system:
# 10.3-13.1 %, rising with depth (7.9-11.0, 10.0-14.1, 10.0-14.0, 11.1-16.2 %
# by layer); float8: 89.0-91.7 %. More than the accepted cells' 4.8-6.4 %,
# and why: the 8th and 9th of 256 scores lie closer than the 4th and 5th of
# 32 (under one seeded noise of the logits, sigma 0.01, 3.5 % of the fourth
# cell's sets change, 4.2 % of the second's, 9.8 % of these). The limit
# near the two readings' geometric mean.
MAX_ROUTER_FLIPS = 0.35
# The largest gap of a flip over a pass's 32,768 pairs. The system:
# 0.72-1.53 %; float8: 32.1-52.0 %. Near their geometric mean.
MAX_FLIP_GAP = 0.06
# One update. Precision hardly moves the loss (a sum over 8,192 tokens): the
# accepted cells' limit, which leaves the first reading (0.029 %) thirty
# times of room; against the free reference 0.004-0.193 % (27 runs), held to
# the system's experts 0.003-0.076 % (9 runs).
UPDATE_LOSS_TOLERANCE = 0.009
# The worst parameter's change, where 1 is what a state left unchanged
# reads. Set against the FREE reference: 5.96 % first (layer 1's held
# experts' gate), near the geometric mean of that reading and 1, the more
# room above; then 1.43-3.39 % (a router) in 25 runs and 22.3 % in one (all
# three matrices of layer 2's experts: one tie falling the other way for an
# expert with few rows). The driver now holds the reference to the system's
# experts, as the forward's comparison does: 0.85-3.00 % in nine runs, the
# two seeds above 1.02 and 0.87 %, the routers 0.4-1.2 %. The limit stays.
UPDATE_TOLERANCE = 0.25

# Queries a block of the attention's score matrix; positions a block of
# the recurrence's scan.
QUERY_BLOCK = 512
RECURRENCE_BLOCK = 64
# Under the root of the L2 normalisation; beside the chosen scores' sum.
L2_EPS = 1e-6
TOPK_EPS = 1e-20

MUTATIONS = (
    "decay_after_the_delta", "one_decay_a_head", "beta_out_of_subtraction",
    "q_not_normalised", "k_not_normalised", "no_key_width_scale",
    "no_silu_after_convolutions", "taps_reversed", "conv_across_reset",
    "state_one_step_stale", "gate_silu", "norm_over_projection",
    "k_r_rotated", "scale_sqrt_nope", "bias_in_weights", "no_scaling_factor")


def _rope(x, positions, theta):
    """Rotate-half RoPE of x [B, S, heads, d] at `positions` [B, S] (no
    layer of the model rotates: the `k_r_rotated` error alone does)."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]
    return x * jnp.cos(angles) + _rotate_half(x) * jnp.sin(angles)


def _convolved(a, w, positions, mutate):
    """The depthwise causal convolution of a [B, S, C] with taps w [C, L]
    as L shifted products, a tap that would reach before its episode's
    first position reading 0."""
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
    return out


def _recurrence(q, k, v, g, beta, starts, mutate):
    """The gated delta rule one position at a time: q, k, g [B, S, heads,
    d_k], v [B, S, heads, d_v], beta [B, S, heads], `starts` [B, S] true
    where an episode begins. Returns (o [B, S, heads, d_v], S after the
    last position [B, heads, d_k, d_v])."""
    B, S, heads, d_k = q.shape

    def position(state, xs):
        q, k, v, g, beta, start = xs
        state = jnp.where(start[:, None, None, None], 0.0, state)
        stale = state
        if mutate == "decay_after_the_delta":
            u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", state, k))
            state = jnp.exp(g)[..., None] * (
                state + k[..., None] * u[..., None, :])
        else:
            state = jnp.exp(g)[..., None] * state
            read = jnp.einsum("bhkv,bhk->bhv", state, k)
            if mutate == "beta_out_of_subtraction":
                u = beta[..., None] * v - read
            else:
                u = beta[..., None] * (v - read)
            state = state + k[..., None] * u[..., None, :]
        o = jnp.einsum("bhkv,bhk->bhv",
                       stale if mutate == "state_one_step_stale" else state,
                       q)
        return state, o

    def block(state, xs):
        return jax.lax.scan(position, state, xs)
    size = S if S % RECURRENCE_BLOCK else RECURRENCE_BLOCK
    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((S // size, size) + a.shape[:1]
                                             + a.shape[2:])
               for a in (q, k, v, g, beta, starts))
    state, o = jax.lax.scan(
        jax.checkpoint(block),
        jnp.zeros((B, heads, d_k, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((S,) + o.shape[2:]), 0, 1), state


def _kda(lp, x, n, positions, net, r, mutate):
    """x + KDA(n); (h, the matrix states after the last position)."""
    lin = net["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    B, S, _ = x.shape
    by_head = (B, S, heads, d)
    q, k, v = (r(jax.nn.silu(c)) if mutate != "no_silu_after_convolutions"
               else r(c) for c in jnp.split(_convolved(
                   r(n @ lp["kda_qkv"]), lp["kda_conv"], positions, mutate),
                   3, axis=-1))
    q, k, v = q.reshape(by_head), k.reshape(by_head), v.reshape(by_head)

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
    if mutate != "q_not_normalised":
        q = unit(q)
    if mutate != "k_not_normalised":
        k = unit(k)
    if mutate != "no_key_width_scale":
        q = q / np.sqrt(d)
    g = -jnp.exp(lp["kda_a_log"])[:, None] * jax.nn.softplus(
        r(r(n @ lp["kda_fa"]) @ lp["kda_fb"]) + lp["kda_dt_bias"]).reshape(
            by_head)
    if mutate == "one_decay_a_head":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(n @ lp["kda_b"])
    o, state = _recurrence(q, k, v, g, beta, positions == 0, mutate)
    gate = r(r(n @ lp["kda_ga"]) @ lp["kda_gb"])
    gate = (jax.nn.silu(gate) if mutate == "gate_silu"
            else jax.nn.sigmoid(gate)).reshape(by_head)
    if mutate == "norm_over_projection":
        o = _rms_norm(o.reshape(B, S, -1), jnp.tile(lp["kda_o_norm"], heads),
                      net["rms_norm_eps"]).reshape(by_head)
    else:
        o = _rms_norm(o, lp["kda_o_norm"], net["rms_norm_eps"])
    return r(x + r(r(o * gate).reshape(B, S, -1) @ lp["kda_out"])), state


def _latent_attention(lp, x, n, episode, positions, net, r, mutate):
    heads, eps = net["num_attention_heads"], net["rms_norm_eps"]
    rank, nope, rot = (net["kv_lora_rank"], net["qk_nope_head_dim"],
                       net["qk_rope_head_dim"])
    B, S, _ = x.shape
    q = r(n @ lp["wq"]).reshape(B, S, heads, nope + rot)
    kv = r(n @ lp["wkv_a"])
    c = r(_rms_norm(kv[..., :rank], lp["kv_a_norm"], eps))
    k_r = kv[..., None, rank:]
    if mutate == "k_r_rotated":
        k_r = _rope(k_r, positions, net["rope_theta"])
    kvb = r(c @ lp["wkv_b"]).reshape(B, S, heads, -1)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_r, (B, S, heads, rot))], axis=-1)
    v = kvb[..., nope:]
    width = nope if mutate == "scale_sqrt_nope" else nope + rot
    block = min(QUERY_BLOCK, S)
    assert S % block == 0, (S, block)
    keys = jnp.arange(S)

    def rows(start):
        t = start + jnp.arange(block)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk",
            jax.lax.dynamic_slice_in_dim(q, start, block, axis=1),
            k) / np.sqrt(width)
        own = jax.lax.dynamic_slice_in_dim(episode, start, block, axis=1)
        allowed = (keys[None, None, :] <= t[None, :, None]) & (
            episode[:, None, :] == own[:, :, None])
        scores = jnp.where(allowed[:, None], scores, -jnp.inf)
        attn = r(jax.nn.softmax(scores, axis=-1))
        return r(jnp.einsum("bhqk,bkhd->bqhd", attn, v))
    o = jax.lax.map(jax.checkpoint(rows), jnp.arange(0, S, block))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, -1)
    return r(x + r(o @ lp["wo"]))


def _moe(lp, bias, h, m, net, r, mutate, held_to):
    """h + MoE(m); (out, this layer's own choice [B, S, k], its selection
    scores [B, S, E]). `held_to` [B, S, k]: the experts every token is sent
    to instead, with the weights computed here for them."""
    k = net["num_experts_per_token"]
    scores = jax.nn.sigmoid(m @ lp["router"])
    select = scores + bias
    _, own = jax.lax.top_k(select, k)
    top_i = own if held_to is None else jnp.asarray(held_to, jnp.int32)
    weigh = select if mutate == "bias_in_weights" else scores
    top_p = jnp.take_along_axis(weigh, top_i, axis=-1)
    if net.get("moe_renormalize", True):
        top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + TOPK_EPS)
    if mutate != "no_scaling_factor":
        top_p = top_p * net.get("routed_scaling_factor", 1)
    moe = jnp.zeros_like(h)
    first = net.get("first_expert_held", 0)
    for e in range(lp["w_gate"].shape[0]):  # the experts held here
        weight = jnp.sum(jnp.where(top_i == first + e, top_p, 0.0), axis=-1)
        moe = moe + weight[..., None] * _swiglu(
            m, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e], r)
    if net.get("num_shared_experts", 1):  # counted once, on every chip
        moe = moe + _swiglu(m, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"], r)
    return r(h + r(moe)), own, select


def forward(variables: dict, tokens, net: dict, round_to=None, mutate=None,
            experts=None, starts=None) -> dict:
    """The model on int tokens [B, S], each sequence from position 0.

    `variables` is the system's own tree: `params` (`embed`, `layer_<i>`,
    `final_norm`, `head`, `value_w`, `value_b`) and `constants` (the
    routers' selection biases), cast to float32. `net` is the
    configuration's `network` block: the published keys
    (`linear_attn_config`'s two lists name the layers 1-indexed), and
    `experts_held` / `first_expert_held`, the share of the experts that
    the weights given are. `round_to` rounds the blocks' activations to
    that dtype ("float8_e4m3": emulated in float32; or a jnp dtype) where
    the system rounds to bfloat16 (never a decay or a matrix state, which
    the system keeps in float32); `mutate` (one of `MUTATIONS`) makes the
    named error: both exist to show that the limits refuse them. `experts`
    [expert layers, B, S, k], where given, are the experts every token is
    sent to; a layer's own choice is still returned, made from its own
    scores there. `starts` [B, S], where given, is 1 where a new episode
    starts inside the sequence.

    Returns logits [B, S, V], values [B, S], experts [L, B, S, k] (each
    expert layer's own choice), select [L, B, S, E] (its selection scores
    s + b), kda_states [KDA layers, B, heads, d_k, d_v] (each after the
    last position)."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                     variables["params"])
    biases = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                          variables["constants"])
    r = _rounder(round_to)
    eps = net["rms_norm_eps"]
    kda_layers = net["linear_attn_config"]["kda_layers"]
    dense_layers = net.get("first_k_dense_replace", 1)
    tokens = jnp.asarray(tokens, jnp.int32)
    episode, positions = _episodes(starts, tokens.shape)
    chosen, selects, states = [], [], []

    def layer(lp, bias, x, held_to, i):
        n = r(_rms_norm(x, lp["attn_norm"], eps))
        state = None
        if i + 1 in kda_layers:
            h, state = _kda(lp, x, n, positions, net, r, mutate)
        else:
            h = _latent_attention(lp, x, n, episode, positions, net, r,
                                  mutate)
        m = r(_rms_norm(h, lp["mlp_norm"], eps))
        if i < dense_layers:
            out = r(h + _swiglu(m, lp["dense_gate"], lp["dense_up"],
                                lp["dense_down"], r)), None, None
        else:
            out = _moe(lp, bias, h, m, net, r, mutate, held_to)
        return out + (state,)

    with jax.default_matmul_precision("highest"):
        x = r(p["embed"][tokens])
        for i in range(net["num_hidden_layers"]):
            name = f"layer_{i}"
            dense = i < dense_layers
            held_to = None if experts is None or dense \
                else experts[len(chosen)]
            bias = None if dense else biases[name]["router_bias"]
            x, own, select, state = jax.checkpoint(
                lambda lp, bias, x, held_to, i=i: layer(
                    lp, bias, x, held_to, i))(p[name], bias, x, held_to)
            if state is not None:
                states.append(state)
            if not dense:
                chosen.append(own)
                selects.append(select)
        y = _rms_norm(x, p["final_norm"], eps)
        logits = y @ p["head"]
        values = y @ p["value_w"] + p["value_b"]
    return {"logits": logits, "values": values,
            "experts": jnp.stack(chosen), "select": jnp.stack(selects),
            "kda_states": jnp.stack(states)}


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


@jax.jit
def _largest(got, want):
    return jnp.max(jnp.abs(got - want)), jnp.max(jnp.abs(want))


def relative_error(got, want, scale=None) -> float:
    """Largest |got - want| over `scale` (None: the largest |want|), as the
    other references' but reduced where the arrays are: a pass's logits
    are 0.67 GB, and a cell's run has a time limit."""
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    if got.shape != want.shape:
        return float("inf")
    distance, largest = (float(x) for x in _largest(got, want))
    if not np.isfinite(distance):  # a NaN or an infinity anywhere in `got`
        return float("inf")
    return distance / max(largest if scale is None else scale, 1e-12)


def output_scales(reference_out) -> tuple:
    """The scale of each output: its largest absolute reference value."""
    return tuple(float(jnp.max(jnp.abs(w))) for w in reference_out)


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
