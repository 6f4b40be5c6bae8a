"""Plain reference of the `qwen3_next` token policy: forward, V-trace's loss,
and the comparison that decides `correct` in its cells.

Straightforward `jax.numpy`, float32 throughout, matrix precision "highest",
no flax, no cache, no chunk, no triangular solve, no kernel, no sort, no
grouped or batched expert product, nothing from `ray_tpu`. The equations are
those of the source named in `configs/impala_qwen3_next_80b_a3b.json`
(`model_type: qwen3_next`; the catalog's `config` and `described_as`: "Gated
DeltaNet linear (conv4); gated softmax attention (head_dim 256, 16Q/2KV) --
48L, 3 GDN : 1 full"; "512 experts, top-10, 1 shared"). Every `RMSNorm` of
the hidden vector and of a head's queries and keys is the family's
zero-centred one, x / sqrt(mean(x^2) + eps) * (1 + w), w 0 at initialisation
(the system's tree holds w). Pre-norm residual blocks; for x [S, H] and the
0-indexed layer i:

    n = RMSNorm_op(x)
    (i + 1) % full_attention_interval != 0 (Gated DeltaNet; K = key heads x
    d_k, V = value heads x d_v, value head j reads key head j // (value
    heads / key heads)):
        [q~ | k~ | v~ | z] = n W_qkvz   [H, 2 K + 2 V] (the source lays the
              same columns out a key head at a time: a permutation of
              seeded columns);  [b | a] = n W_ba  [H, 2 x value heads]
        [q' | k' | v'] = silu(conv([q~ | k~ | v~]))   depthwise causal,
              `linear_conv_kernel_dim` taps as shifted products, w[:, L-1]
              on the current position, no bias; inputs before the episode's
              first position are 0
        q_t = q'_t / sqrt(|q'_t|^2 + 1e-6) a head, times d_k^-1/2;
        k_t = k'_t / sqrt(|k'_t|^2 + 1e-6) a head
        g_t = -exp(A_log) * softplus(a_t + dt_bias)    ONE number a VALUE
              head, the log decay, <= 0;  beta_t = sigmoid(b_t)
        THE RECURRENCE ITSELF, one position at a time (a `lax.scan` over
        the positions), S [d_k, d_v] a value head, 0 where an episode
        begins:
            S' = exp(g_t) S
            u  = beta_t (v_t - S'^T k_t)
            S  = S' + k_t u^T
            o_t = S^T q_t
        h = x + [RMSNorm_head(o_t) * w_o * silu(z_t)] W_out    the norm
              over each value head's d_v values, one PLAIN weight [d_v]
              (ones at initialisation, not zero-centred)
    (i + 1) % full_attention_interval == 0 (gated attention; `heads` query
    heads over `groups` key/value heads of `head_dim` d):
        [q | gate] = n W_q -> heads x (d | d)   a head's columns: its
              query, then its gate
        k, v = n W_k, n W_v -> groups x d
        q = RMSNorm_d(q), k = RMSNorm_d(k)      over each head's d values,
              one zero-centred weight [d] each
        rotate-half RoPE over the FIRST `partial_rotary_factor` x d values
              of a head (the angles' frequencies over that many), the rest
              untouched
        o_h = softmax_s(q_h . k_{h // (heads / groups)},s / sqrt(d)) v_..,s
              over s <= t of the same episode
        h = x + [o * sigmoid(gate)] W_o
    m = RMSNorm_ffn(h)
    p = softmax(m W_r) over ALL `num_experts`, float32; S_t = the k largest;
        w_e = p_e / sum_{e in S_t} p_e  (`norm_topk_prob`)
    y = h + sum_{e in S_t, e HELD HERE} w_e SwiGLU_e(m) (a loop over the
        held experts, each on every token times its 0/1-masked weight; what
        the absent experts would add is left out)
        + sigmoid(m . w_sg) SwiGLU_shared(m)       counted once, on every
        chip
    after the last layer: RMSNorm;  logits = y W_head (untied);  a linear
    value head

An episode starts at position 0 and wherever `starts` says: the matrix
states are 0 there, the convolution's taps before it read 0, and attention
does not look back across it. The attention is a mask on the full score
matrix, computed a block of `QUERY_BLOCK` queries at a time so that 4,096
positions fit a chip; the recurrence is scanned in blocks of
`RECURRENCE_BLOCK` positions; under a gradient each block and each layer is
recomputed (`jax.checkpoint`), which changes no number.

Departures from the source: a value head (an RL policy needs one); no
auxiliary router loss; the family's next-token module is not built (the
catalog's `config` has no key for it); W_qkvz's and W_ba's columns in the
order above. `assumed` in the configuration's file: the decay's and the
gates' initial draws, the epsilons.

Tolerance. The system keeps parameters, router, final norm, heads, the
decays and the matrix states in float32 and the blocks' other activations
in bfloat16 (8 bits of mantissa, ~0.4 % a rounding); on the TPU its float32
products run as bf16 passes at default precision. So it cannot agree with
this reference to float32 accuracy. Measured and bounded, apart, as in the
other token cells:

* the router's choice, A LAYER AT A TIME: this forward is held to the
  experts the system chose (`experts=`), and in each expert layer its own
  choice, from its own probabilities there, is compared with the system's
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
the v5e (PERF.md section 4; my chip runs, PR 52): the system's largest over
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
# verdicts' arithmetic, judged here by this file's limits; the depthwise
# causal convolution as shifted products): one copy.
from lib import reference_glm4_moe_lite as _shared
from lib.reference_glm4_moe_lite import (  # noqa: F401
    _swiglu, adam_change, adam_update, change_error, clip_scale)
from lib.reference_kimi_linear import (  # noqa: F401
    _convolved, output_scales, relative_error)
from lib.reference_lfm2_moe import _attention, _episodes, _rope
from lib.reference_olmoe import _rounder

# Each limit beside the readings that set it (my chip runs, PR 52: the limits
# were set from the first six runs on six seeds, 2147483659, 3000000019,
# 1618033989, 2718281831, 3141592661 and 4000000007; the ranges are those of
# thirteen runs on thirteen seeds, the last seven on the final tree and
# judged by these limits: all `correct`; two sequences of 4,096 positions
# each through the causal pass
# as one pass, the learner's shape, and through the decode as rows of the
# 32-row batch; "float8": this reference with its blocks rounded to
# float8_e4m3 in the system's place). Each forward limit near the geometric
# mean of its two readings.
# Logits and values, four bf16 blocks deep. The system: logits 3.50-6.53 %
# (3.79-5.23 in the first six), values 2.16-4.63 %; float8: 45.6-63.0 %,
# 31.9-48.9 %. (More than the fifth
# cell's 1.6-2.9 % over five blocks, and why: at the family's draw, A
# uniform in (0, 16] beside a dt_bias of 1, nearly every head forgets its
# state within a position, its output is then (q_t . k_t) u_t under a norm of
# its own, and that turns on the SIGN of q . k, which a rounding to bfloat16
# takes the other way in a few heads of a few positions.)
TOLERANCE = 0.13
# (Token, layer) pairs whose ten of 512 differ. The system: 17.6-19.8 %,
# rising with depth (8.3-10.6, 15.8-18.0, 21.5-24.9, 23.4-26.3 % by layer);
# float8: 93.7-94.3 %. More than the fifth cell's 10.3-13.1 %: the 10th and
# 11th of 512 softmax probabilities lie closer than the 8th and 9th of 256
# scores.
MAX_ROUTER_FLIPS = 0.42
# The largest gap of a flip over a pass's 32,768 pairs. The system:
# 9.9-16.4 % (10.0-14.2 in the first six); float8: 80.3-92.1 %. A gap is a share of a PROBABILITY near
# 1/512, so a logit's error of 0.1 is a gap of 10 % (the seventh cell, the
# other softmax router, stands at 0.25).
MAX_FLIP_GAP = 0.34
# One update. Precision hardly moves the loss (a sum over 8,192 tokens): the
# accepted cells' limit, which leaves the first reading (0.064 %) fourteen
# times of room; thirteen runs 0.0005-0.064 %.
UPDATE_LOSS_TOLERANCE = 0.009
# The worst parameter's change, where 1 is what a state left unchanged
# reads: 3.05 % first (layer 2's router), then 2.67-3.56 % (a router every
# time), the reference held to the system's experts as the forward's
# comparison is. Between the first reading and 1, the more room above the
# reading (eight times; four below 1), as the fifth cell's.
UPDATE_TOLERANCE = 0.25

# Positions a block of the recurrence's scan (the attention's block of
# queries is `reference_lfm2_moe.QUERY_BLOCK`).
RECURRENCE_BLOCK = 64
# Under the root of the L2 normalisation.
L2_EPS = 1e-6

MUTATIONS = (
    "decay_after_the_delta", "decay_a_key_head", "beta_out_of_subtraction",
    "q_not_normalised", "k_not_normalised", "no_key_width_scale",
    "no_silu_after_convolution", "taps_reversed", "conv_across_reset",
    "state_one_step_stale", "value_head_j_mod_key_heads", "output_gate_sigmoid",
    "output_norm_zero_centred", "norms_not_zero_centred", "rope_whole_head",
    "rope_frequencies_of_whole_head", "no_attention_gate",
    "attention_gate_silu", "gate_before_query", "qk_norm_after_rope",
    "key_head_h_mod_groups", "sigmoid_router", "no_renormalisation",
    "shared_expert_not_gated")


def _norm(x, w, eps, mutate=None):
    """The zero-centred RMSNorm: x / rms(x) * (1 + w)."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    scale = w if mutate == "norms_not_zero_centred" else 1.0 + w
    return scale * (x * jax.lax.rsqrt(var + eps))


def _recurrence(q, k, v, g, beta, starts, mutate=None):
    """The gated delta rule under one decay a head, one position at a time:
    q, k [B, S, heads, d_k], v [B, S, heads, d_v], g, beta [B, S, heads],
    `starts` [B, S] true where an episode begins. Returns (o [B, S, heads,
    d_v], S after the last position [B, heads, d_k, d_v])."""
    B, S, heads, d_k = q.shape

    def position(state, xs):
        q, k, v, g, beta, start = xs
        state = jnp.where(start[:, None, None, None], 0.0, state)
        stale = state
        decay = jnp.exp(g)[..., None, None]
        if mutate == "decay_after_the_delta":
            u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", state, k))
            state = decay * (state + k[..., None] * u[..., None, :])
        else:
            state = decay * state
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


def _gated_deltanet(lp, x, n, positions, net, r, mutate):
    """x + GatedDeltaNet(n); (h, the matrix states after the last
    position)."""
    key_heads, value_heads = (net["linear_num_key_heads"],
                              net["linear_num_value_heads"])
    d_k, d_v = net["linear_key_head_dim"], net["linear_value_head_dim"]
    B, S, _ = x.shape
    K, V = key_heads * d_k, value_heads * d_v
    mixed = r(n @ lp["gdn_qkvz"])
    z = mixed[..., 2 * K + V:].reshape(B, S, value_heads, d_v)
    conv = _convolved(mixed[..., :2 * K + V], lp["gdn_conv"], positions,
                      mutate)
    conv = r(conv if mutate == "no_silu_after_convolution"
             else jax.nn.silu(conv))
    q = conv[..., :K].reshape(B, S, key_heads, d_k)
    k = conv[..., K:2 * K].reshape(B, S, key_heads, d_k)
    v = conv[..., 2 * K:].reshape(B, S, value_heads, d_v)

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
    if mutate != "q_not_normalised":
        q = unit(q)
    if mutate != "k_not_normalised":
        k = unit(k)
    if mutate != "no_key_width_scale":
        q = q / np.sqrt(d_k)
    # Value head j reads key head j // (value heads / key heads).
    if mutate == "value_head_j_mod_key_heads":
        of_head = jnp.arange(value_heads) % key_heads
    else:
        of_head = jnp.arange(value_heads) // (value_heads // key_heads)
    q, k = q[:, :, of_head], k[:, :, of_head]
    b, a = jnp.split(n @ lp["gdn_ba"], 2, axis=-1)
    g = -jnp.exp(lp["gdn_a_log"]) * jax.nn.softplus(a + lp["gdn_dt_bias"])
    if mutate == "decay_a_key_head":
        g = jnp.repeat(g.reshape(B, S, key_heads, -1)[..., 0],
                       value_heads // key_heads, axis=-1)
    beta = jax.nn.sigmoid(b)
    o, state = _recurrence(q, k, v, g, beta, positions == 0, mutate)
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    w = lp["gdn_o_norm"]
    o = (1.0 + w if mutate == "output_norm_zero_centred" else w) * (
        o * jax.lax.rsqrt(var + net["rms_norm_eps"]))
    gate = (jax.nn.sigmoid(z) if mutate == "output_gate_sigmoid"
            else jax.nn.silu(z))
    return r(x + r(r(o * gate).reshape(B, S, -1) @ lp["gdn_out"])), state


def _partial_rope(x, positions, net, mutate):
    """RoPE over the first `partial_rotary_factor` of a head's values, the
    angles' frequencies over that many."""
    d = x.shape[-1]
    rotated = int(d * net["partial_rotary_factor"])
    theta = net["rope_theta"]
    if mutate == "rope_whole_head":
        return _rope(x, positions, theta)
    if mutate == "rope_frequencies_of_whole_head":
        # The whole head's first frequencies on the rotated part's pairs.
        inv_freq = 1.0 / theta ** (
            jnp.arange(0, rotated, 2, dtype=jnp.float32) / d)
        angles = positions.astype(jnp.float32)[..., None] * inv_freq
        angles = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]
        part, half = x[..., :rotated], rotated // 2
        turned = jnp.concatenate([-part[..., half:], part[..., :half]], -1)
        return jnp.concatenate(
            [part * jnp.cos(angles) + turned * jnp.sin(angles),
             x[..., rotated:]], axis=-1)
    return jnp.concatenate(
        [_rope(x[..., :rotated], positions, theta), x[..., rotated:]],
        axis=-1)


def _gated_attention(lp, x, n, episode, positions, net, r, mutate):
    heads, groups = net["num_attention_heads"], net["num_key_value_heads"]
    d, eps = net["head_dim"], net["rms_norm_eps"]
    B, S, _ = x.shape
    both = r(n @ lp["wq"]).reshape(B, S, heads, 2 * d)
    q, gate = both[..., :d], both[..., d:]
    if mutate == "gate_before_query":
        q, gate = gate, q
    k = r(n @ lp["wk"]).reshape(B, S, groups, d)
    v = r(n @ lp["wv"]).reshape(B, S, groups, d)
    if mutate == "qk_norm_after_rope":
        q = r(_partial_rope(q, positions, net, mutate))
        k = r(_partial_rope(k, positions, net, mutate))
    q = r(_norm(q, lp["q_norm"], eps, mutate))
    k = r(_norm(k, lp["k_norm"], eps, mutate))
    if mutate != "qk_norm_after_rope":
        q = r(_partial_rope(q, positions, net, mutate))
        k = r(_partial_rope(k, positions, net, mutate))
    # Query head h reads key/value head h // (heads / groups).
    if mutate == "key_head_h_mod_groups":
        of_head = jnp.arange(heads) % groups
    else:
        of_head = jnp.arange(heads) // (heads // groups)
    o = _attention(q, k[:, :, of_head], v[:, :, of_head], episode, r)
    if mutate == "attention_gate_silu":
        o = r(o * jax.nn.silu(gate))
    elif mutate != "no_attention_gate":
        o = r(o * jax.nn.sigmoid(gate))
    return r(x + r(o.reshape(B, S, -1) @ lp["wo"]))


def _moe(lp, h, m, net, r, mutate, held_to):
    """h + MoE(m); (out, this layer's own choice [B, S, k], its
    probabilities [B, S, E]). `held_to` [B, S, k]: the experts every token
    is sent to instead, with the weights computed here for them."""
    k = net["num_experts_per_tok"]
    logits = m @ lp["router"]
    probs = (jax.nn.sigmoid(logits) if mutate == "sigmoid_router"
             else jax.nn.softmax(logits, axis=-1))
    _, own = jax.lax.top_k(probs, k)
    top_i = own if held_to is None else jnp.asarray(held_to, jnp.int32)
    top_p = jnp.take_along_axis(probs, top_i, axis=-1)
    if net.get("norm_topk_prob", True) and mutate != "no_renormalisation":
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    moe = jnp.zeros_like(h)
    first = net.get("first_expert_held", 0)
    for e in range(lp["w_gate"].shape[0]):  # the experts held here
        weight = jnp.sum(jnp.where(top_i == first + e, top_p, 0.0), axis=-1)
        moe = moe + weight[..., None] * _swiglu(
            m, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e], r)
    shared = _swiglu(m, lp["shared_gate"], lp["shared_up"],
                     lp["shared_down"], r)
    if mutate != "shared_expert_not_gated":
        shared = jax.nn.sigmoid(m @ lp["shared_scale"]) * shared
    return r(h + r(moe + shared)), own, probs


def is_attention(net: dict, layer: int) -> bool:
    """Whether the 0-indexed `layer` is the gated attention."""
    return (layer + 1) % net["full_attention_interval"] == 0


def forward(variables: dict, tokens, net: dict, round_to=None, mutate=None,
            experts=None, starts=None) -> dict:
    """The model on int tokens [B, S], each sequence from position 0.

    `variables` is the system's own tree: `params` (`embed`, `layer_<i>`,
    `final_norm`, `head`, `value_w`, `value_b`), cast to float32. `net` is
    the configuration's `network` block: the published keys, and
    `experts_held` / `first_expert_held`, the share of the experts that the
    weights given are. `round_to` rounds the blocks' activations to that
    dtype ("float8_e4m3": emulated in float32; or a jnp dtype) where the
    system rounds to bfloat16 (never a decay or a matrix state, which the
    system keeps in float32); `mutate` (one of `MUTATIONS`) makes the named
    error: both exist to show that the limits refuse them. `experts`
    [layers, B, S, k], where given, are the experts every token is sent to;
    a layer's own choice is still returned, made from its own
    probabilities there. `starts` [B, S], where given, is 1 where a new
    episode starts inside the sequence.

    Returns logits [B, S, V], values [B, S], experts [L, B, S, k] (each
    layer's own choice), select [L, B, S, E] (its probabilities),
    gdn_states [Gated DeltaNet layers, B, value heads, d_k, d_v] (each after
    the last position)."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                     variables["params"])
    r = _rounder(round_to)
    eps = net["rms_norm_eps"]
    tokens = jnp.asarray(tokens, jnp.int32)
    episode, positions = _episodes(starts, tokens.shape)
    chosen, selects, states = [], [], []

    def layer(lp, x, held_to, i):
        n = r(_norm(x, lp["attn_norm"], eps, mutate))
        state = None
        if is_attention(net, i):
            h = _gated_attention(lp, x, n, episode, positions, net, r,
                                 mutate)
        else:
            h, state = _gated_deltanet(lp, x, n, positions, net, r, mutate)
        m = r(_norm(h, lp["mlp_norm"], eps, mutate))
        return _moe(lp, h, m, net, r, mutate, held_to) + (state,)

    with jax.default_matmul_precision("highest"):
        x = r(p["embed"][tokens])
        for i in range(net["num_hidden_layers"]):
            held_to = None if experts is None else experts[i]
            x, own, select, state = jax.checkpoint(
                lambda lp, x, held_to, i=i: layer(lp, x, held_to, i))(
                    p[f"layer_{i}"], x, held_to)
            if state is not None:
                states.append(state)
            chosen.append(own)
            selects.append(select)
        y = _norm(x, p["final_norm"], eps, mutate)
        logits = y @ p["head"]
        values = y @ p["value_w"] + p["value_b"]
    return {"logits": logits, "values": values,
            "experts": jnp.stack(chosen), "select": jnp.stack(selects),
            "gdn_states": jnp.stack(states)}


def vtrace_loss(variables: dict, batch: dict, net: dict, cfg: dict,
                mutate=None):
    """IMPALA's loss of one minibatch of whole sequences, as
    `ray_tpu/rllib/agents/impala/vtrace_policy.py` describes it: sums over
    the minibatch of -logp * pg_advantage, 0.5 * (v - vs)^2 and the
    entropy. `batch`: tokens, actions [B, S] int, rewards, behaviour_logp
    [B, S], and every sequence ends its episode at its last step (so no
    bootstrap value is needed); with `experts` [layers, B, S, k] in it, the
    experts every token is sent to (`forward`'s). `mutate` is `forward`'s.
    Returns (total, parts)."""
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
    in each layer, the reference held to the system's choice in the layers
    before it (`forward(experts=system_experts)` gives `own_experts` and
    `select` so). A flip's gap is how far below the reference's k-th
    probability the reference puts the least likely expert the system
    chose, as a share of that k-th probability: 0 is an exact tie. Judged by
    this file's limits."""
    found = _shared.routing_verdict(system_experts, own_experts, select)
    found["ok"] = bool(found["router_flips"] <= MAX_ROUTER_FLIPS
                       and found["max_flip_gap"] <= MAX_FLIP_GAP)
    return found
