"""Plain reference of the `sdar_moe` token policy, which GENERATES BY DIFFUSION
OVER BLOCKS: the forward on a sampler's trace, the block-level V-trace loss,
and the comparison that decides `correct` in its cell.

Straightforward `jax.numpy`, float32 throughout, matrix precision "highest",
no flax, no cache, no kernel, no fused mask, no sort, no grouped or batched
expert product, nothing from `ray_tpu`. The layer is the source's named in
`configs/impala_sdar_30b_a3b.json` (`model_type: sdar_moe`, the Qwen3
mixture-of-experts body; SDAR, arXiv:2510.06303). For x [n, H], positions p
and an attention mask A:

    n   = RMSNorm(x)
    q   = RMSNorm_d(n W_q) -> heads x d;  k = RMSNorm_d(n W_k), v = n W_v ->
          groups x d        (the norm over EACH head's d values, one weight [d])
    q, k = RoPE(q, k; p) (rotate-half, all d);  head h reads key/value head
          h // (heads / groups)
    h   = x + softmax_A(q k^T / sqrt(d)) v W_o
    m   = RMSNorm(h);  P = softmax(m W_r) over all experts;  the k largest;
          w_e = P_e / sum of the chosen P  (`norm_topk_prob`)
    y   = h + sum_{e chosen, e HELD HERE} w_e W_down,e (silu(W_gate,e m) *
          W_up,e m)      (a loop over the held experts, each on every token
          times its 0/1-masked weight; what the absent experts would add is
          left out)
    after the last layer: RMSNorm, the untied head over the ids below the
    MASK id (the vocabulary's last: its probability is 0), a linear value head

The generation (block length L = `block_length`, S = `denoise_steps` passes a
block; block of position i: i // L). A trace is the tokens [B, T] and the pass
each was unmasked at, `steps` [B, T] (-1: given, never masked). Pass s of
block b sees the block's positions as their token where `steps` < s and as
the MASK id elsewhere, attends to the CLEAN tokens of the blocks before b and
to the pass's own L positions (both directions), and its logits at position i
are the distribution of the token AT i. The value of block b is the value
head on its first position in pass 0.

`forward` computes that for every block at once, a pass s at a time, as ONE
sequence of 2T positions, the T clean tokens then the T inputs of pass s, the
mask written out on the whole [2T, 2T] score matrix (`QUERY_BLOCK` queries
at a time): a clean query reads the clean keys of its own and earlier blocks;
a noisy query reads the clean keys of EARLIER blocks and the noisy keys of its
OWN block. `forward_by_blocks` is the definition itself, block by block: the
clean prefix and one noisy block, a forward pass each (a test holds the two
equal).

`vtrace_loss`: a block is ONE action. log pi(block) is the sum of its
generated tokens' log-probabilities at their own passes, log mu the same sum
of the rollout's, its reward the sum of its tokens', its discount gamma, the
episode ends with its last block; V-trace over [T / L]; the entropy is summed
over the generated positions. Given positions weigh nothing.

Tolerance. The system keeps parameters, router, final norm and heads in
float32 and the blocks' activations in bfloat16; on the TPU its float32
products run as bf16 passes at default precision. Measured and bounded,
apart, as in the other token cells: the router's choice a layer at a time
(this forward held to the experts the system chose, `experts=`:
`MAX_ROUTER_FLIPS`, `MAX_FLIP_GAP`), logits and values against this
reference held so (`TOLERANCE`), and one update of the learner by the
trainer's own step (`UPDATE_LOSS_TOLERANCE`, `UPDATE_TOLERANCE`). Each limit
of the forward lies between two readings at published widths on the v5e
(PERF.md section 4): the system's largest over its seeds, and this reference
with its blocks rounded to float8_e4m3 (`round_to`) in the system's place,
which has to be refused. The readings stand beside the constants.
"""

import jax
import jax.numpy as jnp
import numpy as np

# The arithmetic the references share (float32 RMSNorm, the float8_e4m3
# rounding emulated in float32, the errors' measure; Adam's change, the
# global clip, a parameter's change against its float32 storage; the
# routing's verdict by its own limits): one copy.
from lib.reference_glm4_moe_lite import (  # noqa: F401
    adam_change, change_error, clip_scale)
from lib.reference_olmoe import (  # noqa: F401
    _rms_norm, _rotate_half, _rounder, output_scales, relative_error)

# Each limit beside the readings that set it (my chip runs, PR 48; the cell's
# four episodes of 2,048 positions through the learner's pass and through the
# rollout's block steps; "float8": this reference with its blocks rounded to
# float8_e4m3 in the system's place). SET FROM THE CHIP'S READINGS: see
# PERF.md section 4 for the seeds.
TOLERANCE = 0.06
MAX_ROUTER_FLIPS = 0.15
MAX_FLIP_GAP = 0.25
UPDATE_LOSS_TOLERANCE = 0.009
UPDATE_TOLERANCE = 0.25

# Queries a block of the attention's score matrix.
QUERY_BLOCK = 512

MUTATIONS = (
    "causal_inside_a_block", "noisy_reads_its_clean_block",
    "logits_of_the_next_position", "no_qk_norm", "no_renormalisation",
    "key_head_h_mod_groups")


def _rope_at(x, positions, theta):
    """Rotate-half RoPE of x [B, n, heads, d] at `positions` [n]."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    return x * jnp.cos(angles) + _rotate_half(x) * jnp.sin(angles)


def _attention(q, k, v, allowed, r):
    """q [B, n, heads, d], k, v [B, n, heads, d] -> [B, n, heads, d]: the
    masked softmax over the full [n, n] scores, `QUERY_BLOCK` queries at a
    time; `allowed(query numbers [.., 1], key numbers [1, ..])` is the mask."""
    B, n, heads, d = q.shape
    block = min(QUERY_BLOCK, n)
    assert n % block == 0, (n, block)
    keys = jnp.arange(n)

    def rows(start):
        t = start + jnp.arange(block)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk",
            jax.lax.dynamic_slice_in_dim(q, start, block, axis=1),
            k) / np.sqrt(d)
        scores = jnp.where(allowed(t[:, None], keys[None, :])[None, None],
                           scores, -jnp.inf)
        attn = r(jax.nn.softmax(scores, axis=-1))
        return r(jnp.einsum("bhqk,bkhd->bqhd", attn, v))
    out = jax.lax.map(jax.checkpoint(rows), jnp.arange(0, n, block))
    return jnp.moveaxis(out, 0, 1).reshape(B, n, heads, d)


def moe(lp, m, net, r, top_i, top_p, first=None):
    """sum over the held experts e of w_e Expert_e(m) for m [.., H], routed
    to `top_i` [.., k] with weights `top_p`: a loop over the experts given,
    which are the router's `first` .. `first + held - 1`."""
    first = net.get("first_expert_held", 0) if first is None else first
    held = lp["w_gate"].shape[0]
    # weight[e, ..] = w_e where the held expert first + e was chosen.
    weight = jnp.stack([
        jnp.sum(jnp.where(top_i == first + e, top_p, 0.0), axis=-1)
        for e in range(held)])

    def add_expert(total, expert):
        # One held expert on every token, times its 0/1-masked weight.
        w_gate, w_up, w_down, w = expert
        a = r(jax.nn.silu(r(m @ w_gate)) * r(m @ w_up))
        return total + w[..., None] * r(a @ w_down), None
    total, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(m),
        (lp["w_gate"], lp["w_up"], lp["w_down"], weight))
    return total


def _layer(lp, x, positions, allowed, net, r, mutate, held_to):
    """One layer on x [B, n, H]; (out, this layer's own choice [B, n, k],
    its router's probabilities [B, n, E]). `held_to` [B, n, k]: the experts
    every token is sent to instead (an entry below 0: its own), with the
    weights computed here for them."""
    heads, groups = net["num_attention_heads"], net["num_key_value_heads"]
    eps, k = net["rms_norm_eps"], net["num_experts_per_tok"]
    B, n, _ = x.shape

    a = r(_rms_norm(x, lp["attn_norm"], eps))
    q = r(a @ lp["wq"]).reshape(B, n, heads, -1)
    kk = r(a @ lp["wk"]).reshape(B, n, groups, -1)
    v = r(a @ lp["wv"]).reshape(B, n, groups, -1)
    if mutate != "no_qk_norm":
        q = r(_rms_norm(q, lp["q_norm"], eps))
        kk = r(_rms_norm(kk, lp["k_norm"], eps))
    q = r(_rope_at(q, positions, net["rope_theta"]))
    kk = r(_rope_at(kk, positions, net["rope_theta"]))
    # Query head h reads key/value head h // (heads / groups).
    if mutate == "key_head_h_mod_groups":
        of_head = jnp.arange(heads) % groups
    else:
        of_head = jnp.arange(heads) // (heads // groups)
    o = _attention(q, kk[:, :, of_head], v[:, :, of_head], allowed, r)
    h = r(x + r(o.reshape(B, n, -1) @ lp["wo"]))

    m = r(_rms_norm(h, lp["mlp_norm"], eps))
    probs = jax.nn.softmax(m @ lp["router"], axis=-1)
    _, own = jax.lax.top_k(probs, k)
    top_i = own if held_to is None else jnp.where(
        held_to >= 0, jnp.asarray(held_to, jnp.int32), own)
    top_p = jnp.take_along_axis(probs, top_i, axis=-1)
    if net.get("norm_topk_prob", True) and mutate != "no_renormalisation":
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return r(h + r(moe(lp, m, net, r, top_i, top_p))), own, probs


def _hidden(p, tokens, positions, allowed, net, r, mutate, experts):
    """Tokens [B, n] at `positions` [n] under the mask `allowed` through
    every layer: (final hidden [B, n, H], each layer's own choice [L, B, n,
    k], its router's probabilities [L, B, n, E])."""
    x = r(p["embed"][tokens])
    chosen, selects = [], []
    for i in range(net["num_hidden_layers"]):
        held_to = None if experts is None else experts[i]
        x, own, probs = jax.checkpoint(
            lambda lp, x, held_to: _layer(
                lp, x, positions, allowed, net, r, mutate, held_to))(
                    p[f"layer_{i}"], x, held_to)
        chosen.append(own)
        selects.append(probs)
    return x, jnp.stack(chosen), jnp.stack(selects)


def _heads(p, x, net):
    """(logits over the ids below the MASK id, values) of final hidden x."""
    y = _rms_norm(x, p["final_norm"], net["rms_norm_eps"])
    return (y @ p["head"])[..., :-1], y @ p["value_w"] + p["value_b"]


def pass_inputs(tokens, steps, s: int, net: dict):
    """What pass `s` sees of a trace: a position's token where it was given
    or unmasked at a pass before `s`, the MASK id elsewhere."""
    return jnp.where(steps < s, tokens, net["vocab_size"] - 1)


def forward(variables: dict, tokens, steps, net: dict, round_to=None,
            mutate=None, experts=None) -> dict:
    """The model on a trace: int tokens [B, T] and the pass each was
    unmasked at, `steps` [B, T] (-1: given), each sequence one episode from
    position 0, T whole blocks of `net["block_length"]`.

    `variables` is the system's own tree, {"params": ...}; `net` the
    configuration's `network` block. `round_to` rounds the blocks'
    activations to that dtype where the system rounds to bfloat16; `mutate`
    (one of `MUTATIONS`) makes the named error: both exist to show that the
    limits refuse them. `experts` [layers, B, (S + 1) T, k], where given,
    are the experts every position of the clean stream ([.., :T]) and of
    each pass's stream is sent to (below 0: its own choice).

    Returns logits [B, T, V - 1] (a position's at the pass it was unmasked
    at; a given position's at pass 0), values [B, T / L] (a block's first
    position at pass 0), experts [L, B, (S + 1) T, k] (each layer's own
    choice, the clean stream's then each pass's), select [L, B, (S + 1) T,
    E] (its router's probabilities)."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                     variables["params"])
    r = _rounder(round_to)
    tokens = jnp.asarray(tokens, jnp.int32)
    steps = jnp.asarray(steps, jnp.int32)
    L, S = net["block_length"], net["denoise_steps"]
    T = tokens.shape[1]
    assert T % L == 0, (T, L)
    positions = jnp.tile(jnp.arange(T), 2)

    def allowed(q, k):
        """Of the 2T positions, the clean half first."""
        q_block, k_block = (q % T) // L, (k % T) // L
        q_noisy, k_noisy = q >= T, k >= T
        if mutate == "noisy_reads_its_clean_block":
            return ~k_noisy & (k_block <= q_block)
        inside = (q_noisy == k_noisy) & (k_block == q_block)
        if mutate == "causal_inside_a_block":
            inside = inside & (k % T <= q % T)
        return (~k_noisy & (k_block < q_block)) | inside

    logits, values, chosen, selects = None, None, [], []
    with jax.default_matmul_precision("highest"):
        for s in range(S):
            both = jnp.concatenate(
                [tokens, pass_inputs(tokens, steps, s, net)], axis=1)
            held = None if experts is None else jnp.concatenate(
                [experts[:, :, :T], experts[:, :, (1 + s) * T:(2 + s) * T]],
                axis=2)
            x, own, probs = _hidden(
                p, both, positions, allowed, net, r, mutate, held)
            x = x[:, T:]
            if mutate == "logits_of_the_next_position":
                x = jnp.roll(x, 1, axis=1)
            ours, value = _heads(p, x, net)
            mine = (jnp.maximum(steps, 0) == s)[..., None]
            logits = jnp.where(mine, ours, 0.0 if logits is None else logits)
            if s == 0:
                values = value[:, ::L]
                chosen.append(own[:, :, :T])
                selects.append(probs[:, :, :T])
            chosen.append(own[:, :, T:])
            selects.append(probs[:, :, T:])
    return {"logits": logits, "values": values,
            "experts": jnp.concatenate(chosen, axis=2),
            "select": jnp.concatenate(selects, axis=2)}


def forward_by_blocks(variables: dict, tokens, steps, net: dict) -> dict:
    """`forward`'s logits and values by the definition: for every block b
    and pass s, ONE plain forward over the clean tokens of the blocks before
    b followed by pass s's inputs of block b, block-causal (a position reads
    every position of its own and earlier blocks), the outputs read at the
    last block."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                     variables["params"])
    tokens = jnp.asarray(tokens, jnp.int32)
    steps = jnp.asarray(steps, jnp.int32)
    L, S = net["block_length"], net["denoise_steps"]
    T = tokens.shape[1]

    def allowed(q, k):
        return k // L <= q // L
    logits, values = [], []
    with jax.default_matmul_precision("highest"):
        for b in range(T // L):
            at = slice(b * L, (b + 1) * L)
            mine = None
            for s in range(S):
                seen = jnp.concatenate(
                    [tokens[:, :b * L],
                     pass_inputs(tokens, steps, s, net)[:, at]], axis=1)
                x, _, _ = _hidden(p, seen, jnp.arange((b + 1) * L), allowed,
                                  net, lambda a: a, None, None)
                ours, value = _heads(p, x[:, at], net)
                taken = (jnp.maximum(steps[:, at], 0) == s)[..., None]
                mine = jnp.where(taken, ours, 0.0 if mine is None else mine)
                if s == 0:
                    values.append(value[:, 0])
            logits.append(mine)
    return {"logits": jnp.concatenate(logits, axis=1),
            "values": jnp.stack(values, axis=1)}


def block_vtrace(log_rhos, discounts, rewards, values, cfg: dict):
    """(vs, pg_advantages) [B, n] of V-trace over n steps from importance
    ratios exp(`log_rhos`), every sequence ending its episode at its last
    step (discount 0 there, so no bootstrap value is needed)."""
    lam = cfg.get("lambda", 1.0)
    rhos = jnp.exp(log_rhos)
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
    return vs, pg_adv


def vtrace_loss(variables: dict, batch: dict, net: dict, cfg: dict,
                mutate=None):
    """IMPALA's loss of one minibatch of whole episodes with a BLOCK as the
    action (the module docstring). `batch`: tokens, steps [B, T] int,
    rewards, behaviour_logp [B, T] (a given position's are not read), and
    where given `experts` (`forward`'s). Returns (total, parts)."""
    out = forward(variables, batch["tokens"], batch["steps"], net,
                  mutate=mutate, experts=batch.get("experts"))
    L = net["block_length"]
    tokens = jnp.asarray(batch["tokens"], jnp.int32)
    generated = jnp.asarray(batch["steps"]) >= 0
    B, T = tokens.shape

    def by_block(x):
        """The sum over a block's generated positions, [B, T / L]."""
        return jnp.sum(jnp.where(generated, x, 0.0).reshape(
            B, T // L, L), axis=-1)
    logp_all = jax.nn.log_softmax(out["logits"], axis=-1)
    # A given token may be any id; no generated one is the MASK id.
    taken = jnp.take_along_axis(logp_all, jnp.minimum(
        tokens, logp_all.shape[-1] - 1)[..., None], axis=-1)[..., 0]
    target_logp = by_block(taken)
    log_rhos = target_logp - by_block(jnp.asarray(batch["behaviour_logp"]))
    values = out["values"]
    discounts = jnp.full(values.shape, cfg["gamma"]).at[:, -1].set(0.0)
    vs, pg_adv = block_vtrace(
        log_rhos, discounts,
        by_block(jnp.asarray(batch["rewards"], jnp.float32)), values, cfg)
    vs, pg_adv = jax.lax.stop_gradient(vs), jax.lax.stop_gradient(pg_adv)
    pi_loss = -jnp.sum(target_logp * pg_adv)
    vf_loss = 0.5 * jnp.sum((values - vs) ** 2)
    entropy = jnp.sum(by_block(-jnp.sum(jnp.exp(logp_all) * logp_all,
                                        axis=-1)))
    total = (pi_loss + cfg["vf_loss_coeff"] * vf_loss
             - cfg["entropy_coeff"] * entropy)
    return total, {"policy_loss": pi_loss, "vf_loss": vf_loss,
                   "entropy": entropy, "log_rhos": log_rhos}


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
    """The system's choice [L, B, n, k] against the reference's own choice
    in each layer, the reference held to the system's choice in the layers
    before it (`forward(experts=system_experts)` gives `own_experts` and
    `select` so); an entry of the system's below 0 is no choice and is
    judged as the reference's own. A flip's gap is how far below the
    reference's k-th probability the reference puts the least likely expert
    the system chose, as a share of that k-th: 0 is an exact tie."""
    own = np.asarray(own_experts)
    sys_e = np.where(np.asarray(system_experts) >= 0,
                     np.asarray(system_experts), own)
    differ = np.any(np.sort(sys_e, axis=-1) != np.sort(own, axis=-1),
                    axis=-1)  # [L, B, n]
    select = np.asarray(select, np.float64)
    chosen = np.take_along_axis(select, sys_e, axis=-1)
    kth = np.sort(select, axis=-1)[..., -sys_e.shape[-1]]
    gap = (kth - np.min(chosen, axis=-1)) / kth
    flips, gap = float(np.mean(differ)), float(np.max(gap, initial=0.0))
    return {"router_flips": flips, "max_flip_gap": gap,
            "flips_by_layer": [float(f) for f in
                               differ.reshape(len(differ), -1).mean(axis=1)],
            "ok": flips <= MAX_ROUTER_FLIPS and gap <= MAX_FLIP_GAP}
