"""Plain reference of the OLMoE token policy: forward, V-trace loss, and the
comparison that decides `correct` in the token cells.

Straightforward `jax.numpy`, float32 throughout, matrix precision "highest",
no flax, no cache, no sort, no grouped product, nothing from `ray_tpu`. The
equations are OLMoE's (arXiv:2409.02060; `modeling_olmoe.py` of the source
named in `configs/impala_olmoe_1b_7b.json`):

    x = E[tokens]
    per layer:  h = x + Attn(RMSNorm(x));  x = h + MoE(RMSNorm(h))
    y = RMSNorm(x);  logits = y W_head;  value = y w_v + b
    Attn: q, k, v = n W_q, n W_k, n W_v (no bias); q_norm and k_norm are
          RMSNorms over the whole projection before the split into heads;
          rotate-half RoPE; causal softmax(q k^T / sqrt(head_dim)) v; W_o
    MoE:  p = softmax(n W_r) over all experts; the k largest p and their
          experts; the weights are those p as they are (`norm_topk_prob`
          false: not renormalised); sum_e p_e W_down,e (silu(W_gate,e n) *
          W_up,e n). Every expert is computed for every token here and
          multiplied by a 0/1 mask: dropless by construction.

Departures from the source: a value head (OLMoE has none; an RL policy needs
one), and no auxiliary router loss in the objective.

Tolerance. The system keeps parameters, router, final norm and heads in
float32 and the block's activations in bfloat16 (8 bits of mantissa, ~0.4 %
a rounding); on the TPU its float32 head products run as bf16 passes at
default precision. So it cannot agree with this reference to float32
accuracy. Two things are measured and bounded, apart:

* the router's choice. The router is float32 on both sides, but its input is
  the bf16 RMSNorm output in the system and the float32 one here, so the k-th
  and (k+1)-th probabilities swap where they tie within that rounding.
  `router_flips` is the share of (token, layer) pairs whose set of chosen
  experts differs from this reference's; `max_flip_gap` is the largest
  distance, over all tokens, between this reference's k-th probability and
  the one it gives the least likely expert the system chose, as a share of
  the k-th (0: an exact tie). A flip is never a dropped token: the set still
  has k experts. With random weights the router is near uniform, the k-th
  and (k+1)-th of 64 lie ~3 % apart and bf16 moves each by up to ~1 %:
  `MAX_ROUTER_FLIPS` 15 % (the system: 4.3-5.5 % on the v5e over PR 27's
  seeds; the float8 block: 79-81 %), `MAX_FLIP_GAP` 5 %.
* the arithmetic. Logits and values against this reference HELD to the
  experts the system chose (`experts=`; their probabilities are this
  forward's own), as the largest absolute difference over the largest
  absolute reference value, per output (as `reference.py`): `TOLERANCE` 3 %.
  One swapped expert of eight moves a token's logits by a quarter of their
  scale at random weights, so an error taken against the free router would
  measure the ties, not the arithmetic.

Readings at published widths are in PERF.md section 4. This reference with
its block rounded to float8_e4m3 (`round_to`, the nearest precision below the
stated bfloat16), with the eighth expert dropped, or with renormalised weights
(`mutate`), comes out above `TOLERANCE` under the same held routing.
"""

import jax
import jax.numpy as jnp
import numpy as np

TOLERANCE = 0.03
MAX_ROUTER_FLIPS = 0.15
MAX_FLIP_GAP = 0.05


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return weight * (x * jax.lax.rsqrt(var + eps))


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x, theta):
    """x: [B, S, heads, head_dim], positions 0..S-1."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    return x * jnp.cos(angles) + _rotate_half(x) * jnp.sin(angles)


def _round_e4m3(x):
    """x rounded to the nearest float8_e4m3 value (4 significant bits,
    largest 448, steps of 2^-9 below 2^-6), in float32 arithmetic: the
    same on every backend, which a hardware fp8 conversion is not."""
    x = jnp.clip(x, -448.0, 448.0)
    _, exponent = jnp.frexp(x)  # x = m * 2^exponent, 0.5 <= |m| < 1
    step = jnp.exp2((jnp.maximum(exponent, -5) - 4).astype(jnp.float32))
    return jnp.round(x / step) * step


def _rounder(round_to):
    if round_to is None:
        return lambda x: x
    if round_to == "float8_e4m3":
        return _round_e4m3
    return lambda x: x.astype(round_to).astype(jnp.float32)


def forward(params: dict, tokens, net: dict, round_to=None, mutate=None,
            experts=None):
    """(logits [B, S, V], values [B, S], experts [layers, B, S, k]) for int
    tokens [B, S], each sequence from position 0.

    `params` is the system's own parameter tree (`embed`, `layer_<i>`,
    `final_norm`, `head`, `value_w`, `value_b`), cast to float32. `net` is
    the configuration's `network` block. `round_to` rounds the block's
    activations to that dtype ("float8_e4m3": emulated in float32; or a
    jnp dtype) where the system rounds to bfloat16;
    `mutate` in {"drop_last_expert", "renormalise"} makes the named error.
    Both exist to show that the tolerance refuses them. `experts`
    [layers, B, S, k], where given, are the experts every token is sent
    to (with the probabilities this forward computes for them): the
    router's choice is then the caller's, and only the arithmetic is
    compared. Also returns the router's probabilities [layers, B, S, E]."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    r = _rounder(round_to)
    heads, eps, k = net["num_attention_heads"], net["rms_norm_eps"], \
        net["num_experts_per_tok"]
    tokens = jnp.asarray(tokens, jnp.int32)
    B, S = tokens.shape
    chosen, router_probs = [], []
    with jax.default_matmul_precision("highest"):
        x = r(p["embed"][tokens])
        for i in range(net["num_hidden_layers"]):
            lp = p[f"layer_{i}"]
            n = r(_rms_norm(x, lp["attn_norm"], eps))
            q = r(_rms_norm(r(n @ lp["wq"]), lp["q_norm"], eps))
            kk = r(_rms_norm(r(n @ lp["wk"]), lp["k_norm"], eps))
            v = r(n @ lp["wv"])
            q = r(_rope(q.reshape(B, S, heads, -1), net["rope_theta"]))
            kk = r(_rope(kk.reshape(B, S, heads, -1), net["rope_theta"]))
            v = v.reshape(B, S, heads, -1)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(
                q.shape[-1])
            causal = jnp.tril(jnp.ones((S, S), bool))
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            attn = r(jax.nn.softmax(scores, axis=-1))
            o = r(jnp.einsum("bhqk,bkhd->bqhd", attn, v)).reshape(B, S, -1)
            h = r(x + r(o @ lp["wo"]))

            n = r(_rms_norm(h, lp["mlp_norm"], eps))
            probs = jax.nn.softmax(n @ lp["router"], axis=-1)
            top_p, top_i = jax.lax.top_k(probs, k)
            if experts is not None:
                top_i = jnp.asarray(experts[i], jnp.int32)
                top_p = jnp.take_along_axis(probs, top_i, axis=-1)
            chosen.append(top_i)
            router_probs.append(probs)
            if mutate == "drop_last_expert":
                top_p = top_p.at[..., -1].set(0.0)
            if mutate == "renormalise":
                top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
            # weight[b, s, e] = p_e where e was chosen, else 0.
            weight = jnp.sum(
                jax.nn.one_hot(top_i, probs.shape[-1]) * top_p[..., None],
                axis=-2)

            def add_expert(moe, expert):
                # One expert on every token, times its 0/1-masked weight.
                w_gate, w_up, w_down, w = expert
                act = r(jax.nn.silu(r(n @ w_gate)) * r(n @ w_up))
                return moe + w[..., None] * r(act @ w_down), None

            # A loop over the experts (scanned, so that 64 of them
            # compile as one body).
            moe, _ = jax.lax.scan(
                add_expert, jnp.zeros_like(h),
                (lp["w_gate"], lp["w_up"], lp["w_down"],
                 jnp.moveaxis(weight, -1, 0)))
            x = r(h + r(moe))
        y = _rms_norm(x, p["final_norm"], eps)
        logits = y @ p["head"]
        values = y @ p["value_w"] + p["value_b"]
    return logits, values, jnp.stack(chosen), jnp.stack(router_probs)


def vtrace_loss(params: dict, batch: dict, net: dict, cfg: dict):
    """IMPALA's loss of one minibatch of whole sequences, as
    `ray_tpu/rllib/agents/impala/vtrace_policy.py` describes it: sums over
    the minibatch of -logp * pg_advantage, 0.5 * (v - vs)^2 and the
    entropy. `batch`: tokens, actions [B, S] int, rewards, behaviour_logp
    [B, S], and every sequence ends its episode at its last step (so no
    bootstrap value is needed). Returns (total, parts)."""
    gamma, lam = cfg["gamma"], cfg.get("lambda", 1.0)
    logits, values, _, _ = forward(params, batch["tokens"], net)
    actions = jnp.asarray(batch["actions"], jnp.int32)
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    target_logp = jnp.take_along_axis(
        logp_all, actions[..., None], axis=-1)[..., 0]
    rhos = jnp.exp(target_logp - jnp.asarray(batch["behaviour_logp"]))
    S = actions.shape[1]
    discounts = jnp.full(actions.shape, gamma).at[:, -1].set(0.0)
    rewards = jnp.asarray(batch["rewards"], jnp.float32)
    clipped = jnp.minimum(cfg["vtrace_clip_rho_threshold"], rhos)
    cs = lam * jnp.minimum(1.0, rhos)
    next_values = jnp.concatenate(
        [values[:, 1:], jnp.zeros_like(values[:, :1])], axis=1)
    deltas = clipped * (rewards + discounts * next_values - values)
    acc = jnp.zeros_like(values[:, 0])
    vs_minus_v = []
    for t in reversed(range(S)):
        acc = deltas[:, t] + discounts[:, t] * cs[:, t] * acc
        vs_minus_v.append(acc)
    vs = jnp.stack(vs_minus_v[::-1], axis=1) + values
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


def relative_error(got, want, scale=None) -> float:
    """Largest |got - want| over `scale` (None: the largest |want|)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    if scale is None:
        scale = float(np.max(np.abs(want)))
    return float(np.max(np.abs(got - want)) / max(scale, 1e-12))


def router_flips(system_experts, reference_experts, reference_probs):
    """(share of (token, layer) pairs whose expert sets differ, the
    largest gap of a flip). A flip's gap is how far below the reference's
    k-th probability the reference puts the least likely expert the system
    chose, as a share of that k-th probability: 0 is an exact tie."""
    sys_e = np.asarray(system_experts)
    a, b = np.sort(sys_e, axis=-1), np.sort(np.asarray(reference_experts),
                                            axis=-1)
    differ = np.any(a != b, axis=-1)  # [layers, B, S]
    probs = np.asarray(reference_probs, np.float64)
    chosen = np.take_along_axis(probs, sys_e, axis=-1)
    kth = np.sort(probs, axis=-1)[..., -sys_e.shape[-1]]
    gap = (kth - np.min(chosen, axis=-1)) / kth
    return float(np.mean(differ)), float(np.max(gap, initial=0.0))


def output_scales(reference_out) -> tuple:
    """The scale of each output: its largest absolute reference value."""
    return tuple(float(np.max(np.abs(np.asarray(w)))) for w in reference_out)


def compare(system_out, reference_out, scales=None) -> dict:
    """Per-output relative errors and the verdict. `scales` are the
    outputs' scales where `reference_out` is a slice of the positions (an
    output's scale is that of the whole forward)."""
    errs, ok = {}, True
    scales = scales or output_scales(reference_out)
    for name, got, want, scale in zip(("logits", "value"), system_out,
                                      reference_out, scales):
        errs[name] = relative_error(got, want, scale=scale)
        ok = ok and errs[name] <= TOLERANCE
    return {"errors": errs, "tolerance": TOLERANCE, "ok": bool(ok)}


def routing_verdict(system_experts, reference_experts, reference_probs):
    flips, gap = router_flips(system_experts, reference_experts,
                              reference_probs)
    return {"router_flips": flips, "max_flip_gap": gap,
            "ok": flips <= MAX_ROUTER_FLIPS and gap <= MAX_FLIP_GAP}
