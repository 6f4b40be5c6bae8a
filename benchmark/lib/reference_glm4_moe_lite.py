"""Plain reference of the `glm4_moe_lite` token policy: forward, the
next-next-token module's loss, V-trace's loss, and the comparison that
decides `correct` in its cells.

Straightforward `jax.numpy`, float32 throughout, matrix precision "highest",
no flax, no cache, no sort, no grouped or batched expert product, latent
attention in its decompressed form only, nothing from `ray_tpu`. The
equations are those of the source named in
`configs/impala_glm_4_7_flash.json` (`model_type: glm4_moe_lite`; the
DeepSeek-V2/V3 family's published form, arXiv:2405.04434 section 2.1 and
arXiv:2412.19437 section 2.2):

    x = E[tokens]
    layer 0 (first_k_dense_replace):  h = x + MLA(RMSNorm(x))
                                      x = h + SwiGLU_dense(RMSNorm(h))
    layers 1..:                       x = h + MoE(RMSNorm(h))
    y = RMSNorm(x);  logits = y W_head (untied);  value = y w_v + b
    MLA:  c_q = RMSNorm(n W_qa);  q = c_q W_qb -> heads x (nope | rope)
          [c_kv | k_r] = n W_kva;  c_kv = RMSNorm(c_kv);  k_r = RoPE(k_r),
          one head shared by all
          [k_nope | v] = c_kv W_kvb -> heads x (nope | v_head_dim)
          q_h = [q_nope | RoPE(q_rope)], k_h = [k_nope | k_r]
          causal softmax(q_h k_h^T / sqrt(nope + rope)) v_h; heads joined;
          W_o. Rotate-half RoPE (assumed: the config does not say).
    Router (noaux_tc, one group): s = sigmoid(n W_r) over all experts;
          the k largest of s + b choose; weights are s there, without b,
          over their sum (norm_topk_prob), times routed_scaling_factor.
    MoE:  sum over the chosen e HELD HERE of w_e SwiGLU_e(n)  (a loop over
          the held experts, each on every token times its 0/1-masked
          weight; what the absent experts would add is left out)
          + SwiGLU_shared(n)
    Module (num_nextn_predict_layers 1), position t, next token u_{t+1}:
          z_t = W_eh [RMSNorm_h(x_t) | RMSNorm_e(E[u_{t+1}])], x_t the
          trunk's hidden before its final norm; one more MoE layer as above
          on z; its own final norm; the trunk's head; cross-entropy
          against u_{t+2} where t + 2 is inside the sequence.

Departures from the source: a value head (an RL policy needs one); no
auxiliary router loss; the selection bias b is a constant (its balancing
update belongs to pre-training); in the objective the module reads x_t, E
and W_head under `stop_gradient` and its loss has the weight
`NEXTN_LOSS_WEIGHT`.

Tolerance. The system keeps parameters, router, final norm and heads in
float32 and the blocks' activations in bfloat16 (8 bits of mantissa, ~0.4 %
a rounding); on the TPU its float32 products run as bf16 passes at default
precision. So it cannot agree with this reference to float32 accuracy.
Measured and bounded, apart:

* the router's choice, A LAYER AT A TIME: this forward is held to the
  experts the system chose (`experts=`), and in each expert layer its own
  choice, from its own scores there, is compared with the system's. So the
  layers before a layer are the system's on both sides, and one early flip
  is not counted again in every later layer. `router_flips` is the share of
  (token, layer) pairs whose sets differ; `max_flip_gap` the largest
  distance between this reference's k-th selection score (s + b) and the
  one it gives the least likely expert the system chose, as a share of the
  k-th. A flip is never a dropped token: the set still has k experts. The
  k-th and (k+1)-th of 64 sigmoid scores lie ~2 % apart at random weights
  and the rounding of bf16 blocks moves each by a few tenths of a percent:
  `MAX_ROUTER_FLIPS` 15 % (the system: 5.6-6.4 % on the v5e, every layer
  alike; the float8 blocks: 76-77 %), `MAX_FLIP_GAP` 5 % (1.2-1.5 %;
  48-56 %).
* the arithmetic. Logits, values and the module's cross-entropy against
  this reference held to the system's experts: logits and values as the
  largest absolute difference over the largest absolute reference value,
  the cross-entropy position by position over the reference's spread
  (`compare_loss`): `TOLERANCE` 6 % (the system, five bf16 blocks deep:
  logits 1.8-2.0 %, values 1.3-2.8 %, the cross-entropy 1.3-1.6 %; the
  float8 blocks: 53-62 %, 43-67 %, 35-58 %). The cross-entropy's mean is
  printed and not judged: at random weights it is the vocabulary's
  logarithm whatever the blocks compute (the system 2e-7 to 1.9e-5 off
  the reference's, the float8 blocks 6e-5 to 7e-4), and the mean of an
  array compared position by position says nothing of the system's own
  sum. That sum is judged where the system makes it, in the update below.
* one update of the learner, by the trainer's own step (`compare_update`):
  the minibatch's loss as the step reports it against `vtrace_loss` here,
  and the change of every parameter (`change_error`) against `adam_change`
  of this reference's gradients from the optimizer state the step began
  with.
  Precision hardly moves either (the loss is sums over 8,192 tokens, a
  new gradient is a tenth of Adam's first moment), so each limit is about
  three times the system's largest reading over its seeds and no float8
  reading stands beside it: `UPDATE_LOSS_TOLERANCE` 0.9 % (5e-6 to
  0.30 %: it sees a term that is a hundredth of the total, not the
  entropy's sign), `UPDATE_TOLERANCE` 25 % (the worst parameter's
  `change_error`, a router's in every run: 7.0-8.2 %; with the gradient
  of the held experts' dispatch wrong, as it was on the chip until this
  check ran, 258-366 %).

Each limit of the forward lies between two readings at published widths
on the v5e (PERF.md section 4; my chip runs, PR 32): the system's largest
over its seeds, and this reference with its blocks rounded to float8_e4m3
(`round_to`, the nearest precision below the stated bfloat16) in the
system's place, which has to be refused, and is by each of them.
"""

import jax
import jax.numpy as jnp
import numpy as np

# The arithmetic both references share (float32 RMSNorm, rotate-half RoPE
# over positions 0..S-1, the float8_e4m3 rounding emulated in float32, the
# errors' measure): one copy.
from lib.reference_olmoe import (  # noqa: F401
    _rms_norm, _rope, _rounder, output_scales, relative_error)

TOLERANCE = 0.06
MAX_ROUTER_FLIPS = 0.15
MAX_FLIP_GAP = 0.05
UPDATE_LOSS_TOLERANCE = 0.009
UPDATE_TOLERANCE = 0.25
NEXTN_LOSS_WEIGHT = 0.1

MUTATIONS = (
    "no_shared_expert", "softmax_router", "bias_left_out_of_choice",
    "bias_in_weights", "no_renormalisation", "no_scaling_factor",
    "rope_on_nope", "latent_norm_left_out", "scale_sqrt_nope",
    "module_without_norms", "module_mask_off_by_one")


def _swiglu(n, w_gate, w_up, w_down, r):
    return r(r(jax.nn.silu(r(n @ w_gate)) * r(n @ w_up)) @ w_down)


def _mla(lp, x, net, r, mutate):
    heads, eps = net["num_attention_heads"], net["rms_norm_eps"]
    rank, nope, rot = (net["kv_lora_rank"], net["qk_nope_head_dim"],
                       net["qk_rope_head_dim"])
    B, S, _ = x.shape
    n = r(_rms_norm(x, lp["attn_norm"], eps))
    c_q = r(_rms_norm(r(n @ lp["wq_a"]), lp["q_a_norm"], eps))
    q = r(c_q @ lp["wq_b"]).reshape(B, S, heads, nope + rot)
    kv = r(n @ lp["wkv_a"])
    c_kv = kv[..., :rank]
    if mutate != "latent_norm_left_out":
        c_kv = r(_rms_norm(c_kv, lp["kv_a_norm"], eps))
    k_r = r(_rope(kv[..., None, rank:], net["rope_theta"]))
    kvb = r(c_kv @ lp["wkv_b"]).reshape(B, S, heads, -1)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    q_nope, q_rope = q[..., :nope], r(_rope(q[..., nope:], net["rope_theta"]))
    if mutate == "rope_on_nope":
        q_nope = _rope(q_nope, net["rope_theta"])
        k_nope = _rope(k_nope, net["rope_theta"])
    q_h = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_h = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, (B, S, heads, rot))], axis=-1)
    width = nope if mutate == "scale_sqrt_nope" else nope + rot
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_h, k_h) / np.sqrt(width)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = r(jax.nn.softmax(scores, axis=-1))
    o = r(jnp.einsum("bhqk,bkhd->bqhd", attn, v)).reshape(B, S, -1)
    return r(x + r(o @ lp["wo"]))


def _moe(lp, bias, h, net, r, mutate, held_to):
    """h + MoE(RMSNorm(h)); (out, this layer's own choice [B, S, k], its
    selection scores [B, S, E]). `held_to` [B, S, k]: the experts every
    token is sent to instead, with the weights computed here for them."""
    eps, k = net["rms_norm_eps"], net["num_experts_per_tok"]
    n = r(_rms_norm(h, lp["mlp_norm"], eps))
    logits = n @ lp["router"]
    if mutate == "softmax_router":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
    select = scores if mutate == "bias_left_out_of_choice" else scores + bias
    _, own = jax.lax.top_k(select, k)
    top_i = own if held_to is None else jnp.asarray(held_to, jnp.int32)
    weigh = select if mutate == "bias_in_weights" else scores
    top_p = jnp.take_along_axis(weigh, top_i, axis=-1)
    if net["norm_topk_prob"] and mutate != "no_renormalisation":
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if mutate != "no_scaling_factor":
        top_p = top_p * net["routed_scaling_factor"]
    moe = jnp.zeros_like(h)
    first = net.get("first_expert_held", 0)
    for e in range(lp["w_gate"].shape[0]):  # the experts held here
        weight = jnp.sum(jnp.where(top_i == first + e, top_p, 0.0), axis=-1)
        moe = moe + weight[..., None] * _swiglu(
            n, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e], r)
    if net["n_shared_experts"] and mutate != "no_shared_expert":
        moe = moe + _swiglu(n, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"], r)
    return r(h + r(moe)), own, select


def forward(variables: dict, tokens, net: dict, round_to=None, mutate=None,
            experts=None) -> dict:
    """The model on int tokens [B, S], each sequence from position 0.

    `variables` is the system's own tree: `params` (`embed`, `layer_<i>`,
    `nextn_0`, `final_norm`, `head`, `value_w`, `value_b`) and `constants`
    (the routers' selection biases), cast to float32. `net` is the
    configuration's `network` block: the published keys, and
    `experts_held` / `first_expert_held`, the share of the routed experts
    that the weights given are. `round_to` rounds the blocks' activations
    to that dtype ("float8_e4m3": emulated in float32; or a jnp dtype)
    where the system rounds to bfloat16; `mutate` (one of `MUTATIONS`)
    makes the named error: both exist to show that the limits refuse them.
    `experts` [expert layers (and the module's), B, S, k], where given,
    are the experts every token is sent to; a layer's own choice is still
    returned, made from its own scores there.

    Returns logits [B, S, V], values [B, S], experts [L, B, S, k] (each
    layer's own choice), select [L, B, S, E] (its selection scores s + b),
    nextn_nll_by_position [B, S] (the module's cross-entropy, 0 where
    t + 2 leaves the sequence), nextn_nll (its sum), nextn_loss (its mean a
    valid position); L counts the expert layers, the module's last."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                     variables["params"])
    biases = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                          variables["constants"])
    r = _rounder(round_to)
    eps = net["rms_norm_eps"]
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[1]
    chosen, selects = [], []

    def expert_layer(name, h):
        held_to = None if experts is None else experts[len(chosen)]
        out, own, select = _moe(p[name], biases[name]["router_bias"], h,
                                net, r, mutate, held_to)
        chosen.append(own)
        selects.append(select)
        return out

    with jax.default_matmul_precision("highest"):
        x = r(p["embed"][tokens])
        for i in range(net["num_hidden_layers"]):
            lp = p[f"layer_{i}"]
            h = _mla(lp, x, net, r, mutate)
            if i < net["first_k_dense_replace"]:
                n = r(_rms_norm(h, lp["mlp_norm"], eps))
                x = r(h + _swiglu(n, lp["dense_gate"], lp["dense_up"],
                                  lp["dense_down"], r))
            else:
                x = expert_layer(f"layer_{i}", h)
        y = _rms_norm(x, p["final_norm"], eps)
        logits = y @ p["head"]
        values = y @ p["value_w"] + p["value_b"]
        out = {"logits": logits, "values": values}
        if net["num_nextn_predict_layers"] and (
                experts is None or len(experts) > len(chosen)):
            lp = p["nextn_0"]
            x = jax.lax.stop_gradient(x)
            embed = jax.lax.stop_gradient(p["embed"])
            following = jnp.roll(tokens, -1, axis=1)
            target = jnp.roll(tokens, -2, axis=1)
            last = 1 if mutate == "module_mask_off_by_one" else 2
            valid = (jnp.arange(S) + last < S)[None]
            if mutate == "module_without_norms":
                joined = jnp.concatenate([x, r(embed[following])], axis=-1)
            else:
                joined = jnp.concatenate([
                    r(_rms_norm(x, lp["hnorm"], eps)),
                    r(_rms_norm(embed[following], lp["enorm"], eps))],
                    axis=-1)
            z = r(joined @ lp["eh_proj"])
            z = expert_layer("nextn_0", _mla(lp, z, net, r, mutate))
            y = _rms_norm(z, lp["final_norm"], eps)
            logp = jax.nn.log_softmax(
                y @ jax.lax.stop_gradient(p["head"]), axis=-1)
            nll = -jnp.take_along_axis(logp, target[..., None], axis=-1)
            out["nextn_nll_by_position"] = jnp.where(valid, nll[..., 0], 0.0)
            out["nextn_nll"] = jnp.sum(out["nextn_nll_by_position"])
            out["nextn_loss"] = out["nextn_nll"] / (
                tokens.shape[0] * (S - 2))
    out["experts"] = jnp.stack(chosen)
    out["select"] = jnp.stack(selects)
    return out


def vtrace_loss(variables: dict, batch: dict, net: dict, cfg: dict,
                mutate=None):
    """IMPALA's loss of one minibatch of whole sequences, as
    `ray_tpu/rllib/agents/impala/vtrace_policy.py` describes it, plus the
    model's own term: sums over the minibatch of -logp * pg_advantage,
    0.5 * (v - vs)^2, the entropy, and `NEXTN_LOSS_WEIGHT` times the
    module's cross-entropy. `batch`: tokens, actions [B, S] int, rewards,
    behaviour_logp [B, S], and every sequence ends its episode at its last
    step (so no bootstrap value is needed). `mutate` is `forward`'s.
    Returns (total, parts)."""
    gamma, lam = cfg["gamma"], cfg.get("lambda", 1.0)
    out = forward(variables, batch["tokens"], net, mutate=mutate)
    logits, values = out["logits"], out["values"]
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
             - cfg["entropy_coeff"] * entropy
             + NEXTN_LOSS_WEIGHT * out["nextn_nll"])
    return total, {"policy_loss": pi_loss, "vf_loss": vf_loss,
                   "entropy": entropy, "nextn_nll": out["nextn_nll"]}


def clip_scale(grads: dict, cfg: dict):
    """(what `optax.clip_by_global_norm(cfg["grad_clip"])` multiplies every
    gradient by, the gradients' global norm): `grads` flat {name: array}."""
    norm = float(jnp.sqrt(sum(
        jnp.sum(jnp.square(jnp.asarray(g, jnp.float32)))
        for g in grads.values())))
    clip = cfg.get("grad_clip")
    return (clip / norm if clip and norm > clip else 1.0), norm


def adam_change(g, mu, nu, count, cfg: dict, scale=1.0):
    """The change one update makes to a parameter, as `optax.adam(lr,
    eps=adam_epsilon)` is defined, from the moments `mu`, `nu` and the
    `count` of updates it began with: m = 0.9 mu + 0.1 g and
    v = 0.999 nu + 0.001 g^2, each over 1 - its decay to the power
    `count` + 1; -lr m / (sqrt(v) + eps). `g` is multiplied by `scale`
    first (`clip_scale`). float32, as the optimizer's own."""
    b1, b2, t = 0.9, 0.999, int(count) + 1
    g = jnp.asarray(g, jnp.float32) * scale
    m = (b1 * jnp.asarray(mu, jnp.float32) + (1 - b1) * g) / (1 - b1 ** t)
    v = (b2 * jnp.asarray(nu, jnp.float32) + (1 - b2) * g * g) / (1 - b2 ** t)
    return -cfg["lr"] * m / (jnp.sqrt(v) + (cfg.get("adam_epsilon") or 1e-7))


def adam_update(grads: dict, mu: dict, nu: dict, count, cfg: dict):
    """({name: `adam_change`} of every parameter under the global clip,
    the gradients' global norm): `optax.chain(clip_by_global_norm(
    grad_clip), adam(lr, eps=adam_epsilon))`, flat {name: array}s."""
    scale, norm = clip_scale(grads, cfg)
    return {name: adam_change(g, mu[name], nu[name], count, cfg, scale)
            for name, g in grads.items()}, norm


def change_error(old, new, want):
    """||(new - old) - want|| / ||want|| of one float32 parameter around
    an update, `want` the reference's change. What `new - old` shows is
    the system's exact change rounded to the parameter's spacing (with
    `lr` 1e-6 a norm's scale of 1.0 moves by a few of its spacings of
    1.2e-7), so half a spacing an element is taken off the distance
    first: what the storage accounts for and the arithmetic does not."""
    old, new = jnp.asarray(old, jnp.float32), jnp.asarray(new, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    largest = jnp.maximum(jnp.abs(old), jnp.abs(new))
    spacing = jnp.nextafter(largest, jnp.inf) - largest
    distance = jnp.linalg.norm(((new - old) - want).ravel())
    stored = jnp.linalg.norm(spacing.ravel() / 2)
    return jnp.maximum(distance - stored, 0.0) / jnp.maximum(
        jnp.linalg.norm(want.ravel()), 1e-30)


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
    `scales` are the outputs' scales where `reference_out` is a slice of
    the positions (an output's scale is that of the whole forward)."""
    errs, ok = {}, True
    scales = scales or output_scales(reference_out)
    for name, got, want, scale in zip(("logits", "value"), system_out,
                                      reference_out, scales):
        errs[name] = relative_error(got, want, scale=scale)
        ok = ok and errs[name] <= TOLERANCE
    return {"errors": errs, "tolerance": TOLERANCE, "ok": bool(ok)}


def compare_loss(got, want) -> dict:
    """The module's cross-entropy, position by position [B, S] (0 where
    t + 2 leaves the sequence), against the reference's: the largest
    difference at a position over the reference's spread (its largest
    distance from its mean; what the logits' scale is to the logits),
    within `TOLERANCE`. A mask that is off by one shows here as a whole
    cross-entropy where the reference has 0. The means are printed beside
    it and not judged (the module docstring says why)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    valid = want != 0.0
    mean = float(np.mean(want[valid]))
    spread = float(np.max(np.abs(want[valid] - mean)))
    by_position = relative_error(got, want, scale=spread)
    return {"error_by_position": by_position,
            "loss": float(np.mean(got[valid])), "reference_loss": mean,
            "ok": bool(by_position <= TOLERANCE)}


def routing_verdict(system_experts, own_experts, select) -> dict:
    """The system's choice [L, B, S, k] against the reference's own choice
    in each layer, the reference held to the system's choice in the layers
    before it (`forward(experts=system_experts)` gives `own_experts` and
    `select` so). A flip's gap is how far below the reference's k-th
    selection score the reference puts the least likely expert the system
    chose, as a share of that k-th score: 0 is an exact tie."""
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
