"""Transformer policies in flax: the OLMoE sparse-expert block.

`OlmoeNetwork` is OLMoE's decoder (arXiv:2409.02060, `model_type: olmoe`)
as a token policy: observations are token ids, the action logits are the
language-model head's, and a value head reads the same final hidden vector.

    x = E[tokens]
    per layer:  h = x + Attn(RMSNorm(x));  x = h + MoE(RMSNorm(h))
    y = RMSNorm(x);  logits = y W_head (untied);  value = y w_v + b
    Attn: q, k, v = n W_q, n W_k, n W_v (no bias); q_norm, k_norm: RMSNorm
          over the whole projection before the split into heads;
          rotate-half RoPE; causal softmax(q k^T / sqrt(head_dim)) v; W_o
    MoE:  p = softmax(n W_r) in float32; the k largest p; weights are those
          p as they are unless `norm_topk_prob`; sum_e p_e W_down,e
          (silu(W_gate,e n) * W_up,e n). Dropless: no capacity, no token
          dropped or re-routed. One sum, two blockings, chosen from the
          static shape (`experts_batched`). Grouped: tokens are sorted by
          expert, multiplied group by group (`jax.lax.ragged_dot`: each
          expert's rows, however many) and un-sorted; the learner's
          minibatch and a prefill. Batched: every row through every expert
          in products batched over the experts, each term weighted p_e or
          exactly 0 before the sum; a decode step, whose groups of a few
          rows would each cost the grouped product an MXU tile while the
          step is bound by reading every expert's weights once anyway.

Departures from the published model: the value head (OLMoE has none); no
auxiliary router loss (the RL objective has no place for it; the
`expert_load_*` counters show what follows); parameters, router, final norm
and heads are float32 and the block's activations `compute_dtype`
(bfloat16: the repo's convention, as the Nature-CNN's trunk); key/value
heads equal query heads (OLMoE's own layout; grouped heads are refused).

One set of parameters, two forms (the stateful-policy protocol of
`JaxPolicy`: `model(obs[B, T], state, reset[B, T])`):

* `causal`: [B, T] tokens from an empty window, one pass; a `reset` inside
  the fragment starts a new episode (its own positions, no attention across
  the boundary). The learner's form, and the prefill.
* `decode`: one token a row against a key/value cache of `context_len`
  positions a layer ([B, S, heads, head_dim], `compute_dtype`); appends the
  position's K/V and returns its logits and value. The rollout's form.
  Its attention reads the cache positions [0, n) only, n the furthest
  position any row of the batch holds, rounded up to a block of
  `DECODE_CACHE_BLOCK` positions and chosen inside the step from `pos`
  (`cached_attention`); each row masks what it does not hold itself.

Both return the cache, so a decode can follow a causal pass.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

Dtype = Any

# HF `config.json` keys the family is described by -> module fields.
OLMOE_CONFIG_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "num_attention_heads": "num_heads",
    "num_hidden_layers": "num_layers",
    "num_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "intermediate_size": "expert_width",
    "max_position_embeddings": "context_len",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    "norm_topk_prob": "norm_topk_prob",
}


def rms_norm(x, weight, eps, dtype):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (weight * (x32 * jax.lax.rsqrt(var + eps))).astype(dtype)


def rope(x, positions, theta):
    """Rotate-half RoPE. x: [..., heads, head_dim]; positions: x.shape[:-2]."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)[..., None, :]
    x32 = x.astype(jnp.float32)
    half = dim // 2
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * jnp.cos(angles) + rotated * jnp.sin(angles)).astype(x.dtype)


def route(n, router, k, renormalise):
    """Float32 router: (weights [M, k], experts [M, k]) for rows n [M, H]."""
    with jax.named_scope("policy/router"):
        logits = jnp.dot(n.astype(jnp.float32), router,
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        if renormalise:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_i


# The grouped form's costs in units of the batched form's, whose cost is a
# row through one expert: a group costs GROUP_COST_ROWS whatever it holds
# (at 16 rows a group the three `ragged_dot`s run at 37 % of the bandwidth
# their weights could be read with) and a routed row GROUPED_ROW_COST (the
# grouped product runs further from the MXU's peak than the batched one).
# Fitted to a sweep of both forms on a v5e at the published widths, 64
# experts, 8 a token (PERF.md section 5): forward, batched 2.4x faster at
# 128 rows and 1.3x at 512, 2 % slower at 768 and 1.3x at 1,024; forward
# and backward, 1.4x faster at 512 and 1.1x slower at 1,024.
GROUP_COST_ROWS = 540
GROUPED_ROW_COST = 1.7


# Positions in a block of the caches a decode step's attention reads (see
# `cached_attention`). A window that fills from empty is read `1/2 + b/(2S)`
# of, so a smaller block reads less; every block of the window is one more
# branch of the step's `switch`, traced wherever a decode step is (the
# rollout, and the learner's bootstrap step under `value_and_grad`) on
# every start, compile cache or not. On a v5e at the published widths, 128
# rows, a window of 1,024 (PERF.md section 5), a decode step / the token
# cell's warm set-up once the chip is open: the window read whole 3.11 ms
# / 22.5 s; blocks of 512 2.64 ms; of 256 2.44 ms / 23.1 s; of 128 2.35 ms
# / 25.1 s; of 64 2.32 ms.
DECODE_CACHE_BLOCK = 256


def cached_attention(q, k_cache, v_cache, pos):
    """softmax(q k^T / sqrt(head_dim)) v of one query a row, q [B, heads,
    head_dim], over the positions [0, pos[b]] that row holds of the caches
    [B, S, heads, head_dim]. Returns ([B, heads, head_dim] in q's dtype, the
    positions read).

    Read, scored and multiplied are the positions [0, n) alone: n is the
    furthest position any row holds, rounded up to whole blocks. A position
    a row does not hold has weight exp(-inf) = 0 in both sums, so leaving
    those beyond every row unread changes no term; within [0, n) each row
    masks its own. One `switch` over the static prefixes: every branch is
    the same arithmetic on a shorter axis (float32 scores and softmax, the
    weights normalised, cast, then multiplied), and a `switch` has a
    transpose, which a loop whose trip count comes from data has not (the
    learner differentiates its bootstrap step through this).

    Both products are written as what they are, a matrix times one vector
    a row and head: operands in q's dtype, multiplied and summed in
    float32. Outside a conditional XLA:TPU makes that of the einsum itself;
    inside one it made the scores a convolution over a transposed copy of
    the prefix, and the step was slower than with the window read whole
    (3.08 against 2.97 ms, PERF.md section 5). In this form the fusion
    that multiplies the prefix reads it where it lies."""
    S = k_cache.shape[1]
    ends = tuple(range(DECODE_CACHE_BLOCK, S, DECODE_CACHE_BLOCK)) + (S,)
    f32 = jnp.float32

    def attend(n, q, k_cache, v_cache, pos):
        held = jnp.arange(n)[None, :] <= pos[:, None]
        k, v = k_cache[:, :n].astype(f32), v_cache[:, :n].astype(f32)
        # [B, n, heads]
        scores = jnp.sum(q[:, None].astype(f32) * k, axis=-1) * (
            q.shape[-1] ** -0.5)
        scores = jnp.where(held[:, :, None], scores, -jnp.inf)
        attn = jax.nn.softmax(scores, axis=1).astype(q.dtype)
        return jnp.sum(attn[..., None].astype(f32) * v,
                       axis=1).astype(q.dtype)

    block = jnp.minimum(jnp.max(pos) // DECODE_CACHE_BLOCK, len(ends) - 1)
    o = jax.lax.switch(block, [functools.partial(attend, n) for n in ends],
                       q, k_cache, v_cache, pos)
    return o, jnp.asarray(ends)[block]


def experts_batched(M: int, k: int, E: int) -> bool:
    """Whether `M` rows, each routed to `k` of `E` experts, go through the
    batched form (`M * E` rows of work) or the grouped one (`M * k` sorted
    rows in `E` groups): a function of the static shape alone."""
    return M * E <= GROUP_COST_ROWS * E + GROUPED_ROW_COST * M * k


def dropless_experts(n, top_p, top_i, w_gate, w_up, w_down):
    """sum_e p_e W_down,e (silu(W_gate,e n) * W_up,e n) for rows n [M, H]
    routed to `top_i` [M, k] with weights `top_p`; expert weights
    [E, H, W] / [E, W, H] already in n's dtype. Returns ([M, H], rows a
    group [E]).

    Two forms of that sum, chosen by `experts_batched(M, k, E)`; both take
    operands in n's dtype, accumulate in float32, weight in float32 and
    compute every chosen expert of every row.

    Grouped: the M*k (row, expert) pairs sorted by expert, three
    `ragged_dot`s over the E groups, un-sorted, the k terms of a row
    weighted and summed.

    Batched: c[m, e] = p[m, j] where top_i[m, j] == e, else 0;
    a[e, m] = silu(n W_gate,e) * (n W_up,e) for all M rows and every
    expert; out[m] = sum_e c[m, e] a[e, m] W_down,e, the weight applied to
    a and e folded into the contraction: one product of [M, E*W] against
    W_down as [E*W, H]. The same sum: an expert a row did not choose has
    weight exactly 0. It does E/k times the matrix work and reads each
    expert's weights once, where they lie."""
    M, k = top_i.shape
    E = w_gate.shape[0]
    with jax.named_scope("policy/dispatch"):
        group_sizes = jnp.zeros(E, jnp.int32).at[top_i.reshape(-1)].add(1)
    if experts_batched(M, k, E):
        with jax.named_scope("policy/dispatch"):
            chosen = top_i[:, :, None] == jnp.arange(E)
            c = jnp.sum(jnp.where(chosen, top_p[:, :, None], 0.0), axis=1)
        with jax.named_scope("policy/experts_batched"):
            gate = jnp.einsum("mh,ehw->emw", n, w_gate)
            up = jnp.einsum("mh,ehw->emw", n, w_up)
            a = (c.T[:, :, None] * (jax.nn.silu(gate) * up)).astype(n.dtype)
            mixed = jnp.einsum("emw,ewh->mh", a, w_down,
                               preferred_element_type=jnp.float32)
        return mixed.astype(n.dtype), group_sizes
    with jax.named_scope("policy/dispatch"):
        order = jnp.argsort(top_i.reshape(-1), stable=True)
        rows = n[order // k]
    with jax.named_scope("policy/experts"):
        gate = jax.lax.ragged_dot(rows, w_gate, group_sizes)
        up = jax.lax.ragged_dot(rows, w_up, group_sizes)
        out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w_down, group_sizes)
    with jax.named_scope("policy/dispatch"):
        unsorted = out[jnp.argsort(order)].reshape(M, k, -1)
        mixed = jnp.einsum("mkh,mk->mh", unsorted.astype(jnp.float32), top_p)
    return mixed.astype(n.dtype), group_sizes


class OlmoeLayerParams(nn.Module):
    """One layer's parameters, by the names the equations use."""

    hidden_size: int
    num_experts: int
    expert_width: int

    def setup(self):
        H, E, W = self.hidden_size, self.num_experts, self.expert_width
        dense = nn.initializers.lecun_normal()
        experts = nn.initializers.lecun_normal(batch_axis=(0,))
        ones = nn.initializers.ones
        shapes = {
            "attn_norm": (ones, (H,)), "q_norm": (ones, (H,)),
            "k_norm": (ones, (H,)), "mlp_norm": (ones, (H,)),
            "wq": (dense, (H, H)), "wk": (dense, (H, H)),
            "wv": (dense, (H, H)), "wo": (dense, (H, H)),
            "router": (dense, (H, E)),
            "w_gate": (experts, (E, H, W)), "w_up": (experts, (E, H, W)),
            "w_down": (experts, (E, W, H)),
        }
        self.tensors = {name: self.param(name, init, shape)
                        for name, (init, shape) in shapes.items()}

    def __call__(self) -> dict:
        return self.tensors


class OlmoeNetwork(nn.Module):
    """OLMoE as a token policy (see the module docstring)."""

    num_outputs: int
    vocab_size: int = 50304
    hidden_size: int = 2048
    num_heads: int = 16
    num_layers: int = 16
    num_experts: int = 64
    experts_per_token: int = 8
    expert_width: int = 1024
    context_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    norm_topk_prob: bool = False
    compute_dtype: Dtype = jnp.bfloat16

    def setup(self):
        H = self.hidden_size
        self.embed = self.param(
            "embed", nn.initializers.normal(0.02), (self.vocab_size, H))
        self.layers = [
            OlmoeLayerParams(H, self.num_experts, self.expert_width,
                             name=f"layer_{i}")
            for i in range(self.num_layers)]
        self.final_norm = self.param("final_norm", nn.initializers.ones, (H,))
        self.head = self.param(
            "head", nn.initializers.normal(0.01), (H, self.num_outputs))
        self.value_w = self.param(
            "value_w", nn.initializers.normal(0.02), (H,))
        self.value_b = self.param("value_b", nn.initializers.zeros, ())

    # -- the protocol ---------------------------------------------------
    def initial_state(self, batch_size: int):
        """An empty window: per layer a K and a V cache, and each row's
        count of positions held."""
        shape = (batch_size, self.context_len, self.num_heads,
                 self.hidden_size // self.num_heads)
        return {
            "kv": tuple((jnp.zeros(shape, self.compute_dtype),
                         jnp.zeros(shape, self.compute_dtype))
                        for _ in range(self.num_layers)),
            "pos": jnp.zeros(batch_size, jnp.int32),
        }

    def decode_counters(self, batch_size: int) -> dict:
        """What a decode step of `batch_size` rows is, from its static
        shape: the mean rows an expert group holds, whether the experts
        multiply in the batched form (1.0) or the grouped one (0.0), and
        the positions in a block of the caches its attention reads."""
        k, E = self.experts_per_token, self.num_experts
        return {
            "decode_rows_per_expert": batch_size * k / E,
            "decode_experts_batched": float(
                experts_batched(batch_size, k, E)),
            "decode_cache_block": min(DECODE_CACHE_BLOCK, self.context_len),
        }

    def __call__(self, obs, state, reset):
        """obs [B, T] token ids, reset [B, T] (1 where an episode starts
        at that step) -> (logits [B, T, V], value [B, T], state). T = 1 is
        a decode step against `state`; T > 1 is a causal pass from an
        empty window (`state` is not read)."""
        if obs.shape[1] == 1:
            logits, value, state = self.decode(obs[:, 0], state, reset[:, 0])
            return logits[:, None], value[:, None], state
        return self.causal(obs, reset)

    # -- shared pieces --------------------------------------------------
    def _qkv(self, lp, n):
        cd, eps = self.compute_dtype, self.rms_eps
        heads = n.shape[:-1] + (self.num_heads, -1)
        q = rms_norm(jnp.dot(n, lp["wq"].astype(cd)), lp["q_norm"], eps, cd)
        k = rms_norm(jnp.dot(n, lp["wk"].astype(cd)), lp["k_norm"], eps, cd)
        v = jnp.dot(n, lp["wv"].astype(cd))
        return q.reshape(heads), k.reshape(heads), v.reshape(heads)

    def _moe(self, lp, h):
        """h + MoE(RMSNorm(h)) for rows h [M, H]; (out, rows a group,
        experts [M, k])."""
        cd = self.compute_dtype
        n = rms_norm(h, lp["mlp_norm"], self.rms_eps, cd)
        top_p, top_i = route(n, lp["router"], self.experts_per_token,
                             self.norm_topk_prob)
        moe, group_sizes = dropless_experts(
            n, top_p, top_i, lp["w_gate"].astype(cd), lp["w_up"].astype(cd),
            lp["w_down"].astype(cd))
        return h + moe, group_sizes, top_i

    def _heads(self, x):
        with jax.named_scope("policy/head"):
            y = rms_norm(x, self.final_norm, self.rms_eps, jnp.float32)
            logits = jnp.dot(y, self.head)
            value = jnp.dot(y, self.value_w) + self.value_b
        return logits, value

    def _count(self, experts, loads=None, read=None):
        """What a pass counted, kept only where the caller asks for the
        collection (and never among the variables `init` returns): the
        experts chosen [layers, ..., k], for the reference check; in the
        learner's form also the rows of the fullest expert group over the
        layers, and the mean group; in a decode step the share of the
        window's positions its attention read."""
        if self.is_initializing():
            return
        self.sow("routing", "experts", jnp.stack(experts))
        if loads is not None:
            loads = jnp.stack(loads).astype(jnp.float32)
            self.sow("counters", "expert_load_max", jnp.max(loads))
            self.sow("counters", "expert_load_mean", jnp.mean(loads))
        if read is not None:
            self.sow("counters", "decode_cache_read_share",
                     read.astype(jnp.float32) / self.context_len)

    # -- the two forms --------------------------------------------------
    def causal(self, tokens, reset):
        cd, eps = self.compute_dtype, self.rms_eps
        B, T = tokens.shape
        S = self.context_len
        if T > S:
            raise ValueError(
                f"a fragment of {T} tokens does not fit the model's "
                f"window of {S} positions (max_position_embeddings)")
        steps = jnp.arange(T)
        # An episode starts at step 0 and wherever `reset` says.
        starts = (reset > 0).at[:, 0].set(True)
        episode = jnp.cumsum(starts, axis=1)
        start = jax.lax.cummax(jnp.where(starts, steps, 0), axis=1)
        positions = steps - start
        mask = (steps[:, None] >= steps[None, :])[None] & (
            episode[:, :, None] == episode[:, None, :])
        # Where the last episode's K/V go in the cache: its own positions.
        cache_rows = jnp.clip(start[:, -1:] + jnp.arange(S), 0, T - 1)

        x = self.embed[tokens].astype(cd)
        kv, loads, experts = [], [], []
        for layer in self.layers:
            lp = layer()
            with jax.named_scope("policy/attention"):
                n = rms_norm(x, lp["attn_norm"], eps, cd)
                q, k, v = self._qkv(lp, n)
                q = rope(q, positions, self.rope_theta)
                k = rope(k, positions, self.rope_theta)
                scores = jnp.einsum(
                    "bqhd,bkhd->bhqk", q, k,
                    preferred_element_type=jnp.float32) * (
                        q.shape[-1] ** -0.5)
                scores = jnp.where(mask[:, None], scores, -jnp.inf)
                attn = jax.nn.softmax(scores, axis=-1).astype(cd)
                o = jnp.einsum("bhqk,bkhd->bqhd", attn, v).reshape(B, T, -1)
                h = x + jnp.dot(o, lp["wo"].astype(cd))
                kv.append(tuple(
                    jnp.take_along_axis(
                        a, cache_rows[:, :, None, None], axis=1)
                    for a in (k, v)))
            out, group_sizes, top_i = self._moe(lp, h.reshape(B * T, -1))
            x = out.reshape(B, T, -1)
            loads.append(group_sizes)
            experts.append(top_i.reshape(B, T, -1))
        self._count(experts, loads)
        logits, value = self._heads(x)
        return logits, value, {"kv": tuple(kv), "pos": positions[:, -1] + 1}

    def decode(self, token, state, reset):
        cd, eps = self.compute_dtype, self.rms_eps
        B = token.shape[0]
        pos = jnp.where(reset > 0, 0, state["pos"])
        rows = jnp.arange(B)

        x = self.embed[token].astype(cd)
        kv, experts = [], []
        for layer, (k_cache, v_cache) in zip(self.layers, state["kv"]):
            lp = layer()
            with jax.named_scope("policy/attention"):
                n = rms_norm(x, lp["attn_norm"], eps, cd)
                q, k, v = self._qkv(lp, n)
                q = rope(q, pos, self.rope_theta)
                k = rope(k, pos, self.rope_theta)
                k_cache = k_cache.at[rows, pos].set(k)
                v_cache = v_cache.at[rows, pos].set(v)
                o, read = cached_attention(q, k_cache, v_cache, pos)
                h = x + jnp.dot(o.reshape(B, -1), lp["wo"].astype(cd))
                kv.append((k_cache, v_cache))
            x, _, top_i = self._moe(lp, h)
            experts.append(top_i)
        self._count(experts, read=read)
        logits, value = self._heads(x)
        return logits, value, {"kv": tuple(kv), "pos": pos + 1}


def olmoe_from_config(num_outputs: int, cfg: dict, compute_dtype=None):
    """`OlmoeNetwork` from a `custom_model_config` that speaks the
    published `config.json`'s own keys (unknown keys are refused)."""
    unknown = set(cfg) - set(OLMOE_CONFIG_KEYS) - {"num_key_value_heads"}
    if unknown:
        raise ValueError(
            f"custom_model_config keys {sorted(unknown)} are not OLMoE's; "
            f"known: {sorted(OLMOE_CONFIG_KEYS)}")
    kv = cfg.get("num_key_value_heads")
    if kv is not None and kv != cfg.get("num_attention_heads", 16):
        raise ValueError(
            "OlmoeNetwork has as many key/value heads as query heads "
            f"(OLMoE's layout); got num_key_value_heads={kv}")
    fields = {OLMOE_CONFIG_KEYS[k]: v for k, v in cfg.items()
              if k in OLMOE_CONFIG_KEYS}
    if compute_dtype is not None:
        fields["compute_dtype"] = compute_dtype
    return OlmoeNetwork(num_outputs=num_outputs, **fields)
