"""Transformer token policies in flax: one decoder, nine descriptions.

`TokenDecoder` is a pre-norm decoder as a token policy: observations are
token ids, the action logits are the language-model head's, and a value head
reads the same final hidden vector.

    x = E[tokens]
    per layer:  h = x + Op(RMSNorm(x));  x = h + FeedForward(RMSNorm(h))
    (or, `one_function_layers`, nemotron_h: a layer is ONE of the two, x +
    Op(RMSNorm(x)) or x + FeedForward(RMSNorm(x)), by its entry of
    `layer_types`, "experts" naming the feed-forward)
    y = RMSNorm(x);  logits = y W_head (untied), or y E^T (`tie_embeddings`,
    lfm2_moe: the head IS the embedding, one parameter that both the lookup
    and the logits differentiate);  value = y w_v + b

It is assembled from parts that the published `config.json` of a family
names; nothing else chooses between them.

Op, a layer's operator: attention in every layer, or, a layer at a time by
`layer_types`, the gated short convolution (LFM2, `model_type: lfm2_moe`:
18 of its 24 layers) or Kimi Delta Attention (Kimi-Linear, `model_type:
kimi_linear`, arXiv:2510.26692: 20 of its 27, further down). The gated
short convolution, for n = the normalised input [T, H]:
      [b | c | u] = n W_in          W_in [H, 3 H], no bias, thirds in that
                                    order
      g = b * u
      v_t = sum_{j < L} w[:, j] * g_{t - (L-1) + j}    L = `conv_taps` (3),
                                    w [H, L] depthwise, no bias; g before
                                    the episode's first position is 0;
                                    w[:, L-1] meets the current position
      out = (c * v) W_out           no activation anywhere in the operator
  Its whole state is the last L - 1 gated inputs of a row, [B, L - 1, H]
  in `compute_dtype`, whatever the sequence's length: no positions axis,
  so a row is reset by zeroing it, not by `pos`. Two forms: over a
  fragment (`_conv_causal`) L shifted products, a tap that would reach
  back across a `reset` reading 0, returning the last episode's last
  L - 1 gated inputs (zeros where the episode is shorter); a step
  (`_conv_step`) appends g_t, multiplies and drops g_{t-(L-1)}. The taps
  are multiplied and summed in float32: elementwise work XLA fuses, no
  kernel of the repo's own.

Kimi Delta Attention ("kda"), a gated delta rule with a decay a channel of
the key; `kda_heads` heads, d_k = d_v = `kda_head_dim`, P = heads x d:
      [q~ | k~ | v~] = n W_qkv      [H, 3 P], no bias, thirds in that order
      q', k', v' = silu(conv(.))    depthwise causal, `kda_taps` (4) taps
                                    [3 P, taps], the last on the current
                                    position; 0 before the episode's first
      q = q' / sqrt(|q'|^2 + 1e-6) a head, times d_k^-1/2;  k likewise, no
                                    scale
      g = -exp(A_log[head]) * softplus((n W_fa) W_fb + dt_bias)   in R^P:
                                    the LOG decay a channel, <= 0
      beta = sigmoid(n W_b)         one a head
      S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t               S [d_k, d_v] a head, 0 where an episode
                                    begins
      out = (RMSNorm_head(o) * sigmoid((n W_ga) W_gb)) W_out    the norm
                                    over each head's d_v, one weight [d_v]
  Its state is that matrix, [B, heads, d_k, d_v] in FLOAT32 whatever
  `compute_dtype` (every step adds to it: in bfloat16 a few hundred steps
  leave the logits 25 % from the reference, tests/test_kimi_linear_policy),
  and the convolutions' last taps - 1 inputs, [B, taps - 1, 3 P] in
  `compute_dtype`; no positions axis. Two forms that agree. A step
  (`kda_step`): decay S by rows, u = beta (v - S^T k), S += k u^T, read
  q; elementwise work and two sums, three passes over S as XLA compiles
  it. A decode step of a program lowered for a TPU, at states of whole
  tiles, takes the same step as a kernel (`kda_decode_step`,
  `models/state_step.py`): a tile of S is fetched into VMEM once, both
  sums and the update happen there, and it goes back once, in place. A
  fragment
  (`kda_chunked`): chunks of `kda_chunk` (64) positions; inside a chunk
  the delta rule in its triangular (UT / WY) form, one unit lower
  triangular system a chunk and head, (I + Diag(beta) kk) against [beta V
  | beta k_in]. Two phases. The chunk phase makes each chunk's decayed
  products, one chunk a `lax.map` step, then solves ALL the fragment's
  systems in one call outside the loop (`unit_lower_solve`: the library's
  triangular solve works a system a lane, so it is cheap only over
  thousands; the inverse by forward substitution in blocks of a
  sub-block's rows, multiplied in at float32 proper, and why not by a
  product of powers). Between chunks a `lax.scan` that carries S, three
  matrix products a step. Both steps are recomputed in the backward
  pass, so that the scan's residuals are the chunk states and the chunk
  phase's the systems and their solutions. WHY PAIRS ARE FORMED
  FROM DIFFERENCES OF LOG DECAYS: the decay is a channel's, so the
  factorised product (q_i exp(G_i)) . (k_j exp(-G_j)) of cumulative log
  decays G overflows float32 as soon as one channel loses e^88 inside a
  chunk, and at this gate's range (exp(A_log) up to 16, softplus of a few
  units) ONE position can lose e^-50. So no exp(-G) is formed: a pair in
  different sub-blocks of `KDA_SUB_BLOCK` (16) positions goes through the
  point between them, exp(sum of g over i's sub-block up to i) times
  exp(sum over j's after j, plus the sub-blocks between), two factors <=
  1 and one matrix product; a pair inside a sub-block by its own exp(G_i -
  G_j), channel by channel. Every exponent is a sum of g's, <= 0. An
  episode that begins inside a fragment cuts the scan: pairs of different
  episodes are 0 and the carried state is dropped at the boundary.

Gated DeltaNet ("gdn"; qwen3_next, `model_type: qwen3_next`: 36 of its 48
layers), the same delta rule under ONE decay a value head; `gdn_key_heads`
query/key heads of `gdn_key_dim`, `gdn_value_heads` value heads of
`gdn_value_dim`, value head j reading key head j // (value heads / key
heads); K and V the two widths heads x dim:
      [q~ | k~ | v~ | z] = n W_qkvz   [H, 2 K + 2 V], no bias;  [b | a] = n
                                    W_ba [H, 2 x value heads]
      [q' | k' | v'] = silu(conv(.))  ONE depthwise causal convolution over
                                    q, k and v together, `gdn_taps` (4) taps
      q, k normalised a head as KDA's, q times d_k^-1/2
      g = -exp(A_log) * softplus(a + dt_bias)   ONE number a value head,
                                    float32;  beta = sigmoid(b)
      S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t               S [d_k, d_v] a VALUE head
      out = (RMSNorm_head(o) * w * silu(z)) W_out    one plain weight [d_v]
  Its state is that matrix in float32, its key of the policy state "gdn",
  and the convolution's last taps - 1 inputs [B, taps - 1, 2 K + V] under
  "conv". It is KDA's recurrence with exp(g) constant over a head's
  channels, and runs KDA's code in both forms, the decay in the shape the
  model publishes: `kda_chunked` makes a chunk's pairs from a key head's
  plain products times ONE [C, C] matrix of decays a value head
  (`_head_decay_chunk`; no sub-blocks, no exponential a channel) and shares
  the solve, the scan and the backward pass; `kda_decode_step` hands the
  kernel its decays as a row a grid step, a number a head.

Mamba-2 ("mamba2"; nemotron_h, `model_type: nemotron_h`: the state-space
duality form, arXiv:2405.21060), a state-space layer with ONE decay a head;
`ssm_heads` heads of `ssm_head_dim` channels (I = heads x channels), B and C
shared by `ssm_groups` groups of heads, `ssm_state` values each:
      [z | xBC | dt] = n W_in       [H, I + (I + 2 G N) + heads], no bias
      xBC = silu(conv(xBC) + b)     ONE depthwise causal convolution over
                                    x, B and C together, `ssm_taps` (4)
                                    taps and a bias; 0 before the episode's
                                    first position; x [heads, P], B, C
                                    [G, N]; head h reads group h // (heads
                                    / G)
      dt = softplus(dt + dt_bias)   a head, float32, no clamp
      a = exp(-exp(A_log) dt)       the decay, ONE a head
      S_t = a_t S_{t-1} + (dt_t x_t) B_t^T;   y_t = S_t C_t + D x_t
                                    S [P, N] a head, 0 where an episode
                                    begins
      out = GroupRMSNorm(y * silu(z)) W_out    the gate first, then the
                                    norm over each group's I / G channels,
                                    one weight [I]
  Its state is that matrix, [B, heads, P, N] in FLOAT32 (its key of the
  policy state is "ssm"), and the convolution's last taps - 1 inputs, [B,
  taps - 1, I + 2 G N] in `compute_dtype`. Two forms that agree. A step
  (`ssd_step`): the recurrence as written, S read once and written once
  by XLA's own fusions, at the rate a copy through VMEM reads (so it has
  no kernel: `models/state_step.py`).
  A fragment (`ssd_chunked`): chunks of `ssm_chunk` (128) positions; inside
  a chunk position t reads position s <= t of its episode with the weight
  exp(sum of the log decays after s up to t) (C_t . B_s), a [C, C] matrix a
  head against dt x; each chunk leaves sum_s exp(sum of the log decays
  after s) (dt x)_s B_s^T; a `lax.scan` over the chunks carries S. No
  solve, no delta, no decay a channel: what `kda_chunked` shares with it is
  the skeleton (chunk terms by `lax.map`, the scan, both bodies recomputed
  in the backward pass, whose residuals are the chunk states) and the rule
  that every exponent is a SUM OF LOG DECAYS, <= 0, never a difference of
  two cumulative sums (the pair sums by a masked cumulative sum over the
  chunk: `_ssd_chunk`).

Attention, one of:
  a head's own keys and values (OLMoE, arXiv:2409.02060, `model_type:
  olmoe`; SmallThinker, arXiv:2507.20984, `model_type: smallthinker`):
      q, k, v = n W_q, n W_k, n W_v (no bias), `num_heads` query heads and
      `num_kv_heads` key/value heads of `head_dim` (OLMoE: as many, of
      hidden / heads; SmallThinker: 28 over 4 of 128, query head h
      reading key/value head h // 7; LFM2: 32 over 8 of 64); `qk_norm`
      (OLMoE): q_norm, k_norm, RMSNorm over the whole projection before
      the split into heads; `qk_norm: "head"` (LFM2): RMSNorm over EACH
      head's own `head_dim` values, one weight [head_dim] for all heads,
      before RoPE; `partial_rotary_factor` (qwen3_next: 0.25): RoPE over
      the leading quarter of a head's values with that quarter's
      frequencies, the rest as they are; `attention_gate` (qwen3_next):
      W_q makes, a head, its query and as many values again, and the
      head's output is multiplied by their sigmoid ahead of W_o;
      `attention_gate: "head"` (laguna): ONE gate a head beside W_q, W_g
      [hidden, heads], o_h times sigmoid(n . W_g[:, h]) ahead of W_o;
      causal softmax(q k^T / sqrt(head_dim)) v; W_o. A KIND A LAYER
      (`layer_kind(i)`, an `AttentionKind`, from `window_layout`,
      `rope_layout`, `heads_layout` and `rotations`; OLMoE: every layer
      full and rotary; SmallThinker: a period of four, the first full and
      without positions, the next three windowed and rotary; laguna,
      `model_type: laguna`, whose GEOMETRY is the kind's: a period of four,
      the first full with 48 query heads, rotated over the leading HALF of
      a head by YaRN's frequencies (`rope_frequencies`: each frequency as
      it was, or divided by the factor, or a blend of the two by how many
      turns it makes over the original positions; cos and sin times the
      attention factor), the next three within a window of 512 with 64
      query heads under the default rotation over the whole head, all over
      the same 8 key/value heads of 128; a layer's W_q, W_o and gate have
      its own kind's heads): rotate-half RoPE on q and k by the kind's
      rotation, or nothing (a learner's pass, head-major rows of whole
      tiles in a program for a TPU: the rotation with the softmax's scale,
      and the gate, each ONE pass over a head's rows, `models/rowwise.py`,
      kernels with their own pullbacks; every other form `rope` and XLA's
      fusions); the sum over every s <= t of the episode, or
      over those with t - s < `sliding_window`. The cache holds K and V,
      [B, S, key/value heads, head_dim] each a layer, S the context's
      positions in a full layer and a RING of the window in a window
      layer: position p lies in slot p mod the window, written over the
      one that has just left it; keys are cached rotated, so the ring is
      read in whatever order it lies.
  latent (MLA; `kv_lora_rank` given; `model_type: glm4_moe_lite`, the
  DeepSeek-V2/V3 form, arXiv:2405.04434 section 2.1):
      c_q = RMSNorm(n W_qa);  q = c_q W_qb -> heads x (nope | rope)
      [c_kv | k_r] = n W_kva;  c_kv = RMSNorm(c_kv);  k_r = RoPE(k_r), one
      rotary key shared by every head
      [k_nope | v] = c_kv W_kvb -> heads x (nope | v_head_dim)
      q_h = [q_nope | RoPE(q_rope)], k_h = [k_nope | k_r];
      causal softmax(q_h k_h^T / sqrt(nope + rope)) v_h; heads joined; W_o.
      Two forms of that one sum. Decompressed (a causal pass): k_nope and v
      are made for every position. Absorbed (a decode step): W_kvb is split
      by head into W_UK [c, nope] and W_UV [c, v]; a head's score against
      position s is (W_UK q_nope) . c_kv,s + q_rope . k_r,s and its output
      W_UV^T (sum_s a_s c_kv,s), so the cache holds c_kv after its norm and
      k_r after RoPE, [B, S, kv_lora_rank + rope] a layer, and nothing else.
      It is the ATTENTION LAYERS' kind, not the model's: a model may have
      one such layer among operators of another kind. Kimi-Linear's
      differs from the second configuration's in three ways: no query
      latent (`q_lora_rank` 0: q = n W_q, no W_qa, no norm), no rotation
      anywhere (`rope_layout` all false, the source's `mla_use_nope`: q_rope
      and k_r are used as they are made, and position-free), and heads of
      128 + 64 = 192, which is no width the fused causal form takes: its
      causal pass pads q and k with zeros to 256 (q . k is what it was;
      `_latent_key_width`), or the plain form's [T, T] scores would be
      4.3 GB at the cell's minibatch.

Feed-forward, by layer: the first `first_k_dense_replace` layers a dense
SwiGLU; the others routed experts, beside `n_shared_experts` shared ones
that every token passes:
      sum over the chosen e of w_e W_down,e (act(W_gate,e n) * W_up,e n)
(`hidden_act`: SiLU, SwiGLU; or ReLU, SmallThinker's ReGLU); or, WITHOUT a
gate matrix (`gated_feed_forward` false; nemotron_h's `mlp_hidden_act:
relu2`), W_down,e relu(W_up,e n)^2, two products an expert, the shared
expert (of a width of its own, `shared_width`) likewise. Dropless: no
capacity, no token dropped or re-routed. The layer may hold a share of the
experts (`experts_held`, from `first_expert_held`): it routes over all of
them, computes the chosen ones it holds and leaves out what the absent ones
would add; that partial sum goes on, as on one chip of an expert-parallel
stage without its exchange. One sum, three blockings, chosen from the static
shape (`experts_batched`, `experts_sparse`). Grouped: the (row, expert)
pairs sorted by expert, absent experts' pairs last, the landed ones' rows
(up to a static size chosen from the landed count, `dispatch_rows`)
multiplied group by group (`grouped_product`: the library's Pallas
grouped-matmul kernels on a TPU at shapes that have tiles,
`jax.lax.ragged_dot` elsewhere) and added to their rows of the sum; the
learner's minibatch and a prefill. The shared
expert's output may stand behind a gate of its own (`shared_expert_gate`,
qwen3_next: times sigmoid(n . w), w [hidden]).
Batched: every row through every held expert in products batched over the
experts, each term weighted w_e or exactly 0 before the sum; a decode step,
whose groups of a few rows would each cost the grouped product an MXU tile
while the step is bound by reading every expert's weights once anyway.
Chosen: the batched form's sum over the held experts that some row of the
step chose, the others' matrices not read (`models/expert_step.py`, a
kernel); a rollout's step that is expected to leave a tenth or more of them
without a row (the three cells it takes: a fifth to a half).

Router, float32, one of:
  softmax (OLMoE, SmallThinker): p = softmax(n W_r); the k largest p;
      weights are those p as they are unless `norm_topk_prob` (over
      their sum: the softmax over the chosen logits alone).
  sigmoid without a bias (`sigmoid_router`; laguna): s = sigmoid(n W_r);
      the k largest s choose and weigh, over their sum, times
      `routed_scaling_factor`; nothing but parameters, so no "constants".
  sigmoid with a selection bias (`topk_method: noaux_tc`; one group;
  LFM2's `use_expert_bias`):
      s = sigmoid(n W_r); the k largest of s + b choose; weights are s at
      the chosen experts, without b, over their sum (`norm_topk_prob`;
      plus `topk_eps` where the description divides so: LFM2's 1e-6,
      Kimi-Linear's 1e-20),
      times `routed_scaling_factor`. b is a constant of the model: no
      gradient, no optimizer state (its balancing update belongs to
      pre-training).

The router reads the block's post-attention norm, n = RMSNorm(h); or
(`router_before_attention`, SmallThinker) the attention's own normalised
input, n = RMSNorm(x), so that its choice is known before the attention
runs, and the experts then take that choice with the post-attention norm
as their input.

The next-next-token module (`num_nextn_predict_layers`; DeepSeek-V3,
arXiv:2412.19437 section 2.2), in the learner only: for position t with the
trunk's hidden x_t (before the final norm) and the next token u_{t+1},
      z_t = W_eh [RMSNorm_h(x_t) | RMSNorm_e(E[u_{t+1}])]
one more expert layer of the model's own kind on z (positions as the
trunk's), a final norm of its own, the trunk's embedding and head, and the
cross-entropy against u_{t+2}, masked where t + 2 leaves the episode. As a
token policy obs[t+1] is the action taken at t, so the causal pass's own
`obs` is all it needs. x_t, E and W_head are read under `stop_gradient`: the
module follows the policy and does not move it. Its loss goes to the
"losses" collection (the caller's objective adds what a model puts there)
and is computed only where the caller keeps that collection.

Norms: x / rms(x) * w, w 1 at initialisation; or zero-centred
(`zero_centred_norms`, qwen3_next): x / rms(x) * (1 + w), w 0 at
initialisation, for every norm of the hidden vector and of a head's queries
and keys (an operator's own output norm keeps a plain weight). The
parameter is w; a layer's tensors hand out 1 + w.

Departures from the published models: the value head (none has one); no
auxiliary router loss (the RL objective has no place for it; the
`expert_load_*` counters show what follows); parameters, router, final norm
and heads are float32 and the block's activations `compute_dtype`
(bfloat16: the repo's convention, as the Nature-CNN's trunk); lfm2_moe's
tied head is assumed (the catalog's row drops the key; the family's dense
configs tie), and its selection bias is frozen, as kimi_linear's is;
kimi_linear's low ranks, epsilons and the decay's initial draw are assumed
(the configuration's file lists them); nemotron_h is its config's ONE tower as
an autoregressive policy (what the family's description adds, a second,
denoising tower and decoding by diffusion over blocks, has no key there and
is left out), its attention position-free, its decay's and time step's
draws and the convolution's bias assumed; sdar_moe's QK-norm a head
(the Qwen3 body's; no key says it), its block of 4 and 2 passes, its
sampler's rule and the MASK id are assumed (the configuration's file lists
them), and its pre-training noise schedule enters nowhere; qwen3_next's
next-token module is not built (no key of the config names it), its
projections' columns lie [q | k | v | z] and [b | a] where the source lays
them a key head at a time, and its decay's draw is assumed; laguna's gate
is assumed a head's (`gating: true` names no shape; one value a head makes
the published sizes the published 33.44 B), its router's score the
DeepSeek-V3 family's sigmoid without a selection bias (no key names either),
no QK-norm and no bias (none is named), and YaRN's factor on the rotated
half alone (the configuration's file lists them). The OLMoE and
glm4_moe_lite descriptions have as many key/value heads as query heads and
refuse another count (their references have no grouped form; latent
attention has no key/value heads to group).

A causal pass's attention, either layout (`causal_attention`; the latent one
decompressed, every head's key [k_nope | k_r]): one sum, two blockings, as
the experts'. Plain: the [B, heads, T, T] scores as one float32 array,
masked, normalised, cast, multiplied. Fused: the library's TPU splash
attention kernel and its backward kernel, tiles of `CAUSAL_TILE` queries
against tiles of keys with the running maximum and sum in VMEM, tiles above
the diagonal and tiles a window leaves out skipped, the episode as segment
ids, grouped heads a group at a time in the kernel's one-key-head form; no
[T, T] array is written in either pass. Chosen from the static shape
(`causal_fused`: whole tiles, at least two, widths of whole MXU tiles) and
the platform the program is lowered for (a TPU: fused; anything else, and
every fragment of a test or a rehearsal: plain). Queries, keys and values
are made head-major, [B, heads, T, d], the layout the kernel reads.

One set of parameters, two forms (the stateful-policy protocol of
`JaxPolicy`: `model(obs[B, T], state, reset[B, T])`):

* `causal`: [B, T] tokens from an empty window, one pass; a `reset` inside
  the fragment starts a new episode (its own positions, no attention across
  the boundary). The learner's form, and the prefill. With more than one
  block, each is recomputed in the backward pass (`jax.checkpoint`): what a
  backward pass holds is then one block's activations, which with one block
  it holds anyway; beside them it keeps the fused attention's output and
  log-sum-exp, so that kernel runs once a block.
* `decode`: one token a row against the caches of `context_len` positions
  (`compute_dtype`); writes the position's entries and returns its logits
  and value. The rollout's form. Its attention reads the cache positions
  [0, n) only, n the furthest position any row of the batch holds, rounded
  up to a block of `DECODE_CACHE_BLOCK` positions and chosen inside the
  step from `pos` (`cached_attention`); each row masks what it does not
  hold itself. A latent cache, and grouped heads' caches (stored flat, a
  position's cached heads one row), are read by a kernel of the repo's own
  (`models/decode_attention.py`) where the program is lowered for a TPU
  and the window is whole blocks: each block of rows once for both
  products, the blocks up to the furthest position that the rows of a
  grid step hold. Grouped heads' kernel has two forms by the width of a
  position's row (`grouped_lanes`): block-diagonal queries against the
  whole row (up to 512 lanes), or each cached head's queries against its
  own lanes (laguna's 8 x 128).

Generation by diffusion over blocks (`block_len` L, `denoise_steps` S;
sdar_moe, `model_type: sdar_moe`: SDAR, arXiv:2510.06303, the objective and
the mask of block diffusion, arXiv:2503.09573): the same parameters in two
more forms, which take the place of `decode` and `causal` for such a model.
The vocabulary's last id is the MASK id (its logit `MASK_LOGIT`: probability
0); the logits at a position are the distribution of the token AT it.

* `block_step`: one BLOCK of L positions a row. S denoising passes: the
  block's positions enter as their token where given (an episode's first
  position) or already unmasked and as the MASK id elsewhere, read the
  caches' blocks before theirs and each other in both directions, and the L /
  S still-masked positions with the highest top probability are unmasked,
  each token drawn from its own distribution; then a commit pass of the
  clean tokens, whose keys and values stay in the caches. S + 1 passes of L
  rows a block. A pass's L x heads queries, folded into the cached heads'
  rows of queries, read the blocks before theirs from the caches and their
  own block's keys and values as operands, in one softmax
  (`block_attention`: where the program is lowered for a TPU and a cached
  head is whole lane tiles, an entry of the decode kernel's file that folds
  the fresh block into its online softmax and scores a cached head against
  its own lanes). Only the commit pass writes
  (`decode_attention.write_block`): of a denoising pass nothing outlives
  the block.
* `block_causal`: whole episodes [B, T] on the sampler's trace (the pass
  each position was unmasked at), one pass over S + 1 streams of T positions:
  the clean one, block-causal, and one a denoising pass, whose queries read
  the clean stream's keys of earlier blocks and their own block's
  (`block_stream_attention`: the plain mask, or the splash kernel with the
  mask computed inside it). Logits are taken at each position's own pass's
  stream, values at each block's first position of pass 0's. The last
  layer's clean stream stops at its keys and values, as the commit pass's
  does: nothing reads its output.

Both return the state, so a decode can follow a causal pass: {"kv": a
layer's caches (none for a layer that is no attention), "pos"}, and, a key
a kind and only the kinds the model has, {"conv": a convolution layer's
last gated inputs, a KDA layer's convolutions' last inputs (none for an
attention layer)} and {"kda": a KDA layer's float32 matrices} beside them:
{"ssm": a Mamba-2 layer's float32 matrices, its convolution's last inputs
under "conv"}, {"gdn": a Gated DeltaNet layer's, likewise}: every leaf of
"kv" has a positions axis, no leaf of "conv", "kda", "ssm" or "gdn" has
(`STATE_KINDS`). `JaxPolicy` and the Anakin optimizer
carry the whole as one pytree.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import (
    decode_attention, expert_step, rowwise, state_step)

Dtype = Any

# HF `config.json` keys a family is described by -> module fields.
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
GLM4_MOE_LITE_CONFIG_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "num_attention_heads": "num_heads",
    "num_hidden_layers": "num_layers",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "first_k_dense_replace": "dense_layers",
    "intermediate_size": "dense_width",
    "n_routed_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "expert_width",
    "n_shared_experts": "shared_experts",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "num_nextn_predict_layers": "nextn_layers",
    "max_position_embeddings": "context_len",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    # The deployment's, not the model's: the share of the routed experts
    # this chip holds (all of them where not given).
    "experts_held": "experts_held",
    "first_expert_held": "first_expert_held",
}
SMALLTHINKER_CONFIG_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "num_layers",
    "sliding_window_size": "sliding_window",
    "sliding_window_layout": "window_layout",
    "rope_layout": "rope_layout",
    "moe_num_primary_experts": "num_experts",
    "moe_num_active_primary_experts": "experts_per_token",
    "moe_ffn_hidden_size": "expert_width",
    "norm_topk_prob": "norm_topk_prob",
    "max_position_embeddings": "context_len",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    # The deployment's: the share of the experts this chip holds.
    "experts_held": "experts_held",
    "first_expert_held": "first_expert_held",
}
# What SmallThinker-21BA3B's published `config.json` says, for the keys a
# `custom_model_config` leaves out.
SMALLTHINKER_PUBLISHED = {
    "vocab_size": 151936, "hidden_size": 2560, "num_attention_heads": 28,
    "num_key_value_heads": 4, "head_dim": 128, "num_hidden_layers": 52,
    "sliding_window_size": 4096, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "rope_layout": [0, 1, 1, 1] * 13, "moe_num_primary_experts": 64,
    "moe_num_active_primary_experts": 6, "moe_ffn_hidden_size": 768,
    "norm_topk_prob": True, "max_position_embeddings": 16384,
    "rope_theta": 1500000, "rms_norm_eps": 1e-6,
}
SMALLTHINKER_FIXED = {
    "moe_primary_router_apply_softmax": True, "hidden_act": "relu",
    "rope_scaling": None, "tie_word_embeddings": False,
    "model_type": "smallthinker",
}
LFM2_MOE_CONFIG_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "num_hidden_layers": "num_layers",
    "layer_types": "layer_types",
    "conv_L_cache": "conv_taps",
    "num_dense_layers": "dense_layers",
    "intermediate_size": "dense_width",
    "num_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "expert_width",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "max_position_embeddings": "context_len",
    "rope_theta": "rope_theta",
    "norm_eps": "rms_eps",
    # The deployment's: the share of the experts this chip holds.
    "experts_held": "experts_held",
    "first_expert_held": "first_expert_held",
}
# What LFM2-8B-A1B's published `config.json` says, for the keys a
# `custom_model_config` leaves out.
LFM2_MOE_PUBLISHED = {
    "vocab_size": 65536, "hidden_size": 2048, "num_attention_heads": 32,
    "num_key_value_heads": 8, "num_hidden_layers": 24,
    "layer_types": ["conv", "conv", "full_attention", "conv"] * 5 + [
        "conv", "full_attention", "conv", "conv"],
    "conv_L_cache": 3, "num_dense_layers": 2, "intermediate_size": 7168,
    "num_experts": 32, "num_experts_per_tok": 4,
    "moe_intermediate_size": 1792, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "max_position_embeddings": 128000,
    "rope_theta": 1000000, "norm_eps": 1e-5,
}
LFM2_MOE_FIXED = {
    "conv_bias": False, "use_expert_bias": True, "rope_scaling": None,
    "tie_embedding": True, "tie_word_embeddings": True,
    "model_type": "lfm2_moe",
}
KIMI_LINEAR_CONFIG_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "num_attention_heads": "num_heads",
    "num_hidden_layers": "num_layers",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "first_k_dense_replace": "dense_layers",
    "intermediate_size": "dense_width",
    "num_experts": "num_experts",
    "num_experts_per_token": "experts_per_token",
    "moe_intermediate_size": "expert_width",
    "num_shared_experts": "shared_experts",
    "moe_renormalize": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "model_max_length": "context_len",
    "rope_theta": "rope_theta",  # kept; no layer rotates (`mla_use_nope`)
    "rms_norm_eps": "rms_eps",
    # The deployment's: the share of the experts this chip holds, and the
    # positions in a chunk of the learner's scan.
    "experts_held": "experts_held",
    "first_expert_held": "first_expert_held",
    "kda_chunk": "kda_chunk",
}
# What Kimi-Linear-48B-A3B's published `config.json` says, for the keys a
# `custom_model_config` leaves out.
KIMI_LINEAR_PUBLISHED = {
    "vocab_size": 163840, "hidden_size": 2304, "num_attention_heads": 32,
    "num_hidden_layers": 27, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "first_k_dense_replace": 1,
    "intermediate_size": 9216, "num_experts": 256,
    "num_experts_per_token": 8, "moe_intermediate_size": 1024,
    "num_shared_experts": 1, "moe_renormalize": True,
    "routed_scaling_factor": 2.446, "model_max_length": 1048576,
    "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
}
KIMI_LINEAR_FIXED = {
    "mla_use_nope": True, "q_lora_rank": None, "num_expert_group": 1,
    "topk_group": 1, "use_grouped_topk": True, "moe_layer_freq": 1,
    "moe_router_activation_func": "sigmoid", "num_nextn_predict_layers": 0,
    "hidden_act": "silu", "rope_scaling": None, "tie_word_embeddings": False,
    "model_type": "kimi_linear",
}
NEMOTRON_H_CONFIG_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "num_layers",
    "mamba_num_heads": "ssm_heads",
    "mamba_head_dim": "ssm_head_dim",
    "n_groups": "ssm_groups",
    "ssm_state_size": "ssm_state",
    "conv_kernel": "ssm_taps",
    "chunk_size": "ssm_chunk",
    "n_routed_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "expert_width",
    "moe_shared_expert_intermediate_size": "shared_width",
    "n_shared_experts": "shared_experts",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "max_position_embeddings": "context_len",
    "rope_theta": "rope_theta",  # kept; no layer rotates
    "layer_norm_epsilon": "rms_eps",
    # The deployment's: the share of the experts this chip holds.
    "experts_held": "experts_held",
    "first_expert_held": "first_expert_held",
}
# What Nemotron-Labs-TwoTower-30B-A3B-Base's published `config.json` says,
# for the keys a `custom_model_config` leaves out.
NEMOTRON_H_PUBLISHED = {
    "vocab_size": 131072, "hidden_size": 2688, "num_attention_heads": 32,
    "num_key_value_heads": 2, "head_dim": 128, "num_hidden_layers": 52,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "mamba_num_heads": 64, "mamba_head_dim": 64, "n_groups": 8,
    "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128,
    "n_routed_experts": 128, "num_experts_per_tok": 6,
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "max_position_embeddings": 262144, "rope_theta": 10000,
    "layer_norm_epsilon": 1e-5,
}
NEMOTRON_H_FIXED = {
    "n_group": 1, "topk_group": 1, "mamba_proj_bias": False,
    "use_bias": False, "attention_bias": False, "mlp_bias": False,
    "use_conv_bias": True, "sliding_window": None,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "time_step_limit": [0, None], "tie_word_embeddings": False,
    "model_type": "nemotron_h",
}
# Published keys that no part of the decoder reads, each for its reason: an
# initialiser's (the policy's own init draws the weights; the time step's
# range is `_decay_inits`'), the dense feed-forward's width and `expand`
# (no layer of the pattern is a dense feed-forward `-`, and the inner width
# is heads x head_dim whatever `expand` says), a residual's storage, a
# serving option, a kernel switch, the rotary share of a rotation that no
# layer applies.
NEMOTRON_H_UNREAD = (
    "rescale_prenorm_residual", "time_step_min", "time_step_max",
    "time_step_floor", "intermediate_size", "expand", "residual_in_fp32",
    "num_logits_to_keep", "use_mamba_kernels", "partial_rotary_factor",
    "norm_eps")
# A layer of the family's `hybrid_override_pattern` by its letter.
NEMOTRON_H_LAYERS = {"M": "mamba2", "E": "experts", "*": "full_attention"}
SDAR_MOE_CONFIG_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "num_layers",
    "num_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "expert_width",
    "norm_topk_prob": "norm_topk_prob",
    "max_position_embeddings": "context_len",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    # The deployment's: the share of the experts this chip holds.
    "experts_held": "experts_held",
    "first_expert_held": "first_expert_held",
    # The generation's, which `config.json` has no key for: the positions
    # in a block and the denoising passes a block.
    "block_length": "block_len",
    "denoise_steps": "denoise_steps",
}
# What SDAR-30B-A3B-Chat's published `config.json` says, for the keys a
# `custom_model_config` leaves out; the block is the family's Chat models'
# published one, the steps this repo's choice.
SDAR_MOE_PUBLISHED = {
    "vocab_size": 151936, "hidden_size": 2048, "num_attention_heads": 32,
    "num_key_value_heads": 4, "head_dim": 128, "num_hidden_layers": 48,
    "num_experts": 128, "num_experts_per_tok": 8,
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "max_position_embeddings": 32768, "rope_theta": 1000000,
    "rms_norm_eps": 1e-6, "block_length": 4, "denoise_steps": 2,
}
SDAR_MOE_FIXED = {
    "attention_bias": False, "decoder_sparse_step": 1, "hidden_act": "silu",
    "mlp_only_layers": [], "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "model_type": "sdar_moe",
}
# Published keys that no part of the decoder reads: the dense feed-forward's
# width (`mlp_only_layers` is empty and every layer sparse) and the layers a
# window would reach (`use_sliding_window` false).
SDAR_MOE_UNREAD = ("intermediate_size", "max_window_layers")
QWEN3_NEXT_CONFIG_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "num_layers",
    "partial_rotary_factor": "partial_rotary_factor",
    "linear_num_key_heads": "gdn_key_heads",
    "linear_num_value_heads": "gdn_value_heads",
    "linear_key_head_dim": "gdn_key_dim",
    "linear_value_head_dim": "gdn_value_dim",
    "linear_conv_kernel_dim": "gdn_taps",
    "num_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "expert_width",
    "shared_expert_intermediate_size": "shared_width",
    "norm_topk_prob": "norm_topk_prob",
    "max_position_embeddings": "context_len",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    # The deployment's: the share of the experts this chip holds, and the
    # positions in a chunk of the learner's scan.
    "experts_held": "experts_held",
    "first_expert_held": "first_expert_held",
    "gdn_chunk": "gdn_chunk",
}
# What Qwen3-Next-80B-A3B-Instruct's published `config.json` says, for the
# keys a `custom_model_config` leaves out.
QWEN3_NEXT_PUBLISHED = {
    "vocab_size": 151936, "hidden_size": 2048, "num_attention_heads": 16,
    "num_key_value_heads": 2, "head_dim": 256, "num_hidden_layers": 48,
    "full_attention_interval": 4, "partial_rotary_factor": 0.25,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4, "num_experts": 512,
    "num_experts_per_tok": 10, "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512, "norm_topk_prob": True,
    "max_position_embeddings": 262144, "rope_theta": 10000000,
    "rms_norm_eps": 1e-6,
}
QWEN3_NEXT_FIXED = {
    "decoder_sparse_step": 1, "hidden_act": "silu", "mlp_only_layers": [],
    "rope_scaling": None, "use_sliding_window": False,
    "attention_bias": False, "tie_word_embeddings": False,
    "model_type": "qwen3_next",
}
# Published keys that no part of the decoder reads: the dense feed-forward's
# width (`mlp_only_layers` is empty and every layer sparse).
QWEN3_NEXT_UNREAD = ("intermediate_size",)
LAGUNA_CONFIG_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "num_layers",
    "sliding_window": "sliding_window",
    "intermediate_size": "dense_width",
    "num_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "expert_width",
    "shared_expert_intermediate_size": "shared_width",
    "moe_routed_scaling_factor": "routed_scaling_factor",
    "max_position_embeddings": "context_len",
    "rms_norm_eps": "rms_eps",
    # The deployment's: the share of the experts this chip holds.
    "experts_held": "experts_held",
    "first_expert_held": "first_expert_held",
}
# What Laguna-XS.2's published `config.json` says, for the keys a
# `custom_model_config` leaves out.
LAGUNA_PUBLISHED = {
    "vocab_size": 100352, "hidden_size": 2048, "num_attention_heads": 48,
    "num_key_value_heads": 8, "head_dim": 128, "num_hidden_layers": 40,
    "sliding_window": 512, "intermediate_size": 8192, "num_experts": 256,
    "num_experts_per_tok": 8, "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512,
    "moe_routed_scaling_factor": 2.5, "max_position_embeddings": 262144,
    "rms_norm_eps": 1e-6, "partial_rotary_factor": 0.5,
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "num_attention_heads_per_layer": [48, 64, 64, 64],
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
}
# The two lists that are a period of four in the published file stand for
# their repetition over the 40 layers.
for _key in ("layer_types", "num_attention_heads_per_layer"):
    LAGUNA_PUBLISHED[_key] = LAGUNA_PUBLISHED[_key] * 10
LAGUNA_FIXED = {
    "attention_bias": False, "tie_word_embeddings": False, "gating": True,
    "moe_apply_router_weight_on_input": False, "model_type": "laguna",
}
# A kind's rotation in `rope_parameters`: the keys of each `rope_type`.
LAGUNA_ROPE_KEYS = {
    "default": {"rope_type", "rope_theta", "partial_rotary_factor"},
    "yarn": {"rope_type", "rope_theta", "partial_rotary_factor", "factor",
             "original_max_position_embeddings", "beta_fast", "beta_slow",
             "attention_factor"},
}
# The MASK id's logit: its probability is exactly 0 in float32, as minus
# infinity's is, and 0 x it is 0 where the entropy multiplies the two.
MASK_LOGIT = -1e30
# The operators a layer of `layer_types` may name; "experts" (a model of
# `one_function_layers` alone) names a layer that is its feed-forward and
# no operator.
LAYER_TYPES = ("conv", "full_attention", "kda", "mamba2", "experts", "gdn")


class Rotation(NamedTuple):
    """How an attention layer rotates its queries and keys: `rope`'s theta,
    the share of a head's leading values it rotates, and
    `rope_frequencies`' scaling (() for none)."""
    theta: float
    share: float = 1.0
    scaling: tuple = ()


class AttentionKind(NamedTuple):
    """An attention layer's geometry (`TokenDecoder.layer_kind`): the window
    it attends within (0: the whole episode), whether it rotates its
    queries and keys at all, its query heads, and its rotation."""
    window: int
    rotary: bool
    heads: int
    rotation: Rotation


# The kinds of state a layer may keep between positions, each a key of the
# policy state beside "pos": caches with a positions axis; a convolution's
# last inputs; a KDA layer's matrices; a Mamba-2 layer's; a Gated DeltaNet
# layer's.
STATE_KINDS = ("kv", "conv", "kda", "ssm", "gdn")
# The kinds that are a float32 matrix a head, by the layer type that keeps
# one (the layer's convolution inputs stand under "conv" beside it).
MATRIX_STATES = {"kda": "kda", "ssm": "mamba2", "gdn": "gdn"}
# Published keys that must say what the decoder does (a value it has no
# part for is refused, not ignored).
GLM4_MOE_LITE_FIXED = {
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "hidden_act": "silu", "attention_bias": False, "rope_scaling": None,
    "partial_rotary_factor": 1, "tie_word_embeddings": False,
    "model_type": "glm4_moe_lite",
}


def rms_norm(x, weight, eps, dtype, axes=(-1,)):
    """RMSNorm over `axes` of x (the last one; or the axes a projection's
    width lies split over, `weight` shaped to broadcast against x)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=axes, keepdims=True)
    return (weight * (x32 * jax.lax.rsqrt(var + eps))).astype(dtype)


def rope_frequencies(dim: int, theta, scaling=()):
    """(the `dim // 2` angles a position of a rotation over `dim` values,
    the factor on its cos and sin). The default rotation: theta ** (-2 i /
    dim), factor 1. `scaling` = (factor F, original positions P, beta_fast,
    beta_slow, attention factor), YaRN (arXiv:2309.00071; `rope_type:
    yarn`): frequency i stays as it was where it makes more than beta_fast
    turns over the P original positions (extrapolated), is divided by F
    where it makes fewer than beta_slow (interpolated), and is blended by a
    linear ramp over the indices between, from low = floor(c(beta_fast)) to
    high = ceil(c(beta_slow)), c(r) = dim ln(P / (2 pi r)) / (2 ln theta)
    the index that makes r turns; cos and sin are multiplied by the
    attention factor."""
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not scaling:
        return inv_freq, 1.0
    factor, original, beta_fast, beta_slow, attention_factor = scaling

    def turns(r):
        return dim * np.log(original / (2 * np.pi * r)) / (2 * np.log(theta))
    low = max(int(np.floor(turns(beta_fast))), 0)
    high = min(int(np.ceil(turns(beta_slow))), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    # ramp 0: as it was; 1: every position `factor` times nearer.
    return (inv_freq / factor * ramp + inv_freq * (1.0 - ramp),
            float(attention_factor))


def rope(x, positions, theta, scale=1.0, head_major=False, scaling=()):
    """Rotate-half RoPE at `rope_frequencies(.., theta, scaling)`, times
    `scale` before the cast back to x's dtype.
    x: [..., heads, head_dim] with positions x.shape[:-2]; or, head-major,
    [..., heads, T, head_dim] with positions [..., T]."""
    dim = x.shape[-1]
    inv_freq, factor = rope_frequencies(dim, theta, scaling)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    angles = angles[..., None, :, :] if head_major else angles[..., None, :]
    x32 = x.astype(jnp.float32)
    half = dim // 2
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    if factor == 1.0:
        out = x32 * jnp.cos(angles) + rotated * jnp.sin(angles)
    else:
        out = (x32 * (jnp.cos(angles) * factor)
               + rotated * (jnp.sin(angles) * factor))
    return (out if scale == 1.0 else out * scale).astype(x.dtype)


def relu2(x):
    """relu(x)^2: the un-gated feed-forward's activation."""
    return jnp.square(jax.nn.relu(x))


# A feed-forward's activation by its published name (`hidden_act`): the
# gate's in a gated one (SwiGLU's, or ReGLU's), the only one in an un-gated
# one (squared ReLU).
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu, "relu2": relu2}


def swiglu(n, w_gate, w_up, w_down, act=jax.nn.silu):
    """W_down (act(W_gate n) * W_up n) for rows n, weights in n's dtype;
    without a gate matrix (`w_gate` None), W_down act(W_up n)."""
    if w_gate is None:
        return jnp.dot(act(jnp.dot(n, w_up)), w_down)
    return jnp.dot(act(jnp.dot(n, w_gate)) * jnp.dot(n, w_up), w_down)


def route(n, router, k, renormalise, bias=None, scale=1.0, eps=0.0,
          sigmoid=False):
    """Float32 router: (weights [M, k], experts [M, k]) for rows n [M, H].
    Softmax, the k largest; or (`sigmoid`, or with `bias` [E]) sigmoid
    scores, the k largest of the scores (plus `bias` where given, a
    constant here) choose, and the weights are the scores alone. Weights
    are divided by their sum (plus `eps`, where the description has one)
    where `renormalise`, then times `scale`."""
    with jax.named_scope("policy/router"):
        logits = jnp.dot(n.astype(jnp.float32), router,
                         precision=jax.lax.Precision.HIGHEST)
        if bias is not None:
            scores = jax.nn.sigmoid(logits)
            _, top_i = jax.lax.top_k(
                scores + jax.lax.stop_gradient(bias), k)
            top_p = jnp.take_along_axis(scores, top_i, axis=-1)
        elif sigmoid:
            top_p, top_i = jax.lax.top_k(jax.nn.sigmoid(logits), k)
        else:
            top_p, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        if renormalise:
            total = jnp.sum(top_p, axis=-1, keepdims=True)
            top_p = top_p / (total + eps if eps else total)
        if scale != 1.0:
            top_p = top_p * scale
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


# Positions in a block of the caches of a head's own keys and values that
# a decode step's attention reads (see `cached_attention`; a latent cache's
# and grouped heads' caches' block is the kernel's, `decode_attention.BLOCK`,
# and where they take no kernel they have none). A window that fills from
# empty is read `1/2 + b/(2S)` of, so a smaller block reads less; every
# block of the window is one more branch of the step's `switch`, traced
# wherever a decode step is (the rollout, and the learner's bootstrap step
# under `value_and_grad`) on every start, compile cache or not. On a v5e at
# OLMoE's published widths, 128 rows, a window of 1,024 (PERF.md section 5),
# a decode step / the token cell's warm set-up once the chip is open: the
# window read whole 3.11 ms / 22.5 s; blocks of 512 2.64 ms; of 256 2.44 ms
# / 23.1 s; of 128 2.35 ms / 25.1 s; of 64 2.32 ms.
DECODE_CACHE_BLOCK = 256


def cached_attention(q, k_cache, v_cache, pos, scale=None, value_dim=None):
    """softmax(q k^T * scale) v of one query a row, q [B, heads, d], over
    the positions [0, pos[b]] that row holds of the caches. Returns
    ([B, heads, value width] in q's dtype, the positions read). `scale` is
    d ** -0.5 where not given.

    Three kinds of cache. A head's own keys and values: `k_cache`,
    `v_cache` [B, S, heads, d]. Grouped heads: [B, S, groups, d], query
    head h reading cached head h // (heads // groups). One latent head
    that all query heads share: `k_cache` [B, S, d] and `v_cache` None;
    the values are then the first `value_dim` of the same rows.

    The cache may be a ring (`TokenDecoder.initial_state`): its slots are
    read in whatever order they lie, and a row whose position has passed
    the ring's length holds every slot.

    Read, scored and multiplied are the positions [0, n) alone: n is the
    furthest position any row holds, rounded up to whole blocks. A position
    a row does not hold has weight exp(-inf) = 0 in both sums, so leaving
    those beyond every row unread changes no term; within [0, n) each row
    masks its own. One `switch` over the static prefixes: every branch is
    the same arithmetic on a shorter axis (float32 scores and softmax, the
    weights normalised, cast, then multiplied), and a `switch` has a
    transpose, which a loop whose trip count comes from data has not (the
    learner differentiates its bootstrap step through this).

    With a head's own keys both products are a matrix times one vector a
    row and head, and are written as that: operands in q's dtype,
    multiplied and summed in float32. Outside a conditional XLA:TPU makes
    that of the einsum itself; inside one it made the scores a convolution
    over a transposed copy of the prefix, and the step was slower than with
    the window read whole (3.08 against 2.97 ms, PERF.md section 5). In
    this form the fusion that multiplies the prefix reads it where it lies.
    With a shared latent head they are matrix products, every query head
    of a row against the same [n, d] rows, and wherever such a product
    stands in a conditional XLA:TPU first copies the WHOLE window to
    another layout, in every branch, whatever the prefix (glm4_moe_lite's
    widths on a v5e, 128 rows, a window of 1,024: a step 4.16 ms whole,
    4.83 in blocks of 512, 5.11 of 256; PERF.md section 5). So a latent
    cache takes no `switch`, and one of two forms of the same sum
    (`models/decode_attention.py`), chosen from what the program can see:
    `decode_fused` of the static shape, and the platform the program is
    lowered for. A kernel (a TPU, whole blocks): an online softmax over
    blocks of `decode_attention.BLOCK` positions, each block of latent
    rows fetched once for both products, the blocks beyond the furthest
    position that the rows of a grid step hold not fetched; the positions
    read are those blocks', the mean over the rows. The two products over
    the whole window anywhere else (and under a gradient: the kernel form
    carries their derivative). Grouped heads are matrix products too, a
    group's queries against its cached head, and take no `switch` either
    (a prefix taken in a branch is copied first and costs more than the
    whole cache; as a multiply-and-sum they are seven products and a
    cross-lane sum an element, bound by the vector unit: PERF.md section
    5). They take the same kernel by the same kind of choice,
    `grouped_fused` of the static shape and the platform: a position's
    cached heads are one row of groups * d contiguous lanes (8 x 64 = 4 x
    128 = 512, the width the kernel streams best), so the caches are
    viewed [B, 1, S, groups * d], the queries made block-diagonal (head h
    zero outside its cached head's d lanes), and one product scores all
    the heads of a row against a position's whole row; of the output's row
    head h keeps its own d lanes (`decode_attention.grouped_kernel`). The
    lengths are min(pos + 1, S): a ring's slots fill upward from 0. Its
    derivative is the two products' over the caches by head. The view is a
    bitcast only of a cache that is STORED flat (`TokenDecoder
    .initial_state`), which is why it is. Measured alone, in a scan that
    carries the caches and writes a row a step (a v5e, PERF.md section 5;
    ms a step, the cache filling from empty / held whole): 64 rows, 32
    query heads over 8 cached ones of 64, 4,096 positions: XLA's two
    products over [B, S, 8, 64] 1.471 / 1.471 (each cached row half a lane
    tile), the kernel 0.427 / 0.757 (709 GB/s of 819); 16 rows, 28 over 4
    of 128, a cache of 8,192: 0.387 against 0.195 / 0.367; a ring of
    4,096: 0.205 against 0.105 / 0.190. A position's row of 8 x 128 =
    1,024 lanes (laguna; 32 rows, 64 query heads; my chip run, PR 56): the
    kernel in the form that scores a cached head against its own lanes
    (`grouped_lanes`, `decode_attention.lanes_kernel`), ms a step, XLA /
    block-diagonal / own lanes at blocks of 128 x 16 rows: a ring of 512
    held whole 0.1154 / 0.1332 / 0.1118, a cache of 8,192 at position 4,064
    1.7156 / 0.7654 / 0.7441, held whole 1.7159 / 1.4641 / 1.4423; own
    lanes at 128 x 8 0.1101 / 0.7539 / 1.4406, 128 x 32 0.1161 / 0.7391 /
    1.4466, 256 x 8 0.1115 / 0.7521 / 1.4419, 256 x 16 0.1146 / 0.7465 /
    1.4454, 512 x 8 0.1140 / 0.7610 / 1.4447, 64 x 16 0.1107 / 0.7483 /
    1.4412: the file's 128 x 16 stays. Everywhere else the two products
    over the whole cache (`decode_attention.attend_grouped`)."""
    S = k_cache.shape[1]
    f32 = jnp.float32
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if v_cache is None:
        return _latent_attention(q, k_cache, pos, scale, value_dim)
    if k_cache.shape[2] != q.shape[1]:
        return _grouped_attention(q, k_cache, v_cache, pos, scale)
    size = DECODE_CACHE_BLOCK
    ends = tuple(range(size, S, size)) + (S,)

    def attend(n, q, k_cache, v_cache, pos):
        held = jnp.arange(n)[None, :] <= pos[:, None]
        k, v = k_cache[:, :n].astype(f32), v_cache[:, :n].astype(f32)
        # [B, n, heads]
        scores = jnp.sum(q[:, None].astype(f32) * k, axis=-1) * scale
        scores = jnp.where(held[:, :, None], scores, -jnp.inf)
        attn = jax.nn.softmax(scores, axis=1).astype(q.dtype)
        return jnp.sum(attn[..., None].astype(f32) * v,
                       axis=1).astype(q.dtype)

    block = jnp.minimum(jnp.max(pos) // size, len(ends) - 1)
    o = jax.lax.switch(block, [functools.partial(attend, n) for n in ends],
                       q, k_cache, v_cache, pos)
    return o, jnp.asarray(ends)[block]


def grouped_fused(S: int, groups: int, heads: int, d: int) -> bool:
    """Whether a decode step of `heads` query heads a row over `groups`
    cached heads `d` wide, `S` positions of them, can take the kernel form
    of `cached_attention`: a function of the static shape alone. Whole
    blocks and at least two of them; a position's cached heads whole lane
    tiles together, each at least half a tile (the widths the kernel was
    compiled and measured at: 8 x 64, 4 x 128 and 2 x 256); whole groups of
    query heads. No cache's length is left out: a ring of 4,096 held whole,
    the shape nearest to losing, read 0.190 ms a step against XLA's 0.205
    (`cached_attention` has the sweep). 2 x 256 (qwen3_next: 16 query heads
    over 2 cached heads of 256, 32 rows, 4,096 positions, one layer's
    caches in a scan that writes a row a step; my chip run, PR 52): XLA's
    two products 0.387 ms a step whatever the cache holds; the kernel 0.212
    while it fills (the mean of positions 2,016-2,079) and 0.378 held
    whole; 8 or 4 rows a grid step read the same."""
    block = decode_attention.BLOCK
    return (S % block == 0 and S >= 2 * block and heads % groups == 0
            and (groups * d) % 128 == 0 and d % 64 == 0)


def grouped_lanes(groups: int, d: int) -> bool:
    """Whether the kernel form of grouped heads' caches scores a cached
    head against its own lanes of a fetched row
    (`decode_attention.lanes_kernel`) or all the heads of a row against the
    whole row with block-diagonal queries (`grouped_kernel`): a function of
    the static shape alone. Its own lanes where a cached head is whole lane
    tiles (the kernel cuts a fetched row where the tiles' edges are) and a
    position's row is wider than the 512 lanes the block-diagonal form was
    measured at (8 x 64, 4 x 128, 2 x 256: those keep it). At 8 x 128 =
    1,024 lanes the block-diagonal queries multiply 8 x the owed products
    and stop hiding behind the bytes (laguna: 32 rows, 64 or 48 query heads
    over 8 cached heads of 128, one layer's caches in a scan that writes a
    row a step; ms a step at 64 heads, XLA's two products / block-diagonal
    / own lanes; my chip run, PR 56): a ring of 512 held whole 0.1154 /
    0.1332 / 0.1118; a cache of 8,192 at position 1,024 1.7155 / 0.2485 /
    0.2278, at 4,064 1.7156 / 0.7654 / 0.7441, held whole 1.7159 / 1.4641 /
    1.4423 (745 GB/s of 819); 48 heads read the same to 1 % (0.1152 /
    0.1276 / 0.1112 and 1.6424 / 1.4583 / 1.4418). Blocks of 64-512 x 8-32
    rows a grid step moved either kernel by under 1.5 % (`cached_attention`
    has the table)."""
    return d % 128 == 0 and groups * d > 512


def _grouped_attention(q, k_cache, v_cache, pos, scale):
    """`cached_attention` of grouped heads' caches: (o, the positions
    read)."""
    S, groups = k_cache.shape[1:3]
    # Slots fill upward from 0, so a ring is a prefix until its position
    # passes its length, and holds every slot from then on.
    lengths = jnp.minimum(pos + 1, S)

    def whole(q, k_cache, v_cache, lengths):
        return decode_attention.attend_grouped(
            q, k_cache, v_cache, lengths, scale), jnp.asarray(S, jnp.float32)

    def kernel(q, k_cache, v_cache, lengths):
        attend = (decode_attention.lanes_decode_attention
                  if grouped_lanes(groups, q.shape[2])
                  else decode_attention.grouped_decode_attention)
        return attend(q, k_cache, v_cache, lengths,
                      scale), decode_attention.positions_fetched(lengths)
    if grouped_fused(S, groups, q.shape[1], q.shape[2]):
        return jax.lax.platform_dependent(
            q, k_cache, v_cache, lengths, tpu=kernel, default=whole)
    return whole(q, k_cache, v_cache, lengths)


def block_fused(S: int, d: int) -> bool:
    """Whether a block step over cached heads `d` wide, `S` positions of
    them, can take the kernel form of `block_attention`: a function of the
    static shape alone. Whole blocks and at least two of them, and a cached
    head of whole lane tiles: the kernel cuts a fetched row into its cached
    heads where the tiles' edges are."""
    block = decode_attention.BLOCK
    return S % block == 0 and S >= 2 * block and d % 128 == 0


def block_attention(q, k_cache, v_cache, k, v, pos, scale):
    """softmax(q k^T * scale) v of a block of positions a row, over the
    positions [0, pos[b]) that row holds of the caches (the blocks before
    this one) and over the block's own keys and values k, v [B, L, groups *
    d], which are operands and need be in no cache: every position of the
    block reads all of it, in both directions. q [B, groups, R, d]: the R
    rows of a cached head are the queries of its `heads // groups` query
    heads at the block's L positions, in any order. The caches lie flat,
    [B, S, groups * d]. (o like q, the positions read of the caches.)

    Two forms of the one sum (`models/decode_attention.py`), chosen as
    `cached_attention` chooses: `block_fused` of the static shape, and the
    platform the program is lowered for. A kernel (a TPU): the decode
    kernel's grid over the blocks held up to the furthest row of a grid
    step, the fresh block folded into the same online softmax, each cached
    head's queries against that head's own lanes of a fetched row.
    Everywhere else one softmax over the scores of both and two products a
    side over the whole cache (`attend_block`), whose derivative the kernel
    form carries."""
    S = k_cache.shape[1]

    def whole(*operands):
        return decode_attention.attend_block(
            *operands, scale), jnp.asarray(S, jnp.float32)

    def kernel(*operands):
        return decode_attention.block_decode_attention(
            *operands, scale), decode_attention.positions_fetched(operands[-1])
    operands = (q, k_cache, v_cache, k, v, pos)
    if block_fused(S, q.shape[3]):
        return jax.lax.platform_dependent(
            *operands, tpu=kernel, default=whole)
    return whole(*operands)


def decode_fused(S: int, R: int, d_qk: int, value_dim: int) -> bool:
    """Whether a decode step of `R` query heads a row over a latent cache
    of `S` positions, `d_qk` wide of which the first `value_dim` are the
    values, can take the kernel form of `cached_attention`: a function of
    the static shape alone. Whole blocks and at least two of them (one
    block is the whole window with a kernel's set-up on top), values of
    whole lane tiles, and the rest of a row (the rotary key) of whole
    half tiles, which is what the kernel was compiled and measured at;
    any number of query heads."""
    block = decode_attention.BLOCK
    return (S % block == 0 and S >= 2 * block and value_dim % 128 == 0
            and d_qk > value_dim and (d_qk - value_dim) % 64 == 0)


def _latent_attention(q, cache, pos, scale, value_dim):
    """`cached_attention` of a latent cache: (o, the positions read)."""
    S = cache.shape[1]
    # One cached head, every query head of the row against it.
    q, cache, lengths = q[:, None], cache[:, None], pos + 1

    def whole(q, cache, lengths):
        return decode_attention.whole_window(
            q, cache, None, lengths, scale, value_dim), jnp.asarray(
                S, jnp.float32)

    def kernel(q, cache, lengths):
        return decode_attention.decode_attention(
            q, cache, None, lengths, scale,
            value_dim), decode_attention.positions_fetched(lengths)
    if decode_fused(S, q.shape[2], q.shape[3], value_dim):
        o, read = jax.lax.platform_dependent(
            q, cache, lengths, tpu=kernel, default=whole)
    else:
        o, read = whole(q, cache, lengths)
    return o[:, 0], read


# Positions in a tile of the fused causal attention (see
# `causal_attention`): queries and keys alike, in the forward kernel and in
# the backward one.
CAUSAL_TILE = 512
# The name under which the fused form's output and log-sum-exp are kept
# across a recomputed block (`jax.checkpoint`'s policy in `causal`).
CAUSAL_KEPT = "causal_attention_kept"


def causal_fused(T: int, d_qk: int, d_v: int) -> bool:
    """Whether a causal pass over `T` positions whose heads are `d_qk`
    (queries, keys) and `d_v` (values) wide can take the fused form of
    `causal_attention`: a function of the static shape alone. Whole tiles
    and at least two of them (one tile is the plain form with a kernel's
    set-up on top), and widths the MXU takes whole, or half of one (64,
    lfm2_moe's heads: the one narrower width the kernel and its backward
    were compiled for a v5e and compared with the reference at). Heads of
    256 for queries, keys AND values (qwen3_next's 16 over 2; the latent
    layouts' 256 stand over values of 128 or 256 in as many heads): value
    and gradient at [2, 16 / 2, 4096, 256] 8.86 ms fused against 32.2 plain
    (my chip run, PR 52)."""
    return (T % CAUSAL_TILE == 0 and T >= 2 * CAUSAL_TILE and all(
        d % 128 == 0 or d == 64 for d in (d_qk, d_v)))


def _causal_plain(q, k, v, episode, scale, window=0):
    steps = jnp.arange(q.shape[2])
    mask = (steps[:, None] >= steps[None, :])[None] & (
        episode[:, :, None] == episode[:, None, :])
    if window:
        mask = mask & (steps[:, None] - steps[None, :] < window)[None]
    return _masked_plain(q, k, v, mask, scale)


def _masked_plain(q, k, v, mask, scale):
    """The plain form's sum under `mask` [B, queries, keys]."""
    if k.shape[1] != q.shape[1]:
        # Grouped heads: query head h against key/value head h // their
        # number a group.
        B, heads, T, d = q.shape
        q = q.reshape(B, k.shape[1], -1, T, d)
        scores = jnp.einsum("bgrqd,bgkd->bgrqk", q, k,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
        attn = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bgrqk,bgkd->bgrqd", attn, v).reshape(
            B, heads, T, -1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", attn, v)


def _causal_fused(q, k, v, episode, scale, window=0, mask=None):
    from jax.experimental.pallas.ops.tpu import splash_attention as splash
    B, heads, T, _ = q.shape
    groups = k.shape[1]
    t = CAUSAL_TILE
    # The kernel's static mask: which tiles it visits at all, and the
    # mask it computes inside those the boundary crosses (`mask`: another
    # than the causal or the window one, `block_stream_attention`'s, which
    # may be rectangular: the queries are then the keys' last T positions).
    if mask is None:
        mask = (splash.LocalMask((T, T), (window - 1, 0), 0) if window
                else splash.CausalMask((T, T)))
    settings = dict(
        block_sizes=splash.BlockSizes(
            block_q=t, block_kv=t, block_kv_compute=t, block_q_dkv=t,
            block_kv_dkv=t, block_kv_dkv_compute=t,
            use_fused_bwd_kernel=True),
        head_shards=1, q_seq_shards=1, residual_checkpoint_name=CAUSAL_KEPT)
    segments = episode.astype(jnp.int32)
    # Grouped heads: the kernel's form with one key/value head, a group
    # of query heads at a time.
    grouped = groups != heads
    make = splash.make_splash_mqa if grouped else splash.make_splash_mha
    kernel = make(splash.MultiHeadMask(
        [mask] * (heads // groups if grouped else heads)), **settings)

    def a_row(q, k, v, s):
        return kernel(q, k, v, segment_ids=splash.SegmentIds(
            s if T == s.shape[0] else s[-T:], s))
    # The kernel takes no scale.
    if not grouped:
        return jax.vmap(a_row)(q * scale, k, v, segments)
    return jax.vmap(jax.vmap(a_row, in_axes=(0, 0, 0, None)))(
        (q * scale).reshape(B, groups, -1, T, q.shape[3]), k, v,
        segments).reshape(B, heads, T, -1)


def causal_window_tiles(T: int, window: int) -> tuple:
    """(tiles of `CAUSAL_TILE` x `CAUSAL_TILE` the fused form visits over
    `T` positions with a window of `window` of them, tiles it visits with
    the causal mask alone): a tile is visited where any of its queries
    may attend to any of its keys."""
    n, t = T // CAUSAL_TILE, CAUSAL_TILE
    causal = n * (n + 1) // 2
    if not window:
        return causal, causal
    # Tiles i - j apart: the nearest query and key are (i - j) t - (t - 1)
    # apart.
    return sum(n - apart for apart in range(n)
               if apart * t - (t - 1) < window), causal


def causal_attention(q, k, v, episode, scale, window=0):
    """softmax(q k^T * scale) v over a fragment from an empty window, head
    by head: q [B, heads, T, d_qk], k [B, groups, T, d_qk], v [B, groups,
    T, d_v] (query head h reads key/value head h // (heads // groups);
    as a rule groups = heads), `episode` [B, T] the number of the episode
    a step belongs to (it never falls along a row). Position t attends to
    the positions s <= t of its own episode, and with a `window` to those
    among them with t - s < window; so a step that starts an episode
    attends to itself alone. Returns [B, heads, T, d_v] in q's dtype.

    Two forms of that one sum; both multiply operands in q's dtype and
    accumulate in float32, take the softmax's maximum, exponentials and
    sum in float32, and differentiate with probabilities cast to q's dtype
    where they enter a product.

    Plain: the [B, heads, T, T] scores as one float32 array, scaled,
    masked, normalised, cast, multiplied. At the cells' learner shapes
    that array is 0.5-0.7 GB, written, read and cast through HBM in the
    forward pass, again where a block is recomputed, and differentiated.

    Fused (the library's TPU splash attention kernel and its one backward
    kernel, under `vmap` over B): tiles of `CAUSAL_TILE` queries against
    tiles of keys, the running maximum and sum of a tile's rows in VMEM,
    tiles wholly above the diagonal, and those wholly outside a window,
    skipped (`causal_window_tiles`), the causal or window mask the
    kernel's static mask and the episode its segment ids; with grouped
    heads the kernel's one-key/value-head form a group; the backward pass
    recomputes a tile's probabilities from the saved log-sum-exp a row.
    Nothing [T, T]-sized is written in either pass. The kernel takes no
    scale, so q is multiplied by it first, in q's dtype: exact where the
    scale is a power of two (the latent layout's 1/16); a caller whose
    scale is not folds it into q while q is still float32 and passes 1.
    The output and the log-sum-exp carry the name `CAUSAL_KEPT`, so that
    a caller who recomputes the pass around it may keep the two and run
    the forward kernel once.

    The form is chosen from what the program can see: `causal_fused` of
    the static shape, and the platform the program is lowered for (the
    kernel is Mosaic's, so a program lowered for anything but a TPU keeps
    the plain form)."""
    if not causal_fused(q.shape[2], q.shape[3], v.shape[3]):
        return _causal_plain(q, k, v, episode, scale, window)
    return jax.lax.platform_dependent(
        q, k, v, episode,
        tpu=functools.partial(_causal_fused, scale=scale, window=window),
        default=functools.partial(_causal_plain, scale=scale, window=window))


def block_stream_allowed(T: int, block: int):
    """The mask of a block-diffusion learner's pass over `streams * T`
    positions, the clean stream's T first and each noisy stream's T after it,
    as a function of (query ids, key ids) that numpy and jax.numpy arrays
    both pass through: a query of stream a in block b reads the CLEAN
    stream's keys of the blocks before b and its OWN stream's keys of block
    b, all of them. For the clean stream that is block-causal attention:
    every position of its own and earlier blocks."""
    def allowed(q, k):
        q_stream, k_stream = q // T, k // T
        q_block, k_block = (q % T) // block, (k % T) // block
        return ((k_stream == 0) & (k_block < q_block)) | (
            (k_stream == q_stream) & (k_block == q_block))
    return allowed


@functools.lru_cache(maxsize=None)
def _block_stream_mask(T: int, block: int, streams: int, queries: int):
    """`block_stream_allowed` as a mask the splash kernel computes inside
    itself, tile by tile, from the positions' numbers: the LAST `queries`
    streams' queries (all of them: the square mask) against every stream's
    keys, a query's number counted from the first stream's first position
    as a key's is."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as masks)
    allowed = block_stream_allowed(T, block)
    first = (streams - queries) * T

    class BlockStreamMask(masks._ComputableMask):
        def __init__(self):
            super().__init__(
                (queries * T, streams * T),
                (lambda q, k: allowed(q + first, k)) if first else allowed)

        def __getitem__(self, idx):
            """The library reads the mask tile by tile at every start, to
            see which tiles it visits: a block's positions read alike, so
            one of each block is computed (a sixteenth of the work at blocks
            of 4: ~1 s of set-up for the two masks at T 2,048)."""
            q0, q1, k0, k1 = (edge for s, n in zip(idx, self.shape)
                              for edge in s.indices(n)[:2])
            if any(edge % block for edge in (q0, q1, k0, k1)):
                return super().__getitem__(idx)
            one = self.mask_function(np.arange(q0, q1, block)[:, None],
                                     np.arange(k0, k1, block)[None, :])
            return one.repeat(block, axis=0).repeat(block, axis=1)

        def __eq__(self, other):
            return self is other

        def __hash__(self):
            return hash((type(self).__name__, T, block, streams, queries))
    return BlockStreamMask()


def block_stream_tiles(T: int, block: int, streams: int,
                       queries: int) -> tuple:
    """(tiles of `CAUSAL_TILE` x `CAUSAL_TILE` the fused form of
    `block_stream_attention` visits with the last `queries` streams'
    queries against `streams` streams' keys of `T` positions, all the tiles
    of that rectangle): a tile is visited where any of its queries may read
    any of its keys, as `causal_window_tiles` counts. T 2,048, a clean and
    two noisy streams: 38 of 144 with every stream's queries, 28 of 96 with
    the noisy streams' alone."""
    t = CAUSAL_TILE
    allowed = block_stream_allowed(T, block)
    first, at = (streams - queries) * T, np.arange(t)
    rows, columns = queries * T // t, streams * T // t
    return sum(
        bool(allowed(first + i * t + at[:, None], j * t + at[None, :]).any())
        for i in range(rows) for j in range(columns)), rows * columns


def _streams_plain(q, k, v, episode, scale, block, streams):
    n, rows = k.shape[2], q.shape[2]
    ids = jnp.arange(n)
    mask = block_stream_allowed(n // streams, block)(
        ids[n - rows:, None], ids[None, :])[None] & (
            episode[:, n - rows:, None] == episode[:, None, :])
    return _masked_plain(q, k, v, mask, scale)


def block_stream_attention(q, k, v, episode, scale, block, streams):
    """softmax(q k^T * scale) v of a block-diffusion learner's pass: k, v
    [B, groups, streams * T, d], the clean stream's T positions first and
    each noisy stream's after it, `episode` [B, streams * T] the episode a
    position belongs to (the clean stream's, repeated); q [B, heads, n, d]
    the queries of the LAST n positions, whole streams of them: all
    `streams * T` (every layer but the last), or the noisy streams' alone
    (`block_causal`'s last layer, whose clean stream is read for its keys
    and values and nothing else). A query reads, within its episode, what
    `block_stream_allowed` says: the clean stream's keys of earlier blocks
    of `block` positions and its own stream's keys of its own block. Two
    forms of that one sum, as `causal_attention`'s and chosen as its are
    (`causal_fused` of the static shape, and the platform): the plain one's
    scores are [n, streams * T] a head; the fused one is the same splash
    kernel with the mask, square or rectangular, computed inside it from the
    positions' numbers, so that the tiles it visits are those in which any
    query may read any key (`block_stream_tiles`; T 2,048, two noisy
    streams: 38 of 144, and 28 of 96 without the clean queries, whose 10
    are a causal pass's over T)."""
    if not all(causal_fused(a.shape[2], q.shape[3], v.shape[3])
               for a in (q, k)):
        return _streams_plain(q, k, v, episode, scale, block, streams)
    T = k.shape[2] // streams
    return jax.lax.platform_dependent(
        q, k, v, episode,
        tpu=functools.partial(
            _causal_fused, scale=scale,
            mask=_block_stream_mask(T, block, streams, q.shape[2] // T)),
        default=functools.partial(_streams_plain, scale=scale, block=block,
                                  streams=streams))


def _taps_causal(g, w, positions):
    """A depthwise causal convolution over a fragment: g [B, T, channels],
    taps w [channels, L], the last one on the current position, a tap that
    would reach before its episode's first step (`positions`: a step's
    place in its episode) reading 0. Returns (v_t = sum_j w[:, j]
    g_{t - (L - 1) + j}, multiplied and summed in float32; g with L - 1
    zeros ahead of it, for `_taps_tail`)."""
    L, T = w.shape[1], g.shape[1]
    w = w.astype(jnp.float32)
    # back[:, L - 1 - s + t] = g_{t - s}, zeros before the fragment.
    back = jnp.pad(g, ((0, 0), (L - 1, 0), (0, 0)))
    v = jnp.zeros(g.shape, jnp.float32)
    for j in range(L):
        s = L - 1 - j
        v = v + w[:, j] * jnp.where(
            (positions >= s)[..., None], back[:, j:j + T],
            0).astype(jnp.float32)
    return v, back


def _taps_tail(back, positions):
    """The state a decode continues `_taps_causal` from: the last
    episode's last L - 1 inputs [B, L - 1, channels], 0 where the episode
    is shorter."""
    T = positions.shape[1]
    L = back.shape[1] - T + 1
    # Slot j of the state is g_{T - (L - 1) + j}.
    held = positions[:, -1:] >= (L - 2 - jnp.arange(L - 1))
    return jnp.where(held[..., None], back[:, T:], 0)


def _taps_step(g, w, state, reset):
    """The same of one position a row, g [B, channels], against the row's
    last L - 1 inputs `state`, zeroed first where `reset`; (v float32, the
    L inputs the taps met: all but the oldest are the new state)."""
    state = jnp.where((reset > 0)[:, None, None], 0, state)
    taps = jnp.concatenate([state, g[:, None]], axis=1)
    return jnp.sum(taps.astype(jnp.float32)
                   * w.astype(jnp.float32).T, axis=1), taps


# Positions in a sub-block of a chunk of the KDA scan (see `kda_chunked`).
KDA_SUB_BLOCK = 16


def _exactly(subscripts, a, b):
    """A float32 product that is one: on a TPU a float32 `einsum` rounds
    its operands to bfloat16 unless told otherwise."""
    return jnp.einsum(subscripts, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _unit_lower_inverse(L, block):
    """M = (I + L)^-1 for strictly lower triangular L [.., C, C], float32,
    by forward substitution in blocks of `block` rows, every system at
    once: the diagonal blocks by the library's triangular solve against
    the identity (told that the diagonal is 1, it does not read it), then
    a block row at a time, M[I, :] = M[I, I] (E_I - L[I, < I] M[< I, :]),
    two products a block row."""
    lead, C = L.shape[:-2], L.shape[-1]
    n = C // block
    eye = jnp.broadcast_to(jnp.eye(block, dtype=L.dtype),
                           lead + (block, block))
    by_block = L.reshape(lead + (n, block, n, block))
    diagonal = jnp.stack(
        [by_block[..., I, :, I, :] for I in range(n)], axis=-3)
    diagonal = jax.scipy.linalg.solve_triangular(
        diagonal, jnp.broadcast_to(eye[..., None, :, :], diagonal.shape),
        lower=True, unit_diagonal=True)
    rows = []
    for I in range(n):
        done = I * block
        ahead = [-_exactly(
            "...ij,...jk->...ik", L[..., done:done + block, :done],
            jnp.concatenate(rows, axis=-2)[..., :done])] if I else []
        row = _exactly("...ij,...jk->...ik", diagonal[..., I, :, :],
                       jnp.concatenate(ahead + [eye], axis=-1))
        rows.append(jnp.pad(row, ((0, 0),) * (len(lead) + 1)
                            + ((0, C - done - block),)))
    return jnp.concatenate(rows, axis=-2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def unit_lower_solve(L, rhs, block):
    """The X of (I + L) X = R for each R of the tuple `rhs` and every
    system at once: L [.., C, C] strictly lower triangular, each R [.., C,
    W], float32; `block` divides C. The inverse M is made by forward
    substitution (`_unit_lower_inverse`) and multiplied in, so that the
    pullback is products against what the forward pass made and no second
    solve: dR = M^T dX, dL = the strictly lower part of -sum dR X^T. NOT
    by the product (I - L)(I + L^2)(I + L^4)..: where L is the all-ones
    triangle (equal keys, beta = 1, no decay) float32 cancels its powers
    to nothing."""
    return _unit_lower_solve_fwd(L, rhs, block)[0]


def _unit_lower_solve_fwd(L, rhs, block):
    inverse = _unit_lower_inverse(L, block)
    X = tuple(_exactly("...ij,...jw->...iw", inverse, R) for R in rhs)
    return X, (inverse, X)


def _unit_lower_solve_bwd(block, kept, dX):
    inverse, X = kept
    d_rhs = tuple(_exactly("...ji,...jw->...iw", inverse, d) for d in dX)
    return -jnp.tril(sum(_exactly("...iw,...jw->...ij", d, x)
                         for d, x in zip(d_rhs, X)), -1), d_rhs


unit_lower_solve.defvjp(_unit_lower_solve_fwd, _unit_lower_solve_bwd)


def _head_decay_chunk(q, k, v, g, beta, episode, before, dtype):
    """`_kda_chunk` where the decay is ONE a head (Gated DeltaNet): g [B,
    heads, C], the float32 log decay of a VALUE head, v [B, heads, C, d_v],
    beta [B, heads, C, 1], and q, k [B, key heads, C, d_k], a key head
    serving `heads // key heads` consecutive value heads. The same seven
    terms. exp(G_i - G_j) is then one number a pair and head, so the pairs'
    products are a key head's plain q k^T and k k^T ([C, C], matrix
    products, made once for the value heads that share the key) times a
    [C, C] matrix of decays a value head: no [sub, sub, d] products, no
    sub-blocks, no exp a channel. The pair's exponent is a masked cumulative
    sum of g along i (`_ssd_chunk`'s rule: a sum of log decays, <= 0, never
    a difference of two cumulative sums)."""
    f32 = jnp.float32
    C = g.shape[-1]
    shared = v.shape[1] // k.shape[1]
    at = jnp.arange(C)
    earlier = at[:, None] > at[None, :]  # j < i
    # seg[i, j]: the sum of g over the positions after j up to and with i.
    seg = jnp.cumsum(jnp.where(earlier, g[..., :, None], 0.0), axis=-2)
    cum = jnp.cumsum(g, axis=-1)
    same = (episode[:, :, None] == episode[:, None, :])[:, None]
    decay = jnp.exp(jnp.where(same & (at[:, None] >= at[None, :]), seg,
                              -jnp.inf))
    kd = k.astype(dtype)
    kk = jnp.einsum("bhic,bhjc->bhij", kd, kd, preferred_element_type=f32)
    qk = jnp.einsum("bhic,bhjc->bhij", q.astype(dtype), kd,
                    preferred_element_type=f32)
    if shared > 1:
        q, k, kk, qk = (jnp.repeat(a, shared, axis=1) for a in (q, k, kk, qk))
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    kk = jnp.where(earlier, kk * decay, 0.0)
    qk = qk * decay
    # Against the states at the chunk's two ends.
    since = jnp.exp(cum)[..., None]
    until = jnp.exp(seg[..., -1, :])[..., None]
    carried = (episode == before[:, None])[:, None, :, None]
    lasting = (episode == episode[:, -1:])[:, None, :, None]
    q_in = jnp.where(carried, q * since, 0.0).astype(dtype)
    k_in = jnp.where(carried, k * since, 0.0)
    k_out = jnp.where(lasting, k * until, 0.0).astype(dtype)
    keep = jnp.where((episode[:, -1] == before)[:, None, None],
                     jnp.exp(cum[..., -1:]), 0.0)
    return (beta * kk, beta * v, beta * k_in, q_in, qk.astype(dtype), k_out,
            keep)


def _kda_chunk(q, k, v, g, beta, episode, before, sub, dtype):
    """One chunk of `kda_chunked` up to the state it begins with, for every
    row and head at once: q, k, g [B, heads, C, d_k] (g <= 0 the float32
    log decay a channel), v [B, heads, C, d_v], beta [B, heads, C, 1],
    `episode` [B, C] and `before` [B], the episode of the position ahead
    of the chunk. With G_i the sum of g over the chunk's positions up to
    and including i:

        kk[i, j] = sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])     j < i
        qk[i, j] = sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])     j <= i
            both 0 where i and j lie in different episodes;
        q_in[i]  = q_i * exp(G_i), k_in[i] = k_i * exp(G_i): what meets the
            state the chunk begins with, 0 where an episode has begun
            inside the chunk by position i;
        k_out[j] = k_j * exp(G_C - G_j): what position j leaves in the
            state the chunk ends with, 0 where j's episode ends inside it;
        keep     = exp(G_C), 0 where an episode begins inside the chunk;
    and the chunk's triangular system, (I + L) [w_v | w_k] = Diag(beta) [V
    | k_in] with L = Diag(beta) kk, which `kda_chunked` solves for every
    chunk at once. Returns (L, beta V, beta k_in, q_in, qk, k_out, keep).

    No exp(-G) is ever formed: a channel may lose e^50 in ONE position, so
    exp(G_i) * exp(-G_j) overflows float32 inside a chunk while the pair's
    own exp(G_i - G_j) <= 1 is harmless. Every exponent here is a sum of
    g's, never a difference of two cumulative sums across sub-blocks, so
    it is <= 0 by construction and loses nothing to cancellation: in
    sub-blocks of `sub` positions, a pair of different sub-blocks I > J
    through the point between them,
        exp(G_i - G_j) = exp(sum of g over I up to i)
                         * exp(sum over J after j + the sub-blocks between),
    two factors <= 1 and so one matrix product a pair of sub-blocks; a
    pair inside one sub-block from the difference of the sub-block's own
    cumulative sums, channel by channel, elementwise ([sub, sub, d] a
    sub-block: why the sub-blocks are small). Matrix operands are cast to
    `dtype`, sums are float32.

    A decay of ONE number a head (g [B, heads, C]: Gated DeltaNet's) makes
    the same terms by `_head_decay_chunk`, without the sub-blocks."""
    if g.ndim == q.ndim - 1:
        return _head_decay_chunk(q, k, v, g, beta, episode, before, dtype)
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    lead, (C, d) = g.shape[:-2], g.shape[-2:]
    n = C // sub
    by_sub = lead + (n, sub, d)
    gs, qs, ks = g.reshape(by_sub), q.reshape(by_sub), k.reshape(by_sub)
    # Sums of g: over a sub-block up to i; over it after j; over whole
    # sub-blocks ahead of I, behind J, and strictly between J and I.
    within = jnp.cumsum(gs, axis=-2)
    behind = jnp.flip(jnp.cumsum(jnp.flip(gs, -2), axis=-2), -2)
    after = jnp.concatenate(
        [behind[..., 1:, :], jnp.zeros_like(gs[..., :1, :])], axis=-2)
    whole = within[..., -1, :]  # [.., n, d]
    order = jnp.arange(n)

    def over(mask):
        """Sums of whole sub-blocks' g, those `mask` [.., K] names.
        Selected and added in float32, not multiplied by 0 / 1: on a TPU a
        float32 matrix product rounds its operands to bfloat16 by default,
        and a log decay of -50 rounded to eight bits is a decay 20 % off."""
        at = whole.reshape(lead + (1,) * (mask.ndim - 1) + (n, d))
        return jnp.sum(jnp.where(mask[..., None], at, 0.0), axis=-2)
    ahead = over(order[None, :] < order[:, None])  # [.., I, d]: K < I
    astern = over(order[None, :] > order[:, None])  # [.., J, d]: K > J
    between = over((order[None, :, None] < order[None, None, :])
                   & (order[None, None, :] < order[:, None, None]))
    # Inside a sub-block, channel by channel.
    steps = jnp.arange(sub)
    lower = steps[:, None] >= steps[None, :]
    decay = jnp.exp(jnp.where(
        lower[..., None],
        within[..., :, None, :] - within[..., None, :, :], -jnp.inf))
    k_decayed = ks[..., None, :, :] * decay  # [.., n, i, j, d]
    kk_in = jnp.sum(ks[..., :, None, :] * k_decayed, axis=-1)
    qk_in = jnp.sum(qs[..., :, None, :] * k_decayed, axis=-1)
    # Between sub-blocks, through the point ahead of sub-block I.
    left = jnp.exp(within)
    right = (ks[..., None, :, :, :] * jnp.exp(
        after[..., None, :, :, :] + between[..., None, :])).astype(dtype)
    kk_off = jnp.einsum("...Iic,...IJjc->...IiJj", (ks * left).astype(dtype),
                        right, preferred_element_type=f32)
    qk_off = jnp.einsum("...Iic,...IJjc->...IiJj", (qs * left).astype(dtype),
                        right, preferred_element_type=f32)
    same_sub = (order[:, None] == order[None, :])[:, None, :, None]
    earlier = (order[:, None] > order[None, :])[:, None, :, None]

    def joined(inside, off):
        return jnp.where(
            same_sub, inside[..., :, :, None, :],
            jnp.where(earlier, off, 0.0)).reshape(lead + (C, C))
    same = (episode[:, :, None] == episode[:, None, :])[:, None]
    at = jnp.arange(C)
    kk = jnp.where(same & (at[:, None] > at[None, :]),
                   joined(kk_in, kk_off), 0.0)
    qk = jnp.where(same, joined(qk_in, qk_off), 0.0)
    # Against the states at the chunk's two ends.
    since = jnp.exp(ahead[..., None, :] + within).reshape(lead + (C, d))
    until = jnp.exp(after + astern[..., None, :]).reshape(lead + (C, d))
    carried = (episode == before[:, None])[:, None, :, None]
    lasting = (episode == episode[:, -1:])[:, None, :, None]
    q_in = jnp.where(carried, q * since, 0.0).astype(dtype)
    k_in = jnp.where(carried, k * since, 0.0)
    k_out = jnp.where(lasting, k * until, 0.0).astype(dtype)
    keep = jnp.where((episode[:, -1] == before)[:, None, None],
                     jnp.exp(jnp.sum(whole, axis=-2)), 0.0)
    return (beta * kk, beta * v, beta * k_in, q_in, qk.astype(dtype), k_out,
            keep)


def kda_chunked(q, k, v, g, beta, episode, chunk, dtype=jnp.float32,
                scope="policy/kda_state"):
    """The gated delta rule over a fragment from an empty state, in chunks:
    q, k, g [B, T, heads, d_k], v [B, T, heads, d_v], beta [B, T, heads]
    (q and k normalised, q scaled; g <= 0 the LOG decay a channel of the
    key, float32, as beta is), `episode` [B, T] the number of the episode
    a step belongs to (it never falls along a row). The decay in the shape
    the model publishes, and the keys likewise: a channel's (Kimi Delta
    Attention, as above), or ONE a head, g [B, T, heads] (Gated DeltaNet),
    and then q and k may be of fewer heads, [B, T, key heads, d_k], each
    serving `heads // key heads` consecutive value heads; Diag(exp(g_t)) is
    then exp(g_t) I. Both go through this one function: the chunk's terms
    differ (`_kda_chunk`: the per-channel decays' sub-blocks, or
    `_head_decay_chunk`'s one [C, C] matrix of decays a head), the solve,
    the scan and the backward pass are the same code. What the scalar form
    saves, on a v5e, value and gradient of one layer's scan at [2, 4096,
    32, 128], bf16 operands, chunks of 64 (my chip run, PR 52; PERF.md
    section 5): a decay a channel 58.9 ms; one decay a head widened to a
    head's channels and fed to that path 58.6 (so it is never widened);
    one decay a head as it is 30.7 with 32 key heads and 31.2 with 16
    serving two value heads each (sharing a key's products saves nothing:
    the pairs' decays are a value head's); chunks of 32 30.9, of 128 40.0.
    A head's state S [d_k, d_v] is 0 where an episode begins and

        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t

    Returns (o [B, T, heads, d_v] in `dtype`, S after the last position
    [B, heads, d_k, d_v] float32: what a decode continues from).

    With u_t = beta_t (v_t - (Diag(exp(g_t)) S_{t-1})^T k_t) the update
    is S_t = Diag(exp(g_t)) S_{t-1} + k_t u_t^T, so inside a chunk of C
    positions that begins with S_0 (`_kda_chunk` has the names)

        (I + Diag(beta) kk) U = Diag(beta) (V - k_in S_0)
        O = q_in S_0 + qk U
        S_C = Diag(keep) S_0 + k_out^T U

    the delta rule in its triangular (UT / WY) form: one unit lower
    triangular system a chunk and head, with [beta V | beta k_in] on the
    right so that it is solved once, ahead of the states. Two phases.
    The chunks' decayed products (`_kda_chunk`), a chunk at a time
    (`lax.map`), then ALL N x B x heads systems solved in one call
    (`unit_lower_solve`); the scan over the chunks that carries S, three
    matrix products a step. Both bodies are recomputed in the backward
    pass: what either holds at once is one chunk's ([sub, sub, d_k]
    products a sub-block, not the fragment's), the chunk phase's residuals
    are the systems and their solutions ([N, B, heads, C, C + 2 d]
    float32), the scan's the chunk states.
    An episode that begins inside the fragment cuts both: pairs of
    different episodes are 0, a position reads S_0 only while no episode
    has begun in its chunk, and S_C keeps of S_0 and of its own positions
    what belongs to the chunk's last episode. T need not be whole chunks:
    the tail is padded with positions that decay nothing and write
    nothing (g = 0, beta = 0). Matrix operands are cast to `dtype`, sums,
    decays and S are float32."""
    f32 = jnp.float32
    (B, T, _, d_k), heads = q.shape, v.shape[2]
    sub = min(KDA_SUB_BLOCK, chunk)
    assert chunk % sub == 0, (chunk, sub)
    pad = -T % chunk
    N = (T + pad) // chunk

    def chunks(a, mode="constant"):
        """[B, T, ..] -> [N, B, .., chunk] chunk-major, heads ahead of
        the positions; the tail padded with zeros, or with the last
        position's value."""
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2),
                    mode=mode)
        a = jnp.moveaxis(a.reshape((B, N, chunk) + a.shape[2:]), 1, 0)
        return a if a.ndim == 3 else jnp.moveaxis(a, 2, 3)
    q, k, v, g = (chunks(a) for a in (q, k, v, g))  # [N, B, heads, C, d]
    beta = chunks(beta[..., None])  # [N, B, heads, C, 1]
    episode = chunks(episode, mode="edge")  # [N, B, C]
    # The episode ahead of a chunk; ahead of the first the state is 0
    # whatever it is called.
    before = jnp.concatenate([episode[:1, :, 0], episode[:-1, :, -1]])
    with jax.named_scope(scope):
        L, *rhs, q_in, qk, k_out, keep = jax.lax.map(
            jax.checkpoint(lambda xs: _kda_chunk(*xs, sub=sub, dtype=dtype)),
            (q, k, v, g, beta, episode, before))
        w_v, w_k = unit_lower_solve(L, tuple(rhs), sub)
        terms = (jnp.concatenate([w_k.astype(dtype), q_in], axis=-2), w_v,
                 qk, k_out, keep)

        def a_chunk(S, xs):
            into, w_v, qk, k_out, keep = xs
            met = jnp.einsum("bhik,bhkv->bhiv", into, S.astype(dtype),
                             preferred_element_type=f32)
            u = w_v - met[:, :, :chunk]
            o = met[:, :, chunk:] + jnp.einsum(
                "bhij,bhjv->bhiv", qk, u.astype(dtype),
                preferred_element_type=f32)
            S = keep[..., None] * S + jnp.einsum(
                "bhjk,bhjv->bhkv", k_out, u.astype(dtype),
                preferred_element_type=f32)
            return S, o.astype(dtype)
        S, o = jax.lax.scan(
            jax.checkpoint(a_chunk),
            jnp.zeros((B, heads, d_k, v.shape[-1]), f32), terms)
    # [N, B, heads, C, d_v] -> [B, T, heads, d_v]
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(
        (B, N * chunk, heads, -1))
    return o[:, :T], S


def kda_step(S, q, k, v, g, beta):
    """The same recurrence, one position: S [B, heads, d_k, d_v] float32
    against q, k, g [B, heads, d_k], v [B, heads, d_v], beta [B, heads];
    (o [B, heads, d_v], the state after the position). g [B, heads] is ONE
    decay a head (Gated DeltaNet), exp(g) times the whole of a head's
    state. Decay by rows of d_k, one outer product subtracted and added, q
    read:

        S' = Diag(exp(g)) S;  u = beta (v - S'^T k);  S = S' + k u^T
        o = S^T q = S'^T q + (k . q) u

    written so that S is passed over three times and no more: both
    products against S' are sums over the one read of it (what q reads
    of the new state is what it reads of the decayed one, plus u times a
    scalar), then one read and one write make S."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    decayed = jnp.exp(g)[(...,) + (None,) * (S.ndim - g.ndim)] * S
    from_k = jnp.sum(decayed * k[..., None], axis=-2)
    from_q = jnp.sum(decayed * q[..., None], axis=-2)
    u = beta[..., None] * (v - from_k)
    o = from_q + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, decayed + k[..., None] * u[..., None, :]


def _kda_reset_step(S, q, k, v, g, beta, reset):
    """`kda_step` of the states zeroed in the rows that `reset` [B] > 0,
    as XLA compiles it: the select rides in the first pass."""
    S = jnp.where((reset > 0)[:, None, None, None], 0.0, S)
    return kda_step(S, q, k, v, g, beta)


_kda_in_place = state_step.in_place(state_step.kda_kernel, _kda_reset_step)


def kda_decode_step(S, q, k, v, g, beta, reset):
    """A decode step of KDA's states, those of the rows that `reset` zeroed
    first. Where the states are whole tiles (`state_step.whole_tiles`, of the
    static shape) a program lowered for a TPU takes the kernel, S read once
    and written once in place, with the plain form's derivative; everywhere
    else the plain form, XLA's three passes. One decay a head, g [B, heads]
    (Gated DeltaNet's), takes the same kernel and the same plain form. On a
    v5e, three layers' states [32, 32, 128, 128] carried by a scan, ms a
    step for the three (my chip run, PR 52): XLA's passes 0.873 under either
    decay; the kernel under a decay a channel 0.731, under one a head 0.697
    (578 GB/s of the states' 403 MB), and 0.730 where the one is first
    widened to a head's channels: it is handed over as it is."""
    operands = (S, q, k, v, g, beta, reset)
    if state_step.whole_tiles(*S.shape[1:]):
        return jax.lax.platform_dependent(
            *operands, tpu=_kda_in_place, default=_kda_reset_step)
    return _kda_reset_step(*operands)


def _ssd_chunk(x, Bm, Cm, la, episode, before, dtype):
    """One chunk of `ssd_chunked` up to the state it begins with, for every
    row and head at once, the heads by group: x [B, G, R, C, P] (dt x; R
    heads a group), Bm, Cm [B, G, C, N], la [B, G, R, C] (<= 0, the float32
    log decay a head), `episode` [B, C] and `before` [B], the episode of
    the position ahead of the chunk. With seg[t, s] the sum of la over the
    positions after s up to and including t:

        y[t]  = sum_{s <= t} exp(seg[t, s]) (C_t . B_s) x_s, pairs of
            different episodes 0;
        left  = sum_s exp(seg[C - 1, s]) x_s B_s^T: what the chunk's
            positions leave in the state it ends with, those of its last
            episode alone;
        keep  = exp(seg[C - 1, -1]), what it keeps of the state it begins
            with: 0 where an episode begins inside the chunk;
        into[t] = exp(seg[t, -1]), the weight of that state at position t:
            0 where an episode has begun inside the chunk by t.
    Returns (y [B, G, R, C, P], left [B, G, R, P, N], keep [B, G, R], into
    [B, G, R, C]), float32.

    Every exponent is a sum of log decays, <= 0 by construction: seg is a
    cumulative sum along t of la placed at [r, s] for r > s (a [C, C]
    array a head), not the difference of two cumulative sums, which
    cancels where one burst of decay stands ahead of a quiet stretch; the
    sums from the chunk's first position are the plain cumulative sum.
    Selected, never multiplied by 0 / 1 (`_kda_chunk.over` has why). Matrix
    operands are cast to `dtype`, sums are float32."""
    f32 = jnp.float32
    at = jnp.arange(la.shape[-1])
    seg = jnp.cumsum(jnp.where(at[:, None] > at[None, :], la[..., :, None],
                               0.0), axis=-2)  # [.., t, s]
    cum = jnp.cumsum(la, axis=-1)
    same = (episode[:, :, None] == episode[:, None, :]) & (
        at[:, None] >= at[None, :])
    decay = jnp.exp(jnp.where(same[:, None, None], seg, -jnp.inf))
    cb = jnp.einsum("bgtn,bgsn->bgts", Cm, Bm, preferred_element_type=f32)
    y = jnp.einsum("bgrts,bgrsp->bgrtp",
                   (cb[:, :, None] * decay).astype(dtype), x,
                   preferred_element_type=f32)
    carried = (episode == before[:, None])[:, None, None]
    lasting = (episode == episode[:, -1:])[:, None, None]
    into = jnp.where(carried, jnp.exp(cum), 0.0)
    until = jnp.where(lasting, jnp.exp(seg[..., -1, :]), 0.0)
    left = jnp.einsum(
        "bgrsp,bgsn->bgrpn",
        (x.astype(f32) * until[..., None]).astype(dtype), Bm,
        preferred_element_type=f32)
    keep = jnp.where((episode[:, -1] == before)[:, None, None],
                     jnp.exp(cum[..., -1]), 0.0)
    return y, left, keep, into


def ssd_chunked(x, Bm, Cm, la, episode, chunk, dtype=jnp.float32):
    """Mamba-2's state-space layer over a fragment from an empty state, in
    chunks: x [B, T, heads, P] (the input times its time step, dt x), Bm,
    Cm [B, T, G, N] (head h reads group h // (heads / G)), la [B, T, heads]
    (<= 0 the LOG decay a head, float32), `episode` [B, T] the number of
    the episode a step belongs to (it never falls along a row). A head's
    state S [P, N] is 0 where an episode begins and

        S_t = exp(la_t) S_{t-1} + x_t B_t^T;   y_t = S_t C_t

    Returns (y [B, T, heads, P] in `dtype`, S after the last position [B,
    heads, P, N] float32: what a decode continues from). D x is the
    caller's to add.

    Two phases, `kda_chunked`'s skeleton. The chunks' own terms
    (`_ssd_chunk`: what a chunk's positions give each other, and what they
    leave behind), a chunk at a time (`lax.map`); then a `lax.scan` over
    the chunks that carries S, one matrix product a step (what the state a
    chunk begins with gives its positions). Both bodies are recomputed in
    the backward pass: what either holds at once is one chunk's ([C, C]
    pair weights a head), and the scan's residuals are the chunk states.
    An episode that begins inside the fragment cuts both, as it cuts
    `kda_chunked`. T need not be whole chunks: the tail is padded with
    positions that decay nothing and write nothing (la = 0, x = 0)."""
    f32 = jnp.float32
    B, T, heads, P = x.shape
    G, N = Bm.shape[2:]
    R = heads // G
    pad = -T % chunk
    n = (T + pad) // chunk

    def chunks(a, mode="constant"):
        """[B, T, ..] -> [n, B, chunk, ..], the tail padded with zeros, or
        with the last position's value."""
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2),
                    mode=mode)
        return jnp.moveaxis(a.reshape((B, n, chunk) + a.shape[2:]), 1, 0)
    # Heads by group, ahead of the positions.
    x = jnp.moveaxis(chunks(x).reshape(n, B, chunk, G, R, P), 2, 4)
    la = jnp.moveaxis(chunks(la).reshape(n, B, chunk, G, R), 2, 4)
    Bm, Cm = (jnp.moveaxis(chunks(a), 2, 3) for a in (Bm, Cm))
    episode = chunks(episode, mode="edge")  # [n, B, C]
    # The episode ahead of a chunk; ahead of the first the state is 0
    # whatever it is called.
    before = jnp.concatenate([episode[:1, :, 0], episode[:-1, :, -1]])
    with jax.named_scope("policy/ssm_state"):
        y, left, keep, into = jax.lax.map(
            jax.checkpoint(lambda xs: _ssd_chunk(*xs, dtype=dtype)),
            (x, Bm, Cm, la, episode, before))

        def a_chunk(S, xs):
            Cm, left, keep, into = xs
            met = jnp.einsum("bgtn,bgrpn->bgrtp", Cm, S.astype(dtype),
                             preferred_element_type=f32)
            return (keep[..., None, None] * S + left,
                    into[..., None] * met)
        S, carried = jax.lax.scan(
            jax.checkpoint(a_chunk), jnp.zeros((B, G, R, P, N), f32),
            (Cm, left, keep, into))
        y = (y + carried).astype(dtype)
    # [n, B, G, R, C, P] -> [B, T, heads, P]
    y = jnp.moveaxis(jnp.moveaxis(y, 4, 2), 0, 1).reshape(
        B, n * chunk, heads, P)
    return y[:, :T], S.reshape(B, heads, P, N)


def ssd_step(S, x, Bm, Cm, la):
    """The same recurrence, one position: S [B, heads, P, N] float32
    against x [B, heads, P] (dt x), Bm, Cm [B, G, N], la [B, heads]; (y [B,
    heads, P] float32, the state after the position):

        S = exp(la) S + x B^T;   y = S C

    the recurrence as written, elementwise work and one sum: S is read
    once and written once."""
    B, heads, P, N = S.shape
    G = Bm.shape[1]
    by_group = (B, G, heads // G)
    x, Bm, Cm = (a.astype(jnp.float32) for a in (x, Bm, Cm))
    S = (jnp.exp(la).reshape(by_group)[..., None, None]
         * S.reshape(by_group + (P, N))
         + x.reshape(by_group + (P, 1)) * Bm[:, :, None, None, :])
    y = jnp.sum(S * Cm[:, :, None, None, :], axis=-1)
    return y.reshape(B, heads, P), S.reshape(B, heads, P, N)


def experts_batched(M: int, k: int, E: int) -> bool:
    """Whether `M` rows, each routed to `k` of `E` experts, go through the
    batched form (`M * E` rows of work) or the grouped one (`M * k` sorted
    rows in `E` groups): a function of the static shape alone. A layer
    that holds a share of the experts asks the same question of the same
    numbers: with `held` of them here the batched form is `M * held` rows
    of work and the grouped one `held` groups of `M * k * held / E`
    expected rows, the same inequality times `held / E`. (The grouped form
    of a share works on the rows that landed, by `dispatch_rows`, so its
    side of the inequality is what it costs, not a worst case.)"""
    return M * E <= GROUP_COST_ROWS * E + GROUPED_ROW_COST * M * k


# The share of the held experts that a step must be expected to leave
# without a row before its products read the chosen ones alone
# (`experts_sparse`): the edge measured on a v5e lies between 0.08 and 0.13.
SPARSE_EMPTY_SHARE = 0.1


def experts_sparse(M: int, k: int, E: int, H: int, W: int,
                   dtype=jnp.bfloat16) -> bool:
    """Whether a rollout's step of `M` rows of hidden `H`, each routed to
    `k` of `E` experts `W` wide, reads the matrices of the held experts that
    some row chose and of no other (`expert_step.chosen_kernel`) in a
    program lowered for a TPU: a function of the static shape alone,
    `experts_batched`'s neighbour, and the one place that decides it
    (`dropless_experts` asks it for the products, `TokenDecoder.
    decode_sparse` for the step's counter and `static_counters`). A step of
    so few rows is bound by reading the experts' matrices in either form,
    which `experts_batched`'s inequality does not describe; what decides is
    the share of them a step leaves without a row, `(1 - k / E) ** M` under
    a uniform router (a skewed one leaves more), whatever share of the
    experts is held here, at `SPARSE_EMPTY_SHARE` or more, and the kernel's
    tiles (`expert_step.whole_tiles`).

    The edge, on a v5e (my chip run, PR 53, `chiprun_out/pr53/micro2.json`:
    one layer's held experts alone under a uniform seeded router, the
    kernel's ms a step over the batched form's, by the rows a step brings):
    at the three cells' experts the kernel took 0.32-0.86 of the batched
    form's time wherever this count is 0.15 or more (16 to 96 rows over 32
    held of 512, 10 a row; 16 to 48 over 8 of 256, 8 a row; 16 over 16 of
    64, 6 a row), 0.94 at 0.13 (64 rows over 8 of 256), and 0.98-1.05 at
    0.047 and under (96 rows there; from 32 rows over 16 of 64), where
    nearly every held expert has a row and a grid step's work grows with
    the rows; past that XLA's batched product changes with the rows (at 128
    rows a faster program, the kernel 1.17-2.0 of it; at 192 a slower one,
    0.84-0.98; at 256 1.02-1.19). At the other token cells' own steps (count
    under 0.003) it tied or lost: 0.99 (OLMoE's 128 rows over 64 of 64),
    1.04 (LFM2's 64 over 8 of 32), 1.13 (Nemotron-H's 128 over 8 of 128),
    1.96 (GLM's 128 over 8 of 64, 1,536 wide). The three cells the rule
    takes leave 0.21, 0.36 and 0.53 empty by this count (their routers
    0.24, 0.62 and 0.54)."""
    return (experts_batched(M, k, E)
            and (1 - k / E) ** M >= SPARSE_EMPTY_SHARE
            and expert_step.whole_tiles(M, H, W, dtype))


# What the grouped form of a layer that holds a share of the experts
# compiles: row counts, as multiples of the expected number of (row, expert)
# pairs that land here, `M * k * held / E`, rounded up to whole tiles of
# DISPATCH_TILE rows; the whole `M * k` is always the last, so no pair is
# ever dropped. Every size is one more branch of a `switch` that Python
# traces on every start. On a v5e at the four cells' shapes (PERF.md section
# 5) a layer's forward and backward cost 4-9 ms + 0.2-0.4 ms a thousand
# rows, so a size twice too long costs a seventh to a quarter more and the
# steps need not be fine; at random weights a layer lands 0.45-1.9 times
# the expected count and one (Kimi's first expert layer) 2.2-2.6 times.
DISPATCH_MULTIPLES = (1.25, 2.0, 4.0)
DISPATCH_TILE = 128
# The grouped form's products take the experts' width in whole tiles of this
# many: XLA:TPU's `ragged_dot` over 3,072 rows in 8 groups, hidden 2,688,
# took 2.29 ms at a width of 1,856 (14.5 lane tiles), 2.07 at 1,920 (15) and
# 0.90 at 2,048; a layer's recomputed forward and backward 22.6-26.4 ms as
# published and 13.6-15.1 padded with zeros (a v5e, PERF.md section 7, "Left
# by PR 45"). Every other family's width is whole tiles already. One rule:
# the width is padded where the products are `ragged_dot` by the static
# shape (`experts_fused` of the ladder's first size), and not where they
# may be the kernels, which took 1,856 as published faster than 2,048
# padded (a layer's recomputed forward and backward 6.3 against 7.3 ms, and
# `ragged_dot`'s 12.6; PERF.md section 5).
RAGGED_TILE = 256


# The tiles of the grouped products' kernels (`grouped_product`,
# `grouped_tiles`). On a v5e at the six cells' learner shapes, bf16 (PERF.md
# section 5 has the sweep): 256 rows a tile beat 128 by 1-3 % and 512 by
# 4-30 % (a group that starts inside a tile costs the tile twice); the
# contraction whole, so that a group's matrix is fetched once a column tile
# and not once a tile of rows; and the widest column tile up to 1,024 that
# divides the width beat 512 by 3-14 % (1,792 = 2 x 896, 1,536 = 2 x 768,
# 2,560 = 4 x 640) and a tile that leaves the last one part-filled.
GROUPED_ROWS = (256, 128)
GROUPED_COLUMNS = 1024
# Bytes of VMEM a kernel's tiles may take, each operand's tile twice (the
# pipeline's two buffers) and the float32 sums: the largest measured to
# compile under the chip's 16 MiB (128 x 2,688 x 1,024).
GROUPED_VMEM = 13.5 * 2 ** 20


def _columns_tile(n: int) -> int:
    """Columns in a tile over a width `n`: the widest whole number of lane
    tiles up to `GROUPED_COLUMNS` that divides it, of at least three; else
    `GROUPED_COLUMNS`, the last tile part-filled (nemotron_h's 1,856)."""
    whole = [t for t in range(GROUPED_COLUMNS, 383, -128) if n % t == 0]
    return whole[0] if whole else GROUPED_COLUMNS


def _product_tiles(R: int, K: int, N: int):
    """`gmm`'s tiles for [R, K] against [K, N] a group: the contraction
    whole, the columns' tile, and the most rows that divide `R` and fit."""
    tn = _columns_tile(N)
    for tm in GROUPED_ROWS:
        if R % tm == 0 and (4 * (tm * K + K * tn) + 8 * tm * tn
                            <= GROUPED_VMEM):
            return tm, K, tn
    return None


def grouped_tiles(R: int, K: int, N: int, dtype=jnp.bfloat16):
    """The tiles `(rows, contraction, columns)` of the three kernels of one
    grouped product of `R` sorted rows `K` wide against `[E, K, N]`
    (`grouped_product`): the product's own, its pullback to the rows (`N`
    contracted, `K` columns) and its pullback to the matrices (the rows
    contracted, a `[K, N]` tile a group); or None where the product stays
    XLA's `jax.lax.ragged_dot`. A function of the static shape alone:
    operands of two bytes, whole tiles of rows, and widths of at least 512
    in whole half lane tiles, which is what the kernels were measured at
    (no shape of the six cells lost to `ragged_dot`, so the rule leaves
    none of them out). The narrowest experts so far, qwen3_next's 2,048 x
    512 and 512 x 2,048 at 6,400 gathered rows in 32 groups, take (256,
    2048, 512), (256, 512, 1024), (256, 1024, 512) by the same rule: one
    layer's recomputed forward and backward through `dropless_experts` at
    the cell's minibatch 11.73 ms against `ragged_dot`'s 12.67; rows of
    128 11.53, columns up to 512 11.95 (256 rows) and 11.78 (128): within
    2 % of the rule's, which stays one rule (my chip run, PR 52)."""
    if jnp.dtype(dtype).itemsize != 2 or any(
            d < 512 or d % 64 for d in (K, N)):
        return None
    forward, to_rows = _product_tiles(R, K, N), _product_tiles(R, N, K)
    if forward is None or to_rows is None:
        return None
    rows = next(tm for tm in GROUPED_ROWS if R % tm == 0)
    return forward, to_rows, (rows, _columns_tile(K), _columns_tile(N))


def experts_fused(R: int, H: int, W: int, dtype=jnp.bfloat16) -> bool:
    """Whether the grouped form's products over `R` gathered rows, hidden
    `H` and the experts' width `W`, can be the library's grouped-matmul
    kernel: a function of the static shape alone (beside `causal_fused`,
    `grouped_fused`, `decode_fused`): the up product's shape has tiles,
    and with it the down product's, whose kernels are the up product's
    pullbacks' with the widths' places traded."""
    return grouped_tiles(R, H, W, dtype) is not None


def _megablox():
    """The library's grouped matrix products for a TPU (Pallas), `gmm` and
    `tgmm`. (The package's own name `gmm` is its `custom_vjp` over the two,
    which takes one tiling for all three products, whose contracted and
    column widths trade places.)"""
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _gmm(lhs, rhs, group_sizes, tiles, transpose_rhs=False, interpret=False):
    """Row r of group e of lhs [R, K] times rhs[e] [K, N] (`transpose_rhs`:
    [N, K]), tile by tile over the tiles that hold rows of a group: [R, N]
    in lhs' dtype, float32 sums; rows in no group are not written."""
    return _megablox().gmm(lhs, rhs, group_sizes, lhs.dtype, tiles,
                           transpose_rhs=transpose_rhs, interpret=interpret)


def _tgmm(lhs, rhs, group_sizes, tiles, interpret=False):
    """lhs [R, K]^T rhs [R, N] over each group's rows: [E, K, N] in lhs'
    dtype, float32 sums, zeros for a group without rows; rows in no group
    are masked out of both operands."""
    # The library takes lhs as [K, R] and views it back: no copy is made.
    return _megablox().tgmm(lhs.swapaxes(0, 1), rhs, group_sizes, lhs.dtype,
                            tiles, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_product(tiles, rows, w, group_sizes):
    return jax.lax.platform_dependent(
        rows, w, group_sizes, default=jax.lax.ragged_dot,
        tpu=lambda rows, w, sizes: _gmm(rows, w, sizes, tiles[0]))


def _fused_product_pullback(tiles, kept, cotangent):
    def kernels(rows, w, sizes, cotangent):
        return (_gmm(cotangent, w, sizes, tiles[1], transpose_rhs=True),
                _tgmm(rows, cotangent, sizes, tiles[2]))

    def plain(rows, w, sizes, cotangent):
        return jax.vjp(lambda rows, w: jax.lax.ragged_dot(rows, w, sizes),
                       rows, w)[1](cotangent)
    return (*jax.lax.platform_dependent(
        *kept, cotangent, tpu=kernels, default=plain), None)


_fused_product.defvjp(
    lambda tiles, *operands: (_fused_product(tiles, *operands), operands),
    _fused_product_pullback)


def grouped_product(rows, w, group_sizes):
    """Row r of rows [R, K], sorted by group, times the matrix of its group
    in w [E, K, N]; `group_sizes` [E] int32, the rows of each group in
    turn: [R, N] in the operands' dtype, accumulated in float32. Rows past
    the last group are in no product: their place in the result, and in
    the gradient handed back for `rows`, holds whatever the memory held on
    a TPU, in both forms (`dropless_experts`' `landed` is what keeps that
    out); they add nothing to the matrices' gradient, and a group without
    rows gets zeros.

    Two forms of that one product. XLA's `jax.lax.ragged_dot` and its
    transposes; and, where the static shape has tiles (`grouped_tiles`)
    AND the program is lowered for a TPU (`jax.lax.platform_dependent`),
    the library's Pallas grouped-matmul kernels (megablox): `gmm` for the
    product and, against the transposed matrices, for the rows' gradient,
    `tgmm` for the matrices' gradient, each with tiles of its own, over
    the tiles of rows that hold a row of some group and no others."""
    tiles = grouped_tiles(*rows.shape, w.shape[2], rows.dtype)
    if tiles is None:
        return jax.lax.ragged_dot(rows, w, group_sizes)
    return _fused_product(tiles, rows, w, group_sizes)


def dispatch_rows(M: int, k: int, held: int, E: int) -> tuple:
    """The sorted row counts R that the grouped form of `M` rows, each
    routed to `k` of `E` experts of which `held` are here, is compiled
    for, ascending: a function of the static shape alone. A pass takes the
    first that holds the pairs that landed (`dispatch_index`); the last is
    `M * k`, and the only one where every expert is here or the shape
    takes the batched form, which gathers no rows."""
    pairs = M * k
    if held == E or experts_batched(M, k, E):
        return (pairs,)
    expected = pairs * held / E
    sizes = {-(-int(m * expected) // DISPATCH_TILE) * DISPATCH_TILE
             for m in DISPATCH_MULTIPLES}
    return tuple(sorted({R for R in sizes if R < pairs} | {pairs}))


def dispatch_index(count, sizes):
    """Which of `sizes` (`dispatch_rows`) a layer on which `count` pairs
    landed takes: the first that holds them."""
    return sum((count > R).astype(jnp.int32) for R in sizes[:-1])


def dropless_experts(n, top_p, top_i, w_gate, w_up, w_down, first=0,
                     num_experts=None, act=jax.nn.silu, rollout=False):
    """sum_e p_e W_down,e (act(W_gate,e n) * W_up,e n) for rows n [M, H]
    routed to `top_i` [M, k] of `num_experts` with weights `top_p`, over
    the experts held here: `first` .. `first + E - 1`, whose weights
    [E, H, W] / [E, W, H] are given in n's dtype (all of them where
    `num_experts` is not given). What an absent expert would add is left
    out. Without a gate matrix (`w_gate` None) an expert is W_down,e
    act(W_up,e n): two products where the gated one has three, in both
    forms and in the pullbacks. Returns ([M, H], rows a held group [E], the
    sorted rows that were gathered).

    Three forms of that sum, the first two chosen by `experts_batched(M, k,
    num_experts)`; all take operands in n's dtype, accumulate in float32,
    weight in float32 and compute every chosen held expert of every row.

    Grouped: the M*k (row, expert) pairs sorted by expert, those of absent
    experts last and in no group; the first R sorted pairs' rows gathered,
    three products over the E groups (at the ladder's first size
    `grouped_product`: the library's grouped-matmul kernels in a program
    lowered for a TPU at shapes that have tiles; `jax.lax.ragged_dot` at
    the other sizes and everywhere else), and each output row weighted
    and scatter-added to its row of the float32 sum. Where every expert
    is here R is M*k. A layer that holds a share gathers, multiplies and
    adds the pairs that landed on it: R is the first of `dispatch_rows`'
    static sizes that holds the landed count (a `switch` around
    everything after the sort); the last size is M*k, so no pair is
    dropped, whatever the router does. The rows between the landed count
    and R are in no group; they are made 0 on the way into the products
    and on the way out, in both passes (`landed`), and add nothing. Where
    the products are `ragged_dot` by the static shape the experts' width
    is padded with zeros to whole tiles of `RAGGED_TILE` (no width but
    nemotron_h's 1,856 needs it): ahead of the `switch` where no size
    takes the kernels, inside a later size's branch where the first
    does.

    Batched: c[m, e] = p[m, j] where top_i[m, j] == first + e, else 0;
    a[e, m] = silu(n W_gate,e) * (n W_up,e) for all M rows and every held
    expert; out[m] = sum_e c[m, e] a[e, m] W_down,e, the weight applied to
    a and e folded into the contraction: one product of [M, E*W] against
    W_down as [E*W, H]. The same sum: an expert a row did not choose has
    weight exactly 0. It does E/k times the matrix work and reads each
    expert's weights once, where they lie.

    Chosen (`rollout`: the caller is a rollout's step, which nothing
    differentiates; `experts_sparse` of the static shape, and a program
    lowered for a TPU; the batched
    form everywhere else, the learner's bootstrap step included): the
    batched form's c and its sum, over the held experts with a row alone;
    the matrices of an expert no row chose, whose products the batched form
    multiplies by 0, are not read (`expert_step.chosen_kernel`)."""
    M, k = top_i.shape
    E = w_up.shape[0]
    share = num_experts is not None and (first, E) != (0, num_experts)
    local = top_i - first if share else top_i
    with jax.named_scope("policy/dispatch"):
        if share:
            here = (local >= 0) & (local < E)
            local = jnp.where(here, local, E)  # past every group
        # An index past the last group is dropped by the scatter.
        group_sizes = jnp.zeros(E, jnp.int32).at[local.reshape(-1)].add(1)
    def batched(n, c, group_sizes, w_gate, w_up, w_down):
        with jax.named_scope("policy/experts_batched"):
            if w_gate is not None:
                gate = jnp.einsum("mh,ehw->emw", n, w_gate)
            up = jnp.einsum("mh,ehw->emw", n, w_up)
            weight = c.T[:, :, None]
            hidden = act(up) if w_gate is None else act(gate) * up
            a = (weight * hidden).astype(n.dtype)
            return jnp.einsum("emw,ewh->mh", a, w_down,
                              preferred_element_type=jnp.float32)

    def chosen(*operands):
        with jax.named_scope("policy/experts_chosen"):
            return expert_step.chosen_kernel(*operands, act)
    if experts_batched(M, k, num_experts or E):
        with jax.named_scope("policy/dispatch"):
            c = jnp.sum(jnp.where(local[:, :, None] == jnp.arange(E),
                                  top_p[:, :, None], 0.0), axis=1)
        operands = (n, c, group_sizes, w_gate, w_up, w_down)
        if rollout and experts_sparse(
                M, k, num_experts or E, *w_up.shape[1:], n.dtype):
            mixed = jax.lax.platform_dependent(
                *operands, tpu=chosen, default=batched)
        else:
            mixed = batched(*operands)
        return mixed.astype(n.dtype), group_sizes, jnp.int32(M * k)

    sizes = dispatch_rows(M, k, E, num_experts or E)
    # The kernels serve the size that the expected load takes, the ladder's
    # first: every kernel a size compiles is traced anew at every start
    # (~0.2 s each, eight a size; PERF.md section 6), for branches that a
    # cell takes in none to a quarter of its layer-updates.
    fused = experts_fused(sizes[0], *w_up.shape[1:], n.dtype)
    pad = -w_up.shape[-1] % RAGGED_TILE

    def padded(*weights):
        """Zero columns of W_up (and W_gate) and zero rows of W_down: act(0)
        is 0 for every activation here, and the sum is the same sum."""
        if not pad:
            return weights
        return tuple(jnp.pad(w, ((0, 0), (0, 0), (0, pad)))
                     for w in weights[:-1]) + (
            jnp.pad(weights[-1], ((0, 0), (0, pad), (0, 0))),)

    def grouped(R, n, top_p, *operands):
        """The sum over the first R sorted pairs, [M, H] float32;
        `operands`: the experts' matrices (`weights`), then `whole`."""
        weights, (order, count, group_sizes) = operands[:-3], operands[-3:]
        if fused and R == sizes[0]:
            product = grouped_product
        else:
            product = jax.lax.ragged_dot
            if fused:
                weights = padded(*weights)
        *gate, w_up, w_down = weights
        def landed(x):
            """Sorted rows [R, ..] with those past the last group made 0,
            and their cotangents with them. A `ragged_dot` computes no row
            that is in no group, and neither do its transposes: XLA:CPU
            leaves 0 there, XLA:TPU whatever the memory held, so what the
            backward pass handed back for the absent pairs was not 0 until
            it was made so (a v5e, the cell's minibatch: gradients 35 times
            the reference's norm and unrelated to them; PERF.md section 6).
            A select, so that nothing that memory held can reach a
            product."""
            if not share:
                return x
            return jnp.where((jnp.arange(R) < count)[:, None], x, 0)

        with jax.named_scope("policy/dispatch"):
            pairs = order[:R]
            source = pairs // k
            rows = landed(n[source])
        with jax.named_scope("policy/experts"):
            if gate:
                gate = landed(product(rows, gate[0], group_sizes))
                up = landed(product(rows, w_up, group_sizes))
                hidden = act(gate) * up
            else:
                hidden = act(landed(product(rows, w_up, group_sizes)))
            out = product(landed(hidden), w_down, group_sizes)
        with jax.named_scope("policy/dispatch"):
            weighted = landed(out).astype(jnp.float32) \
                * top_p.reshape(-1)[pairs][:, None]
            return jnp.zeros((M, n.shape[1]), jnp.float32).at[source].add(
                weighted)

    with jax.named_scope("policy/dispatch"):
        order = jnp.argsort(local.reshape(-1), stable=True)
        count = jnp.sum(group_sizes)
    weights = (w_up, w_down) if w_gate is None else (w_gate, w_up, w_down)
    if not fused:
        weights = padded(*weights)
    floats, whole = (n, top_p) + weights, (order, count, group_sizes)
    if len(sizes) == 1:
        return (grouped(M * k, *floats, *whole).astype(n.dtype),
                group_sizes, jnp.int32(M * k))

    # The size is a branch of a `switch`, and the backward pass another
    # `switch` over the sizes' pullbacks, each its forward again and then
    # its backward, from the operands. Left to differentiate the `switch`,
    # JAX hands the backward pass the union of the branches' residuals,
    # those of the branches not taken filled with zeros: as many bytes as
    # the whole size makes, whichever ran (a v5e, the four cells' shapes:
    # 2.2-4.4 ms a layer and update, of 4.3-13.9); and with each branch
    # under `jax.checkpoint` a third conditional that copies the operands
    # (the three weight tensors among them) to its outputs, to be the
    # residuals. The branches hand back the float32 sum, so that the
    # cotangent comes in as it would without them.
    @jax.custom_vjp
    def taken(index, *operands):
        return jax.lax.switch(
            index, [functools.partial(grouped, R) for R in sizes], *operands)

    def pullback(R):
        def pull(cotangent, *operands):
            floats, whole = operands[:-3], operands[-3:]
            return jax.vjp(lambda *floats: grouped(R, *floats, *whole),
                           *floats)[1](cotangent)
        return pull

    def backward(operands, cotangent):
        index, *operands = operands
        # Behind a barrier, or XLA moves the weight gradients' conversion
        # to float32 into the branches, and every expert layer's are held
        # at twice their bytes until the optimizer reads them.
        return (None, *jax.lax.optimization_barrier(jax.lax.switch(
            index, [pullback(R) for R in sizes], cotangent, *operands)),
            *(None for _ in whole))

    taken.defvjp(lambda *operands: (taken(*operands), operands), backward)
    index = dispatch_index(count, sizes)
    return (taken(index, *floats, *whole).astype(n.dtype), group_sizes,
            jnp.asarray(sizes, jnp.int32)[index])


# The router's selection bias at initialisation: the published model's is
# what its balancing left there. Small beside the scores' spread (sigmoid
# of a unit normal: ~0.2), large enough that choosing by score + bias and
# weighing by score differ.
ROUTER_BIAS_SCALE = 0.02


def _decay_inits() -> dict:
    """A KDA layer's decay at initialisation, the family's Mamba-style
    draw: A = exp(a_log) uniform in [1, 16] a head, and dt_bias the inverse
    softplus of a step drawn log-uniform in [0.001, 0.1] a channel, so
    that g = -A softplus(. + dt_bias) starts between -0.001 and -1.6."""
    def a_log(key, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))

    def dt_bias(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(
            key, shape, dtype, jnp.log(0.001), jnp.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    return {"a_log": a_log, "dt_bias": dt_bias}


def _gdn_a_log(key, shape, dtype=jnp.float32):
    """A Gated DeltaNet layer's decay at initialisation, the family's draw:
    A = exp(a_log) uniform in (0, 16] a value head (never 0: its log is
    taken), beside a `dt_bias` of 1."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


def _conv_bias_init(key, shape, dtype=jnp.float32):
    """A depthwise convolution's bias at initialisation: uniform within
    taps^-1/2 of 0 at the family's four taps (the source library's draw
    for a convolution of that fan-in)."""
    return jax.random.uniform(key, shape, dtype, -0.5, 0.5)


class DecoderLayerParams(nn.Module):
    """One layer's parameters, by the names the equations use: `shapes` is
    ((name, kind, shape), ...), kind one of ones / dense / experts (a
    leading axis of experts) / taps (a depthwise filter [channels, taps],
    a channel's fan-in its taps) / bias (a constant of the model, small
    and seeded, in the "constants" collection: no gradient, no optimizer
    state) / a_log, dt_bias (a KDA or Mamba-2 layer's decay:
    `_decay_inits`) / gdn_a_log (a Gated DeltaNet layer's: `_gdn_a_log`) /
    conv_bias (`_conv_bias_init`) / centred (a zero-centred norm's weight:
    the parameter w is 0 at initialisation and the tensor handed out is 1 +
    w, which every `rms_norm` then multiplies by as it does a plain
    one)."""

    shapes: tuple

    def setup(self):
        inits = {"ones": nn.initializers.ones,
                 "dense": nn.initializers.lecun_normal(),
                 "experts": nn.initializers.lecun_normal(batch_axis=(0,)),
                 "taps": nn.initializers.lecun_normal(in_axis=1, out_axis=0),
                 "conv_bias": _conv_bias_init, "gdn_a_log": _gdn_a_log,
                 **_decay_inits()}
        tensors = {}
        for name, kind, shape in self.shapes:
            if kind == "centred":
                tensors[name] = 1.0 + self.param(
                    name, nn.initializers.zeros, shape)
            elif kind == "bias":
                tensors[name] = self.variable(
                    "constants", name, lambda s=shape: ROUTER_BIAS_SCALE
                    * jax.random.normal(self.make_rng("params"), s)).value
            else:
                tensors[name] = self.param(name, inits[kind], shape)
        self.tensors = tensors

    def __call__(self) -> dict:
        return self.tensors


class TokenDecoder(nn.Module):
    """A decoder as a token policy (see the module docstring). The
    defaults are OLMoE's parts."""

    num_outputs: int
    vocab_size: int = 50304
    hidden_size: int = 2048
    num_heads: int = 16
    num_layers: int = 16
    # Attention: a head's own keys and values, or latent where
    # `kv_lora_rank`. A head's own: `num_kv_heads` of them (0: as many as
    # query heads) of `head_dim` (0: hidden_size // num_heads), QK-norm (of
    # the projection, or of a head) or none; a kind a layer, by the
    # layer's entry in two layouts (the leading `num_layers` entries of a
    # longer layout are read; an empty one: every layer full and
    # rotary): attending within `sliding_window`
    # positions or to the whole episode, RoPE or no positions at all.
    num_kv_heads: int = 0
    head_dim: int = 0
    # True: over the whole projection; "head": over each head, one weight
    # [head_dim]; False: none.
    qk_norm: Any = True
    # The share of a head's leading values that RoPE rotates (the angles'
    # frequencies over that many, not over the head); the rest pass as they
    # are.
    partial_rotary_factor: float = 1.0
    # Where the attention layers differ in their geometry (laguna), a
    # layer's own, read as the two layouts below are: its query heads
    # (`heads_layout`; empty: `num_heads` in every layer) and its rotation
    # (`rotations`, an entry (theta, the share of a head rotated, scaling:
    # `rope_frequencies`'s, () for none); empty: `rope_theta` over
    # `partial_rotary_factor`, unscaled, in every layer).
    heads_layout: tuple = ()
    rotations: tuple = ()
    # True: W_q yields, a head, its query and as many values again whose
    # sigmoid multiplies the head's output ahead of W_o. "head": ONE gate a
    # head, sigmoid(n . W_g[:, h]), W_g [hidden, heads] beside W_q.
    attention_gate: Any = False
    # Every norm of the hidden vector and of a head's queries and keys is
    # x / rms(x) * (1 + w), w 0 at initialisation (an operator's own output
    # norm keeps a plain weight).
    zero_centred_norms: bool = False
    sliding_window: int = 0
    window_layout: tuple = ()
    rope_layout: tuple = ()
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # The operator a layer, where not every layer is an attention: each of
    # the leading `num_layers` entries one of `LAYER_TYPES`; "conv" is the
    # gated short convolution of `conv_taps` taps.
    layer_types: tuple = ()
    conv_taps: int = 3
    # "kda", Kimi Delta Attention: `kda_heads` heads (0: `num_heads`) whose
    # keys and values are both `kda_head_dim` wide (which is also the rank
    # of the decay's and the output gate's projections), short convolutions
    # of `kda_taps` taps, the learner's scan in chunks of `kda_chunk`.
    kda_heads: int = 0
    kda_head_dim: int = 128
    kda_taps: int = 4
    kda_chunk: int = 64
    # "mamba2", the state-space layer: `ssm_heads` heads of `ssm_head_dim`
    # channels, B and C of `ssm_state` values shared by `ssm_groups` groups
    # of heads, one short convolution of `ssm_taps` taps over x, B and C
    # together, the learner's scan in chunks of `ssm_chunk`.
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    ssm_taps: int = 4
    ssm_chunk: int = 128
    # "gdn", Gated DeltaNet: the delta rule under ONE decay a value head,
    # `gdn_key_heads` query/key heads of `gdn_key_dim`, each serving
    # `gdn_value_heads // gdn_key_heads` consecutive value heads of
    # `gdn_value_dim`, one short convolution of `gdn_taps` taps over q, k
    # and v together, the learner's scan in chunks of `gdn_chunk`.
    gdn_key_heads: int = 16
    gdn_value_heads: int = 32
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_taps: int = 4
    gdn_chunk: int = 64
    # A layer is its operator OR its feed-forward ("experts" in
    # `layer_types`), x + f(RMSNorm(x)) with one f, not one after the other.
    one_function_layers: bool = False
    # Feed-forward: `dense_layers` leading dense layers, then experts.
    dense_layers: int = 0
    dense_width: int = 0
    num_experts: int = 64  # the router's outputs
    experts_per_token: int = 8
    expert_width: int = 1024
    experts_held: int = 0  # 0: all of them
    first_expert_held: int = 0
    shared_experts: int = 0
    shared_width: int = 0  # 0: `shared_experts` x `expert_width`
    # The shared expert's output times sigmoid(n . w), w [hidden].
    shared_expert_gate: bool = False
    hidden_act: str = "silu"  # the gate's, in every gated feed-forward
    # False: no gate matrix, W_down act(W_up n), experts and shared alike.
    gated_feed_forward: bool = True
    # Router: softmax, or sigmoid scores (`sigmoid_router`; with a
    # selection bias they always are); on the block's post-attention norm,
    # or on the attention's own normalised input.
    selection_bias: bool = False
    sigmoid_router: bool = False
    router_before_attention: bool = False
    norm_topk_prob: bool = False
    topk_eps: float = 0.0  # beside the chosen weights' sum, where divided
    routed_scaling_factor: float = 1.0
    # The next-next-token module, its loss's weight in the objective.
    nextn_layers: int = 0
    nextn_loss_weight: float = 0.1
    tie_embeddings: bool = False  # the head is the embedding, transposed
    # Generation by diffusion over blocks: a step yields `block_len`
    # positions a row, unmasked over `denoise_steps` passes (0: one token a
    # step, autoregressive); the MASK id is the vocabulary's last.
    block_len: int = 0
    denoise_steps: int = 1
    context_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    compute_dtype: Dtype = jnp.bfloat16

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def mask_id(self) -> int:
        return self.vocab_size - 1

    @property
    def latent_width(self) -> int:
        """Values a position a layer of the latent cache (0: full heads)."""
        return self.kv_lora_rank and self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_width(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def decode_sparse(self, rows: int) -> bool:
        """Whether a rollout's step of `rows` rows reads the chosen held
        experts' matrices alone, in a program lowered for a TPU
        (`experts_sparse` of this model's shapes)."""
        return experts_sparse(
            rows, self.experts_per_token, self.num_experts,
            self.hidden_size, self.expert_width, self.compute_dtype)

    def layer_kind(self, i: int):
        """"conv" where layer `i`'s operator is the short convolution,
        "kda" where it is Kimi Delta Attention, "gdn" where it is Gated
        DeltaNet, "mamba2" where it is the state-space layer, "experts"
        where the layer is its feed-forward alone; of an attention layer
        its geometry, an `AttentionKind`: the window it attends within (0
        for the whole episode), whether its queries and keys are rotated,
        its query heads and its rotation."""
        if self.layer_types and self.layer_types[i] != "full_attention":
            if self.layer_types[i] not in LAYER_TYPES:
                raise ValueError(f"layer type {self.layer_types[i]!r}: "
                                 f"TokenDecoder has {LAYER_TYPES}")
            return self.layer_types[i]
        window = bool(self.window_layout) and bool(self.window_layout[i])
        return AttentionKind(
            self.sliding_window if window else 0,
            not self.rope_layout or bool(self.rope_layout[i]),
            self.heads_layout[i] if self.heads_layout else self.num_heads,
            Rotation(*self.rotations[i]) if self.rotations
            else self._rotation)

    @property
    def _rotation(self):
        """The model's one rotation, where the layers do not differ."""
        return Rotation(self.rope_theta, self.partial_rotary_factor)

    @property
    def _plain_kind(self):
        """The kind of a full, rotated layer of `num_heads`."""
        return AttentionKind(0, True, self.num_heads, self._rotation)

    @property
    def attention_layers(self) -> tuple:
        return tuple(i for i in range(self.num_layers)
                     if not isinstance(self.layer_kind(i), str))

    @property
    def kda_layers(self) -> tuple:
        return tuple(i for i in range(self.num_layers)
                     if self.layer_kind(i) == "kda")

    @property
    def kda_width(self) -> int:
        """A KDA layer's projections' width: heads x head_dim."""
        return (self.kda_heads or self.num_heads) * self.kda_head_dim

    @property
    def gdn_conv_width(self) -> int:
        """The channels a Gated DeltaNet layer's one convolution runs over:
        q, k (key heads x key dim each) and v (value heads x value dim)."""
        return (2 * self.gdn_key_heads * self.gdn_key_dim
                + self.gdn_value_heads * self.gdn_value_dim)

    @property
    def ssm_width(self) -> int:
        """A Mamba-2 layer's inner channels: heads x head_dim."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """The channels its one convolution runs over: x, B and C."""
        return self.ssm_width + 2 * self.ssm_groups * self.ssm_state

    def cache_len(self, i: int) -> int:
        """Positions layer `i`'s cache holds: the context, or a window
        layer's ring of its window (an attention layer's)."""
        window = self.layer_kind(i)[0]
        return min(window or self.context_len, self.context_len)

    def _layer_shapes(self, dense: bool, kind=None) -> tuple:
        """`attn_norm` is the norm ahead of the layer's operator, whichever
        that is (`kind`: `layer_kind`'s; a full layer of `num_heads` where
        not given), `mlp_norm` the one ahead of its feed-forward; a layer
        of `one_function_layers` has one of the two."""
        kind = kind or self._plain_kind
        H = self.hidden_size
        heads = self.num_heads if isinstance(kind, str) else kind.heads
        feed_forward = not self.one_function_layers or kind == "experts"
        norm = "centred" if self.zero_centred_norms else "ones"
        shapes = [("attn_norm", norm, (H,))] * (kind != "experts") + [
            ("mlp_norm", norm, (H,))] * feed_forward
        if kind == "experts":
            pass
        elif kind == "gdn":
            V = self.gdn_value_heads
            P = V * self.gdn_value_dim
            shapes += [
                # [q | k | v | z], [b | a], and the one convolution's taps
                # over q, k and v
                ("gdn_qkvz", "dense", (H, self.gdn_conv_width + P)),
                ("gdn_ba", "dense", (H, 2 * V)),
                ("gdn_conv", "taps", (self.gdn_conv_width, self.gdn_taps)),
                ("gdn_a_log", "gdn_a_log", (V,)),
                ("gdn_dt_bias", "ones", (V,)),
                ("gdn_o_norm", "ones", (self.gdn_value_dim,)),
                ("gdn_out", "dense", (P, H))]
        elif kind == "mamba2":
            I, C = self.ssm_width, self.ssm_conv_width
            shapes += [
                # [z | x B C | dt], and the one convolution's taps and bias
                ("ssm_in", "dense", (H, I + C + self.ssm_heads)),
                ("ssm_conv", "taps", (C, self.ssm_taps)),
                ("ssm_conv_bias", "conv_bias", (C,)),
                ("ssm_a_log", "a_log", (self.ssm_heads,)),
                ("ssm_dt_bias", "dt_bias", (self.ssm_heads,)),
                ("ssm_d", "ones", (self.ssm_heads,)),
                ("ssm_norm", "ones", (I,)),
                ("ssm_out", "dense", (I, H))]
        elif kind == "conv":
            shapes += [("conv_in", "dense", (H, 3 * H)),
                       ("conv_w", "taps", (H, self.conv_taps)),
                       ("conv_out", "dense", (H, H))]
        elif kind == "kda":
            P, d = self.kda_width, self.kda_head_dim
            shapes += [
                # [W_q | W_k | W_v], and their convolutions' taps
                ("kda_qkv", "dense", (H, 3 * P)),
                ("kda_conv", "taps", (3 * P, self.kda_taps)),
                ("kda_fa", "dense", (H, d)), ("kda_fb", "dense", (d, P)),
                ("kda_a_log", "a_log", (P // d,)),
                ("kda_dt_bias", "dt_bias", (P,)),
                ("kda_b", "dense", (H, P // d)),
                ("kda_ga", "dense", (H, d)), ("kda_gb", "dense", (d, P)),
                ("kda_o_norm", "ones", (d,)),
                ("kda_out", "dense", (P, H))]
        elif self.kv_lora_rank:
            rq, rkv = self.q_lora_rank, self.kv_lora_rank
            nope, rot, vd = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                             self.v_head_dim)
            # The queries through a latent of their own, or straight.
            shapes += [
                ("wq_a", "dense", (H, rq)), ("q_a_norm", "ones", (rq,)),
                ("wq_b", "dense", (rq, heads * (nope + rot)))] if rq else [
                ("wq", "dense", (H, heads * (nope + rot)))]
            shapes += [
                ("wkv_a", "dense", (H, rkv + rot)),
                ("kv_a_norm", "ones", (rkv,)),
                ("wkv_b", "dense", (rkv, heads * (nope + vd))),
                ("wo", "dense", (heads * vd, H))]
        else:
            q, kv = heads * self.head_width, self.kv_heads * self.head_width
            if self.qk_norm == "head":
                shapes += [("q_norm", norm, (self.head_width,)),
                           ("k_norm", norm, (self.head_width,))]
            elif self.qk_norm:
                shapes += [("q_norm", norm, (q,)), ("k_norm", norm, (kv,))]
            # A head's query, and with a gate as many values again, or a
            # gate a head beside it.
            shapes += [("wq", "dense",
                        (H, q * (1 + (self.attention_gate is True)))),
                       ("wk", "dense", (H, kv)),
                       ("wv", "dense", (H, kv)), ("wo", "dense", (q, H))]
            if self.attention_gate == "head":
                shapes.append(("wg", "dense", (H, heads)))
        if not feed_forward:
            return tuple(shapes)
        if dense:
            D = self.dense_width
            return tuple(shapes + [
                ("dense_gate", "dense", (H, D)), ("dense_up", "dense", (H, D)),
                ("dense_down", "dense", (D, H))])
        E, W = self.held, self.expert_width
        gate = self.gated_feed_forward
        shapes += [("router", "dense", (H, self.num_experts))] + [
            ("w_gate", "experts", (E, H, W))] * gate + [
            ("w_up", "experts", (E, H, W)),
            ("w_down", "experts", (E, W, H))]
        if self.selection_bias:
            shapes.append(("router_bias", "bias", (self.num_experts,)))
        if self.shared_experts:
            SW = self.shared_width or self.shared_experts * W
            shapes += [("shared_gate", "dense", (H, SW))] * gate + [
                ("shared_up", "dense", (H, SW)),
                ("shared_down", "dense", (SW, H))]
            if self.shared_expert_gate:
                shapes.append(("shared_scale", "dense", (H, 1)))
        return tuple(shapes)

    def setup(self):
        H = self.hidden_size
        if self.first_expert_held + self.held > self.num_experts:
            raise ValueError(
                f"experts {self.first_expert_held} .. "
                f"{self.first_expert_held + self.held - 1} are not among "
                f"the router's {self.num_experts}")
        for heads in {self.num_heads, *self.heads_layout[:self.num_layers]}:
            if heads % self.kv_heads:
                raise ValueError(
                    f"{heads} query heads do not fall into "
                    f"{self.kv_heads} key/value heads' groups")
        if (self.heads_layout or self.rotations) and (
                self.kv_lora_rank or self.block_len or self.nextn_layers):
            raise ValueError(
                "heads and rotations by layer are those of layers of a "
                "head's own keys and values, one token a step, without a "
                "next-next-token module")
        kinds = self.layer_types[:self.num_layers]
        if "experts" in kinds and not self.one_function_layers:
            raise ValueError(
                "a layer that is its feed-forward alone (\"experts\") "
                "belongs to a model of one_function_layers")
        if (self.one_function_layers or not self.gated_feed_forward) and (
                self.dense_layers or self.nextn_layers
                or self.router_before_attention):
            raise ValueError(
                "TokenDecoder has no dense layer, next-next-token module "
                "or router ahead of the attention in a model whose layers "
                "are one function each or whose feed-forward has no gate")
        if "gdn" in kinds and self.gdn_value_heads % self.gdn_key_heads:
            raise ValueError(
                f"{self.gdn_value_heads} value heads do not fall to "
                f"{self.gdn_key_heads} key heads in whole groups")
        if "mamba2" in kinds and self.ssm_heads % self.ssm_groups:
            raise ValueError(
                f"{self.ssm_heads} state-space heads do not fall into "
                f"{self.ssm_groups} groups")
        if self.block_len and (
                self.layer_types or self.kv_lora_rank or self.window_layout
                or self.nextn_layers or self.router_before_attention
                or self.block_len % self.denoise_steps
                or self.context_len % self.block_len
                or self.num_outputs != self.vocab_size):
            raise ValueError(
                "a block of positions a step is generated by a decoder whose "
                "every layer is full attention over a head's own keys, in "
                "denoising passes that divide the block, with logits over "
                "the vocabulary whose last id is the MASK id, in a context "
                "of whole blocks")
        self.embed = self.param(
            "embed", nn.initializers.normal(0.02), (self.vocab_size, H))
        self.layers = [
            DecoderLayerParams(
                self._layer_shapes(i < self.dense_layers,
                                   self.layer_kind(i)),
                name=f"layer_{i}")
            for i in range(self.num_layers)]
        self.nextn = [
            DecoderLayerParams(self._layer_shapes(False) + (
                ("hnorm", "ones", (H,)), ("enorm", "ones", (H,)),
                ("eh_proj", "dense", (2 * H, H)),
                ("final_norm", "ones", (H,))), name=f"nextn_{i}")
            for i in range(self.nextn_layers)]
        if self.zero_centred_norms:
            self.final_norm = 1.0 + self.param(
                "final_norm", nn.initializers.zeros, (H,))
        else:
            self.final_norm = self.param(
                "final_norm", nn.initializers.ones, (H,))
        if not self.tie_embeddings:
            self.head = self.param(
                "head", nn.initializers.normal(0.01), (H, self.num_outputs))
        elif self.num_outputs != self.vocab_size or self.nextn_layers:
            raise ValueError(
                f"a head tied to the embedding gives {self.vocab_size} "
                f"logits, not {self.num_outputs}, and no next-next-token "
                "module reads it")
        self.value_w = self.param(
            "value_w", nn.initializers.normal(0.02), (H,))
        self.value_b = self.param("value_b", nn.initializers.zeros, ())

    # -- the protocol ---------------------------------------------------
    def initial_state(self, batch_size: int):
        """An empty window: a layer's caches (K and V of every key/value
        head, or the one latent), and each row's count of positions held.
        A layer's caches are as long as what it attends to: the context's
        positions, or a window layer's RING of its window, where position
        p lies in slot p mod the window. A convolution layer has no cache
        (its entry of "kv" is empty); its state, the last `conv_taps` - 1
        gated inputs of a row, [B, taps - 1, hidden], is its entry of
        "conv", a key a model without such layers does not have: every
        leaf of "kv" has a positions axis, no leaf of "conv" has. Grouped
        heads' caches are stored flat, [B, S, groups * d], a position's
        cached heads one row of contiguous lanes: what the decode's kernel
        reads as it lies (`cached_attention`; as [B, S, groups, d] XLA:TPU
        tiles the last two axes, pads heads of 64 to whole lane tiles and
        copies the cache to the kernel's view every step). A KDA layer
        has no cache either: its state is a matrix a head, [B, heads, d_k,
        d_v] in float32 whatever `compute_dtype` (it is summed into over
        thousands of steps), its entry of "kda", and the last `kda_taps` -
        1 inputs of its three convolutions, [B, taps - 1, 3 x heads x d]
        in `compute_dtype`, its entry of "conv". A Mamba-2 layer likewise:
        [B, heads, P, N] float32 under "ssm", its one convolution's last
        inputs [B, taps - 1, I + 2 G N] under "conv". A Gated DeltaNet
        layer likewise: [B, value heads, d_k, d_v] float32 under "gdn", its
        one convolution's last inputs [B, taps - 1, 2 K + V] under "conv".
        A layer that is its feed-forward alone keeps nothing."""
        B = batch_size
        kinds = [self.layer_kind(i) for i in range(self.num_layers)]

        def shapes(i):
            if isinstance(kinds[i], str):
                return ()
            S = self.cache_len(i)
            if self.kv_lora_rank:
                return ((B, S, self.latent_width),)
            if self.kv_heads != self.num_heads:
                return ((B, S, self.kv_heads * self.head_width),) * 2
            return ((B, S, self.kv_heads, self.head_width),) * 2
        tails = {"conv": (self.conv_taps - 1, self.hidden_size),
                 "kda": (self.kda_taps - 1, 3 * self.kda_width),
                 "mamba2": (self.ssm_taps - 1, self.ssm_conv_width),
                 "gdn": (self.gdn_taps - 1, self.gdn_conv_width)}
        d = self.kda_head_dim
        matrices = {
            "kda": (self.kda_width // d, d, d),
            "ssm": (self.ssm_heads, self.ssm_head_dim, self.ssm_state),
            "gdn": (self.gdn_value_heads, self.gdn_key_dim,
                    self.gdn_value_dim)}
        held = {
            "kv": [tuple(jnp.zeros(s, self.compute_dtype) for s in shapes(i))
                   for i in range(self.num_layers)],
            "conv": [jnp.zeros((B,) + tails[kind], self.compute_dtype)
                     if kind in tails else () for kind in kinds]}
        for key, of in MATRIX_STATES.items():
            held[key] = [jnp.zeros((B,) + matrices[key], jnp.float32)
                         if kind == of else () for kind in kinds]
        return self._policy_state(held, jnp.zeros(batch_size, jnp.int32))

    def _policy_state(self, held: dict, pos) -> dict:
        """The policy state of what each layer holds, by kind ({kind: an
        entry a layer, () where the layer has none} for every one of
        `STATE_KINDS`), and the rows' positions: a key a kind, and only the
        kinds the model has."""
        kinds = self.layer_types[:self.num_layers]
        has = {"kv": True, "conv": bool(self.layer_types),
               **{key: of in kinds for key, of in MATRIX_STATES.items()}}
        return {**{kind: tuple(held[kind]) for kind in STATE_KINDS
                   if has[kind]}, "pos": pos}

    def static_counters(self, batch_size: int, fragment_len: int,
                        platform: str, learner_rows: int = 0) -> dict:
        """What the program is, from its static shapes and the platform
        it is compiled for. A decode step of `batch_size` rows: the mean
        rows a held expert group holds, whether the experts multiply in
        the batched form (1.0) or another (0.0), whether a rollout's step
        reads the chosen held experts' matrices alone (1.0:
        `decode_sparse`, in a program for a TPU; the batched form is then
        the learner's bootstrap step's alone) and, where it does not, the
        share of them its products read, 1.0 (where it does the step counts
        it: `_count`), the positions in
        a block of the caches its attention reads, whether that attention
        is the kernel (1.0: over a latent cache, or over the grouped caches
        of every attention layer) or XLA's products (0.0), and with a
        latent cache its bytes a position. A causal pass over
        fragments of `fragment_len` tokens: whether its attention takes
        the fused form (1.0) or the plain one (0.0), and how many
        layers' passes rotate a head's rows in one pass over them
        (`rowwise.rotation`: the layers that rotate, where the fragment
        and a head are whole tiles, in a program for a TPU; a gated
        layer's gate follows it); and over a minibatch
        of `learner_rows` tokens (0: not said), whether the experts'
        grouped products are the grouped-matmul kernel (1.0: at the first
        of the sizes the dispatch compiles, the one the expected load
        takes) or XLA's `ragged_dot` (0.0, and where the minibatch takes
        the batched form). A model with caches of
        a head's own keys and values: the bytes of cache a position of the
        context that its attention layers hold together (a ring counts
        for its own length). A model with window layers: how many they
        are, the query heads a key/value head, and the share of the
        causal tiles that the fused form visits in a window layer. A
        model with convolution state (gated short convolutions, or KDA's
        three, or Mamba-2's one): the layers that have it, and the bytes of
        it a row, whatever the length, from the state's own leaves. A model
        with KDA layers, or with Mamba-2 layers: how many they are, the
        bytes of their matrix states a row, the positions in a chunk of
        the learner's scan, and whether a decode step passes over the
        states in place, by the kernel (1.0: the delta rule's, KDA's or
        Gated DeltaNet's, of whole tiles), or by XLA's fusions (0.0). A model whose heads are grouped: the query
        heads a key/value head. A model that generates a block of positions
        a step: the block, the denoising passes, the passes a generated
        token costs the rollout ((denoise_steps + 1) / block_len) and the
        rows a token costs a learner's layer in the mean (its streams,
        denoise_steps + 1, less the last layer's clean stream, which stops
        at its keys and values: - 1 / num_layers), whether its step's
        attention is the block entry's kernel
        (1.0: the block's own keys and values operands beside the caches,
        a cached head against its own lanes) or the plain form (0.0), and
        the writes a layer's caches take for one block (1: the commit
        pass's; a pass that wrote where it read would make it denoise_steps
        + 1); its step's rows are `batch_size` blocks."""
        k, E = self.experts_per_token, self.num_experts
        kernel = False
        attention = self.attention_layers
        step_rows = batch_size * (self.block_len or 1)
        # The learner's rows a position: a block model's streams.
        streams = self.denoise_steps + 1 if self.block_len else 1
        fragment_len, learner_rows = (
            streams * fragment_len, streams * learner_rows)
        if self.kv_lora_rank:
            widths = (self._latent_key_width(fragment_len), self.v_head_dim)
            kernel = platform == "tpu" and bool(attention) and decode_fused(
                self.context_len, self.num_heads, self.latent_width,
                self.kv_lora_rank)
        else:
            widths = (self.head_width,) * 2
            if self.block_len:
                kernel = platform == "tpu" and block_fused(
                    self.context_len, self.head_width)
            else:
                kernel = (platform == "tpu"
                          and self.kv_heads != self.num_heads and all(
                              grouped_fused(self.cache_len(i), self.kv_heads,
                                            self.layer_kind(i).heads,
                                            self.head_width)
                              for i in attention))
        if kernel:
            block = decode_attention.BLOCK
        elif (self.kv_lora_rank or self.kv_heads != self.num_heads
              or self.block_len):
            block = self.context_len
        else:
            block = min(DECODE_CACHE_BLOCK, self.context_len)
        sparse = (platform == "tpu" and not self.block_len
                  and self.decode_sparse(step_rows))
        # The layers whose learner pass may rotate a head's own rows.
        rotating = attention if (
            platform == "tpu" and not self.kv_lora_rank) else ()
        out = {
            "decode_rows_per_expert": step_rows * k / E,
            "decode_experts_batched": float(
                experts_batched(step_rows, k, E) and not sparse),
            "decode_experts_sparse": float(sparse),
            **({} if sparse else {"decode_experts_read_share": 1.0}),
            "decode_cache_block": block,
            "decode_attention_kernel": float(kernel),
            "causal_attention_fused": float(
                platform == "tpu" and causal_fused(fragment_len, *widths)),
            "rotation_fused_layers": float(sum(
                kind.rotary and rowwise.whole_tiles(
                    fragment_len, self.head_width,
                    int(self.head_width * kind.rotation.share))
                for kind in map(self.layer_kind, rotating))),
        }
        if self.block_len:
            out.update(
                block_len=self.block_len, denoise_steps=self.denoise_steps,
                decode_passes_per_token=streams / self.block_len,
                learner_rows_per_token=streams - 1 / self.num_layers,
                block_attention_kernel=float(kernel),
                block_cache_writes_per_block=1)
        if learner_rows:
            out["experts_grouped_kernel"] = float(
                platform == "tpu"
                and not experts_batched(learner_rows, k, E)
                and experts_fused(
                    dispatch_rows(learner_rows, k, self.held, E)[0],
                    self.hidden_size, self.expert_width, self.compute_dtype))
        itemsize = jnp.dtype(self.compute_dtype).itemsize
        if self.kv_lora_rank:
            out["latent_cache_bytes_per_token"] = (
                len(attention) * self.latent_width * itemsize)
        if not self.kv_lora_rank:
            out["kv_cache_bytes_per_token"] = (
                2 * self.kv_heads * self.head_width * itemsize
                * sum(self.cache_len(i) for i in attention)
                / self.context_len)
        windows = [i for i in attention if self.layer_kind(i)[0]]
        if windows:
            kept, causal = causal_window_tiles(
                fragment_len, self.sliding_window) if out[
                    "causal_attention_fused"] else (1, 1)
            out.update(
                window_layers=len(windows),
                causal_window_tiles_kept=kept / causal)
        if not self.kv_lora_rank and self.kv_heads != self.num_heads:
            out["kv_groups"] = self.num_heads // self.kv_heads
        state = jax.eval_shape(lambda: self.initial_state(1))
        chunks = {"conv": None, "kda": self.kda_chunk, "ssm": self.ssm_chunk,
                  "gdn": self.gdn_chunk}
        for kind in STATE_KINDS[1:]:
            layers = sum(bool(entry) for entry in state.get(kind, ()))
            if not layers:
                continue
            out.update({f"{kind}_layers": layers,
                        f"{kind}_state_bytes_per_row": sum(
                            a.size * a.dtype.itemsize
                            for a in jax.tree.leaves(state[kind]))})
            if chunks[kind]:
                out[f"{kind}_chunk"] = chunks[kind]
        if any(f"{kind}_layers" in out for kind in ("kda", "ssm", "gdn")):
            # The delta rule's states, a channel's decay or a head's.
            stepped = jax.tree.leaves(
                [state.get(kind, ()) for kind in ("kda", "gdn")])
            out["state_step_kernel"] = float(
                platform == "tpu" and bool(stepped) and all(
                    state_step.whole_tiles(*S.shape[1:]) for S in stepped))
        return out

    def __call__(self, obs, state, reset, rollout=False):
        """obs [B, T] token ids, reset [B, T] (1 where an episode starts
        at that step) -> (logits [B, T, V], value [B, T], state). T = 1 is
        a decode step against `state`; T > 1 is a causal pass from an
        empty window (`state` is not read). `rollout`: the caller is a
        rollout's step (`JaxPolicy.step_state`), which nothing
        differentiates, and not a learner's bootstrap step."""
        if obs.shape[1] == 1:
            logits, value, state = self.decode(
                obs[:, 0], state, reset[:, 0], rollout)
            return logits[:, None], value[:, None], state
        return self.causal(obs, reset)

    # -- attention, both kinds, both forms --------------------------------
    def _qkv(self, lp, n, kind):
        """(q, k, v) by head of rows n of a layer of `kind`; with
        `attention_gate` a fourth, the values whose sigmoid multiplies a
        head's output: a head's columns of W_q are its query's, then its
        gate's; or ("head") one value a head, n W_g."""
        cd, eps = self.compute_dtype, self.rms_eps
        heads = n.shape[:-1] + (kind.heads, -1)
        groups = n.shape[:-1] + (self.kv_heads, -1)

        def projected(w, norm):
            a = jnp.dot(n, lp[w].astype(cd))
            return rms_norm(a, lp[norm], eps, cd) if self.qk_norm is True \
                else a
        q, k = projected("wq", "q_norm"), projected("wk", "k_norm")
        v = jnp.dot(n, lp["wv"].astype(cd))
        q, k = q.reshape(heads), k.reshape(groups)
        gate = ()
        if self.attention_gate == "head":
            gate = (jnp.dot(n, lp["wg"].astype(cd))[..., None],)
        elif self.attention_gate:
            q, *gate = jnp.split(q, 2, axis=-1)
        if self.qk_norm == "head":
            q = rms_norm(q, lp["q_norm"], eps, cd)
            k = rms_norm(k, lp["k_norm"], eps, cd)
        return (q, k, v.reshape(groups), *gate)

    def _gated(self, o, gate, rows=False):
        """A head's output o times sigmoid(gate), in float32. `rows`: o is
        a causal pass's, [B, heads, T, d] of `rowwise.whole_tiles`, and a
        head's gate lies [B, T, heads]: one pass over o in a program
        lowered for a TPU (`rowwise.gating`)."""
        def plain(o, gate):
            return (o.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(self.compute_dtype)

        def by_head(o, gate):
            return plain(o, gate if gate.ndim == o.ndim
                         else jnp.swapaxes(gate, 1, 2)[..., None])
        with jax.named_scope("policy/attention_gate"):
            if not rows:
                return plain(o, gate)
            return jax.lax.platform_dependent(
                o, gate, tpu=rowwise.gating, default=by_head)

    def _rotate(self, x, positions, rotation, scale=1.0, head_major=False):
        """`rope` by a layer's `rotation`: of the leading share of a head's
        values (all of them as a rule), the rest as they are; everything
        times `scale` in float32. Head-major rows of `rowwise.whole_tiles`
        (a learner's pass) in a program lowered for a TPU: the same
        arithmetic as one pass over them (`rowwise.rotation`)."""
        theta, share, scaling = rotation
        rotated = int(x.shape[-1] * share)

        def plain(x, positions):
            if rotated == x.shape[-1]:
                return rope(x, positions, theta, scale, head_major, scaling)
            rest = x[..., rotated:]
            if scale != 1.0:
                rest = (rest.astype(jnp.float32) * scale).astype(x.dtype)
            return jnp.concatenate([
                rope(x[..., :rotated], positions, theta, scale, head_major,
                     scaling), rest], axis=-1)

        def rows(x, positions):
            cos, sin = rowwise.tables(
                positions, *rope_frequencies(rotated, theta, scaling),
                x.shape[-1])
            return rowwise.rotation(x, cos, sin, rotated, scale)
        with jax.named_scope("policy/rope"):
            if head_major and rowwise.whole_tiles(*x.shape[2:], rotated):
                return jax.lax.platform_dependent(
                    x, positions, tpu=rows, default=plain)
            return plain(x, positions)

    def _attention_scope(self, window: int) -> str:
        """The name a layer's own-heads attention has in a trace: by its
        kind where the model has more than one."""
        if not self.window_layout:
            return "policy/attention"
        return "policy/attention_window" if window else "policy/attention_full"

    def _latents(self, lp, n, positions, rotary=True):
        """(c_q, the cache's rows [c_kv | k_r]) of rows n at `positions`:
        c_q the queries' own latent, or n itself where they have none
        (`q_lora_rank` 0); k_r rotated where `rotary`."""
        cd, eps = self.compute_dtype, self.rms_eps
        with jax.named_scope("policy/mla_latent"):
            c_q = rms_norm(jnp.dot(n, lp["wq_a"].astype(cd)),
                           lp["q_a_norm"], eps, cd) if self.q_lora_rank \
                else n
            kv = jnp.dot(n, lp["wkv_a"].astype(cd))
            c_kv = rms_norm(kv[..., :self.kv_lora_rank], lp["kv_a_norm"],
                            eps, cd)
            if rotary:
                k_r = rope(kv[..., None, self.kv_lora_rank:], positions,
                           self.rope_theta)[..., 0, :]
            else:
                k_r = kv[..., self.kv_lora_rank:]
            return c_q, jnp.concatenate([c_kv, k_r], axis=-1)

    def _wq(self, lp):
        """The weights that make the queries of `_latents`' c_q."""
        return lp["wq_b" if self.q_lora_rank else "wq"]

    def _queries(self, lp, c_q, positions, rotary=True):
        """(q_nope [..., heads, nope], q_rope [..., heads, rope], rotated
        where `rotary`)."""
        q = jnp.dot(c_q, self._wq(lp).astype(self.compute_dtype)).reshape(
            c_q.shape[:-1] + (self.num_heads, -1))
        nope = self.qk_nope_head_dim
        q_nope, q_rope = q[..., :nope], q[..., nope:]
        if rotary:
            q_rope = rope(q_rope, positions, self.rope_theta)
        return q_nope, q_rope

    def _latent_key_width(self, T: int) -> int:
        """The width a latent layer's decompressed queries and keys have in
        a causal pass over `T` positions: nope + rope, or, where that is
        no width the fused form takes and the next whole lane tile is (192:
        256), that, the rest of a head zeros: q . k is what it was, and a
        pass that would write [T, T] scores runs the kernel instead."""
        width = self.qk_nope_head_dim + self.qk_rope_head_dim
        padded = width + -width % 128
        fits = causal_fused(T, width, self.v_head_dim)
        return padded if not fits and causal_fused(
            T, padded, self.v_head_dim) else width

    def _wkv_b(self, lp):
        """W_kvb by head: (W_UK [c, heads, nope], W_UV [c, heads, v])."""
        w = lp["wkv_b"].astype(self.compute_dtype).reshape(
            self.kv_lora_rank, self.num_heads, -1)
        return w[..., :self.qk_nope_head_dim], w[..., self.qk_nope_head_dim:]

    def _attend_causal(self, lp, x, positions, episode, cache_rows,
                       kind=None, streams=0, unread=0):
        """x + Attention(RMSNorm(x)) over a fragment [B, T, H] from an
        empty window; (h, the layer's caches: the rows `cache_rows` of the
        fragment). A head's own keys: the geometry `kind`'s (a full,
        rotated layer of `num_heads` where not given: within its window
        where it has one, rotated where it rotates, by its rotation, as
        many query heads as it has); with `streams`, the fragment is a
        block-diffusion learner's (`block_causal`: that many streams of T /
        streams positions, read by `block_stream_attention`) and hands over
        no caches; of such a fragment's first `unread` positions (its last
        layer's clean stream, whose output nothing reads) only the keys and
        values are made, as a commit pass's last layer makes them
        (`_attend_block`): no query, no score, no `W_o`, and h is of the
        T - `unread` positions after them. Head-major throughout: the
        projections write queries, keys and values as [B, heads, T, d],
        which `causal_attention` reads, and `W_o` contracts its output
        over (head, d) as it lies, so that no transposed copy of any of
        them is made on the way."""
        cd, eps = self.compute_dtype, self.rms_eps
        window, rotary, heads, rotation = kind or self._plain_kind
        H = x.shape[-1]

        def by_head(n, w, heads=heads):
            """n [B, T, r] W [r, heads * d] -> [B, heads, T, d]."""
            return jnp.einsum("btr,rhd->bhtd", n,
                              w.astype(cd).reshape(w.shape[0], heads, -1))

        def joined(o, x=x):
            """x + [B, heads, T, d] W_o."""
            return x + jnp.einsum("bhtd,hdo->bto", o,
                                  lp["wo"].astype(cd).reshape(heads, -1, H))

        if not self.kv_lora_rank:
            groups = self.kv_heads
            with jax.named_scope("policy/block_attention" if streams
                                 else self._attention_scope(window)):
                n = rms_norm(x, lp["attn_norm"], eps, cd)
                # The rows that ask: the `unread` before them only answer.
                asking = (lambda a: a[:, unread:]) if unread else (lambda a: a)

                def projected(w, norm, heads, n=n):
                    return normed(by_head(n, lp[w], heads), norm, heads)

                def normed(a, norm, heads):
                    if self.qk_norm == "head":
                        # Over each head's own values, one weight for all.
                        return rms_norm(a, lp[norm], eps, cd)
                    if not self.qk_norm:
                        return a
                    # QK-norm over the whole projection: heads and d.
                    return rms_norm(a, lp[norm].reshape(heads, 1, -1), eps,
                                    cd, axes=(1, 3))
                if self.attention_gate is True:
                    q, gate = jnp.split(
                        by_head(asking(n), lp["wq"], heads), 2, axis=-1)
                    q = normed(q, "q_norm", heads)
                else:
                    q = projected("wq", "q_norm", heads, asking(n))
                # Whether the gate is one pass over a head's rows.
                rows = bool(self.attention_gate) and rowwise.whole_tiles(
                    *q.shape[2:])
                if self.attention_gate == "head":
                    gate = jnp.einsum(
                        "btr,rh->bth", asking(n), lp["wg"].astype(cd)
                    ) if rows else jnp.einsum(
                        "btr,rh->bht", asking(n),
                        lp["wg"].astype(cd))[..., None]
                k = projected("wk", "k_norm", groups)
                scale = q.shape[-1] ** -0.5
                if rotary:
                    # The softmax's scale goes onto q in RoPE's float32.
                    q = self._rotate(q, asking(positions), rotation, scale,
                                     head_major=True)
                    k = self._rotate(k, positions, rotation, head_major=True)
                    scale = 1.0
                v = by_head(n, lp["wv"], groups)
                if streams:
                    return joined(block_stream_attention(
                        q, k, v, episode, scale, self.block_len,
                        streams), asking(x)), ()
                o = causal_attention(q, k, v, episode, scale, window)
                h = joined(self._gated(o, gate, rows) if self.attention_gate
                           else o)
                caches = tuple(
                    jnp.take_along_axis(jnp.swapaxes(a, 1, 2),
                                        cache_rows[:, :, None, None], axis=1)
                    for a in (k, v))
                if groups != heads:
                    caches = tuple(a.reshape(a.shape[:2] + (-1,))
                                   for a in caches)
            return h, caches
        # Latent attention, decompressed: keys and values of every head
        # are made from the latents for every position, the one rotary
        # key copied to every head's.
        n = rms_norm(x, lp["attn_norm"], eps, cd)
        c_q, latent = self._latents(lp, n, positions, rotary)
        with jax.named_scope("policy/mla_latent"):
            caches = (jnp.take_along_axis(
                latent, cache_rows[:, :, None], axis=1),)
        with jax.named_scope("policy/mla_expand"):
            nope = self.qk_nope_head_dim
            q = by_head(c_q, self._wq(lp))
            if rotary:
                q = jnp.concatenate([
                    q[..., :nope], rope(q[..., nope:], positions,
                                        self.rope_theta, head_major=True)],
                    axis=-1)
            w_uk, w_uv = self._wkv_b(lp)
            c_kv, k_r = (latent[..., :self.kv_lora_rank],
                         latent[..., self.kv_lora_rank:])
            k = jnp.concatenate([
                by_head(c_kv, w_uk), jnp.broadcast_to(
                    k_r[:, None], (k_r.shape[0], heads) + k_r.shape[1:])],
                axis=-1)
            v = by_head(c_kv, w_uv)
            # Zeros up to a width the fused form takes, where there is one.
            spare = self._latent_key_width(q.shape[2]) - q.shape[3]
            if spare:
                q, k = (jnp.pad(a, ((0, 0),) * 3 + ((0, spare),))
                        for a in (q, k))
        with jax.named_scope("policy/mla_attend"):
            o = causal_attention(
                q, k, v, episode,
                (nope + self.qk_rope_head_dim) ** -0.5)
        with jax.named_scope("policy/mla_expand"):
            h = joined(o)
        return h, caches

    def _attend_step(self, lp, x, pos, caches, kind):
        """x + Attention(RMSNorm(x)) of one token a row, x [B, H], against
        the layer's caches, this position written first; (h, the caches,
        the positions read); `kind`: `_attend_causal`'s. A window layer's
        caches are a ring: position
        p is written to slot p mod its length, over position p - length,
        which has just left the window; keys are rotated before they are
        cached, so the ring is read in whatever order it lies."""
        cd, eps = self.compute_dtype, self.rms_eps
        B = x.shape[0]
        rows = jnp.arange(B)
        window, rotary, _, rotation = kind
        if not self.kv_lora_rank:
            k_cache, v_cache = caches
            with jax.named_scope(self._attention_scope(window)):
                n = rms_norm(x, lp["attn_norm"], eps, cd)
                q, k, v, *gate = self._qkv(lp, n, kind)
                if rotary:
                    q = self._rotate(q, pos, rotation)
                    k = self._rotate(k, pos, rotation)
                slot = pos % k_cache.shape[1] if window else pos
                # Grouped heads' caches are stored flat: a position's row
                # is written whole, and read through its view by head.
                k_cache = k_cache.at[rows, slot].set(
                    k.reshape(k_cache.shape[:1] + k_cache.shape[2:]))
                v_cache = v_cache.at[rows, slot].set(
                    v.reshape(v_cache.shape[:1] + v_cache.shape[2:]))
                by_head = k_cache.shape[:2] + k.shape[1:]
                o, read = cached_attention(
                    q, k_cache.reshape(by_head), v_cache.reshape(by_head),
                    pos)
                if gate:
                    o = self._gated(o, gate[0])
                h = x + jnp.dot(o.reshape(B, -1), lp["wo"].astype(cd))
            return h, (k_cache, v_cache), read
        # Latent attention, absorbed: W_UK goes into the query and W_UV
        # onto the weighted latents, so the cache's rows are read as they
        # lie.
        (cache,) = caches
        n = rms_norm(x, lp["attn_norm"], eps, cd)
        c_q, latent = self._latents(lp, n, pos, rotary)
        with jax.named_scope("policy/mla_latent"):
            cache = cache.at[rows, pos].set(latent)
        with jax.named_scope("policy/mla_expand"):
            q_nope, q_rope = self._queries(lp, c_q, pos, rotary)
            w_uk, w_uv = self._wkv_b(lp)
            q = jnp.concatenate(
                [jnp.einsum("bhd,chd->bhc", q_nope, w_uk), q_rope], axis=-1)
        with jax.named_scope("policy/mla_attend"):
            o, read = cached_attention(
                q, cache, None, pos,
                scale=(self.qk_nope_head_dim
                       + self.qk_rope_head_dim) ** -0.5,
                value_dim=self.kv_lora_rank)
        with jax.named_scope("policy/mla_expand"):
            o = jnp.einsum("bhc,chd->bhd", o, w_uv).reshape(B, -1)
            h = x + jnp.dot(o, lp["wo"].astype(cd))
        return h, (cache,), read

    # -- the short convolution, both forms ---------------------------------
    def _conv_gates(self, lp, x):
        """(g = b * u, c) of rows x [.., H]: [b | c | u] = RMSNorm(x) W_in."""
        cd = self.compute_dtype
        n = rms_norm(x, lp["attn_norm"], self.rms_eps, cd)
        b, c, u = jnp.split(jnp.dot(n, lp["conv_in"].astype(cd)), 3, axis=-1)
        return b * u, c

    def _conv_causal(self, lp, x, positions):
        """x + (c * v) W_out over a fragment [B, T, H] from empty states,
        v_t = sum_j w[:, j] g_{t - (L - 1) + j}, a tap that would reach
        before its episode's first step (`positions`: a step's place in
        its episode) reading 0; (h, the state a decode continues from: the
        last episode's last L - 1 gated inputs [B, L - 1, H], 0 where the
        episode is shorter). The taps are multiplied and summed in
        float32: elementwise work beside the two projections."""
        cd = self.compute_dtype
        with jax.named_scope("policy/short_conv"):
            g, c = self._conv_gates(lp, x)
            v, back = _taps_causal(g, lp["conv_w"], positions)
            h = x + jnp.dot(c * v.astype(cd), lp["conv_out"].astype(cd))
            state = _taps_tail(back, positions)
        return h, state

    def _conv_step(self, lp, x, state, reset):
        """The same of one token a row, x [B, H], against the row's state
        [B, L - 1, H], zeroed first where `reset`; (h, the state with g_t
        appended and its oldest entry dropped)."""
        cd = self.compute_dtype
        with jax.named_scope("policy/short_conv"):
            g, c = self._conv_gates(lp, x)
            v, taps = _taps_step(g, lp["conv_w"], state, reset)
            h = x + jnp.dot(c * v.astype(cd), lp["conv_out"].astype(cd))
        return h, taps[:, 1:]

    # -- Kimi Delta Attention, both forms -----------------------------------
    def _kda_inputs(self, lp, x):
        """(n = RMSNorm(x), [q~ | k~ | v~] = n W_qkv ahead of their
        convolutions) of rows x [.., H]."""
        cd = self.compute_dtype
        n = rms_norm(x, lp["attn_norm"], self.rms_eps, cd)
        return n, jnp.dot(n, lp["kda_qkv"].astype(cd))

    def _kda_heads(self, lp, n, mixed):
        """(q, k, v, g, beta) by head of rows' normalised input n and their
        convolved projections `mixed` [.., 3 P] (float32, ahead of the
        activation): q, k, v = silu(.), q and k of unit length a head (eps
        1e-6 under the root) and q times d_k^-1/2, made in float32 and
        kept in `compute_dtype`; float32, the LOG decay a channel g =
        -exp(A_log) softplus((n W_fa) W_fb + dt_bias) <= 0 and beta =
        sigmoid(n W_b)."""
        cd, f32, d = self.compute_dtype, jnp.float32, self.kda_head_dim
        by_head = n.shape[:-1] + (-1, d)
        q, k, v = (a.reshape(by_head)
                   for a in jnp.split(jax.nn.silu(mixed), 3, axis=-1))

        def unit(a):
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
        rate = jnp.dot(jnp.dot(n, lp["kda_fa"].astype(cd)),
                       lp["kda_fb"].astype(cd)).astype(f32)
        g = -jnp.exp(lp["kda_a_log"])[:, None] * jax.nn.softplus(
            rate + lp["kda_dt_bias"]).reshape(by_head)
        beta = jax.nn.sigmoid(
            jnp.dot(n, lp["kda_b"].astype(cd)).astype(f32))
        return ((unit(q) * d ** -0.5).astype(cd), unit(k).astype(cd),
                v.astype(cd), g, beta)

    def _kda_output(self, lp, x, n, o):
        """x + (RMSNorm_head(o) * sigmoid((n W_ga) W_gb)) W_out for a
        head's outputs o [.., heads, d_v]: the norm over each head's own
        values, one weight [d_v]."""
        cd = self.compute_dtype
        gate = jax.nn.sigmoid(jnp.dot(
            jnp.dot(n, lp["kda_ga"].astype(cd)),
            lp["kda_gb"].astype(cd)).astype(jnp.float32))
        o = rms_norm(o, lp["kda_o_norm"], self.rms_eps, jnp.float32)
        o = (o.reshape(gate.shape) * gate).astype(cd)
        return x + jnp.dot(o, lp["kda_out"].astype(cd))

    def _kda_causal(self, lp, x, positions, episode):
        """x + KDA(RMSNorm(x)) over a fragment [B, T, H] from empty states
        (`kda_chunked`); (h, (the three convolutions' last taps - 1 inputs
        [B, taps - 1, 3 P], the matrix state after the last position
        [B, heads, d_k, d_v] float32): what a decode continues from)."""
        with jax.named_scope("policy/kda"):
            n, mixed = self._kda_inputs(lp, x)
            mixed, back = _taps_causal(mixed, lp["kda_conv"], positions)
            q, k, v, g, beta = self._kda_heads(lp, n, mixed)
            o, S = kda_chunked(q, k, v, g, beta, episode, self.kda_chunk,
                               self.compute_dtype)
            return self._kda_output(lp, x, n, o), (
                _taps_tail(back, positions), S)

    def _kda_step(self, lp, x, tails, S, reset):
        """The same of one token a row, x [B, H], against the row's
        states, zeroed first where `reset` (`kda_decode_step`: in a
        program lowered for a TPU the kernel that reads a state once and
        writes it once in place, elsewhere `kda_step` after a select); (h,
        the convolutions' inputs with this one appended and the oldest
        dropped, the matrix state)."""
        with jax.named_scope("policy/kda"):
            n, mixed = self._kda_inputs(lp, x)
            mixed, taps = _taps_step(mixed, lp["kda_conv"], tails, reset)
            q, k, v, g, beta = self._kda_heads(lp, n, mixed)
            with jax.named_scope("policy/kda_state"):
                o, S = kda_decode_step(S, q, k, v, g, beta, reset)
            return self._kda_output(lp, x, n, o), taps[:, 1:], S

    # -- Gated DeltaNet, both forms -----------------------------------------
    def _gdn_inputs(self, lp, x):
        """([q~ | k~ | v~] ahead of their convolution, z, [b | a]) of rows
        x [.., H]: [q~ | k~ | v~ | z] = RMSNorm(x) W_qkvz, [b | a] =
        RMSNorm(x) W_ba."""
        cd = self.compute_dtype
        n = rms_norm(x, lp["attn_norm"], self.rms_eps, cd)
        mixed = jnp.dot(n, lp["gdn_qkvz"].astype(cd))
        C = self.gdn_conv_width
        return mixed[..., :C], mixed[..., C:], jnp.dot(
            n, lp["gdn_ba"].astype(cd))

    def _gdn_heads(self, lp, mixed, ba):
        """(q, k [.., key heads, d_k], v [.., value heads, d_v], g, beta
        [.., value heads]) of the convolved projections `mixed` [.., 2 K +
        V] (float32, ahead of the activation) and the rows' [b | a]: q, k,
        v = silu(.), q and k of unit length a head (eps 1e-6 under the
        root) and q times d_k^-1/2, made in float32 and kept in
        `compute_dtype`; float32, the LOG decay of a value head g =
        -exp(A_log) softplus(a + dt_bias) <= 0 and beta = sigmoid(b)."""
        cd, f32 = self.compute_dtype, jnp.float32
        K = self.gdn_key_heads * self.gdn_key_dim
        mixed = jax.nn.silu(mixed)
        by = mixed.shape[:-1]
        q, k = (mixed[..., i * K:(i + 1) * K].reshape(
            by + (self.gdn_key_heads, -1)) for i in range(2))
        v = mixed[..., 2 * K:].reshape(by + (self.gdn_value_heads, -1))

        def unit(a):
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
        b, a = jnp.split(ba.astype(f32), 2, axis=-1)
        g = -jnp.exp(lp["gdn_a_log"]) * jax.nn.softplus(
            a + lp["gdn_dt_bias"])
        return ((unit(q) * self.gdn_key_dim ** -0.5).astype(cd),
                unit(k).astype(cd), v.astype(cd), g, jax.nn.sigmoid(b))

    def _gdn_output(self, lp, x, z, o):
        """x + (RMSNorm_head(o) * w * silu(z)) W_out for a value head's
        outputs o [.., value heads, d_v]: the norm over each head's own
        values, one plain weight [d_v], then the gate."""
        cd, f32 = self.compute_dtype, jnp.float32
        o = rms_norm(o, lp["gdn_o_norm"], self.rms_eps, f32)
        o = (o.reshape(z.shape) * jax.nn.silu(z.astype(f32))).astype(cd)
        return x + jnp.dot(o, lp["gdn_out"].astype(cd))

    def _gdn_causal(self, lp, x, positions, episode):
        """x + GatedDeltaNet(RMSNorm(x)) over a fragment [B, T, H] from
        empty states (`kda_chunked` with one decay a head, a key head
        serving its value heads); (h, (the convolution's last taps - 1
        inputs [B, taps - 1, 2 K + V], the matrix state after the last
        position [B, value heads, d_k, d_v] float32): what a decode
        continues from)."""
        with jax.named_scope("policy/gdn"):
            mixed, z, ba = self._gdn_inputs(lp, x)
            mixed, back = _taps_causal(mixed, lp["gdn_conv"], positions)
            q, k, v, g, beta = self._gdn_heads(lp, mixed, ba)
            o, S = kda_chunked(q, k, v, g, beta, episode, self.gdn_chunk,
                               self.compute_dtype, scope="policy/gdn_state")
            return self._gdn_output(lp, x, z, o), (
                _taps_tail(back, positions), S)

    def _gdn_step(self, lp, x, tails, S, reset):
        """The same of one token a row, x [B, H], against the row's
        states, zeroed first where `reset` (`kda_decode_step` with one
        decay a head: in a program lowered for a TPU the kernel that reads
        a state once and writes it once in place); (h, the convolution's
        inputs with this one appended and the oldest dropped, the matrix
        state)."""
        shared = self.gdn_value_heads // self.gdn_key_heads
        with jax.named_scope("policy/gdn"):
            mixed, z, ba = self._gdn_inputs(lp, x)
            mixed, taps = _taps_step(mixed, lp["gdn_conv"], tails, reset)
            q, k, v, g, beta = self._gdn_heads(lp, mixed, ba)
            # A key head's vectors, once a value head it serves.
            q, k = (jnp.repeat(a, shared, axis=1) for a in (q, k))
            with jax.named_scope("policy/gdn_state"):
                o, S = kda_decode_step(S, q, k, v, g, beta, reset)
            return self._gdn_output(lp, x, z, o), taps[:, 1:], S

    # -- Mamba-2, both forms ------------------------------------------------
    def _ssm_inputs(self, lp, x):
        """(z, x B C ahead of their convolution, dt ahead of its bias) of
        rows x [.., H]: [z | xBC | dt] = RMSNorm(x) W_in."""
        cd = self.compute_dtype
        n = rms_norm(x, lp["attn_norm"], self.rms_eps, cd)
        mixed = jnp.dot(n, lp["ssm_in"].astype(cd))
        inner, heads = self.ssm_width, self.ssm_heads
        return mixed[..., :inner], mixed[..., inner:-heads], mixed[
            ..., -heads:]

    def _ssm_heads(self, lp, mixed, dt):
        """(dt x and x by head, B and C by group, in `compute_dtype`; the
        float32 LOG decay a head) of the convolved `mixed` [.., I + 2 G N]
        (float32, ahead of its bias and activation) and the rows' dt [..,
        heads]: xBC = silu(. + b), dt = softplus(. + dt_bias), the log
        decay -exp(A_log) dt <= 0."""
        cd, f32 = self.compute_dtype, jnp.float32
        inner, G = self.ssm_width, self.ssm_groups
        by = mixed.shape[:-1]
        xBC = jax.nn.silu(mixed + lp["ssm_conv_bias"])
        x = xBC[..., :inner].reshape(by + (self.ssm_heads, -1))
        Bm, Cm = (a.reshape(by + (G, -1)).astype(cd)
                  for a in jnp.split(xBC[..., inner:], 2, axis=-1))
        dt = jax.nn.softplus(dt.astype(f32) + lp["ssm_dt_bias"])
        return ((dt[..., None] * x).astype(cd), x.astype(cd), Bm, Cm,
                -jnp.exp(lp["ssm_a_log"]) * dt)

    def _ssm_output(self, lp, x, z, y, by_head):
        """x + GroupRMSNorm((y + D x_h) * silu(z)) W_out for the state's
        outputs y and the heads' inputs `by_head` [.., heads, P]: the gate
        first, then the norm over each group's channels, one weight [I]."""
        cd, f32, G = self.compute_dtype, jnp.float32, self.ssm_groups
        y = y.astype(f32) + lp["ssm_d"][:, None] * by_head.astype(f32)
        gated = (y.reshape(z.shape) * jax.nn.silu(z.astype(f32))).reshape(
            z.shape[:-1] + (G, -1))
        o = rms_norm(gated, lp["ssm_norm"].reshape(G, -1), self.rms_eps, cd)
        return x + jnp.dot(o.reshape(z.shape), lp["ssm_out"].astype(cd))

    def _ssm_causal(self, lp, x, positions, episode):
        """x + Mamba2(RMSNorm(x)) over a fragment [B, T, H] from empty
        states (`ssd_chunked`); (h, (the convolution's last taps - 1 inputs
        [B, taps - 1, I + 2 G N], the matrix state after the last position
        [B, heads, P, N] float32): what a decode continues from)."""
        with jax.named_scope("policy/mamba2"):
            z, xBC, dt = self._ssm_inputs(lp, x)
            with jax.named_scope("policy/short_conv"):
                mixed, back = _taps_causal(xBC, lp["ssm_conv"], positions)
                tail = _taps_tail(back, positions)
            dtx, by_head, Bm, Cm, la = self._ssm_heads(lp, mixed, dt)
            y, S = ssd_chunked(dtx, Bm, Cm, la, episode, self.ssm_chunk,
                               self.compute_dtype)
            return self._ssm_output(lp, x, z, y, by_head), (tail, S)

    def _ssm_step(self, lp, x, tails, S, reset):
        """The same of one token a row, x [B, H], against the row's
        states, zeroed first where `reset` (`ssd_step`, XLA's fusions on
        every platform); (h, the convolution's inputs with this one
        appended and the oldest dropped, the matrix state)."""
        with jax.named_scope("policy/mamba2"):
            z, xBC, dt = self._ssm_inputs(lp, x)
            with jax.named_scope("policy/short_conv"):
                mixed, taps = _taps_step(xBC, lp["ssm_conv"], tails, reset)
            dtx, by_head, Bm, Cm, la = self._ssm_heads(lp, mixed, dt)
            with jax.named_scope("policy/ssm_state"):
                # No kernel: these fusions pass over S once each way, at
                # the rate of a copy through VMEM (`state_step`'s header).
                S = jnp.where((reset > 0)[:, None, None, None], 0.0, S)
                y, S = ssd_step(S, dtx, Bm, Cm, la)
            return self._ssm_output(lp, x, z, y, by_head), taps[:, 1:], S

    # -- feed-forward -----------------------------------------------------
    def _route(self, lp, n):
        return route(
            n, lp["router"], self.experts_per_token, self.norm_topk_prob,
            lp.get("router_bias"), self.routed_scaling_factor, self.topk_eps,
            self.sigmoid_router)

    def _route_ahead(self, lp, x):
        """The routing of rows x [.., H] where the router reads the
        attention's normalised input (None where it reads the block's
        post-attention norm: `_feed_forward` then routes itself)."""
        if not self.router_before_attention:
            return None
        n = rms_norm(x, lp["attn_norm"], self.rms_eps, self.compute_dtype)
        return self._route(lp, n.reshape(-1, n.shape[-1]))

    def _feed_forward(self, lp, h, routing=None, rollout=False):
        """h + FeedForward(RMSNorm(h)) for rows h [M, H]; (out, (rows a
        held group, sorted rows gathered), experts [M, k]), the last two
        None of a dense layer.
        `routing`: (weights, experts) chosen ahead of the attention;
        `rollout`: `dropless_experts`'."""
        cd = self.compute_dtype
        act = ACTIVATIONS[self.hidden_act]
        n = rms_norm(h, lp["mlp_norm"], self.rms_eps, cd)
        if "dense_gate" in lp:
            with jax.named_scope("policy/dense_mlp"):
                return h + swiglu(n, *(lp[w].astype(cd) for w in (
                    "dense_gate", "dense_up", "dense_down")),
                    act=act), None, None
        top_p, top_i = routing or self._route(lp, n)

        def matrices(*names):
            """In the blocks' dtype; None for a gate the model has not."""
            return (lp[w].astype(cd) if w in lp else None for w in names)
        moe, *load = dropless_experts(
            n, top_p, top_i, *matrices("w_gate", "w_up", "w_down"),
            self.first_expert_held, self.num_experts, act, rollout)
        if "shared_scale" in lp:
            with jax.named_scope("policy/shared_expert"):
                shared = swiglu(n, *matrices(
                    "shared_gate", "shared_up", "shared_down"), act=act)
                scale = jax.nn.sigmoid(jnp.dot(
                    n, lp["shared_scale"].astype(cd)).astype(jnp.float32))
                moe = moe + (scale * shared.astype(jnp.float32)).astype(cd)
        elif "shared_up" in lp:
            with jax.named_scope("policy/shared_expert"):
                moe = moe + swiglu(n, *matrices(
                    "shared_gate", "shared_up", "shared_down"), act=act)
        return h + moe, tuple(load), top_i

    def _heads(self, x):
        with jax.named_scope("policy/head"):
            y = rms_norm(x, self.final_norm, self.rms_eps, jnp.float32)
            if self.tie_embeddings:
                logits = jnp.einsum("...h,vh->...v", y, self.embed)
            else:
                logits = jnp.dot(y, self.head)
            value = jnp.dot(y, self.value_w) + self.value_b
        return logits, value

    def _count(self, experts, loads=None, reads=None, pairs=None,
               chosen=None):
        """What a pass counted, kept only where the caller asks for the
        collection (and never among the variables `init` returns): the
        experts chosen [expert layers, ..., k], for the reference check;
        in the learner's form (`loads`: an expert layer's load as
        `_feed_forward` gives it) also the rows of the fullest held expert
        group over the layers and the mean group, and where the layer
        holds a share, the share of the (row, expert) pairs that landed
        here and the share that its product gathered (the rows that
        `dropless_experts` reports over `M k`, the mean over the expert
        layers; 1.0: the whole size, or the batched form, which sorts
        none; `pairs`: a layer's `M k` in the mean, where the layers differ
        in it); in a decode step the share of the context's positions its
        attention read, the mean over the attention layers (`reads`:
        {layer: the positions it read}), and where the model has window
        layers the same of its full layers and of its window layers
        apart; and where the step's expert products read the chosen held
        experts' matrices alone (`chosen`: an expert layer's held experts
        [held], whether some row chose each), the share of them they read,
        the mean over the expert layers."""
        if self.is_initializing():
            return
        if experts:
            self.sow("routing", "experts", jnp.stack(experts))
        if loads:
            loads, gathered = (jnp.stack(part).astype(jnp.float32)
                               for part in zip(*loads))
            self.sow("counters", "expert_load_max", jnp.max(loads))
            self.sow("counters", "expert_load_mean", jnp.mean(loads))
            if self.held != self.num_experts:
                pairs = pairs or experts[0].size
                self.sow("counters", "experts_held_row_share",
                         jnp.mean(jnp.sum(loads, axis=-1)) / pairs)
                self.sow("counters", "dispatch_rows_share",
                         jnp.mean(gathered) / pairs)
        if chosen:
            # Counted where the products are the kernel's, which is where
            # the program is lowered for a TPU, as `dropless_experts`
            # chooses; the batched form reads every held expert.
            self.sow("counters", "decode_experts_read_share",
                     jax.lax.platform_dependent(
                         jnp.stack(chosen).astype(jnp.float32),
                         tpu=jnp.mean, default=lambda held: jnp.ones(())))
        window = [bool(self.layer_kind(i)[0]) for i in reads or ()]
        reads = list((reads or {}).values())
        if reads and not (any(window) and not all(window)):
            # Layers of one kind read alike.
            self.sow("counters", "decode_cache_read_share",
                     reads[-1].astype(jnp.float32) / self.context_len)
        elif reads:
            shares = jnp.stack(reads).astype(jnp.float32) / self.context_len
            window = jnp.asarray(window)
            for kind, of in (("", None), ("_full", ~window),
                             ("_window", window)):
                self.sow("counters", "decode_cache_read_share" + kind,
                         jnp.mean(shares, where=of))

    # -- the two forms --------------------------------------------------
    def causal(self, tokens, reset):
        cd = self.compute_dtype
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
        # Where the last episode's entries go in the cache: its own
        # positions.
        cache_rows = jnp.clip(start[:, -1:] + jnp.arange(S), 0, T - 1)

        def ring_rows(R):
            """The same for a ring of R slots: slot j holds the last of
            the episode's positions that is j mod R (none yet: a row the
            decode will not read)."""
            last = positions[:, -1:]
            held = last - jnp.mod(last - jnp.arange(R), R)
            return jnp.clip(start[:, -1:] + held, 0, T - 1)

        def block(lp, x, kind):
            """One layer; `caches` are its caches, or the convolution's
            state, or KDA's or Mamba-2's two (nothing where the layer is
            its feed-forward alone)."""
            routing = self._route_ahead(lp, x)
            if kind == "experts":
                h, caches = x, ()
            elif kind == "conv":
                h, caches = self._conv_causal(lp, x, positions)
            elif kind == "kda":
                h, caches = self._kda_causal(lp, x, positions, episode)
            elif kind == "mamba2":
                h, caches = self._ssm_causal(lp, x, positions, episode)
            elif kind == "gdn":
                h, caches = self._gdn_causal(lp, x, positions, episode)
            else:
                rows = ring_rows(min(kind[0], S)) if kind[0] else cache_rows
                h, caches = self._attend_causal(
                    lp, x, positions, episode, rows, kind)
            if "mlp_norm" not in lp:  # the operator alone
                return h, caches, None, None
            out, load, top_i = self._feed_forward(
                lp, h.reshape(B * T, -1), routing)
            return out.reshape(B, T, -1), caches, load, top_i
        if self.num_layers + self.nextn_layers > 1:
            # Recomputed in the backward pass, but for what the fused
            # attention keeps (its output and log-sum-exp: 85 MB a block
            # at 8 x 1,024 tokens of 20 heads x 256, where the plain
            # form's scores were 1 GB): its forward kernel runs once.
            block = jax.checkpoint(
                block, policy=jax.checkpoint_policies.save_only_these_names(
                    CAUSAL_KEPT), static_argnums=(2,))

        x = self.embed[tokens].astype(cd)
        held = {kind: [] for kind in STATE_KINDS}
        loads, experts = [], []
        for i, layer in enumerate(self.layers):
            kind = self.layer_kind(i)
            x, caches, load, top_i = block(layer(), x, kind)
            matrix = ()
            if kind in MATRIX_STATES.values():
                caches, matrix = caches
            held["kv"].append(() if isinstance(kind, str) else caches)
            held["conv"].append(caches if isinstance(kind, str) else ())
            for key, of in MATRIX_STATES.items():
                held[key].append(matrix if kind == of else ())
            if top_i is not None:
                loads.append(load)
                experts.append(top_i.reshape(B, T, -1))
        if self.nextn and (self.is_initializing()
                           or self.is_mutable_collection("losses")):
            load, top_i = self._next_next_token(
                block, x, tokens, episode)
            loads.append(load)
            experts.append(top_i.reshape(B, T, -1))
        self._count(experts, loads)
        logits, value = self._heads(x)
        return logits, value, self._policy_state(
            held, positions[:, -1] + 1)

    def _next_next_token(self, block, x, tokens, episode):
        """The module's loss over a fragment (see the module docstring),
        into "losses" (weighted, a sum over the positions, as the
        objective's other terms), "counters" (`mtp_loss`, a position's
        mean) and "routing" (position by position, for the reference
        check); (its expert layer's load, as `_feed_forward` gives it,
        experts)."""
        cd, eps = self.compute_dtype, self.rms_eps
        T = tokens.shape[1]
        (module,) = self.nextn
        lp = module()
        with jax.named_scope("policy/mtp"):
            following = jnp.roll(tokens, -1, axis=1)
            target = jnp.roll(tokens, -2, axis=1)
            valid = (jnp.arange(T) + 2 < T)[None] & (
                jnp.roll(episode, -2, axis=1) == episode)
            embed = jax.lax.stop_gradient(self.embed)
            z = jnp.dot(jnp.concatenate([
                rms_norm(jax.lax.stop_gradient(x), lp["hnorm"], eps, cd),
                rms_norm(embed[following], lp["enorm"], eps, cd)], axis=-1),
                lp["eh_proj"].astype(cd))
        z, _, load, top_i = block(lp, z, self._plain_kind)
        with jax.named_scope("policy/mtp"):
            y = rms_norm(z, lp["final_norm"], eps, jnp.float32)
            logp = jax.nn.log_softmax(
                jnp.dot(y, jax.lax.stop_gradient(self.head)), axis=-1)
            nll = -jnp.take_along_axis(logp, target[..., None], axis=-1)
            by_position = jnp.where(valid, nll[..., 0], 0.0)
            nll = jnp.sum(by_position)
        if not self.is_initializing():
            self.sow("routing", "nextn_nll", by_position)
            self.sow("losses", "next_next_token",
                     self.nextn_loss_weight * nll)
            self.sow("counters", "mtp_loss",
                     nll / jnp.maximum(jnp.sum(valid), 1))
        return load, top_i

    def decode(self, token, state, reset, rollout=False):
        pos = jnp.where(reset > 0, 0, state["pos"])
        sparse = rollout and self.decode_sparse(token.shape[0])
        x = self.embed[token].astype(self.compute_dtype)
        held = {kind: [] for kind in STATE_KINDS}
        experts, reads, chosen = [], {}, []
        for i, (layer, caches) in enumerate(zip(self.layers, state["kv"])):
            lp = layer()
            kind = self.layer_kind(i)
            routing = self._route_ahead(lp, x)
            h, tails, matrix = x, (), ()
            if kind == "experts":
                pass
            elif kind == "conv":
                h, tails = self._conv_step(lp, x, state["conv"][i], reset)
            elif kind == "kda":
                h, tails, matrix = self._kda_step(
                    lp, x, state["conv"][i], state["kda"][i], reset)
            elif kind == "mamba2":
                h, tails, matrix = self._ssm_step(
                    lp, x, state["conv"][i], state["ssm"][i], reset)
            elif kind == "gdn":
                h, tails, matrix = self._gdn_step(
                    lp, x, state["conv"][i], state["gdn"][i], reset)
            else:
                h, caches, reads[i] = self._attend_step(
                    lp, x, pos, caches, kind)
            held["kv"].append(caches)
            held["conv"].append(tails)
            for key, of in MATRIX_STATES.items():
                held[key].append(matrix if kind == of else ())
            x, top_i = h, None
            if "mlp_norm" in lp:
                x, load, top_i = self._feed_forward(lp, h, routing, rollout)
            if top_i is not None:
                experts.append(top_i)
                if sparse:
                    chosen.append(load[0] > 0)
        if self.is_initializing():
            for module in self.nextn:
                module()
        self._count(experts, reads=reads, chosen=chosen)
        logits, value = self._heads(x)
        return logits, value, self._policy_state(held, pos + 1)


    # -- a block of positions a step: the two forms ----------------------
    def _attend_block(self, lp, x, pos, caches, commit=False, last=False):
        """x + Attention(RMSNorm(x)) of a block of positions a row, x
        [N, L, H] at positions `pos` .. `pos + L - 1`, against the layer's
        caches: the L positions' queries read the blocks before theirs from
        the caches (positions [0, pos)) and the whole of their own block, in
        both directions, from the pass's own keys and values, which reach
        `block_attention` as operands. One read of the cache serves the L
        positions: they are folded into a cached head's rows of queries
        (heads / groups x L of them a cached head). A pass that is no
        `commit` writes nothing: of a denoising pass nothing outlives the
        block. A commit pass puts the block's keys and values into their
        slots (`decode_attention.write_block`), and `last` (its last layer,
        whose output nothing reads) stops there. (h, the caches, the
        positions read.)"""
        cd, eps = self.compute_dtype, self.rms_eps
        N, L, _ = x.shape
        groups, d = self.kv_heads, self.head_width
        k_cache, v_cache = caches
        stored = k_cache.shape
        # A position's cached heads as one row, as grouped heads' lie.
        k_cache, v_cache = (c.reshape(N, stored[1], groups * d)
                            for c in caches)
        with jax.named_scope("policy/block_attention"):
            n = rms_norm(x, lp["attn_norm"], eps, cd)
            q, k, v = self._qkv(lp, n, self._plain_kind)
            positions = pos[:, None] + jnp.arange(L)
            q = rope(q, positions, self.rope_theta)
            k = rope(k, positions, self.rope_theta).reshape(N, L, -1)
            v = v.reshape(N, L, -1)
            if commit:
                k_cache, v_cache = decode_attention.write_block(
                    k_cache, v_cache, k, v, pos)
            held = (k_cache.reshape(stored), v_cache.reshape(stored))
            if last:
                return None, held, None
            # [N, L, groups, heads a group, d] -> a cached head's rows.
            q = jnp.swapaxes(q.reshape(N, L, groups, -1, d), 1, 2)
            # A commit pass reads the caches it has written: one buffer
            # lives at a time, and the block's slots lie beyond `pos`.
            o, read = block_attention(
                q.reshape(N, groups, -1, d), k_cache, v_cache, k, v, pos,
                d ** -0.5)
            o = jnp.swapaxes(o.reshape(N, groups, L, -1, d), 1, 2)
            h = x + jnp.dot(o.reshape(N, L, -1), lp["wo"].astype(cd))
        return h, held, read

    def _block_pass(self, tokens, pos, caches, commit=False):
        """One pass of a block step: `tokens` [N, L] at the positions
        `pos` .. `pos + L - 1` through every layer; (the final hidden
        vectors [N, L, H], the caches, the experts chosen [N, L, k] a layer
        that chose, {layer: the positions its attention read}). A `commit` pass is run
        for the keys and values it leaves: its last layer stops at them."""
        N, L = tokens.shape
        x = self.embed[tokens].astype(self.compute_dtype)
        held, experts, reads = [], [], {}
        for i, (layer, layer_caches) in enumerate(zip(self.layers, caches)):
            lp = layer()
            last = commit and i == self.num_layers - 1
            h, layer_caches, read = self._attend_block(
                lp, x, pos, layer_caches, commit, last)
            held.append(layer_caches)
            if last:
                break
            reads[i] = read
            out, _, top_i = self._feed_forward(lp, h.reshape(N * L, -1))
            x = out.reshape(N, L, -1)
            experts.append(top_i.reshape(N, L, -1))
        return x, tuple(held), experts, reads

    def _token_logits(self, x):
        """(logits over the vocabulary with the MASK id's at `MASK_LOGIT`,
        values) of final hidden vectors x."""
        logits, values = self._heads(x)
        return jnp.where(jnp.arange(self.vocab_size) == self.mask_id,
                         MASK_LOGIT, logits), values

    def block_step(self, obs, state, reset, rng):
        """The rollout's form of a model with a `block_len`: one BLOCK a row,
        L = `block_len` positions generated by diffusion in S =
        `denoise_steps` passes, then committed. obs [N] (the env's newest
        token: read where a row begins an episode, whose first position it
        is, GIVEN and never masked), reset [N] -> (tokens [N, L], their
        log-probabilities [N, L] (0 of a given one), the pass each was
        unmasked at [N, L] (-1 of a given one), value [N], state).

        Pass s: every position of the block enters as its token where that
        is given or was unmasked at a pass before s, as the MASK id
        elsewhere; the logits at a position are the distribution of the
        token AT it. Of the positions still masked the L / S with the highest
        top probability are unmasked (ties to the lower position; the last
        pass takes what is left), each token drawn from its own
        distribution; which positions is a function of the pass's own
        logits, so the block's probability is the product of its tokens'
        at their own passes. The value is the value head's on the block's
        first position in pass 0, which has seen the blocks before, the
        given token and masks. After pass S - 1 the commit pass puts the
        clean tokens through the layers for the keys and values that later
        blocks read. A block costs S + 1 passes of L rows.

        A pass's queries read the block's own keys and values as operands
        beside the caches (`_attend_block`); the commit pass alone writes
        them into the block's slots: of a denoising pass nothing outlives
        the block."""
        L, S = self.block_len, self.denoise_steps
        pos = jnp.where(reset > 0, 0, state["pos"])
        given = (pos == 0)[:, None] & (jnp.arange(L) == 0)
        tokens = jnp.where(given, obs[:, None], self.mask_id).astype(jnp.int32)
        steps = jnp.where(given, -1, S)  # S: still masked
        logp = jnp.zeros(tokens.shape, jnp.float32)
        caches = state["kv"]
        experts, confident = [], []
        before = jnp.arange(L)[:, None] < jnp.arange(L)[None, :]
        for s in range(S):
            with jax.named_scope("policy/block_denoise"):
                x, caches, chosen_experts, reads = self._block_pass(
                    tokens, pos, caches)
                experts.append(jnp.stack(chosen_experts))
                logits, values = self._token_logits(x)
                if s == 0:
                    value = values[:, 0]
                logp_all = jax.nn.log_softmax(logits, axis=-1)
                masked = steps == S
                top = jnp.where(masked, jnp.max(logp_all, axis=-1), -jnp.inf)
                # How many positions of the row come before position i in
                # the order (top probability down, then position up).
                ahead = jnp.sum(
                    (top[:, :, None] > top[:, None, :])
                    | ((top[:, :, None] == top[:, None, :]) & before),
                    axis=1)
                take = L // S if s < S - 1 else L
                chosen = masked & (ahead < take)
                drawn = jax.random.categorical(
                    jax.random.fold_in(rng, s), logits, axis=-1)
                tokens = jnp.where(chosen, drawn, tokens)
                logp = jnp.where(chosen, jnp.take_along_axis(
                    logp_all, drawn[..., None], axis=-1)[..., 0], logp)
                steps = jnp.where(chosen, s, steps)
                confident.append(jnp.sum(jnp.where(chosen, jnp.exp(top), 0.0))
                                 / jnp.maximum(jnp.sum(chosen), 1))
        with jax.named_scope("policy/block_commit"):
            _, caches, commit_experts, _ = self._block_pass(
                tokens, pos, caches, commit=True)
        if not self.is_initializing():
            self.sow("routing", "experts", jnp.stack(experts))
            if commit_experts:  # none in a model of one layer
                self.sow("routing", "commit_experts",
                         jnp.stack(commit_experts))
            self.sow("counters", "unmask_top_prob_mean",
                     jnp.mean(jnp.stack(confident)))
            self._count((), reads=reads)
        return tokens, logp, steps, value, self._policy_state(
            {"kv": caches}, pos + L)

    def block_causal(self, tokens, steps, reset):
        """The learner's form of a model with a `block_len`: a minibatch of
        whole episodes [B, T] replayed on the sampler's own trace, `steps`
        [B, T] the pass each position was unmasked at (-1: given). One pass
        over S + 1 streams of T positions: the clean stream (block-causal),
        and for each pass s the stream that pass saw, every position its
        token where its step is below s and the MASK id elsewhere, whose
        queries read the clean stream's keys of earlier blocks and their
        own block's (`block_stream_attention`). Returns (logits [B, T, V],
        each position's from the stream of the pass it was unmasked at (a
        given position's from pass 0: nothing reads them), so that the head
        runs over T rows; values [B, T / block_len], the value head on each
        block's first position in pass 0). Every layer is recomputed in the
        backward pass, as `causal`'s are.

        The LAST layer stops where the rollout's commit pass stops
        (`_block_pass(commit=True)`): of its clean stream only the keys and
        values are read (by the noisy streams' queries of that layer), so
        it makes those for all (S + 1) T positions and everything else
        (queries, scores, `W_o`, the router, the dispatch, the experts) for
        the S T noisy positions alone; the fused attention's mask is then
        the rectangle of the noisy queries against every key
        (`block_stream_tiles`: 28 of 96 tiles at T 2,048, where the square
        one visits 38 of 144). The rows left out fed an output that neither
        the logits nor the values index: their cotangent was zero, so the
        loss and every parameter's gradient are what they were. A position
        costs the layers S + 1 - 1 / num_layers rows in the mean. The
        "routing" collection keeps [layers, B, (S + 1) T, k]: the last
        layer's clean positions state -1, no choice, as the commit pass's
        last layer does in the rollout's trace. A model of one layer takes
        the same path: its only layer is its last."""
        cd = self.compute_dtype
        L, S = self.block_len, self.denoise_steps
        B, T = tokens.shape
        if T > self.context_len or T % L:
            raise ValueError(
                f"a fragment of {T} positions is whole blocks of {L} within "
                f"the model's {self.context_len} positions")
        at = jnp.arange(T)
        starts = (reset > 0).at[:, 0].set(True)
        episode = jnp.cumsum(starts, axis=1)
        positions = at - jax.lax.cummax(jnp.where(starts, at, 0), axis=1)
        with jax.named_scope("policy/block_streams"):
            inputs = jnp.concatenate([tokens] + [
                jnp.where(steps < s, tokens, self.mask_id)
                for s in range(S)], axis=1)
            positions = jnp.tile(positions, (1, S + 1))
            episode = jnp.tile(episode, (1, S + 1))

        def block(lp, x, last=False):
            h, _ = self._attend_causal(
                lp, x, positions, episode, None, streams=S + 1,
                unread=T if last else 0)
            out, load, top_i = self._feed_forward(
                lp, h.reshape(-1, h.shape[-1]))
            top_i = top_i.reshape(B, -1, top_i.shape[-1])
            if last:
                top_i = jnp.concatenate([jnp.full(
                    (B, T) + top_i.shape[2:], -1, top_i.dtype), top_i], axis=1)
            return out.reshape(h.shape), load, top_i
        if self.num_layers > 1:
            block = jax.checkpoint(
                block, policy=jax.checkpoint_policies.save_only_these_names(
                    CAUSAL_KEPT), static_argnums=(2,))
        x = self.embed[inputs].astype(cd)
        loads, experts = [], []
        for i, layer in enumerate(self.layers):
            x, load, top_i = block(layer(), x, i == self.num_layers - 1)
            loads.append(load)
            experts.append(top_i)
        self._count(experts, loads, pairs=B * T * self.experts_per_token * (
            S + 1 - 1 / self.num_layers))
        with jax.named_scope("policy/block_streams"):
            # x: the S noisy streams alone.
            own = jnp.maximum(steps, 0) * T + at
            taken = jnp.take_along_axis(x, own[..., None], axis=1)
            first = x[:, :T:L]
        logits, _ = self._token_logits(taken)
        _, values = self._heads(first)
        return logits, values


def _refuse_unknown(cfg: dict, known, family: str) -> None:
    unknown = set(cfg) - set(known)
    if unknown:
        raise ValueError(
            f"custom_model_config keys {sorted(unknown)} are not {family}'s; "
            f"known: {sorted(known)}")


def _refuse_grouped_heads(cfg: dict, family: str, default_heads: int) -> None:
    kv = cfg.get("num_key_value_heads")
    if kv is not None and kv != cfg.get("num_attention_heads", default_heads):
        raise ValueError(
            "TokenDecoder has as many key/value heads as query heads "
            f"({family}'s layout); got num_key_value_heads={kv}")


def _refuse_other_values(cfg: dict, fixed: dict) -> None:
    for key, only in fixed.items():
        if key in cfg and cfg[key] != only:
            raise ValueError(
                f"custom_model_config {key}={cfg[key]!r}: TokenDecoder has "
                f"{only!r} alone")


def olmoe_from_config(num_outputs: int, cfg: dict, compute_dtype=None):
    """`TokenDecoder` from a `custom_model_config` that speaks OLMoE's
    published `config.json`'s own keys (unknown keys are refused)."""
    _refuse_unknown(cfg, set(OLMOE_CONFIG_KEYS) | {"num_key_value_heads"},
                    "OLMoE")
    _refuse_grouped_heads(cfg, "OLMoE", 16)
    fields = {OLMOE_CONFIG_KEYS[k]: v for k, v in cfg.items()
              if k in OLMOE_CONFIG_KEYS}
    if compute_dtype is not None:
        fields["compute_dtype"] = compute_dtype
    return TokenDecoder(num_outputs=num_outputs, **fields)


def glm4_moe_lite_from_config(num_outputs: int, cfg: dict,
                              compute_dtype=None):
    """`TokenDecoder` from a `custom_model_config` that speaks
    `glm4_moe_lite`'s published `config.json`'s own keys, and the two that
    state the chip's share of the experts (unknown keys are refused, and
    so is a published key whose value the decoder has no part for)."""
    known = (set(GLM4_MOE_LITE_CONFIG_KEYS) | set(GLM4_MOE_LITE_FIXED)
             | {"num_key_value_heads"})
    _refuse_unknown(cfg, known, "glm4_moe_lite")
    _refuse_grouped_heads(cfg, "glm4_moe_lite", 20)
    _refuse_other_values(cfg, GLM4_MOE_LITE_FIXED)
    fields = {GLM4_MOE_LITE_CONFIG_KEYS[k]: v for k, v in cfg.items()
              if k in GLM4_MOE_LITE_CONFIG_KEYS}
    fields["selection_bias"] = True  # `topk_method: noaux_tc`
    if compute_dtype is not None:
        fields["compute_dtype"] = compute_dtype
    return TokenDecoder(num_outputs=num_outputs, **fields)


def smallthinker_from_config(num_outputs: int, cfg: dict,
                             compute_dtype=None):
    """`TokenDecoder` from a `custom_model_config` that speaks
    `smallthinker`'s published `config.json`'s own keys (a key left out
    has SmallThinker-21BA3B's value), and the two that state the chip's
    share of the experts; unknown keys are refused, and so is a published
    key whose value the decoder has no part for. The family's parts:
    grouped key/value heads of their own width, no QK-norm; a layer
    windowed or full, rotary or position-free, by its entry in the two
    layouts (whose leading `num_hidden_layers` entries are read, so that
    a cut in depth keeps the leading layers' kinds); a softmax router
    that reads the attention's normalised input; ReGLU experts."""
    _refuse_unknown(
        cfg, set(SMALLTHINKER_CONFIG_KEYS) | set(SMALLTHINKER_FIXED),
        "smallthinker")
    _refuse_other_values(cfg, SMALLTHINKER_FIXED)
    fields = {SMALLTHINKER_CONFIG_KEYS[k]: v
              for k, v in {**SMALLTHINKER_PUBLISHED, **cfg}.items()
              if k in SMALLTHINKER_CONFIG_KEYS}
    for layout in ("window_layout", "rope_layout"):
        fields[layout] = tuple(bool(kind) for kind in fields[layout])
        if len(fields[layout]) < fields["num_layers"]:
            raise ValueError(
                f"the {layout.replace('_', ' ')} has {len(fields[layout])} "
                f"entries for {fields['num_layers']} layers")
    fields.update(qk_norm=False, router_before_attention=True,
                  hidden_act="relu")
    if compute_dtype is not None:
        fields["compute_dtype"] = compute_dtype
    return TokenDecoder(num_outputs=num_outputs, **fields)


def lfm2_moe_from_config(num_outputs: int, cfg: dict, compute_dtype=None):
    """`TokenDecoder` from a `custom_model_config` that speaks `lfm2_moe`'s
    published `config.json`'s own keys (a key left out has LFM2-8B-A1B's
    value), and the two that state the chip's share of the experts;
    unknown keys are refused, and so is a published key whose value the
    decoder has no part for. The family's parts: an operator a layer by
    `layer_types` (whose leading `num_hidden_layers` entries are read),
    the gated short convolution or grouped-head attention with QK-norm
    over each head; `num_dense_layers` leading dense layers; sigmoid
    scores with a selection bias, renormalised over their sum + 1e-6; the
    head tied to the embedding."""
    _refuse_unknown(cfg, set(LFM2_MOE_CONFIG_KEYS) | set(LFM2_MOE_FIXED),
                    "lfm2_moe")
    _refuse_other_values(cfg, LFM2_MOE_FIXED)
    fields = {LFM2_MOE_CONFIG_KEYS[k]: v
              for k, v in {**LFM2_MOE_PUBLISHED, **cfg}.items()
              if k in LFM2_MOE_CONFIG_KEYS}
    fields["layer_types"] = tuple(fields["layer_types"])
    if len(fields["layer_types"]) < fields["num_layers"]:
        raise ValueError(
            f"layer_types has {len(fields['layer_types'])} entries for "
            f"{fields['num_layers']} layers")
    # The sum of the chosen scores is never 0 in the source's division.
    fields.update(qk_norm="head", selection_bias=True, tie_embeddings=True,
                  topk_eps=1e-6)
    if compute_dtype is not None:
        fields["compute_dtype"] = compute_dtype
    return TokenDecoder(num_outputs=num_outputs, **fields)


def kimi_linear_from_config(num_outputs: int, cfg: dict, compute_dtype=None):
    """`TokenDecoder` from a `custom_model_config` that speaks
    `kimi_linear`'s published `config.json`'s own keys (a key left out has
    Kimi-Linear-48B-A3B's value), and the three that state the deployment
    (the chip's share of the experts; the positions in a chunk of the
    learner's scan); unknown keys are refused, and so is a published key
    whose value the decoder has no part for (`num_expert_group` > 1, a
    next-token module, a query latent, rotated latent attention). The
    family's parts: an operator a layer by `linear_attn_config`'s two
    lists of 1-indexed layers (those up to `num_hidden_layers` are read),
    Kimi Delta Attention or latent attention without a query latent and
    without positions; `first_k_dense_replace` leading dense layers;
    sigmoid scores with a selection bias in one group, renormalised over
    their sum + 1e-20 and scaled; one shared expert; an untied head."""
    known = (set(KIMI_LINEAR_CONFIG_KEYS) | set(KIMI_LINEAR_FIXED)
             | {"linear_attn_config", "num_key_value_heads", "head_dim"})
    _refuse_unknown(cfg, known, "kimi_linear")
    _refuse_grouped_heads(cfg, "kimi_linear", 32)
    _refuse_other_values(cfg, KIMI_LINEAR_FIXED)
    merged = {**KIMI_LINEAR_PUBLISHED, **cfg}
    fields = {KIMI_LINEAR_CONFIG_KEYS[k]: v for k, v in merged.items()
              if k in KIMI_LINEAR_CONFIG_KEYS}
    linear = {**KIMI_LINEAR_PUBLISHED["linear_attn_config"],
              **cfg.get("linear_attn_config", {})}
    _refuse_unknown(linear, KIMI_LINEAR_PUBLISHED["linear_attn_config"],
                    "kimi_linear's linear_attn_config")
    kinds = {**{i: "full_attention" for i in linear["full_attn_layers"]},
             **{i: "kda" for i in linear["kda_layers"]}}
    layers = range(1, fields["num_layers"] + 1)
    if set(linear["full_attn_layers"]) & set(linear["kda_layers"]) or any(
            i not in kinds for i in layers):
        raise ValueError(
            f"linear_attn_config names each of the {fields['num_layers']} "
            f"layers once: kda_layers {linear['kda_layers']}, "
            f"full_attn_layers {linear['full_attn_layers']}")
    layer_types = tuple(kinds[i] for i in layers)
    fields.update(
        layer_types=layer_types, rope_layout=(False,) * len(layer_types),
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        kda_taps=linear["short_conv_kernel_size"], selection_bias=True,
        topk_eps=1e-20)
    if compute_dtype is not None:
        fields["compute_dtype"] = compute_dtype
    return TokenDecoder(num_outputs=num_outputs, **fields)


def nemotron_h_from_config(num_outputs: int, cfg: dict, compute_dtype=None):
    """`TokenDecoder` from a `custom_model_config` that speaks `nemotron_h`'s
    published `config.json`'s own keys (a key left out has
    Nemotron-Labs-TwoTower-30B-A3B-Base's value), and the two that state
    the chip's share of the experts; unknown keys are refused, and so is a
    published key whose value the decoder has no part for (a bias on a
    projection, no bias on the convolution, expert groups, a window, a
    clamp on the time step, a gated feed-forward). The family's parts: ONE
    function a layer by the letters of `hybrid_override_pattern` (the
    leading `num_hidden_layers` are read): `M` Mamba-2, `E` un-gated
    squared-ReLU experts beside a shared one of a width of its own, `*`
    grouped-head attention without positions and without QK-norm (a dense
    feed-forward `-` is refused: the decoder has no un-gated dense one);
    sigmoid scores with a selection bias in one group, renormalised over
    their sum and scaled; an untied head. It is the config's one tower as
    an autoregressive policy: the second, denoising tower that the
    family's description speaks of has no key here."""
    known = (set(NEMOTRON_H_CONFIG_KEYS) | set(NEMOTRON_H_FIXED)
             | set(NEMOTRON_H_UNREAD) | {"hybrid_override_pattern"})
    _refuse_unknown(cfg, known, "nemotron_h")
    if "time_step_limit" in cfg:  # a tuple says what a list says
        cfg = dict(cfg, time_step_limit=list(cfg["time_step_limit"]))
    _refuse_other_values(cfg, NEMOTRON_H_FIXED)
    merged = {**NEMOTRON_H_PUBLISHED, **cfg}
    fields = {NEMOTRON_H_CONFIG_KEYS[k]: v for k, v in merged.items()
              if k in NEMOTRON_H_CONFIG_KEYS}
    pattern = merged["hybrid_override_pattern"]
    unknown = set(pattern) - set(NEMOTRON_H_LAYERS)
    if unknown or len(pattern) < fields["num_layers"]:
        raise ValueError(
            f"hybrid_override_pattern {pattern!r} names "
            f"{fields['num_layers']} layers by {sorted(NEMOTRON_H_LAYERS)}; "
            f"TokenDecoder has no layer {sorted(unknown)}")
    layer_types = tuple(NEMOTRON_H_LAYERS[c] for c in pattern)
    fields.update(
        layer_types=layer_types, rope_layout=(False,) * len(layer_types),
        one_function_layers=True, gated_feed_forward=False,
        hidden_act="relu2", qk_norm=False, selection_bias=True)
    if compute_dtype is not None:
        fields["compute_dtype"] = compute_dtype
    return TokenDecoder(num_outputs=num_outputs, **fields)


def sdar_moe_from_config(num_outputs: int, cfg: dict, compute_dtype=None):
    """`TokenDecoder` from a `custom_model_config` that speaks `sdar_moe`'s
    published `config.json`'s own keys (a key left out has
    SDAR-30B-A3B-Chat's value), the two that state the chip's share of the
    experts and the two that state the generation, which the config has no
    key for (`block_length`: the family's Chat models' 4; `denoise_steps`: 2,
    this repo's choice); unknown keys are refused, and so is a published
    key whose value the decoder has no part for. The family's parts (the
    Qwen3 mixture-of-experts body): grouped-head attention of a `head_dim`
    of its own with QK-norm over each head and RoPE, a softmax router over
    every expert renormalised over the chosen, SwiGLU experts, no shared
    expert, no dense layer, an untied head; generated and trained a BLOCK
    at a time (`TokenDecoder.block_step`, `block_causal`). The vocabulary's
    last id is the MASK id: the env draws from the ids below it, which is
    what `num_outputs` has to say."""
    known = (set(SDAR_MOE_CONFIG_KEYS) | set(SDAR_MOE_FIXED)
             | set(SDAR_MOE_UNREAD))
    _refuse_unknown(cfg, known, "sdar_moe")
    if "mlp_only_layers" in cfg:  # a tuple says what a list says
        cfg = dict(cfg, mlp_only_layers=list(cfg["mlp_only_layers"]))
    _refuse_other_values(cfg, SDAR_MOE_FIXED)
    fields = {SDAR_MOE_CONFIG_KEYS[k]: v
              for k, v in {**SDAR_MOE_PUBLISHED, **cfg}.items()
              if k in SDAR_MOE_CONFIG_KEYS}
    if num_outputs != fields["vocab_size"] - 1:
        raise ValueError(
            f"sdar_moe's vocabulary of {fields['vocab_size']} ids ends in "
            f"the MASK id: the env draws from {fields['vocab_size'] - 1}, "
            f"not {num_outputs}")
    fields.update(qk_norm="head")
    if compute_dtype is not None:
        fields["compute_dtype"] = compute_dtype
    return TokenDecoder(num_outputs=fields["vocab_size"], **fields)


def qwen3_next_from_config(num_outputs: int, cfg: dict, compute_dtype=None):
    """`TokenDecoder` from a `custom_model_config` that speaks `qwen3_next`'s
    published `config.json`'s own keys (a key left out has
    Qwen3-Next-80B-A3B-Instruct's value), and the three that state the
    deployment (the chip's share of the experts; the positions in a chunk
    of the learner's scan); unknown keys are refused, and so is a published
    key whose value the decoder has no part for (a dense layer, a window, a
    bias, a scaled rotation). The family's parts: an operator a layer by
    `full_attention_interval` (layer i is attention where (i + 1) is a
    multiple of it, else Gated DeltaNet: the delta rule under one decay a
    value head, a key head serving two value heads); grouped-head attention
    of a `head_dim` of its own with QK-norm over each head, a rotation of
    the leading `partial_rotary_factor` of a head, and a sigmoid gate on
    its output that W_q makes beside the query; every norm zero-centred; a
    softmax router over every expert renormalised over the chosen, SwiGLU
    experts beside ONE shared expert of a width of its own behind a sigmoid
    gate; no dense layer; an untied head. The family's next-token module
    has no key in the config and is not built."""
    known = (set(QWEN3_NEXT_CONFIG_KEYS) | set(QWEN3_NEXT_FIXED)
             | set(QWEN3_NEXT_UNREAD) | {"full_attention_interval"})
    _refuse_unknown(cfg, known, "qwen3_next")
    if "mlp_only_layers" in cfg:  # a tuple says what a list says
        cfg = dict(cfg, mlp_only_layers=list(cfg["mlp_only_layers"]))
    _refuse_other_values(cfg, QWEN3_NEXT_FIXED)
    merged = {**QWEN3_NEXT_PUBLISHED, **cfg}
    fields = {QWEN3_NEXT_CONFIG_KEYS[k]: v for k, v in merged.items()
              if k in QWEN3_NEXT_CONFIG_KEYS}
    interval = merged["full_attention_interval"]
    if not isinstance(interval, int) or interval < 1:
        raise ValueError(
            f"full_attention_interval {interval!r}: a whole number of "
            "layers, the last of which is the attention")
    fields.update(
        layer_types=tuple(
            "gdn" if (i + 1) % interval else "full_attention"
            for i in range(fields["num_layers"])),
        qk_norm="head", attention_gate=True, zero_centred_norms=True,
        shared_experts=1, shared_expert_gate=True)
    if compute_dtype is not None:
        fields["compute_dtype"] = compute_dtype
    return TokenDecoder(num_outputs=num_outputs, **fields)


def _laguna_rotation(kind: str, rope: dict) -> tuple:
    """A `rotations` entry from `rope_parameters[kind]`."""
    known = LAGUNA_ROPE_KEYS.get(rope.get("rope_type", "default"))
    if known is None:
        raise ValueError(
            f"rope_parameters[{kind!r}] rope_type {rope['rope_type']!r}: "
            f"TokenDecoder has {sorted(LAGUNA_ROPE_KEYS)}")
    _refuse_unknown(rope, known, f"laguna's rope_parameters[{kind!r}]")
    scaling = ()
    if rope.get("rope_type") == "yarn":
        factor = rope["factor"]
        # The family's default where the file gives none: 0.1 ln F + 1.
        scaling = (factor, rope["original_max_position_embeddings"],
                   rope.get("beta_fast", 32), rope.get("beta_slow", 1),
                   rope.get("attention_factor")
                   or 0.1 * float(np.log(factor)) + 1.0)
    return (rope["rope_theta"], rope.get("partial_rotary_factor", 1.0),
            scaling)


def laguna_from_config(num_outputs: int, cfg: dict, compute_dtype=None):
    """`TokenDecoder` from a `custom_model_config` that speaks `laguna`'s
    published `config.json`'s own keys (a key left out has Laguna-XS.2's
    value), and the two that state the chip's share of the experts; unknown
    keys are refused, and so is a published key whose value the decoder has
    no part for (a bias, a tied head, no gate, the router's weight on an
    expert's input, a rotation of another type, a dense layer after a
    sparse one). The family's parts: an attention layer's GEOMETRY by its
    kind in `layer_types` (the leading `num_hidden_layers` entries are
    read): "full_attention" over the whole episode or "sliding_attention"
    within `sliding_window`, `num_attention_heads_per_layer[i]` query heads
    over the same key/value heads, rotated as `rope_parameters` says of its
    kind (the default rotation, or YaRN, over that kind's share of a head);
    one sigmoid gate a head on the attention's output (`gating`; W_g
    [hidden, heads]: the shape that makes the published sizes the published
    count); no QK-norm; the leading "dense" layers of `mlp_layer_types` a
    dense SwiGLU, the others sigmoid-routed experts WITHOUT a selection
    bias, the chosen scores over their sum times
    `moe_routed_scaling_factor`, beside one shared expert of a width of its
    own without a gate; an untied head."""
    known = (set(LAGUNA_CONFIG_KEYS) | set(LAGUNA_FIXED) | {
        "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
        "rope_parameters", "partial_rotary_factor"})
    _refuse_unknown(cfg, known, "laguna")
    _refuse_other_values(cfg, LAGUNA_FIXED)
    merged = {**LAGUNA_PUBLISHED, **cfg}
    fields = {LAGUNA_CONFIG_KEYS[k]: v for k, v in merged.items()
              if k in LAGUNA_CONFIG_KEYS}
    layers = fields["num_layers"]
    kinds = list(merged["layer_types"])
    heads = list(merged["num_attention_heads_per_layer"])
    feeds = list(merged["mlp_layer_types"])
    for name, listed, allowed in (
            ("layer_types", kinds, ("full_attention", "sliding_attention")),
            ("num_attention_heads_per_layer", heads, None),
            ("mlp_layer_types", feeds, ("dense", "sparse"))):
        if len(listed) < layers or (allowed and set(listed) - set(allowed)):
            raise ValueError(
                f"{name} names each of the {layers} layers"
                + (f" by {allowed}" if allowed else "") + f": {listed}")
    dense = feeds[:layers].index("sparse") if "sparse" in feeds[:layers] \
        else layers
    if "dense" in feeds[dense:layers]:
        raise ValueError(
            "TokenDecoder's dense layers are the leading ones; "
            f"mlp_layer_types {feeds[:layers]}")
    rope = dict(merged["rope_parameters"])
    rope.pop("original_max_position_embeddings", None)  # yarn's, said twice
    _refuse_unknown(rope, ("full_attention", "sliding_attention"),
                    "laguna's rope_parameters")
    rotation = {kind: _laguna_rotation(kind, rope[kind])
                for kind in set(kinds[:layers])}
    if merged["partial_rotary_factor"] != rope["full_attention"].get(
            "partial_rotary_factor", 1.0):
        raise ValueError(
            "partial_rotary_factor is the full layers' share of a head, "
            "which rope_parameters states too; they differ")
    fields.update(
        window_layout=tuple(k == "sliding_attention" for k in kinds),
        heads_layout=tuple(heads),
        rotations=tuple(rotation[k] for k in kinds[:layers]),
        dense_layers=dense, qk_norm=False, attention_gate="head",
        shared_experts=1, sigmoid_router=True, norm_topk_prob=True)
    if compute_dtype is not None:
        fields["compute_dtype"] = compute_dtype
    return TokenDecoder(num_outputs=num_outputs, **fields)
