"""The `qwen3_next` token policy at a tiny size on the CPU: the family's row,
the checks it shares with the other families (`tests/token_families.py`: the
model against the plain reference `benchmark/lib/reference_qwen3_next.py`,
whose Gated DeltaNet is the recurrence itself, one position at a time, in its
causal form and decoded through three kinds of state, a Gated DeltaNet layer's
matrix a value head and its convolution's last inputs and the attention
layer's grouped cache; a decode that continues a causal pass; resets inside a
chunk, at a chunk's edge, and an episode one token long against separate
passes; the model's gradient against the recurrence's; the partial rotation,
the two gates and the zero-centred norms, each named wrong mathematics refused
by the cell's limits; the grouped form of the expert product; the cell's
program from its shapes; the builder's refusals; the tuned example) and what
is its own: the delta rule's chunked scan under ONE decay a head, a key head
serving two value heads, against the literal loop, forward and gradients, at
several chunk lengths with episodes that begin inside a chunk; the
scalar-decay path against the per-channel one (`kda_chunked`, `kda_step`) fed
the same decay on every channel, which ties the two models' shared code; a
matrix state kept in bfloat16 refused; the sixteen shares of the experts
against the uncut layer. The loss and the loop:
`tests/test_qwen3_next_update.py`.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from token_families import (  # noqa: F401: pytest collects what is named
    BENCH, Family, build, configuration, count, noise, read_by, shapes_of,
    share_of,
    test_a_bfloat16_matrix_state_is_refused_by_the_decode_s_limit,
    test_a_causal_pass_over_the_landed_rows_is_the_batched_pass,
    test_a_decode_continues_a_causal_pass_from_the_state_it_hands_over,
    test_causal_pass_matches_reference,
    test_custom_model_config_without_a_part_is_refused,
    test_decode_through_every_kind_of_state_matches_reference
    as test_decode_through_three_kinds_of_state_matches_reference,
    test_limits_refuse_wrong_mathematics,
    test_resets_inside_a_chunk_at_its_edge_and_an_episode_one_token_long,
    test_the_cell_s_program_is_known_from_its_static_shapes,
    test_the_model_s_gradient_is_the_reference_s,
    test_the_tuned_example_is_the_benchmark_s_cell)

from lib import reference_qwen3_next as reference

from ray_tpu.models import catalog, transformer
from ray_tpu.models.transformer import dropless_experts

# The cell's four layers, one period G G G A: 2 key heads serving 4 value
# heads of 16, chunks of 8 positions solved in blocks of 4; 4 query heads
# over 2 key/value heads of 16, a quarter of a head rotated; 4 of 16 experts
# held, 3 a token, beside the gated shared one.
S, B, CHUNK, SUB = 24, 3, 8, 4
NET = dict(vocab_size=96, hidden_size=64, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, num_hidden_layers=4,
           full_attention_interval=4, partial_rotary_factor=0.25,
           linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=16, linear_value_head_dim=16,
           linear_conv_kernel_dim=4, num_experts=16, experts_held=4,
           first_expert_held=0, num_experts_per_tok=3,
           moe_intermediate_size=32, shared_expert_intermediate_size=32,
           norm_topk_prob=True, max_position_embeddings=S,
           rope_theta=10000000, rms_norm_eps=1e-6, gdn_chunk=CHUNK)
GDN_LAYERS = ("layer_0", "layer_1", "layer_2")
# (value heads, d_k, d_v) of a matrix state; (taps - 1, 2 K + V) of the
# convolution's inputs; (positions, groups x d) of a cache stored flat.
MATRIX, TAILS, CACHE = (4, 16, 16), (3, 2 * 32 + 64), (S, 2 * 16)
# A reset inside a chunk, an episode one token long after it, and a reset
# at a chunk's edge.
RESET = jnp.zeros((B, S)).at[:, 11].set(1.0).at[:, 12].set(1.0).at[
    :, 16].set(1.0)
EPISODES = ((0, 11), (11, 12), (12, 16), (16, S))
# The cell's parameters at the published widths, by hand.
GDN = 2048 * 12288 + 2048 * 64 + 8192 * 4 + 32 + 32 + 128 + 4096 * 2048
GATED = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
EXPERTS = 2048 * 512 + 32 * 3 * 2048 * 512 + 3 * 2048 * 512 + 2048
PARAMETERS = (2 * 18992 * 2048 + 3 * GDN + GATED + 4 * (EXPERTS + 4096)
              + 2048 + 2048 + 1)


def seeded(path, a):
    """The norms' weights are seeded too (zero-centred ones stand at 0 at
    initialisation, the output norm at 1), so that a norm's place and its
    centring show, and the decays are slowed."""
    name = path[-1].key
    if name == "gdn_a_log":
        # A = exp(A_log) in (0, 0.8) where the family draws (0, 16): at
        # its draw nearly every head forgets its state within a
        # position, and what these tests are for is a state that lasts.
        return a - 3.0
    if not name.endswith("norm") and name != "shared_scale":
        return a
    return a + 0.5 * noise(path, a)


def attention_layer_shown(variables):
    """The attention layer's softmax far enough from uniform (its queries
    and keys are normalised a head, so it is their norms' weights that
    sharpen it: 1 + w about 4), and its output large enough beside the
    three layers before it, that its rotation and its gate show in the
    logits; the decays slow enough that a state outlives a few
    positions."""
    params = dict(variables["params"])
    params["layer_3"] = dict(params["layer_3"],
                             q_norm=params["layer_3"]["q_norm"] + 3.0,
                             k_norm=params["layer_3"]["k_norm"] + 3.0,
                             wo=6.0 * params["layer_3"]["wo"])
    for name in GDN_LAYERS:
        params[name] = dict(
            params[name], gdn_a_log=params[name]["gdn_a_log"] - 3.0,
            gdn_out=4.0 * params[name]["gdn_out"])
    return dict(variables, params=params)


def long_sums(variables):
    """Decays slow enough that the sums into a state are long."""
    params = dict(variables["params"])
    for name in GDN_LAYERS:
        params[name] = dict(
            params[name], gdn_a_log=params[name]["gdn_a_log"] - 5.0,
            gdn_out=3.0 * params[name]["gdn_out"])
    return dict(variables, params=params)


FAMILY = Family(
    name="qwen3_next", net=NET, reference=reference, B=B, S=S,
    # What a pass hands a decode: the one layer's two caches, three
    # layers' convolution inputs, three layers' matrices, a key a kind.
    state_kinds=("kv", "conv", "gdn"), matrix_kind="gdn",
    state_shapes=lambda positions: (
        [(positions, CACHE[1])] * 2, [TAILS] * 3, [MATRIX] * 3),
    state_layers={"kv": [0, 0, 0, 2], "conv": [1, 1, 1, 0],
                  "gdn": [1, 1, 1, 0]},
    expert_layers=4, experts_per_token=3,  # every layer
    seeded=seeded, shown=attention_layer_shown, reset=RESET,
    episodes=EPISODES,
    # Blocks in bfloat16, at these widths (heads of 16 values under a norm
    # of their own): the reference itself, its blocks rounded to bfloat16,
    # stands 5-17 % from its float32 self. No further off than three times
    # that (two roundings of the same sums differ that much between seeds
    # here: a head's normalised output turns on the sign of q . k, which a
    # rounding may take either way), and the routing within a fifth (3 of
    # 16 experts after four bfloat16 blocks).
    bfloat16=(3, 0.6, 0.2),
    # A fragment of whole chunks and one that ends inside a chunk.
    other_lengths=(S - 3,), handed_atol=2e-5,
    # Prefixes shorter than the taps, at a chunk's edge, inside a chunk.
    prefixes=(2, 8, 13),
    long_lived=long_sums,
    carried_error=lambda wrong, kept: (
        max(wrong["errors"].values()) > max(
            2e-3, 100 * max(kept["errors"].values()))),
    # The attention layer alone reads a cache: off a TPU, all of it.
    decode_counters={"decode_cache_read_share": 1.0},
    wrong_updates={
        "taps_reversed_in_the_gradient": dict(mutate="taps_reversed"),
        "a_decay_a_key_head": dict(mutate="decay_a_key_head"),
        "beta_out_of_the_subtraction": dict(
            mutate="beta_out_of_subtraction"),
        "the_whole_head_rotated": dict(mutate="rope_whole_head"),
        "no_attention_gate": dict(mutate="no_attention_gate"),
        "norms_not_zero_centred": dict(mutate="norms_not_zero_centred"),
        "vf_coeff_doubled": dict(cfg={"vf_loss_coeff": 1.0},
                                 by="loss_error"),
        "no_clip": dict(cfg={"grad_clip": None}, by="update_error"),
        "ten_times_the_lr": dict(cfg={"lr": 6e-3}, by="update_error")},
    refused=(
        ({"n_routed_experts": 8}, "not qwen3_next's"),
        ({"layer_types": ["linear_attention"]}, "not qwen3_next's"),
        ({"num_nextn_predict_layers": 1}, "not qwen3_next's"),
        ({"mlp_only_layers": [0]}, "mlp_only_layers"),
        ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
        ({"use_sliding_window": True}, "use_sliding_window"),
        ({"attention_bias": True}, "attention_bias"),
        ({"hidden_act": "gelu"}, "hidden_act"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
        ({"full_attention_interval": 0}, "full_attention_interval"),
        ({"linear_num_value_heads": 3}, "value heads"),
        ({"num_key_value_heads": 3}, "key/value heads"),
        ({"experts_held": 6, "first_expert_held": 12}, "not among")),
    example="qwen3-next-token-impala.yaml",
    cell="qwen3_next_token_anakin_4k",
    config="impala_qwen3_next_80b_a3b",
    # At the published widths: 625.7 M parameters; ONE layer's caches of
    # 4,096 positions, 2,048 bytes a position; three layers' convolution
    # inputs, 147,456 bytes a sequence, and three layers' matrices,
    # 6,291,456, whatever its length; heads of 256 in groups of 8 take the
    # decode kernel and the fused causal form; the 32 held experts of 512
    # take the grouped-matmul kernels.
    program=dict(
        rows=32, fragment=4096, minibatch=(8192,),
        on_tpu={
            # Under two rows a held expert: a rollout's step reads the
            # chosen ones' matrices alone, and counts their share itself.
            "decode_rows_per_expert": 0.625, "decode_experts_batched": 0.0,
            "decode_experts_sparse": 1.0,
            "decode_cache_block": 128, "decode_attention_kernel": 1.0,
            "causal_attention_fused": 1.0, "experts_grouped_kernel": 1.0,
            # The one gated layer: a quarter of a head of 256 rotated.
            "rotation_fused_layers": 1.0,
            "kv_cache_bytes_per_token": 2048.0, "kv_groups": 8,
            "conv_layers": 3, "conv_state_bytes_per_row": 147456,
            "gdn_layers": 3, "gdn_state_bytes_per_row": 6291456,
            "gdn_chunk": 64, "state_step_kernel": 1.0},
        # Off a TPU the cache is read whole, by XLA's products, and every
        # held expert's matrices.
        off_tpu={
            "decode_experts_batched": 1.0, "decode_experts_sparse": 0.0,
            "decode_experts_read_share": 1.0,
            "causal_attention_fused": 0.0, "decode_cache_block": 4096,
            "decode_attention_kernel": 0.0, "state_step_kernel": 0.0,
            "rotation_fused_layers": 0.0, "experts_grouped_kernel": 0.0},
        state={"kv": [((32, 4096, 512), "bfloat16")] * 2,
               "conv": [((32, 3, 8192), "bfloat16")] * 3,
               "gdn": [((32, 32, 128, 128), "float32")] * 3},
        parameters=PARAMETERS))


@pytest.fixture(scope="module", autouse=True)
def solve_in_blocks_of_four():
    """Chunks of 8 whose triangular systems are solved in blocks of 4, so
    that the solve has block rows; ahead of the module's first trace."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(transformer, "KDA_SUB_BLOCK", SUB)
        yield


def test_a_layer_s_parameters_are_the_delta_rule_s_or_the_gated_attention_s():
    """What the model's gradient is taken with respect to."""
    _, variables, _ = build(FAMILY, "f32")
    assert {"gdn_qkvz", "gdn_ba", "gdn_conv", "gdn_a_log", "gdn_dt_bias",
            "gdn_o_norm", "gdn_out"} < set(variables["params"]["layer_0"])
    assert {"wq", "q_norm", "shared_scale"} < set(
        variables["params"]["layer_3"])
    assert set(variables) == {"params"}


# -- the delta rule under one decay a head -----------------------------
def fragment_of_episodes(seed=43, T=37, heads=4, key_heads=2, d=8, rows=2):
    """Seeded operands of `kda_chunked` under one decay a head, `key_heads`
    serving `heads` value heads, with episodes that begin inside a chunk of
    8 (5, 6, 20, 34), at a chunk's edge (16), one position long (5), and a
    tail that is no whole chunk (37 = 4 x 8 + 5). The decays reach e^-40 a
    position: a chunk loses far more than float32 holds as a ratio."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = (jax.random.normal(key, (rows, T, key_heads, d))
            for key in keys[:2])
    q, k = (a / jnp.linalg.norm(a, axis=-1, keepdims=True) for a in (q, k))
    v = jax.random.normal(keys[2], (rows, T, heads, d))
    g = -40.0 * jax.random.uniform(keys[3], (rows, T, heads)) ** 3
    beta = jax.random.uniform(keys[4], (rows, T, heads))
    starts = np.zeros((rows, T), bool)
    starts[0, [5, 6, 20]] = True
    starts[1, [16, 34]] = True
    starts[:, 0] = True
    return (q, k, v, g, beta), starts


def literal_loop(q, k, v, g, beta, starts):
    """The recurrence as ISSUE 52 writes it, a Python loop over t, a key
    head's vectors repeated for the value heads it serves:

        S' = exp(g_t) S;  u = beta_t (v_t - S'^T k_t);  S = S' + k_t u^T
        o_t = S^T q_t"""
    shared = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(a, shared, axis=2) for a in (q, k))
    S = jnp.zeros(v.shape[:1] + v.shape[2:3] + (k.shape[-1], v.shape[-1]))
    out = []
    for t in range(q.shape[1]):
        S = jnp.where(jnp.asarray(starts)[:, t, None, None, None], 0.0, S)
        S = jnp.exp(g[:, t])[..., None, None] * S
        u = beta[:, t, :, None] * (
            v[:, t] - jnp.einsum("bhkv,bhk->bhv", S, k[:, t]))
        S = S + k[:, t][..., None] * u[..., None, :]
        out.append(jnp.einsum("bhkv,bhk->bhv", S, q[:, t]))
    return jnp.stack(out, axis=1), S


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_the_chunked_scan_is_the_literal_recurrence(chunk):
    """`kda_chunked` under one decay a head against the literal loop:
    chunks of one solve block and of several, a fragment that ends inside a
    chunk, episodes that begin anywhere; float32 to 1e-5 (decays of e^-40 a
    position: a difference of cumulative sums anywhere would read 1)."""
    operands, starts = fragment_of_episodes(seed=3)
    episode = jnp.cumsum(jnp.asarray(starts), axis=1)
    want, want_state = literal_loop(*operands, starts)
    got, state = transformer.kda_chunked(*operands, episode, chunk)
    assert got.shape == want.shape and state.shape == want_state.shape
    assert reference.relative_error(got, want) < 1e-5
    assert reference.relative_error(state, want_state) < 1e-5


@pytest.fixture(scope="module")
def by_the_literal_loop():
    operands, starts = fragment_of_episodes()
    return read_by(lambda *operands: literal_loop(*operands, starts),
                   operands)


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_the_chunked_scan_s_gradients_are_the_literal_recurrence_s(
        chunk, by_the_literal_loop):
    """Outputs, final state and gradients by q, k (of the KEY heads: a
    key's gradient is the sum over the value heads it serves), v, g (one a
    head) and beta: resets inside a chunk, at a chunk's edge, and a tail
    that is not a whole chunk."""
    operands, starts = fragment_of_episodes()
    episode = jnp.cumsum(jnp.asarray(starts), axis=1)
    got = read_by(
        lambda *operands: transformer.kda_chunked(*operands, episode, chunk),
        operands)
    names = ("o", "S", "dq", "dk", "dv", "dg", "dbeta")
    for name, mine, want, operand in zip(
            names, got, by_the_literal_loop, (None, None) + operands):
        if operand is not None:
            assert mine.shape == operand.shape, name
        assert reference.relative_error(mine, want) < 2e-5, (name, chunk)


@pytest.mark.parametrize("chunk", [4, 8])
def test_one_decay_a_head_is_the_per_channel_path_fed_it_on_every_channel(
        chunk):
    """What ties the two models' shared code: `kda_chunked` and `kda_step`
    under g [.., heads] against the SAME functions under g broadcast over a
    head's channels and the keys repeated for the value heads, which is the
    fifth configuration's path: outputs, final states and the gradients by
    v, beta and the decay (summed over a head's channels on the per-channel
    side)."""
    (q, k, v, g, beta), starts = fragment_of_episodes(seed=11)
    episode = jnp.cumsum(jnp.asarray(starts), axis=1)
    shared = v.shape[2] // k.shape[2]

    def scalar_path(q, k, v, g, beta):
        return transformer.kda_chunked(q, k, v, g, beta, episode, chunk)

    def channel_path(q, k, v, g, beta):
        q, k = (jnp.repeat(a, shared, axis=2) for a in (q, k))
        g = jnp.broadcast_to(g[..., None], g.shape + k.shape[-1:])
        return transformer.kda_chunked(q, k, v, g, beta, episode, chunk)
    got = read_by(scalar_path, (q, k, v, g, beta))
    want = read_by(channel_path, (q, k, v, g, beta))
    for name, mine, theirs in zip(
            ("o", "S", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert reference.relative_error(mine, theirs) < 2e-5, name
    # A position: bit for bit (exp(g) times a row is the same product).
    S = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 8, 8))
    at = 9
    vectors = (jnp.repeat(q[:, at], shared, axis=1),
               jnp.repeat(k[:, at], shared, axis=1), v[:, at])
    one = transformer.kda_step(S, *vectors, g[:, at], beta[:, at])
    wide = transformer.kda_step(
        S, *vectors, jnp.broadcast_to(g[:, at, :, None], (2, 4, 8)),
        beta[:, at])
    for a, b in zip(one, wide):
        np.testing.assert_array_equal(a, b)


def test_the_scalar_chunk_forms_no_decay_a_channel():
    """Under one decay a head no array of the lowered scan is [.., C, C, d]
    (the per-channel path's sub-block products) and no exponential is taken
    of anything wider than [.., heads, C, C]: what the per-channel chunk
    pays for and the scalar one does not."""
    (q, k, v, g, beta), starts = fragment_of_episodes()
    episode = jnp.cumsum(jnp.asarray(starts), axis=1)

    def exps(*operands):
        jaxpr = jax.make_jaxpr(lambda *a: transformer.kda_chunked(
            *a, episode, 8))(*operands)
        found = []

        def walk(jaxpr):
            for e in jaxpr.eqns:
                if e.primitive.name == "exp":
                    found.append(e.outvars[0].aval.shape)
                for sub in jax.core.jaxprs_in_params(e.params):
                    walk(sub)
        walk(jaxpr.jaxpr)
        return found
    rows, d = q.shape[0], q.shape[-1]
    scalar = exps(q, k, v, g, beta)
    # [B, heads, C, C] the pairs', [B, heads, C] and [B, heads, 1] the ends'.
    assert scalar and all(len(shape) <= 4 for shape in scalar), scalar
    assert max(int(np.prod(shape)) for shape in scalar) == rows * 4 * 8 * 8
    wide = exps(jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), v,
                jnp.broadcast_to(g[..., None], g.shape + (d,)), beta)
    assert max(int(np.prod(shape)) for shape in wide) > rows * 4 * 8 * 8


# -- the expert layer that holds a share ---------------------------------
def test_the_16_shares_add_up_to_the_uncut_layer():
    """Sixteen shares of 4 of 64 experts: their parts, with the gated
    shared expert that every chip computes counted once, add up to what the
    uncut reference gives for the whole layer (the reference's shares, and
    the system's in both forms of its product)."""
    rng = np.random.default_rng(0)
    H, W, E, k, held = 64, 32, 64, 5, 4
    lp = jax.tree.map(jnp.asarray, {
        "router": rng.normal(size=(H, E)).astype(np.float32) / 4,
        "w_gate": rng.normal(size=(E, H, W)).astype(np.float32) / 8,
        "w_up": rng.normal(size=(E, H, W)).astype(np.float32) / 8,
        "w_down": rng.normal(size=(E, W, H)).astype(np.float32) / 6,
        "shared_gate": rng.normal(size=(H, W)).astype(np.float32) / 8,
        "shared_up": rng.normal(size=(H, W)).astype(np.float32) / 8,
        "shared_down": rng.normal(size=(W, H)).astype(np.float32) / 6,
        "shared_scale": rng.normal(size=(H, 1)).astype(np.float32) / 4})
    h = jnp.asarray(rng.normal(size=(2, 12, H)), jnp.float32)
    m = transformer.rms_norm(h, jnp.ones(H), 1e-6, jnp.float32)
    net = dict(NET, num_experts=E, num_experts_per_tok=k)

    @functools.partial(jax.jit, static_argnums=(1,))
    def layer(first, size):
        share = dict(net, experts_held=size, first_expert_held=first)
        with jax.default_matmul_precision("highest"):
            return reference._moe(share_of(lp, first, size), h, m, share,
                                  lambda a: a, None, None)
    whole, chosen, _ = layer(0, E)
    with jax.default_matmul_precision("highest"):
        shared = jax.nn.sigmoid(m @ lp["shared_scale"]) * reference._swiglu(
            m, lp["shared_gate"], lp["shared_up"], lp["shared_down"],
            lambda a: a)
    shares = [layer(first, held)[0] - h for first in range(0, E, held)]
    assert len(shares) == 16
    # Every chip's part holds the gated shared expert: counted once.
    parts = sum(s - shared for s in shares) + shared
    assert reference.relative_error(parts, whole - h) < 1e-5

    # The system's shares of the same routing, in the form each shape
    # takes (24 rows batched, 64 times as many grouped).
    rows = m.reshape(-1, H)
    top_p, top_i = transformer.route(rows, lp["router"], k, True)
    assert np.array_equal(np.sort(top_i, -1),
                          np.sort(chosen.reshape(-1, k), -1))
    for reps in (1, 64):
        n, p, i = (jnp.tile(a, (reps, 1)) for a in (rows, top_p, top_i))
        routed, landed = jnp.zeros_like(n), 0
        for first in range(0, E, held):
            s = share_of(lp, first, held)
            part, sizes, _ = dropless_experts(
                n, p, i, s["w_gate"], s["w_up"], s["w_down"], first, E)
            routed, landed = routed + part, landed + int(jnp.sum(sizes))
        assert landed == n.shape[0] * k
        assert reference.relative_error(
            routed[:rows.shape[0]],
            (whole - h - shared).reshape(-1, H)) < 1e-4
    assert transformer.experts_batched(rows.shape[0], k, E)
    assert not transformer.experts_batched(64 * rows.shape[0], k, E)

# -- what the program is, from its static shapes --------------------------
def test_the_published_cut_s_parameters_are_the_hand_count_s():
    """A layer is its Gated DeltaNet or its gated attention, and its
    experts; the learner's ladder and the grouped-matmul's tiles."""
    network = configuration(FAMILY)[3]
    model = transformer.qwen3_next_from_config(
        network["vocab_size"], network)
    assert transformer.causal_fused(4096, 256, 256)
    assert transformer.grouped_fused(4096, 2, 16, 256)
    # The learner's ladder: 81,920 pairs, 5,120 expected here.
    assert transformer.dispatch_rows(8192, 10, 32, 512) == (
        6400, 10240, 20480, 81920)
    assert transformer.grouped_tiles(6400, 2048, 512) == (
        (256, 2048, 512), (256, 512, 1024), (256, 1024, 512))
    variables = shapes_of(model)
    attention = variables["params"]["layer_3"]
    assert attention["wq"].shape == (2048, 16 * 512)
    assert attention["q_norm"].shape == attention["k_norm"].shape == (256,)
    assert (GDN, GATED, EXPERTS) == (33_718_464, 27_263_488, 104_859_648)
    assert count(variables["params"]["layer_0"]) == GDN + EXPERTS + 4096
    assert count(attention) == GATED + EXPERTS + 4096
    assert count(variables["params"]) == PARAMETERS == 625_669_185


def test_the_counters_count_each_kind_of_state_from_its_own_layers():
    """The matrix states stand under their own key and are counted from
    their own leaves; the accepted delta-rule configuration reads what it
    read, and has no such key."""
    model, _, _ = build(FAMILY, "bf16")
    counted = model.static_counters(4, S, "cpu")
    assert counted["kv_cache_bytes_per_token"] == 2 * 2 * 16 * 2
    assert (counted["conv_layers"], counted["conv_state_bytes_per_row"]) == (
        3, 3 * 3 * 128 * 2)
    assert (counted["gdn_layers"], counted["gdn_state_bytes_per_row"],
            counted["gdn_chunk"]) == (3, 3 * 4 * 16 * 16 * 4, CHUNK)
    assert "kda_layers" not in counted and "ssm_layers" not in counted
    with open(os.path.join(
            BENCH, "configs", "impala_kimi_linear_48b_a3b.json")) as f:
        network = json.load(f)["network"]
    network.pop("param_count")
    other = catalog.get_model(None, network["vocab_size"], {
        "custom_model": "kimi_linear", "custom_model_config": network})
    accepted = other.static_counters(32, 4096, "tpu")
    assert "gdn_layers" not in accepted
    assert accepted["kda_state_bytes_per_row"] == 8388608
    assert accepted["state_step_kernel"] == 1.0
    assert "gdn" not in jax.eval_shape(lambda: other.initial_state(1))


def test_keys_left_out_have_the_published_model_s_values():
    """An empty description is Qwen3-Next-80B-A3B itself: 48 layers, 36 of
    them Gated DeltaNet in the period G G G A, 80 B parameters of which a
    token meets 3 B."""
    model = transformer.qwen3_next_from_config(151936, {})
    kinds = [model.layer_kind(i) for i in range(48)]
    assert kinds.count("gdn") == 36 and model.attention_layers == tuple(
        range(3, 48, 4))
    assert all(kind[:2] == (0, True) for kind in kinds if kind != "gdn")
    assert (model.num_heads, model.kv_heads, model.head_width,
            model.partial_rotary_factor, model.gdn_key_heads,
            model.gdn_value_heads, model.gdn_key_dim, model.gdn_value_dim,
            model.gdn_taps, model.gdn_chunk, model.num_experts, model.held,
            model.experts_per_token, model.expert_width, model.shared_width,
            model.rope_theta, model.rms_eps, model.dense_layers) == (
                16, 2, 256, 0.25, 16, 32, 128, 128, 4, 64, 512, 512, 10,
                512, 512, 10000000, 1e-6, 0)
    assert (model.qk_norm, model.attention_gate, model.zero_centred_norms,
            model.shared_expert_gate, model.norm_topk_prob,
            model.selection_bias) == ("head", True, True, True, True, False)
    params = count(shapes_of(model)["params"])
    assert 79e9 < params < 81e9
    per_token = params - 48 * (512 - 10) * 3 * 2048 * 512
    assert 2.9e9 < per_token < 4.0e9


def test_the_cell_s_traffic_is_its_sibling_s_and_its_file_its_source_s():
    """The cell's traffic is the fifth cell's letter for letter (the two
    delta-rule models stand under one load), and the configuration's file
    holds every published number of its source (the catalog's row) but the
    ones it lists as reduced."""
    _, cell, config, network = configuration(FAMILY)
    with open(os.path.join(
            BENCH, "workloads", "kimi_linear_token_anakin_4k.json")) as f:
        sibling = json.load(f)
    traffic = dict(sibling["trainer_config"], env_config=dict(
        sibling["trainer_config"]["env_config"], vocab_size=18992))
    assert cell["trainer_config"] == traffic
    assert (cell["warmup"], cell["trace_slice_s"], cell["check"]) == (
        sibling["warmup"], sibling["trace_slice_s"], sibling["check"])
    # The source's config (the catalog's row), the reduced keys apart.
    published = dict(
        transformer.QWEN3_NEXT_PUBLISHED, intermediate_size=5120,
        **{k: v for k, v in transformer.QWEN3_NEXT_FIXED.items()
           if k != "attention_bias"})
    reduced = {"num_hidden_layers": (48, 4), "num_experts": (512, 32),
               "vocab_size": (151936, 18992),
               "max_position_embeddings": (262144, 4096)}
    for key, value in published.items():
        if key in reduced:
            assert (config["published"][key], config[key]) == reduced[key]
        else:
            assert config[key] == value, key
            if key in network:
                assert network[key] == value, key
    assert (network["num_experts"], network["experts_held"]) == (512, 32)
    assert config["reduced"] == list(reduced) + ["env"]
    assert set(config["reduced"]) == set(config["reduced_why"])
    assert "next_token_module" in config["assumed"]
    assert config["network"]["param_count"] == PARAMETERS
    model = transformer.qwen3_next_from_config(18992, network)
    assert model.layer_types == ("gdn", "gdn", "gdn", "full_attention")
