"""The `kimi_linear` token policy at a tiny size on the CPU: the family's row,
the checks it shares with the other families (`tests/token_families.py`: the
model against the plain reference `benchmark/lib/reference_kimi_linear.py`,
whose KDA is the recurrence itself, one position at a time, in its causal form
and decoded through three kinds of state, a KDA layer's matrix a head and its
convolutions' last inputs and the latent layer's cache; a decode that
continues a causal pass from the state it handed over; resets inside a chunk,
at a chunk's edge, and an episode one token long against separate passes; the
model's gradient against the recurrence's; each named wrong mathematics
refused by the cell's limits; the grouped form of the expert product; the
cell's program from its shapes; the builder's refusals; the tuned example) and
what is its own: the causal form as a scan over chunks, the delta rule's
triangular systems solved for every chunk at once ahead of it, outside every
loop, against the library's solve; the scan's backward pass against `jax.grad`
through the recurrence, for every KDA parameter; gates that lose more than
e^100 inside one chunk; a matrix state kept in bfloat16 refused; the expert
layer that holds a share against the uncut layer. The loss and the loop:
`tests/test_kimi_linear_update.py`.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from token_families import (  # noqa: F401: pytest collects what is named
    BENCH, Family, build, causal_routed, configuration, count, decode_routed,
    judged, model_gradients, read_by, seeded_norms, shapes_of,
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

from lib import reference_kimi_linear as reference

from ray_tpu.models import catalog, transformer
from ray_tpu.models.transformer import dropless_experts

# The cell's five layers: a dense KDA layer, then one period of expert
# layers, K K M K; 4 heads; KDA heads of 16, chunks of 8 positions in
# sub-blocks of 4; 2 of 8 experts held beside a shared one.
S, B, CHUNK, SUB = 24, 3, 8, 4
LINEAR = dict(kda_layers=[1, 2, 3, 5], full_attn_layers=[4], head_dim=16,
              num_heads=4, short_conv_kernel_size=4)
NET = dict(vocab_size=96, hidden_size=64, num_attention_heads=4,
           num_key_value_heads=4, num_hidden_layers=5,
           linear_attn_config=LINEAR, mla_use_nope=True, q_lora_rank=None,
           kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, first_k_dense_replace=1, intermediate_size=96,
           num_experts=8, experts_held=2, first_expert_held=0,
           num_experts_per_token=2, moe_intermediate_size=32,
           num_shared_experts=1, moe_renormalize=True,
           routed_scaling_factor=2.446, model_max_length=S, rope_theta=10000,
           rms_norm_eps=1e-5, kda_chunk=CHUNK)
KDA_LAYERS = ("layer_0", "layer_1", "layer_2", "layer_4")
# (heads, d_k, d_v) of a matrix state; (taps - 1, 3 x heads x d) of the
# convolutions' inputs; (positions, rank + rope) of the latent cache.
MATRIX, TAILS, CACHE = (4, 16, 16), (3, 3 * 64), (S, 16 + 8)
# A reset inside a chunk, an episode one token long after it, and a reset
# at a chunk's edge.
RESET = jnp.zeros((B, S)).at[:, 11].set(1.0).at[:, 12].set(1.0).at[
    :, 16].set(1.0)
EPISODES = ((0, 11), (11, 12), (12, 16), (16, S))
KDA_PARAMETERS = {"kda_qkv", "kda_conv", "kda_fa", "kda_fb", "kda_a_log",
                  "kda_dt_bias", "kda_b", "kda_ga", "kda_gb", "kda_o_norm",
                  "kda_out"}
# The cell's parameters at the published widths, by hand.
KDA = (3 * 2304 * 4096 + 3 * 4096 * 4 + 2 * (2304 * 128 + 128 * 4096)
       + 32 + 4096 + 2304 * 32 + 128 + 4096 * 2304)
ATTENTION = (2304 * 32 * 192 + 2304 * 576 + 512 + 512 * 32 * 256
             + 4096 * 2304)
EXPERTS = 2304 * 256 + 9 * 3 * 2304 * 1024
PARAMETERS = (2 * 20480 * 2304 + KDA + 3 * 2304 * 9216 + 3 * (KDA + EXPERTS)
              + ATTENTION + EXPERTS + 5 * 2 * 2304 + 2304 + 2304 + 1)


def latent_layer_shown(variables):
    """The one latent layer's softmax far enough from uniform, and its
    output large enough beside the other four layers', that its scale
    shows in the logits."""
    params = dict(variables["params"])
    params["layer_3"] = dict(params["layer_3"],
                             wq=2.0 * params["layer_3"]["wq"],
                             wo=3.0 * params["layer_3"]["wo"])
    return dict(variables, params=params)


FAMILY = Family(
    name="kimi_linear", net=NET, reference=reference, B=B, S=S,
    # What a pass hands a decode: the one latent cache, four layers'
    # convolution inputs, four layers' matrices, a key a kind.
    state_kinds=("kv", "conv", "kda"), matrix_kind="kda",
    state_shapes=lambda positions: (
        [(positions, CACHE[1])], [TAILS] * 4, [MATRIX] * 4),
    state_layers={"kv": [0, 0, 0, 1, 0], "conv": [1, 1, 1, 0, 1],
                  "kda": [1, 1, 1, 0, 1]},
    collections=frozenset({"params", "constants"}),
    expert_layers=4, experts_per_token=2,  # the expert layers
    # The norms' weights are seeded too, so that a norm's place shows.
    seeded=seeded_norms(), limits_build=dict(bias_scale=0.2),
    shown=latent_layer_shown, reset=RESET, episodes=EPISODES,
    # Blocks in bfloat16, at these widths (heads of 16 values under a norm
    # of their own): the reference itself, its blocks rounded to bfloat16,
    # stands 6-12 % from its float32 self. No further off than twice that,
    # and the routing within a tenth.
    bfloat16=(2, 0.3, 0.1),
    # A fragment of whole chunks and one that ends inside a chunk.
    other_lengths=(S - 3,), length_key="model_max_length",
    handed_atol=2e-5,
    # Prefixes shorter than the taps, at a chunk's edge, inside a chunk.
    prefixes=(2, 8, 13),
    # A matrix state in bfloat16, the decays as drawn: by far more than
    # the cell's limit (6 %: 3 % by step 50 and past 25 % by step 384).
    carried_error=lambda wrong, kept: (
        not wrong["ok"]
        and wrong["errors"]["logits"] > 4 * reference.TOLERANCE),
    # The latent layer alone reads a cache: off a TPU, all of it.
    decode_counters={"decode_cache_read_share": 1.0},
    wrong_updates={
        "taps_reversed_in_the_gradient": dict(mutate="taps_reversed"),
        "one_decay_a_head": dict(mutate="one_decay_a_head"),
        "beta_out_of_the_subtraction": dict(
            mutate="beta_out_of_subtraction"),
        "a_rotated_latent_key": dict(mutate="k_r_rotated"),
        "vf_coeff_doubled": dict(cfg={"vf_loss_coeff": 1.0},
                                 by="loss_error"),
        "no_clip": dict(cfg={"grad_clip": None}, by="update_error"),
        "ten_times_the_lr": dict(cfg={"lr": 6e-3}, by="update_error")},
    refused=(
        ({"n_routed_experts": 8}, "not kimi_linear's"),
        ({"layer_types": ["kda"]}, "not kimi_linear's"),
        ({"q_lora_rank": 16}, "q_lora_rank"),
        ({"mla_use_nope": False}, "mla_use_nope"),
        ({"num_expert_group": 2}, "num_expert_group"),
        ({"topk_group": 2}, "topk_group"),
        ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
        ({"moe_router_activation_func": "softmax"}, "moe_router_activation"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
        ({"num_key_value_heads": 2}, "key/value heads"),
        ({"linear_attn_config": dict(LINEAR, kda_layers=[1, 2, 3])},
         "names each of the 5 layers once"),
        ({"linear_attn_config": dict(LINEAR, full_attn_layers=[4, 5])},
         "names each of the 5 layers once"),
        ({"linear_attn_config": dict(LINEAR, chunk_size=64)},
         "linear_attn_config"),
        ({"experts_held": 6, "first_expert_held": 4}, "not among")),
    example="kimi-linear-token-impala.yaml",
    cell="kimi_linear_token_anakin_4k",
    config="impala_kimi_linear_48b_a3b",
    # At the published widths: 602.4 M parameters; ONE latent cache of
    # 4,096 positions, 1,152 bytes a position; four layers' convolution
    # inputs, 294,912 bytes a sequence, and four layers' matrices,
    # 8,388,608, whatever its length; a head's 192 padded to 256 takes the
    # fused causal form.
    program=dict(
        rows=32, fragment=4096,
        on_tpu={
            # Under two rows a held expert: a rollout's step reads the
            # chosen ones' matrices alone, and counts their share itself.
            "decode_rows_per_expert": 1.0, "decode_experts_batched": 0.0,
            "decode_experts_sparse": 1.0,
            "decode_cache_block": 128, "decode_attention_kernel": 1.0,
            "causal_attention_fused": 1.0,
            "latent_cache_bytes_per_token": 1152,
            "rotation_fused_layers": 0.0, "conv_layers": 4,
            "conv_state_bytes_per_row": 294912,
            "kda_layers": 4, "kda_state_bytes_per_row": 8388608,
            "kda_chunk": 64, "state_step_kernel": 1.0},
        # Off a TPU the cache is read whole, by XLA's products, and every
        # held expert's matrices.
        off_tpu={
            "decode_experts_batched": 1.0, "decode_experts_sparse": 0.0,
            "decode_experts_read_share": 1.0,
            "causal_attention_fused": 0.0, "decode_cache_block": 4096,
            "decode_attention_kernel": 0.0, "state_step_kernel": 0.0},
        state={"kv": [((32, 4096, 576), "bfloat16")],
               "conv": [((32, 3, 3 * 4096), "bfloat16")] * 4,
               "kda": [((32, 32, 128, 128), "float32")] * 4},
        # 602,435,713 trained parameters and four routers' 256 biases.
        parameters=PARAMETERS + 4 * 256))


@pytest.fixture(scope="module", autouse=True)
def sub_blocks_of_four():
    """Chunks of 8 in sub-blocks of 4, so that both kinds of pair (inside
    a sub-block, between two) are made; ahead of the module's trainer."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(transformer, "KDA_SUB_BLOCK", SUB)
        yield


# -- the scan over chunks against the recurrence ----------------------------
_OPERATOR = {}  # compiled once for the fragment whole, once cut by resets


def operator_gradients(variables, layer, reset=None):
    """One KDA layer's operator alone, x + KDA(RMSNorm(x)) of seeded x:
    (the outputs, the gradients of a scalar of them with respect to the
    layer's parameters) through the system's scan over chunks and through
    the reference's recurrence."""
    if (reset is None) not in _OPERATOR:
        model, _, _ = build(FAMILY, "f32")
        x = jax.random.normal(jax.random.PRNGKey(5),
                              (B, S, NET["hidden_size"]))
        weight = jax.random.normal(jax.random.PRNGKey(6), x.shape)
        episode, positions = reference._episodes(reset, (B, S))

        def system(lp, variables):
            h, _ = model.apply(variables, lp, x, positions, episode,
                               method="_kda_causal")
            return jnp.sum(h * weight), h

        def recurrence(lp, variables):
            with jax.default_matmul_precision("highest"):
                n = reference._rms_norm(x, lp["attn_norm"],
                                        NET["rms_norm_eps"])
                h, _ = reference._kda(lp, x, n, positions, NET, lambda a: a,
                                      None)
            return jnp.sum(h * weight), h
        _OPERATOR[reset is None] = tuple(
            jax.jit(jax.value_and_grad(f, has_aux=True))
            for f in (system, recurrence))
    lp = variables["params"][layer]
    return tuple(f(lp, variables) for f in _OPERATOR[reset is None])


@pytest.mark.parametrize("gates", ["drawn", "fast"])
@pytest.mark.parametrize("reset", [None, RESET], ids=["whole", "resets"])
def test_the_scan_s_backward_pass_is_the_recurrence_s_gradient(reset, gates):
    """The operator alone: every KDA parameter's gradient through the scan
    over chunks (its solve, its `lax.map` and `lax.scan`, their recomputed
    bodies) is `jax.grad`'s through the recurrence, to 1e-5; the fragment
    whole and cut by resets; the gates as drawn and so fast that a channel
    loses more than e^100 inside one chunk."""
    _, variables, _ = build(FAMILY, "f32")
    if gates == "fast":
        variables = fast_gates(variables)
    for layer in ("layer_0", "layer_2"):
        ((_, got_h), got), ((_, want_h), want) = operator_gradients(
            variables, layer, reset)
        assert np.isfinite(got_h).all()
        assert reference.relative_error(got_h, want_h) < 1e-5
        assert KDA_PARAMETERS < set(want)
        for name in KDA_PARAMETERS | {"attn_norm"}:
            assert np.isfinite(got[name]).all()
            assert reference.relative_error(
                got[name], want[name]) < 1e-5, (layer, name)


def fast_gates(variables, by=16.0):
    """Every other channel's decay so fast that it loses more than e^100
    inside one chunk: softplus(. + 16) >= 15 a position, times exp(A_log)
    >= 1, over 8 positions."""
    params = dict(variables["params"])
    for layer in KDA_LAYERS:
        bias = params[layer]["kda_dt_bias"]
        params[layer] = dict(params[layer], kda_dt_bias=bias.at[::2].set(by))
    return dict(variables, params=params)


def test_gates_that_lose_e100_inside_a_chunk_stay_finite_and_agree():
    """The pairs' decays are formed from differences of log decays, never
    from exp(-G): the whole model's outputs and gradients are finite and
    the recurrence's, which multiplies by exp(g) one position at a time
    (the operator's own gradients at these gates: the test above)."""
    built = build(FAMILY, "f32")
    _, variables, tokens = built
    variables = fast_gates(variables)
    lp = variables["params"]["layer_0"]
    assert CHUNK * float(jnp.min(jnp.exp(lp["kda_a_log"]))) * 15.0 > 100.0
    for reset in (None, RESET):
        system, state, _ = causal_routed(built, variables, tokens, reset)
        assert all(np.isfinite(a).all() for a in system[:2])
        verdicts, _ = judged(FAMILY, system, variables, tokens, starts=reset)
        assert max(verdicts["outputs"]["errors"].values()) < 1e-5, verdicts
        assert verdicts["routing"]["router_flips"] == 0.0
    got, want = model_gradients(FAMILY, variables, tokens, RESET)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        # Sums of products of decays e^-100 apart, in two orders.
        assert np.isfinite(a).all()
        assert reference.relative_error(a, b) < 2e-4
    stepped, _, _ = decode_routed(built, variables, tokens, RESET)
    assert reference.relative_error(stepped[0], system[0]) < 1e-5
    # The factorised product the scan avoids overflows at these gates.
    n = transformer.rms_norm(
        variables["params"]["embed"][tokens], lp["attn_norm"], 1e-5,
        jnp.float32)
    g = -jnp.exp(lp["kda_a_log"])[:, None] * jax.nn.softplus(
        (n @ lp["kda_fa"]) @ lp["kda_fb"] + lp["kda_dt_bias"]).reshape(
            B, S, 4, 16)
    lost = jnp.cumsum(g[:, :CHUNK], axis=1)[:, -1]
    assert float(jnp.min(lost)) < -100.0
    assert not np.isfinite(np.asarray(jnp.exp(-lost))).all()


def fragment_of_episodes(seed=43, T=37, heads=3, d=8, rows=2):
    """Seeded operands of `kda_chunked` with episodes that begin inside a
    chunk of 8 (5, 6, 20, 34), at a chunk's edge (16), one position long
    (5), and a tail that is no whole chunk (37 = 4 x 8 + 5)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = (jax.random.normal(key, (rows, T, heads, d)) for key in keys[:2])
    q, k = (a / jnp.linalg.norm(a, axis=-1, keepdims=True) for a in (q, k))
    v = jax.random.normal(keys[2], (rows, T, heads, d))
    g = -40.0 * jax.random.uniform(keys[3], (rows, T, heads, d)) ** 3
    beta = jax.random.uniform(keys[4], (rows, T, heads))
    starts = np.zeros((rows, T), bool)
    starts[0, [5, 6, 20]] = True
    starts[1, [16, 34]] = True
    starts[:, 0] = True
    return (q, k, v, g, beta), starts


def test_the_chunked_scan_is_the_recurrence_at_any_chunk_and_sub_block():
    """`kda_chunked` alone against `kda_step` one position at a time:
    chunks of one sub-block and of several, a fragment that ends inside a
    chunk, episodes that begin anywhere."""
    (q, k, v, g, beta), starts = fragment_of_episodes(seed=3)
    T, heads, d = q.shape[1:]
    episode = jnp.cumsum(jnp.asarray(starts), axis=1)

    def recurrence():
        S = jnp.zeros((2, heads, d, d))
        out = []
        for t in range(T):
            S = jnp.where(starts[:, t, None, None, None], 0.0, S)
            o, S = transformer.kda_step(
                S, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
            out.append(o)
        return jnp.stack(out, axis=1), S
    want, want_state = recurrence()
    for chunk in (4, 8, 16):
        got, state = jax.jit(transformer.kda_chunked, static_argnums=6)(
            q, k, v, g, beta, episode, chunk)
        assert reference.relative_error(got, want) < 1e-5, chunk
        assert reference.relative_error(state, want_state) < 1e-5, chunk


@pytest.fixture(scope="module")
def by_the_recurrence():
    operands, starts = fragment_of_episodes()

    def recurrence(*operands):
        def a_position(S, xs):
            q, k, v, g, beta, start = xs
            o, S = transformer.kda_step(
                jnp.where(start[:, None, None, None], 0.0, S), q, k, v, g,
                beta)
            return S, o
        q = operands[0]
        S, o = jax.lax.scan(
            a_position, jnp.zeros(q.shape[:1] + q.shape[2:] + q.shape[-1:]),
            tuple(jnp.moveaxis(a, 1, 0)
                  for a in operands + (jnp.asarray(starts),)))
        return jnp.moveaxis(o, 0, 1), S
    return read_by(recurrence, operands)


# Chunks of one sub-block (the solve is its diagonal block alone), of two
# and of four (block rows by products).
@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_the_chunked_scan_s_gradients_are_the_recurrence_s(
        chunk, by_the_recurrence):
    """`kda_chunked`'s outputs, final state and gradients by q, k, v, g and
    beta are `kda_step`'s one position at a time: resets inside a chunk, at
    a chunk's edge, and a tail that is not a whole chunk."""
    operands, starts = fragment_of_episodes()
    episode = jnp.cumsum(jnp.asarray(starts), axis=1)
    got = read_by(
        lambda *operands: transformer.kda_chunked(*operands, episode, chunk),
        operands)
    names = ("o", "S", "dq", "dk", "dv", "dg", "dbeta")
    for name, mine, want in zip(names, got, by_the_recurrence):
        assert reference.relative_error(mine, want) < 2e-5, (name, chunk)


def systems_of_a_chunk(case):
    """(L, (R, R')) of the chunk phase's systems (I + L) X = R, as
    `_kda_chunk` makes them from seeded keys: chunks of 64 in sub-blocks of
    16, as the cell's."""
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    rows, heads, C, d = 2, 3, 64, 16
    q, k, v = (jax.random.normal(key, (rows, heads, C, d))
               for key in keys[:3])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -2.0 * jax.random.uniform(keys[3], (rows, heads, C, d)) ** 2
    beta = jax.random.uniform(keys[3], (rows, heads, C, 1))
    if case == "equal_keys":
        # Every key the same, beta = 1, nothing decays: L is the all-ones
        # triangle, whose powers reach 1e17 (the product form of the
        # inverse cancels them to nothing in float32).
        k = jnp.broadcast_to(k[:, :, :1], k.shape)
        g, beta = jnp.zeros_like(g), jnp.ones_like(beta)
    elif case == "fast_decays":
        g = -50.0 * jax.random.uniform(keys[3], (rows, heads, C, d))
    episode = jnp.zeros((rows, C), jnp.int32)
    L, *rhs = jax.jit(lambda *operands: transformer._kda_chunk(
        *operands, sub=16, dtype=jnp.float32)[:3])(
            q, k, v, g, beta, episode, episode[:, 0])
    return L, tuple(rhs)


@pytest.mark.parametrize("case", ["equal_keys", "fast_decays", "drawn"])
def test_the_solve_in_blocks_is_the_triangular_solve_in_float32(case):
    """`unit_lower_solve` (the inverse in blocks of 16 rows, multiplied
    in) against `jax.scipy.linalg.solve_triangular` of the whole systems:
    the all-ones triangle, log decays down to -50 a position, and drawn
    ones; its pullback against the library's."""
    L, rhs = systems_of_a_chunk(case)
    C = L.shape[-1]
    if case == "equal_keys":
        assert np.array_equal(np.asarray(L[0, 0]), np.tril(np.ones((C, C)), -1))

    def library(L, rhs):
        return tuple(jax.scipy.linalg.solve_triangular(
            jnp.eye(C) + jnp.tril(L, -1), R, lower=True, unit_diagonal=True)
            for R in rhs)
    def mine(L, rhs):
        return transformer.unit_lower_solve(L, rhs, 16)
    for got, want in zip(jax.jit(mine)(L, rhs), jax.jit(library)(L, rhs)):
        assert reference.relative_error(got, want) < 1e-5

    def scalar(solve):
        return lambda L, rhs: sum(
            jnp.sum(X * jnp.cos(jnp.arange(X.size, dtype=jnp.float32))
                    .reshape(X.shape)) for X in solve(L, rhs))
    got, want = (jax.tree.leaves(jax.jit(jax.grad(
        scalar(solve), argnums=(0, 1)))(L, rhs)) for solve in (mine, library))
    for mine_, want_ in zip(got, want):
        assert reference.relative_error(mine_, want_) < 1e-5


def loops_of(text):
    """The bodies of a StableHLO module's `while` ops, as text."""
    bodies, at = [], 0
    while True:
        at = text.find("stablehlo.while", at)
        if at < 0:
            return bodies
        # Two regions follow: `cond { .. } do { .. }`.
        start = text.index(" do {", at)
        depth, end = 0, start + 4
        while True:
            depth += {"{": 1, "}": -1}.get(text[end], 0)
            end += 1
            if depth == 0:
                break
        bodies.append(text[start:end])
        at = start


def test_no_loop_of_the_lowered_scan_holds_a_triangular_solve():
    """At the rehearsal's size (2 fragments of 32 tokens, 4 heads of 16,
    chunks of 8) the lowered gradient has the chunk phase's loop and the
    scan's, forward and transposed, and no loop's body holds a triangular
    solve."""
    shape = (2, 32, 4, 16)
    operands = [jax.ShapeDtypeStruct(shape, jnp.float32)] * 4 + [
        jax.ShapeDtypeStruct(shape[:3], jnp.float32)]
    episode = jnp.ones(shape[:2], jnp.int32)

    def scalar(*operands):
        o, S = transformer.kda_chunked(*operands, episode, 8)
        return jnp.sum(o) + jnp.sum(S)
    text = jax.jit(jax.grad(scalar, argnums=(0, 1, 2, 3, 4))).lower(
        *operands).as_text()
    bodies = loops_of(text)
    assert len(bodies) == 4
    assert all(len(body) > 1000 for body in bodies)
    assert "triangular" in text
    assert not any("triangular" in body for body in bodies)


# -- the expert layer that holds a share ---------------------------------
def test_the_32_shares_add_up_to_the_uncut_layer():
    """32 shares of 2 of 64 experts: their parts, with the shared expert
    that every chip computes counted once, add up to what the uncut
    reference gives for the whole layer (the reference's shares, and the
    system's in both forms of its product)."""
    rng = np.random.default_rng(0)
    H, W, E, k, held = 64, 32, 64, 4, 2
    lp = jax.tree.map(jnp.asarray, {
        "router": rng.normal(size=(H, E)).astype(np.float32) / 4,
        "w_gate": rng.normal(size=(E, H, W)).astype(np.float32) / 8,
        "w_up": rng.normal(size=(E, H, W)).astype(np.float32) / 8,
        "w_down": rng.normal(size=(E, W, H)).astype(np.float32) / 6,
        "shared_gate": rng.normal(size=(H, W)).astype(np.float32) / 8,
        "shared_up": rng.normal(size=(H, W)).astype(np.float32) / 8,
        "shared_down": rng.normal(size=(W, H)).astype(np.float32) / 6})
    bias = jnp.asarray(rng.normal(size=E) * 0.05, jnp.float32)
    h = jnp.asarray(rng.normal(size=(2, 12, H)), jnp.float32)
    m = transformer.rms_norm(h, jnp.ones(H), 1e-5, jnp.float32)
    net = dict(NET, num_experts=E, num_experts_per_token=k)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def layer(first, size, shared=1):
        share = dict(net, experts_held=size, first_expert_held=first,
                     num_shared_experts=shared)
        with jax.default_matmul_precision("highest"):
            return reference._moe(share_of(lp, first, size), bias, h, m, share,
                                  lambda a: a, None, None)
    whole, chosen, _ = layer(0, E)
    with jax.default_matmul_precision("highest"):
        shared = reference._swiglu(m, lp["shared_gate"], lp["shared_up"],
                                   lp["shared_down"], lambda a: a)
    shares = [layer(first, held)[0] - h for first in range(0, E, held)]
    assert len(shares) == 32
    # Every chip's part holds the shared expert: counted once.
    parts = sum(s - shared for s in shares) + shared
    assert reference.relative_error(parts, whole - h) < 1e-5
    assert reference.relative_error(
        sum(layer(first, held, shared=0)[0] - h
            for first in range(0, E, held)), whole - h - shared) < 1e-5

    # The system's shares of the same routing, in the form each shape
    # takes (24 rows batched, 64 times as many grouped).
    rows = m.reshape(-1, H)
    top_p, top_i = transformer.route(rows, lp["router"], k, True, bias,
                                     NET["routed_scaling_factor"], 1e-20)
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


def test_the_published_cut_s_parameters_are_the_hand_count_s():
    """The latent layer has no query compression; a layer is its KDA and
    its experts; `causal_fused` takes a head's 192 once padded to 256."""
    network = configuration(FAMILY)[3]
    model = transformer.kimi_linear_from_config(
        network["vocab_size"], network)
    assert not transformer.causal_fused(4096, 192, 128)
    assert model._latent_key_width(4096) == 256
    assert model._latent_key_width(S) == 192
    variables = shapes_of(model)
    latent = variables["params"]["layer_3"]
    assert latent["wq"].shape == (2304, 32 * 192)
    assert not {"wq_a", "q_a_norm", "wq_b"} & set(latent)
    assert count(variables["params"]["layer_1"]) == KDA + EXPERTS + 2 * 2304
    assert count(variables["params"]) == PARAMETERS == 602_435_713
    assert count(variables["constants"]) == 4 * 256


def test_the_counters_count_each_kind_of_state_from_its_own_layers():
    """`latent_cache_bytes_per_token` counts the latent layers alone, and
    the convolution state is read off the state's own leaves: a KDA
    layer's is 3 x 12,288 values, not (taps - 1) x hidden. The second and
    the fourth configuration read what they read: 5,760 and 32,768."""
    model, _, _ = build(FAMILY, "bf16")
    counted = model.static_counters(4, S, "cpu")
    assert counted["latent_cache_bytes_per_token"] == 1 * (16 + 8) * 2
    assert (counted["conv_layers"], counted["conv_state_bytes_per_row"]) == (
        4, 4 * 3 * 3 * 64 * 2)
    assert (counted["kda_layers"], counted["kda_state_bytes_per_row"],
            counted["kda_chunk"]) == (4, 4 * 4 * 16 * 16 * 4, CHUNK)
    accepted = {}
    for name, family, rows, fragment in (
            ("impala_glm_4_7_flash", "glm4_moe_lite", 128, 1024),
            ("impala_lfm2_8b_a1b", "lfm2_moe", 64, 4096)):
        with open(os.path.join(BENCH, "configs", name + ".json")) as f:
            network = json.load(f)["network"]
        network.pop("param_count")
        other = catalog.get_model(None, network["vocab_size"], {
            "custom_model": family, "custom_model_config": network})
        accepted[name] = other.static_counters(rows, fragment, "tpu")
        assert "kda_layers" not in accepted[name]
        assert "kda" not in jax.eval_shape(lambda: other.initial_state(1))
    assert accepted["impala_glm_4_7_flash"][
        "latent_cache_bytes_per_token"] == 5760
    assert "conv_layers" not in accepted["impala_glm_4_7_flash"]
    assert accepted["impala_lfm2_8b_a1b"]["conv_state_bytes_per_row"] == 32768
    assert accepted["impala_lfm2_8b_a1b"]["conv_layers"] == 4


def test_keys_left_out_have_the_published_model_s_values():
    """An empty description is Kimi-Linear-48B-A3B itself: 27 layers, 20
    of them KDA in the period K K K M, the first dense, 48 B parameters
    of which a token meets 3 B."""
    model = transformer.kimi_linear_from_config(163840, {})
    kinds = [model.layer_kind(i) for i in range(27)]
    assert kinds.count("kda") == 20 and model.attention_layers == (
        3, 7, 11, 15, 19, 23, 26)
    assert all(kind[:2] == (0, False) for kind in kinds if kind != "kda")
    assert (model.dense_layers, model.num_experts, model.held,
            model.experts_per_token, model.shared_experts, model.kda_width,
            model.kda_taps, model.kda_chunk, model.q_lora_rank,
            model.latent_width, model.routed_scaling_factor,
            model.topk_eps) == (1, 256, 256, 8, 1, 4096, 4, 64, 0, 576,
                                2.446, 1e-20)
    params = count(shapes_of(model)["params"])
    assert 48e9 < params < 50e9
    per_token = params - 26 * (256 - 8) * 3 * 2304 * 1024
    assert 2.9e9 < per_token < 3.6e9


def test_the_configuration_s_file_holds_its_source_s_published_numbers():
    """Every published number of the source (the catalog's row) but the
    ones the file lists as reduced."""
    _, _, config, network = configuration(FAMILY)
    # The source's config (the catalog's row), the reduced keys apart.
    published = dict(
        transformer.KIMI_LINEAR_PUBLISHED, **transformer.KIMI_LINEAR_FIXED,
        head_dim=72, num_key_value_heads=32)
    reduced = {"num_hidden_layers": (27, 5), "num_experts": (256, 8),
               "vocab_size": (163840, 20480),
               "model_max_length": (1048576, 4096)}
    for key, value in published.items():
        if key in reduced:
            assert (config["published"][key], config[key]) == reduced[key]
        else:
            assert config[key] == value, key
            if key in network and key != "linear_attn_config":
                assert network[key] == value, key
    # The published layers 1-5 of the two lists, the rest of the group as
    # published.
    linear = network["linear_attn_config"]
    whole = config["linear_attn_config"]
    assert linear["kda_layers"] == [i for i in whole["kda_layers"] if i <= 5]
    assert linear["full_attn_layers"] == [
        i for i in whole["full_attn_layers"] if i <= 5]
    assert {k: linear[k] for k in (
        "head_dim", "num_heads", "short_conv_kernel_size")} == {
            k: whole[k] for k in (
                "head_dim", "num_heads", "short_conv_kernel_size")}
    assert (network["num_experts"], network["experts_held"]) == (256, 8)
    assert config["reduced"] == list(reduced) + ["env"]
    assert set(config["reduced"]) == set(config["reduced_why"])
    assert config["network"]["param_count"] == PARAMETERS + 4 * 256
    model = transformer.kimi_linear_from_config(20480, network)
    assert model.layer_types == (
        "kda", "kda", "kda", "full_attention", "kda")
    assert (model.hidden_size, model.num_heads, model.kda_heads,
            model.kda_head_dim, model.kda_taps, model.kv_lora_rank,
            model.qk_nope_head_dim, model.qk_rope_head_dim, model.v_head_dim,
            model.dense_width, model.expert_width, model.experts_per_token,
            model.rms_eps) == (2304, 32, 32, 128, 4, 512, 128, 64, 128, 9216,
                               1024, 8, 1e-5)
