"""The `nemotron_h` token policy at a tiny size on the CPU: the family's row,
the checks it shares with the other families (`tests/token_families.py`: the
model against the plain reference `benchmark/lib/reference_nemotron_h.py`,
whose Mamba-2 is the recurrence itself, one position at a time, in its causal
form and decoded through three kinds of state, a Mamba-2 layer's matrix a head
and its one convolution's last inputs and the attention layer's grouped cache;
a decode that continues a causal pass; resets inside a chunk, at a chunk's
edge, and an episode one token long against separate passes; the model's
gradient against the recurrence's; each named wrong mathematics refused by
the cell's limits; the grouped form of the expert product, without a gate
matrix; the cell's program from its shapes; the builder's refusals; the tuned
example) and what is its own: layers that are ONE function each; experts
without a gate matrix; the causal form's chunk terms by a masked cumulative
sum and its scan over chunks, `ssd_chunked` alone against a loop of
`ssd_step`, gradients too; decays that lose more than e^100 inside one chunk;
a matrix state kept in bfloat16 refused; the sixteen shares of an expert layer
against the uncut layer. The loss and the loop:
`tests/test_nemotron_h_update.py`.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from token_families import (  # noqa: F401: pytest collects what is named
    Family, build, causal_routed, decode_routed, judged, model_gradients,
    configuration, read_by, seeded_norms, share_of,
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

from lib import reference_nemotron_h as reference

from ray_tpu.models import transformer
from ray_tpu.models.transformer import dropless_experts

# The cell's seven layers, M E M E M * E; 8 state-space heads of 8 channels
# in 2 groups, a state of 16, chunks of 8; 4 query heads over 2 cached ones
# of 16; 2 of 8 experts of 32 held beside a shared one of 48.
S, B, CHUNK = 24, 3, 8
NET = dict(vocab_size=96, hidden_size=64, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, num_hidden_layers=7,
           hybrid_override_pattern="MEMEM*E", mamba_num_heads=8,
           mamba_head_dim=8, n_groups=2, ssm_state_size=16, conv_kernel=4,
           chunk_size=CHUNK, n_routed_experts=8, experts_held=2,
           first_expert_held=0, num_experts_per_tok=2,
           moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
           n_shared_experts=1, norm_topk_prob=True,
           routed_scaling_factor=2.5, max_position_embeddings=S,
           rope_theta=10000, layer_norm_epsilon=1e-5)
SSM_LAYERS = ("layer_0", "layer_2", "layer_4")
# (heads, P, N) of a matrix state; (taps - 1, I + 2 G N) of the
# convolution's inputs; (positions, groups x d) of a grouped cache, flat.
MATRIX, TAILS, CACHE = (8, 8, 16), (3, 64 + 2 * 2 * 16), (S, 2 * 16)
# A reset inside a chunk, an episode one token long after it, and a reset
# at a chunk's edge.
RESET = jnp.zeros((B, S)).at[:, 11].set(1.0).at[:, 12].set(1.0).at[
    :, 16].set(1.0)
EPISODES = ((0, 11), (11, 12), (12, 16), (16, S))
SSM_PARAMETERS = {"ssm_in", "ssm_conv", "ssm_conv_bias", "ssm_a_log",
                  "ssm_dt_bias", "ssm_d", "ssm_norm", "ssm_out"}


def attention_layer_shown(variables):
    """The one attention layer's softmax far enough from uniform, and its
    output large enough beside the other layers', that a rotation shows in
    the logits."""
    params = dict(variables["params"])
    params["layer_5"] = dict(params["layer_5"],
                             wq=4.0 * params["layer_5"]["wq"],
                             wk=4.0 * params["layer_5"]["wk"],
                             wo=3.0 * params["layer_5"]["wo"])
    return dict(variables, params=params)


def slow_decays(variables):
    """Decays slow enough that a state holds hundreds of positions."""
    params = dict(variables["params"])
    for layer in SSM_LAYERS:
        params[layer] = dict(
            params[layer],
            ssm_a_log=params[layer]["ssm_a_log"] - jnp.log(16.0))
    return dict(variables, params=params)


FAMILY = Family(
    name="nemotron_h", net=NET, reference=reference, B=B, S=S,
    # What a pass hands a decode: the one grouped cache, three layers'
    # convolution inputs, three layers' matrices, a key a kind; a layer
    # that is its feed-forward alone keeps nothing.
    state_kinds=("kv", "conv", "ssm"), matrix_kind="ssm",
    state_shapes=lambda positions: (
        [(positions, CACHE[1])] * 2, [TAILS] * 3, [MATRIX] * 3),
    state_layers={"kv": [0, 0, 0, 0, 0, 2, 0],
                  "conv": [1, 0, 1, 0, 1, 0, 0],
                  "ssm": [1, 0, 1, 0, 1, 0, 0]},
    collections=frozenset({"params", "constants"}),
    expert_layers=3, experts_per_token=2,  # the expert layers
    # The norms' weights and D are seeded too (one at initialisation), so
    # that a norm's place shows.
    seeded=seeded_norms("ssm_d"), limits_build=dict(bias_scale=0.2),
    shown=attention_layer_shown, reset=RESET, episodes=EPISODES,
    # Blocks in bfloat16, at these widths: the reference itself, its
    # blocks rounded to bfloat16, stands several per cent from its float32
    # self. No further off than twice that, and the routing within a tenth.
    bfloat16=(2, 0.3, 0.1),
    # A fragment of whole chunks and one that ends inside a chunk.
    other_lengths=(S - 3,), handed_atol=2e-5,
    # Prefixes shorter than the taps, at a chunk's edge, inside a chunk.
    prefixes=(2, 3, 5, 8, 13),
    long_lived=slow_decays,
    carried_error=lambda wrong, kept: not wrong["ok"],
    # The attention layer alone reads a cache: off a TPU, all of it.
    decode_counters={"decode_cache_read_share": 1.0},
    wrong_updates={
        "taps_reversed_in_the_gradient": dict(mutate="taps_reversed"),
        "a_decay_a_channel": dict(mutate="decay_a_channel"),
        "relu_for_relu2": dict(mutate="relu_not_squared"),
        "the_gate_after_the_norm": dict(mutate="gate_after_norm"),
        "vf_coeff_doubled": dict(cfg={"vf_loss_coeff": 1.0},
                                 by="loss_error"),
        "no_clip": dict(cfg={"grad_clip": None}, by="update_error"),
        "ten_times_the_lr": dict(cfg={"lr": 6e-3}, by="update_error")},
    refused=(
        (dict(n_group=2), "n_group"), (dict(topk_group=2), "topk_group"),
        (dict(mamba_proj_bias=True), "mamba_proj_bias"),
        (dict(use_bias=True), "use_bias"),
        (dict(attention_bias=True), "attention_bias"),
        (dict(mlp_bias=True), "mlp_bias"),
        (dict(use_conv_bias=False), "use_conv_bias"),
        (dict(sliding_window=128), "sliding_window"),
        (dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
        (dict(time_step_limit=[0, 1.0]), "time_step_limit"),
        (dict(hybrid_override_pattern="ME-M*EM"), "no layer"),
        (dict(hybrid_override_pattern="MEM"), "names 7 layers"),
        (dict(kda_chunk=64), "not nemotron_h's"),
        (dict(first_expert_held=7), "not among"),
        (dict(mamba_num_heads=9), "groups")),
    example="nemotron-h-token-impala.yaml",
    cell="nemotron_h_token_anakin_2k",
    config="impala_nemotron_twotower_30b_a3b",
    # At the published widths a decode step of 128 rows sends 6 rows to a
    # held expert, in the batched form; the one grouped cache takes the
    # kernel (32 query heads over 2 cached ones: 256 lanes a position) and
    # the causal pass the fused form; three matrix states of 2 MB a row.
    program=dict(
        rows=128, fragment=2048,
        on_tpu={
            "decode_rows_per_expert": 6.0, "decode_experts_batched": 1.0,
            "decode_experts_sparse": 0.0, "decode_experts_read_share": 1.0,
            "decode_cache_block": 128, "decode_attention_kernel": 1.0,
            "causal_attention_fused": 1.0,
            "kv_cache_bytes_per_token": 1024.0,
            "rotation_fused_layers": 0.0, "kv_groups": 16, "conv_layers": 3,
            "conv_state_bytes_per_row": 110592, "ssm_layers": 3,
            "ssm_state_bytes_per_row": 6291456, "ssm_chunk": 128,
            "state_step_kernel": 0.0},
        off_tpu={"causal_attention_fused": 0.0, "decode_cache_block": 2048,
                 "decode_attention_kernel": 0.0},
        state={"kv": [((128, 2048, 256), "bfloat16")] * 2,
               "conv": [((128, 3, 6144), "bfloat16")] * 3,
               "ssm": [((128, 64, 64, 128), "float32")] * 3},
        parameters=528_095_809))


def test_a_layer_is_one_function_and_an_expert_has_no_gate():
    """The parameters say it: a mixer layer has the operator's norm and no
    feed-forward, an expert layer the feed-forward's norm, two matrices an
    expert and two for the shared one, and no operator."""
    _, variables, _ = build(FAMILY, "f32")
    params = variables["params"]
    for i, letter in enumerate(NET["hybrid_override_pattern"]):
        names = set(params[f"layer_{i}"])
        if letter == "M":
            assert names == SSM_PARAMETERS | {"attn_norm"}
        elif letter == "*":
            assert names == {"attn_norm", "wq", "wk", "wv", "wo"}
        else:
            assert names == {"mlp_norm", "router", "w_up", "w_down",
                             "shared_up", "shared_down"}
    assert params["layer_1"]["shared_up"].shape == (64, 48)
    assert params["layer_0"]["ssm_in"].shape == (64, 64 + 128 + 8)
    assert set(variables["constants"]) == {"layer_1", "layer_3", "layer_6"}


# -- the scan over chunks against the recurrence ----------------------------
_OPERATOR = {}  # compiled once for the fragment whole, once cut by resets


def operator_gradients(variables, layer, reset=None):
    """One Mamba-2 layer alone, x + Mamba2(RMSNorm(x)) of seeded x: (the
    outputs, the gradients of a scalar of them with respect to the layer's
    parameters) through the system's scan over chunks and through the
    reference's recurrence."""
    if (reset is None) not in _OPERATOR:
        model, _, _ = build(FAMILY, "f32")
        x = jax.random.normal(jax.random.PRNGKey(5),
                              (B, S, NET["hidden_size"]))
        weight = jax.random.normal(jax.random.PRNGKey(6), x.shape)
        episode, positions = reference._episodes(reset, (B, S))

        def system(lp, variables):
            h, _ = model.apply(variables, lp, x, positions, episode,
                               method="_ssm_causal")
            return jnp.sum(h * weight), h

        def recurrence(lp, variables):
            with jax.default_matmul_precision("highest"):
                n = reference._rms_norm(x, lp["attn_norm"],
                                        NET["layer_norm_epsilon"])
                h, _ = reference._mamba2(lp, x, n, positions, NET,
                                         lambda a: a, None)
            return jnp.sum(h * weight), h
        _OPERATOR[reset is None] = tuple(
            jax.jit(jax.value_and_grad(f, has_aux=True))
            for f in (system, recurrence))
    lp = variables["params"][layer]
    return tuple(f(lp, variables) for f in _OPERATOR[reset is None])


def fast_decays(variables, by=16.0):
    """Every other head's decay so fast that it loses more than e^100
    inside one chunk: softplus(. + 16) >= 15 a position, times exp(A_log)
    >= 1, over 8 positions."""
    params = dict(variables["params"])
    for layer in SSM_LAYERS:
        bias = params[layer]["ssm_dt_bias"]
        params[layer] = dict(params[layer], ssm_dt_bias=bias.at[::2].set(by))
    return dict(variables, params=params)


@pytest.mark.parametrize("decays", ["drawn", "fast"])
@pytest.mark.parametrize("reset", [None, RESET], ids=["whole", "resets"])
def test_the_scan_s_backward_pass_is_the_recurrence_s_gradient(reset, decays):
    """The operator alone: every Mamba-2 parameter's gradient through the
    scan over chunks (its `lax.map` and `lax.scan`, their recomputed
    bodies) is `jax.grad`'s through the recurrence, to 1e-5; the fragment
    whole and cut by resets; the decays as drawn and so fast that a head
    loses more than e^100 inside one chunk."""
    _, variables, _ = build(FAMILY, "f32")
    if decays == "fast":
        variables = fast_decays(variables)
    for layer in ("layer_0", "layer_4"):
        ((_, got_h), got), ((_, want_h), want) = operator_gradients(
            variables, layer, reset)
        assert np.isfinite(got_h).all()
        assert reference.relative_error(got_h, want_h) < 1e-5
        assert SSM_PARAMETERS < set(want)
        for name in SSM_PARAMETERS | {"attn_norm"}:
            assert np.isfinite(got[name]).all()
            assert reference.relative_error(
                got[name], want[name]) < 1e-5, (layer, name)


def test_decays_that_lose_e100_inside_a_chunk_stay_finite_and_agree():
    """Every exponent of the scan is a sum of log decays: the whole model's
    outputs and gradients are finite and the recurrence's, which multiplies
    by exp(la) one position at a time."""
    built = build(FAMILY, "f32")
    _, variables, tokens = built
    variables = fast_decays(variables)
    lp = variables["params"]["layer_0"]
    assert CHUNK * float(jnp.min(jnp.exp(lp["ssm_a_log"]))) * 15.0 > 100.0
    for reset in (None, RESET):
        system, state, _ = causal_routed(built, variables, tokens, reset)
        assert all(np.isfinite(a).all() for a in system[:2])
        verdicts, _ = judged(FAMILY, system, variables, tokens, starts=reset)
        assert max(verdicts["outputs"]["errors"].values()) < 1e-5, verdicts
        assert verdicts["routing"]["router_flips"] == 0.0
    got, want = model_gradients(FAMILY, variables, tokens, RESET)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(a).all()
        assert reference.relative_error(a, b) < 2e-4
    stepped, _, _ = decode_routed(built, variables, tokens, RESET)
    assert reference.relative_error(stepped[0], system[0]) < 1e-5


# -- the chunked scan alone ------------------------------------------------
def fragment_of_episodes(seed=43, T=37, heads=6, groups=2, P=4, N=8, rows=2,
                         rate=40.0):
    """Seeded operands of `ssd_chunked` with episodes that begin inside a
    chunk of 8 (5, 6, 20, 34), at a chunk's edge (16), one position long
    (5), a tail that is no whole chunk (37 = 4 x 8 + 5), and log decays
    down to -`rate` a position."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (rows, T, heads, P))
    Bm, Cm = (jax.random.normal(key, (rows, T, groups, N))
              for key in keys[1:3])
    la = -rate * jax.random.uniform(keys[3], (rows, T, heads)) ** 3
    starts = np.zeros((rows, T), bool)
    starts[0, [5, 6, 20]] = True
    starts[1, [16, 34]] = True
    starts[:, 0] = True
    return (x, Bm, Cm, la), starts


def by_steps(starts):
    """`ssd_step` one position at a time, the state zeroed where an
    episode begins: a scan, so that it can be differentiated."""
    def run(x, Bm, Cm, la):
        def a_position(S, xs):
            x, Bm, Cm, la, start = xs
            y, S = transformer.ssd_step(
                jnp.where(start[:, None, None, None], 0.0, S), x, Bm, Cm, la)
            return S, y
        S, y = jax.lax.scan(
            a_position,
            jnp.zeros(x.shape[:1] + x.shape[2:] + Bm.shape[-1:]),
            tuple(jnp.moveaxis(a, 1, 0)
                  for a in (x, Bm, Cm, la, jnp.asarray(starts))))
        return jnp.moveaxis(y, 0, 1), S
    return run


@pytest.mark.parametrize("rate", [3.0, 40.0], ids=["slow", "fast"])
@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_the_chunked_scan_is_a_loop_of_steps_and_so_is_its_gradient(
        chunk, rate):
    """`ssd_chunked`'s outputs, final state and gradients by x, B, C and
    the log decay are `ssd_step`'s one position at a time: chunks of 4, 8
    and 16, a fragment that is not whole chunks, resets inside a chunk, at
    its edge and an episode one position long; decays that a float32
    product of factors would still carry, and decays of e^-40 a position
    (e^-300 inside a chunk)."""
    operands, starts = fragment_of_episodes(rate=rate)
    episode = jnp.cumsum(jnp.asarray(starts), axis=1)
    want = read_by(by_steps(starts), operands)
    got = read_by(
        lambda *a: transformer.ssd_chunked(*a, episode, chunk), operands)
    for name, g, w in zip(("y", "S", "dx", "dB", "dC", "dla"), got, want):
        assert np.isfinite(g).all(), name
        assert reference.relative_error(g, w) < 1e-5, (name, chunk)


def test_the_pair_weights_are_sums_of_log_decays_not_differences():
    """One burst of decay ahead of a quiet stretch: the difference of two
    cumulative sums of float32 loses the quiet stretch's small decays to
    the burst's size (here 3e-4 of a weight), the masked cumulative sum
    does not; the chunked scan keeps to the recurrence."""
    rows, T, heads, P, N = 1, 16, 2, 4, 8
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(keys[0], (rows, T, heads, P))
    Bm, Cm = (jax.random.normal(key, (rows, T, 1, N)) for key in keys[1:])
    la = jnp.full((rows, T, heads), -1e-3).at[:, 1].set(-5000.0)
    starts = np.zeros((rows, T), bool)
    starts[:, 0] = True
    want, _ = by_steps(starts)(x, Bm, Cm, la)
    got, _ = transformer.ssd_chunked(
        x, Bm, Cm, la, jnp.ones((rows, T), jnp.int32), 16)
    assert reference.relative_error(got, want) < 1e-6
    cum = jnp.cumsum(la[0, :, 0])
    lost = jnp.exp(cum[-1] - cum[2]) / jnp.exp(-13e-3) - 1.0
    assert abs(float(lost)) > 1e-4


# -- the expert layer that holds a share ---------------------------------
def test_the_16_shares_add_up_to_the_uncut_layer():
    """16 shares of 2 of 32 experts without a gate matrix: their parts,
    with the shared expert that every chip computes counted once, add up
    to what the uncut reference gives for the whole layer (the reference's
    shares, and the system's in both forms of its product)."""
    rng = np.random.default_rng(0)
    H, W, SW, E, k, held = 64, 32, 48, 32, 4, 2
    lp = jax.tree.map(jnp.asarray, {
        "router": rng.normal(size=(H, E)).astype(np.float32) / 4,
        "w_up": rng.normal(size=(E, H, W)).astype(np.float32) / 8,
        "w_down": rng.normal(size=(E, W, H)).astype(np.float32) / 6,
        "shared_up": rng.normal(size=(H, SW)).astype(np.float32) / 8,
        "shared_down": rng.normal(size=(SW, H)).astype(np.float32) / 6})
    bias = jnp.asarray(rng.normal(size=E) * 0.05, jnp.float32)
    h = jnp.asarray(rng.normal(size=(2, 12, H)), jnp.float32)
    m = transformer.rms_norm(h, jnp.ones(H), 1e-5, jnp.float32)
    net = dict(NET, n_routed_experts=E, num_experts_per_tok=k)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def layer(first, size, shared=1):
        share = dict(net, experts_held=size, first_expert_held=first,
                     n_shared_experts=shared)
        with jax.default_matmul_precision("highest"):
            return reference._moe(
                share_of(lp, first, size, ("w_up", "w_down")), bias, h, m,
                share, lambda a: a, None, None)
    whole, chosen, _ = layer(0, E)
    with jax.default_matmul_precision("highest"):
        shared = reference._relu2_mlp(m, lp["shared_up"], lp["shared_down"],
                                      lambda a: a, None)
    shares = [layer(first, held)[0] - h for first in range(0, E, held)]
    assert len(shares) == 16
    # Every chip's part holds the shared expert: counted once.
    parts = sum(s - shared for s in shares) + shared
    assert reference.relative_error(parts, whole - h) < 1e-5
    assert reference.relative_error(
        sum(layer(first, held, shared=0)[0] - h
            for first in range(0, E, held)), whole - h - shared) < 1e-5

    # The system's shares of the same routing, in the form each shape
    # takes (24 rows batched, 64 times as many grouped), no gate given.
    rows = m.reshape(-1, H)
    top_p, top_i = transformer.route(rows, lp["router"], k, True, bias,
                                     NET["routed_scaling_factor"])
    assert np.array_equal(np.sort(top_i, -1),
                          np.sort(chosen.reshape(-1, k), -1))
    for reps in (1, 64):
        n, p, i = (jnp.tile(a, (reps, 1)) for a in (rows, top_p, top_i))
        routed, landed = jnp.zeros_like(n), 0
        for first in range(0, E, held):
            s = share_of(lp, first, held, ("w_up", "w_down"))
            part, sizes, _ = dropless_experts(
                n, p, i, None, s["w_up"], s["w_down"], first, E,
                transformer.relu2)
            routed, landed = routed + part, landed + int(jnp.sum(sizes))
        assert landed == n.shape[0] * k
        assert reference.relative_error(
            routed[:rows.shape[0]],
            (whole - h - shared).reshape(-1, H)) < 1e-4
    assert transformer.experts_batched(rows.shape[0], k, E)
    assert not transformer.experts_batched(64 * rows.shape[0], k, E)


# -- the cell, from its static shapes ---------------------------------------
def test_the_cell_s_learner_goes_grouped_through_a_ladder_of_row_counts():
    """The learner's 8,192 rows go grouped, through a ladder of row
    counts; 32 query heads over 2 cached ones take the grouped kernel."""
    assert transformer.grouped_fused(2048, 2, 32, 128)
    assert not transformer.experts_batched(8192, 6, 128)
    assert transformer.dispatch_rows(8192, 6, 8, 128) == (
        3840, 6144, 12288, 49152)


def test_the_other_families_states_and_counters_are_what_they_were():
    """The state's kinds come from one tuple now: a model without a kind
    has no key for it, and the five accepted descriptions count what they
    counted."""
    olmoe = transformer.olmoe_from_config(64, dict(
        vocab_size=64, hidden_size=32, num_attention_heads=2,
        num_hidden_layers=2, num_experts=4, num_experts_per_tok=2,
        intermediate_size=16, max_position_embeddings=16))
    assert set(jax.eval_shape(lambda: olmoe.initial_state(2))) == {
        "kv", "pos"}
    counted = olmoe.static_counters(2, 16, "cpu")
    assert not any(key.startswith(("conv", "kda", "ssm", "gdn", "kv_groups"))
                   for key in counted)
    # The fifth kind came with the eighth description (PR 52).
    assert transformer.STATE_KINDS == ("kv", "conv", "kda", "ssm", "gdn")
    assert transformer.ACTIVATIONS["relu2"] is transformer.relu2
    np.testing.assert_allclose(
        transformer.relu2(jnp.asarray([-2.0, 0.5, 3.0])), [0.0, 0.25, 9.0])


def test_keys_left_out_have_the_published_model_s_values():
    model = transformer.nemotron_h_from_config(131072, {})
    assert (model.hidden_size, model.num_layers, model.num_heads,
            model.kv_heads, model.head_width) == (2688, 52, 32, 2, 128)
    assert (model.ssm_heads, model.ssm_head_dim, model.ssm_groups,
            model.ssm_state, model.ssm_taps, model.ssm_chunk) == (
                64, 64, 8, 128, 4, 128)
    assert (model.num_experts, model.experts_per_token, model.expert_width,
            model.shared_width, model.routed_scaling_factor) == (
                128, 6, 1856, 3712, 2.5)
    kinds = [model.layer_kind(i) for i in range(52)]
    assert (kinds.count("mamba2"), kinds.count("experts"),
            [kind[:2] for kind in kinds].count((0, False))) == (23, 23, 6)
    assert model.one_function_layers and not model.gated_feed_forward
    assert model.hidden_act == "relu2" and model.selection_bias
    assert not model.qk_norm and not model.tie_embeddings
    # Every key of the published config is taken, the unread ones too.
    published = configuration(FAMILY)[3]
    assert transformer.nemotron_h_from_config(16384, published).held == 8
