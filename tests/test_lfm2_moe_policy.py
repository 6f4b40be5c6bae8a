"""The `lfm2_moe` token policy at a tiny size on the CPU: the model against
the plain reference (`benchmark/lib/reference_lfm2_moe.py`) in its causal form
and in its decode through two kinds of state (a convolution layer's last two
gated inputs, an attention layer's cache); a decode that continues a causal
pass from the state it handed over; a reset inside a fragment and an episode
one token long against separate passes; the expert layer that holds a share
against the uncut layer; the renormalisation's epsilon; each named wrong
mathematics refused by the cell's limits; V-trace's loss, its gradients and
one update of the optimizer's own against the reference's; and the trainer
on the fused Anakin path.
"""

import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import reference_lfm2_moe as reference  # noqa: E402

from ray_tpu.models import catalog, transformer  # noqa: E402
from ray_tpu.models.transformer import dropless_experts  # noqa: E402
from ray_tpu.rllib import sample_batch as sb  # noqa: E402
from ray_tpu.rllib.agents.impala import IMPALATrainer  # noqa: E402
from ray_tpu.rllib.agents.impala.vtrace_policy import vtrace_loss  # noqa: E402

# The cell's five layers: a dense convolution layer, then one period of
# expert layers, an attention and three convolutions; 8 query heads in 2
# groups; 2 of 8 experts held.
S, B = 24, 3
TYPES = ["conv", "full_attention", "conv", "conv", "conv"]
NET = dict(vocab_size=96, hidden_size=64, num_attention_heads=8,
           num_key_value_heads=2, num_hidden_layers=5, layer_types=TYPES,
           conv_L_cache=3, conv_bias=False, num_dense_layers=1,
           intermediate_size=96, num_experts=8, experts_held=2,
           first_expert_held=0, num_experts_per_tok=2,
           moe_intermediate_size=32, norm_topk_prob=True,
           routed_scaling_factor=1, use_expert_bias=True,
           max_position_embeddings=S, rope_theta=1e6, norm_eps=1e-5)
# Grouped heads' caches are stored flat: 2 cached heads of 8 a row.
CONV_STATE, CACHE = (2, 64), (S, 2 * 8)
# A reset inside the fragment, and an episode one token long after it.
RESET = jnp.zeros((B, S)).at[:, 11].set(1.0).at[:, 12].set(1.0)


def build(dtype, net=NET, bias_scale=None):
    """(model, seeded variables, tokens). The norms' weights are seeded
    too (one at initialisation): a norm with unit weights commutes with
    RoPE, and a per-head norm's place would not show."""
    model = catalog.get_model(None, net["vocab_size"], {
        "custom_model": "lfm2_moe", "custom_model_config": net,
        "compute_dtype": dtype})
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (B, S), 0, net["vocab_size"])
    variables = model.init(jax.random.PRNGKey(0), tokens[:, :1],
                           model.initial_state(B), jnp.zeros((B, 1)))

    def seeded(path, a):
        if not path[-1].key.endswith("norm"):
            return a
        key = jax.random.fold_in(jax.random.PRNGKey(2), zlib.crc32(
            jax.tree_util.keystr(path).encode()) % 2 ** 31)
        return a * (1.0 + 0.5 * jax.random.normal(key, a.shape))
    variables = dict(variables, params=jax.tree_util.tree_map_with_path(
        seeded, variables["params"]))
    if bias_scale is not None:
        # A selection bias as large as the scores' own spread, so that
        # choosing by score + bias and weighing by score differ.
        variables = dict(variables, constants=jax.tree.map(
            lambda b: b * (bias_scale / transformer.ROUTER_BIAS_SCALE),
            variables["constants"]))
    return model, variables, tokens


def judged(system, variables, tokens, net=NET, starts=None):
    """The system's (logits, values, experts) against the reference held
    to those experts: (outputs, routing)."""
    logits, values, experts = system
    held = reference.forward(variables, tokens, net, experts=experts,
                             starts=starts)
    return (reference.compare((logits, values),
                              (held["logits"], held["values"])),
            reference.routing_verdict(experts, held["experts"],
                                      held["select"]))


def causal_routed(model, variables, tokens, reset=None):
    (logits, values, state), kept = model.apply(
        variables, tokens, None,
        jnp.zeros(tokens.shape) if reset is None else reset,
        mutable=["routing", "counters"])
    return (logits, values, kept["routing"]["experts"][-1]), state, kept


def decode_routed(model, variables, tokens, reset=None, jit=True):
    """Every position one token at a time from empty state:
    ((logits, values, experts), the last state, the counters a step)."""
    def step(token, state, reset):
        return model.apply(variables, token, state, reset, method="decode",
                           mutable=["routing", "counters"])
    if jit:
        step = jax.jit(step)
    if reset is None:
        reset = jnp.zeros(tokens.shape)
    state = model.initial_state(B)
    logits, values, experts, counted = [], [], [], []
    for t in range(tokens.shape[1]):
        (step_l, step_v, state), kept = step(
            tokens[:, t], state, reset[:, t])
        logits.append(step_l)
        values.append(step_v)
        experts.append(kept["routing"]["experts"][-1])
        counted.append({k: float(v[-1])
                        for k, v in kept["counters"].items()})
    return (jnp.stack(logits, 1), jnp.stack(values, 1),
            jnp.stack(experts, 2)), state, counted


def state_shapes(state):
    return ([c.shape[1:] for c in jax.tree.leaves(state["kv"])],
            [c.shape[1:] for c in jax.tree.leaves(state["conv"])])


# -- the model against the reference -----------------------------------
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_causal_pass_matches_reference(dtype):
    """float32 blocks: to float32 accuracy, the same experts in every
    layer. bfloat16 blocks: the limits written beside the reference."""
    model, variables, tokens = build(dtype)
    system, state, _ = causal_routed(model, variables, tokens)
    assert system[2].shape == (4, B, S, 2)  # the expert layers
    outputs, routing = judged(system, variables, tokens)
    if dtype == "f32":
        assert routing["router_flips"] == 0.0
        assert max(outputs["errors"].values()) < 1e-5, outputs
    else:
        assert routing["router_flips"] <= 0.1
        assert routing["max_flip_gap"] <= reference.MAX_FLIP_GAP
        assert outputs["ok"], outputs
    # What the pass hands a decode: the one attention layer's K and V,
    # and two rows of every convolution layer, under a key of their own.
    assert state_shapes(state) == ([CACHE] * 2, [CONV_STATE] * 4)
    assert [len(kv) for kv in state["kv"]] == [0, 2, 0, 0, 0]
    assert np.all(np.asarray(state["pos"]) == S)


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_decode_through_both_kinds_of_state_matches_reference(dtype):
    """Against the reference, which has neither cache nor state; and,
    float32, against the causal pass and the state it returns."""
    model, variables, tokens = build(dtype)
    system, state, counted = decode_routed(model, variables, tokens,
                                           jit=dtype == "f32")
    outputs, routing = judged(system, variables, tokens)
    if dtype == "f32":
        assert routing["router_flips"] == 0.0
        assert max(outputs["errors"].values()) < 1e-5, outputs
        causal, handed, _ = causal_routed(model, variables, tokens)
        assert reference.relative_error(system[0], causal[0]) < 1e-5
        assert np.array_equal(system[2], causal[2])
        for got, want in zip(jax.tree.leaves(state),
                             jax.tree.leaves(handed)):
            np.testing.assert_allclose(got, want, atol=1e-5)
    else:
        assert routing["router_flips"] <= 0.1
        assert outputs["ok"], outputs
    assert state_shapes(state) == ([CACHE] * 2, [CONV_STATE] * 4)
    assert state["conv"][0].dtype == (
        jnp.float32 if dtype == "f32" else jnp.bfloat16)
    # The attention layer alone reads a cache: grouped, so all of it.
    assert counted[-1] == {"decode_cache_read_share": 1.0}


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_a_decode_through_the_kernel_form_is_the_causal_pass(
        dtype, kernel_here):
    """The grouped cache through the kernel form (`conftest.kernel_here`:
    three blocks of 8 positions, interpreted), a reset and an episode one
    token long inside the fragment: a step reads the blocks up to the
    furthest position a row holds of its own episode, and the logits are
    the causal pass's over the same resets."""
    model, variables, tokens = build(dtype)
    system, state, counted = decode_routed(model, variables, tokens, RESET,
                                           jit=dtype == "f32")
    # Episodes start at 0, 11 and 12: a row's position within its own.
    place = [t if t < 11 else 0 if t == 11 else t - 12 for t in range(S)]
    assert [step["decode_cache_read_share"] for step in counted] == [
        pytest.approx(8 * (at // 8 + 1) / S) for at in place]
    assert state_shapes(state) == ([CACHE] * 2, [CONV_STATE] * 4)
    if dtype == "f32":
        causal, _, _ = causal_routed(model, variables, tokens, RESET)
        assert reference.relative_error(system[0], causal[0]) < 1e-5
        assert reference.relative_error(system[1], causal[1]) < 1e-5
        assert np.array_equal(system[2], causal[2])
    else:
        outputs, routing = judged(system, variables, tokens, starts=RESET)
        assert routing["router_flips"] <= 0.1
        assert outputs["ok"], outputs


def test_a_decode_continues_a_causal_pass_from_the_state_it_hands_over():
    """Prefixes shorter than the taps, as long, and longer: the pass's
    state is the last two gated inputs (zeros where the episode is
    shorter), and the decode goes on from it."""
    model, variables, tokens = build("f32")
    decode = jax.jit(lambda token, state, reset: model.apply(
        variables, token, state, reset))
    full, _, _ = model.apply(variables, tokens, None, jnp.zeros((B, S)))
    for prefix in (2, 3, 5, 13):
        _, _, state = model.apply(variables, tokens[:, :prefix], None,
                                  jnp.zeros((B, prefix)))
        for t in range(prefix, S):
            step, _, state = decode(tokens[:, t:t + 1], state,
                                    jnp.zeros((B, 1)))
            assert reference.relative_error(
                step[:, 0], full[:, t]) < 1e-5, (prefix, t)


def test_a_reset_inside_a_fragment_and_an_episode_one_token_long():
    """Three episodes in a fragment, the second one token long: what
    separate passes give, in both forms and in the reference; the state
    handed over is the last episode's alone."""
    model, variables, tokens = build("f32")
    both, state, _ = causal_routed(model, variables, tokens, RESET)
    parts = [causal_routed(model, variables, tokens[:, a:b])
             for a, b in ((0, 11), (12, S))]
    # A causal pass takes two tokens or more: the lone token as a decode
    # step from empty state.
    lone, _, _ = model.apply(variables, tokens[:, 11:12],
                             model.initial_state(B), jnp.ones((B, 1)))
    separate = jnp.concatenate(
        [parts[0][0][0], lone, parts[1][0][0]], axis=1)
    assert reference.relative_error(both[0], separate) < 1e-5
    for got, want in zip(jax.tree.leaves(state["conv"]),
                         jax.tree.leaves(parts[1][1]["conv"])):
        np.testing.assert_allclose(got, want, atol=1e-5)
    assert np.all(np.asarray(state["pos"]) == S - 12)
    outputs, routing = judged(both, variables, tokens, starts=RESET)
    assert max(outputs["errors"].values()) < 1e-5, outputs
    assert routing["router_flips"] == 0.0
    stepped, _, _ = decode_routed(model, variables, tokens, RESET)
    assert reference.relative_error(stepped[0], both[0]) < 1e-5
    # A fragment that ends one token into an episode hands over one
    # gated input and a zero row.
    _, _, short = model.apply(
        variables, tokens[:, :13], None, RESET[:, :13])
    for held in jax.tree.leaves(short["conv"]):
        assert not np.any(np.asarray(held[:, 0]))
        assert np.any(np.asarray(held[:, 1]))


def test_the_convolution_is_the_sum_written_out():
    """`_conv_causal` against v_t = sum_j w[:, j] g_{t - 2 + j} written
    as a loop over positions and taps, an episode boundary in the middle."""
    model, variables, tokens = build("f32")
    lp = variables["params"]["layer_2"]
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, 64))
    positions = jnp.broadcast_to(
        jnp.where(jnp.arange(S) < 9, jnp.arange(S), jnp.arange(S) - 9),
        (B, S))
    h, state = model.apply(variables, lp, x, positions,
                           method="_conv_causal")
    n = np.asarray(transformer.rms_norm(x, lp["attn_norm"], 1e-5,
                                        jnp.float32))
    b, c, u = np.split(n @ np.asarray(lp["conv_in"]), 3, axis=-1)
    g, w = b * u, np.asarray(lp["conv_w"])
    v = np.zeros_like(g)
    for t in range(S):
        for j in range(3):
            s = t - 2 + j
            if s >= 0 and (t < 9) == (s < 9):
                v[:, t] += w[:, j] * g[:, s]
    want = np.asarray(x) + (c * v) @ np.asarray(lp["conv_out"])
    np.testing.assert_allclose(h, want, atol=2e-5)
    np.testing.assert_allclose(state, g[:, -2:], atol=1e-6)


@pytest.mark.parametrize("wrong", reference.MUTATIONS + ("float8_e4m3",))
def test_limits_refuse_wrong_mathematics(wrong):
    """The comparison fails each named error and blocks computed a
    precision lower: the reference, so altered, in the system's place
    against itself, by its outputs or by its routing. The fragment holds
    a reset, so that a convolution that reaches across it shows."""
    _, variables, tokens = build("f32", bias_scale=0.2)
    if wrong == "float8_e4m3":
        got = reference.forward(variables, tokens, NET, round_to=wrong,
                                starts=RESET)
    else:
        got = reference.forward(variables, tokens, NET, mutate=wrong,
                                starts=RESET)
    outputs, routing = judged(
        (got["logits"], got["values"], got["experts"]), variables, tokens,
        starts=RESET)
    assert not (outputs["ok"] and routing["ok"]), (wrong, outputs, routing)
    if wrong == "untied_head":
        assert outputs["errors"]["value"] == 0.0  # the head's alone


# -- the router's division ------------------------------------------------
def test_the_renormalisation_s_epsilon_is_the_description_s():
    """`route` divides the chosen scores by their sum, plus the epsilon the
    description has: lfm2_moe's 1e-6, none anywhere else. It shows where
    the chosen scores are small beside it; without it the second
    configuration's numbers are what they were, bit for bit."""
    rng = np.random.default_rng(0)
    n = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 8)) / 8, jnp.float32)
    bias = jnp.asarray(rng.normal(size=8) * 0.02, jnp.float32)
    # Scores near 1e-7: every logit 16 lower.
    shifted = jnp.concatenate([n, jnp.ones((16, 1))], axis=1)
    faint = jnp.concatenate([router, jnp.full((1, 8), -16.0)], axis=0)
    for rows, w in ((n, router), (shifted, faint)):
        scores = jax.nn.sigmoid(jnp.dot(
            rows, w, precision=jax.lax.Precision.HIGHEST))
        p, i = transformer.route(rows, w, 2, True, bias, 1.0, 1e-6)
        chosen = jnp.take_along_axis(scores, i, axis=-1)
        want = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(p, want, rtol=1e-6)
        plain, same = transformer.route(rows, w, 2, True, bias, 1.0)
        assert np.array_equal(i, same)
        np.testing.assert_array_equal(
            plain, chosen / jnp.sum(chosen, axis=-1, keepdims=True))
    # Faint scores: the weights no longer add up to one.
    assert float(jnp.max(jnp.sum(p, axis=-1))) < 0.5
    model, _, _ = build("f32")
    assert model.topk_eps == reference.TOPK_EPS == 1e-6
    for name, cfg in (("glm4_moe_lite", dict(
            q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8)), ("olmoe", {}),
            ("smallthinker", {})):
        other = catalog.get_model(None, 96, {
            "custom_model": name, "custom_model_config": cfg})
        assert other.topk_eps == 0.0 and not other.tie_embeddings


# -- the expert layer that holds a share ---------------------------------
def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four shares of 2 of the 8 experts: their parts add up to what the
    uncut reference gives for the whole layer (the reference's shares, and
    the system's in both forms of its product)."""
    rng = np.random.default_rng(0)
    H, W, E, k, held = 64, 32, 8, 2, 2
    lp = jax.tree.map(jnp.asarray, {
        "router": rng.normal(size=(H, E)).astype(np.float32) / 4,
        "w_gate": rng.normal(size=(E, H, W)).astype(np.float32) / 8,
        "w_up": rng.normal(size=(E, H, W)).astype(np.float32) / 8,
        "w_down": rng.normal(size=(E, W, H)).astype(np.float32) / 6})
    bias = jnp.asarray(rng.normal(size=E) * 0.05, jnp.float32)
    h = jnp.asarray(rng.normal(size=(2, 12, H)), jnp.float32)
    m = transformer.rms_norm(h, jnp.ones(H), 1e-5, jnp.float32)

    def share_of(first, size):
        return dict(lp, **{w: lp[w][first:first + size]
                           for w in ("w_gate", "w_up", "w_down")})

    def layer(first, size):
        net = dict(NET, experts_held=size, first_expert_held=first)
        with jax.default_matmul_precision("highest"):
            return reference._moe(share_of(first, size), bias, h, m, net,
                                  lambda a: a, None, None)
    whole, chosen, _ = layer(0, E)
    parts = sum(layer(first, held)[0] - h for first in range(0, E, held))
    assert reference.relative_error(parts, whole - h) < 1e-5

    # The system's shares of the same routing, in the form each shape
    # takes (24 rows batched, 64 times as many grouped).
    rows = m.reshape(-1, H)
    top_p, top_i = transformer.route(rows, lp["router"], k, True, bias,
                                     1.0, 1e-6)
    assert np.array_equal(np.sort(top_i, -1),
                          np.sort(chosen.reshape(-1, k), -1))
    for reps in (1, 64):
        n, p, i = (jnp.tile(a, (reps, 1)) for a in (rows, top_p, top_i))
        routed, landed = jnp.zeros_like(n), 0
        for first in range(0, E, held):
            s = share_of(first, held)
            part, sizes, _ = dropless_experts(
                n, p, i, s["w_gate"], s["w_up"], s["w_down"], first, E)
            routed, landed = routed + part, landed + int(jnp.sum(sizes))
        assert landed == n.shape[0] * k
        assert reference.relative_error(
            routed[:rows.shape[0]], (whole - h).reshape(-1, H)) < 1e-4
    assert transformer.experts_batched(rows.shape[0], k, E)
    assert not transformer.experts_batched(64 * rows.shape[0], k, E)


def test_a_causal_pass_over_the_landed_rows_is_the_batched_pass(
        grouped_pass_is_the_batched_pass):
    grouped_pass_is_the_batched_pass(*build("f32"))


def published_cut():
    return dict(NET, vocab_size=16384, hidden_size=2048,
                num_attention_heads=32, num_key_value_heads=8,
                intermediate_size=7168, num_experts=32, experts_held=8,
                num_experts_per_tok=4, moe_intermediate_size=1792,
                max_position_embeddings=4096)


def test_the_cell_s_program_is_known_from_its_static_shapes():
    """At the published widths: 507.8 M parameters, the embedding counted
    once; one cache of 4,096 positions, 2,048 bytes a position, and four
    states of two rows, 32,768 bytes a sequence whatever its length;
    heads of 64 take the fused causal form; nothing but shapes is built."""
    net = published_cut()
    model = catalog.get_model(None, net["vocab_size"], {
        "custom_model": "lfm2_moe", "custom_model_config": net})
    assert model.static_counters(64, 4096, "tpu") == {
        "decode_rows_per_expert": 8.0, "decode_experts_batched": 1.0,
        "decode_experts_sparse": 0.0, "decode_experts_read_share": 1.0,
        "decode_cache_block": 128, "decode_attention_kernel": 1.0,
        "causal_attention_fused": 1.0, "kv_cache_bytes_per_token": 2048.0,
        # Heads of 64 are half a lane tile: the learner's rotation keeps
        # `rope` (`rowwise.whole_tiles`).
        "rotation_fused_layers": 0.0,
        "kv_groups": 4, "conv_layers": 4, "conv_state_bytes_per_row": 32768}
    # Off a TPU the cache is read whole, by XLA's products.
    off = model.static_counters(64, 4096, "cpu")
    assert (off["causal_attention_fused"], off["decode_cache_block"],
            off["decode_attention_kernel"]) == (0.0, 4096, 0.0)
    state = jax.eval_shape(lambda: model.initial_state(64))
    assert [c.shape for c in jax.tree.leaves(state["kv"])] == [
        (64, 4096, 8 * 64)] * 2
    assert [c.shape for c in jax.tree.leaves(state["conv"])] == [
        (64, 2, 2048)] * 4
    variables = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
        jax.eval_shape(lambda: model.initial_state(1)),
        jax.ShapeDtypeStruct((1, 1), jnp.float32))
    assert set(variables) == {"params", "constants"}
    assert "head" not in variables["params"]
    assert variables["params"]["layer_1"]["q_norm"].shape == (64,)
    count = sum(int(np.prod(v.shape))
                for v in jax.tree.leaves(variables["params"]))
    conv = 4 * 2048 * 2048 + 2048 * 3
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    experts = 2048 * 32 + 8 * 3 * 2048 * 1792
    assert count == (16384 * 2048 + conv + 3 * 2048 * 7168
                     + attention + experts + 3 * (conv + experts)
                     + 5 * 2 * 2048 + 2048 + 2048 + 1)
    assert count == 507_822_209
    assert sum(int(np.prod(v.shape)) for v in jax.tree.leaves(
        variables["constants"])) == 4 * 32


def test_the_accepted_descriptions_keep_their_state_and_counters():
    """A model whose layers are all attention has no "conv" key in its
    state, and every model with caches of a head's own now says what they
    hold a position."""
    olmoe = catalog.get_model(None, 96, {
        "custom_model": "olmoe", "custom_model_config": dict(
            vocab_size=96, hidden_size=64, num_attention_heads=4,
            num_hidden_layers=2, num_experts=4, num_experts_per_tok=2,
            intermediate_size=32, max_position_embeddings=16)})
    assert set(olmoe.initial_state(2)) == {"kv", "pos"}
    counted = olmoe.static_counters(2, 16, "cpu")
    # K and V, 4 heads of 16 in bfloat16, two layers.
    assert counted["kv_cache_bytes_per_token"] == 2 * 2 * 4 * 16 * 2
    assert "conv_layers" not in counted and "window_layers" not in counted


# -- the loss and the loop ------------------------------------------------
def token_trainer_config(**over):
    cfg = dict(
        env="TokenBigram-v0",
        env_config={"vocab_size": NET["vocab_size"], "episode_len": S},
        anakin=True, num_workers=0, num_envs_per_worker=4,
        rollout_fragment_length=S, train_batch_size=4 * S,
        sgd_minibatch_size=2 * S, num_sgd_iter=1,
        anakin_updates_per_call=1, min_iter_time_s=0, lr=6e-4, seed=3,
        model={"custom_model": "lfm2_moe", "custom_model_config": NET,
               "compute_dtype": "f32"})
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def token_trainer():
    trainer = IMPALATrainer(config=token_trainer_config())
    yield trainer
    trainer.stop()


def seeded_batch(frags, seed):
    """`frags` whole episodes of a walk (`TokenBigram-v0`: the action
    taken is the next observation), as the learner's packed batch and as
    the reference's."""
    rng = np.random.default_rng(seed)
    walk = rng.integers(0, NET["vocab_size"], size=(frags, S + 1))
    ref_batch = {
        "tokens": walk[:, :S], "actions": walk[:, 1:],
        "rewards": rng.integers(0, 2, size=(frags, S)).astype(np.float32),
        "behaviour_logp": rng.uniform(-5.0, -4.0, size=(frags, S)).astype(
            np.float32)}
    dones = np.zeros((frags, S), np.float32)
    dones[:, -1] = 1.0
    batch = {
        sb.OBS: jnp.asarray(ref_batch["tokens"].reshape(-1), jnp.int32),
        sb.ACTIONS: jnp.asarray(ref_batch["actions"].reshape(-1), jnp.int32),
        sb.REWARDS: jnp.asarray(ref_batch["rewards"].reshape(-1)),
        sb.DONES: jnp.asarray(dones.reshape(-1)),
        sb.ACTION_LOGP: jnp.asarray(ref_batch["behaviour_logp"].reshape(-1)),
        sb.VF_PREDS: jnp.zeros(frags * S, jnp.float32),
        sb.BOOTSTRAP_OBS: jnp.asarray(walk[:, S], jnp.int32)}
    return batch, ref_batch


def test_vtrace_minibatch_loss_and_gradients_match_reference(token_trainer):
    """One minibatch of whole episodes through the system's loss (packed
    rows, ACTION_LOGP, the bootstrap step differentiated through both
    kinds of state) and through `jax.grad` of the plain reference; the
    tied embedding's gradient is the lookup's and the head's together; the
    router bias has no gradient and no optimizer state."""
    policy = token_trainer.get_policy()
    batch, ref_batch = seeded_batch(B, 5)
    variables = jax.tree.map(jnp.asarray, policy.get_weights())
    assert set(variables) == {"params", "constants"}
    (total, stats), grads = jax.value_and_grad(
        lambda v: vtrace_loss(policy, v, batch, None, {}),
        has_aux=True)(variables)
    (want_total, _), want_grads = jax.value_and_grad(
        lambda v: reference.vtrace_loss(v, ref_batch, NET, policy.config),
        has_aux=True)(variables)
    np.testing.assert_allclose(total, want_total, rtol=1e-4)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads["params"])
    want_flat = jax.tree.leaves(want_grads["params"])
    assert len(flat) == len(want_flat)
    for (path, got), want in zip(flat, want_flat):
        scale = float(jnp.max(jnp.abs(want))) + 1e-8
        assert float(jnp.max(jnp.abs(got - want))) <= 2e-3 * scale, path
    assert not any(bool(jnp.any(g != 0))
                   for g in jax.tree.leaves(grads["constants"]))
    moments = [leaf for leaf in jax.tree.leaves(policy.opt_state)
               if leaf.dtype == jnp.float32]
    assert len(moments) == 2 * len(jax.tree.leaves(variables["params"]))
    assert stats["expert_load_mean"] > 0
    assert 0.0 < stats["experts_held_row_share"] < 1.0


def one_update(trainer, seed=7, **wrong):
    """One update of seeded whole episodes by the optimizer's own step
    (`AnakinOptimizer.learn`) from the trainer's parameters and optimizer
    state, against the reference's loss, gradients and Adam: what the
    benchmark's driver does at the cell's minibatch. `wrong` plants a
    fault in the reference's side."""
    policy, opt = trainer.get_policy(), trainer.optimizer
    cfg = dict(policy.config, **wrong.get("cfg", {}))
    batch, ref_batch = seeded_batch(opt.minibatch // opt.T, seed)

    def flat(tree):
        return {jax.tree_util.keystr(path): np.asarray(leaf)
                for path, leaf in
                jax.tree_util.tree_flatten_with_path(tree)[0]}
    before = policy.params
    (adam,) = [s for s in jax.tree.leaves(
        policy.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
        if hasattr(s, "mu")]
    after, _, stats = jax.jit(opt.learn)(
        before, policy.opt_state, batch, jax.random.PRNGKey(0))
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(after["constants"]),
        jax.tree.leaves(before["constants"])))
    (want_loss, _), grads = jax.value_and_grad(
        lambda p: reference.vtrace_loss(
            {"params": p, "constants": before["constants"]}, ref_batch,
            NET, cfg, mutate=wrong.get("mutate")),
        has_aux=True)(before["params"])
    count = int(adam.count)
    assert count > 0
    want_change, norm = reference.adam_update(
        flat(grads), flat(adam.mu["params"]), flat(adam.nu["params"]),
        count, cfg)
    assert norm > 0
    old, new = flat(before["params"]), flat(after["params"])
    return reference.compare_update(stats["total_loss"], want_loss, {
        name: float(reference.change_error(old[name], new[name], want))
        for name, want in want_change.items()})


def test_one_update_by_the_optimizer_s_own_step_matches_reference(
        token_trainer):
    token_trainer.train()  # Adam's moments are not zero
    found = one_update(token_trainer)
    assert found["ok"], found
    assert found["loss_error"] < 1e-5 and found["update_error"] < 1e-2, found


WRONG_UPDATES = {
    "taps_reversed_in_the_gradient": dict(mutate="taps_reversed"),
    "qk_norm_after_rope": dict(mutate="qk_norm_after_rope"),
    "an_untied_head": dict(mutate="untied_head"),
    "vf_coeff_doubled": dict(cfg={"vf_loss_coeff": 1.0}, by="loss_error"),
    "no_clip": dict(cfg={"grad_clip": None}, by="update_error"),
    "ten_times_the_lr": dict(cfg={"lr": 6e-3}, by="update_error"),
}


@pytest.mark.parametrize("wrong", WRONG_UPDATES)
def test_update_limits_refuse_a_wrong_update(wrong, token_trainer):
    """The comparison of one update fails each named error, planted in
    the reference's side: by the loss, by the worst parameter's change, or
    by either."""
    token_trainer.train()
    fault = dict(WRONG_UPDATES[wrong])
    by = fault.pop("by", None)
    found = one_update(token_trainer, **fault)
    assert not found["ok"], found
    if by:
        limits = {"loss_error": reference.UPDATE_LOSS_TOLERANCE,
                  "update_error": reference.UPDATE_TOLERANCE}
        assert found[by] > limits[by], found


def test_lfm2_token_trainer_trains_on_the_fused_path(token_trainer):
    """`IMPALATrainer(anakin, TokenBigram-v0, lfm2_moe)` by config alone:
    two iterations, a finite loss, a rising count, a policy state of two
    kinds of leaf carried by the optimizer as one pytree, the new counters
    in `learner_stats`."""
    counts = []
    for _ in range(2):
        result = token_trainer.train()
        stats = result["info"]["learner"]
        assert np.isfinite(stats["total_loss"])
        counts.append(result["timesteps_total"])
    assert counts[1] - counts[0] == 4 * S and counts[0] > 0
    kept = token_trainer.optimizer.learner_stats
    assert kept["expert_load_max"] >= kept["expert_load_mean"] > 0
    # 2 of 8 experts held: about a quarter of the (row, expert) pairs.
    assert 0.05 < kept["experts_held_row_share"] < 0.6
    # What the learner's product gathered: all, in the batched form these
    # sizes take.
    assert kept["dispatch_rows_share"] == 1.0
    assert kept["experts_grouped_kernel"] == 0.0  # this is no TPU
    assert kept["decode_rows_per_expert"] == 4 * 2 / 8
    assert kept["decode_cache_read_share"] == 1.0
    assert kept["causal_attention_fused"] == 0.0
    # float32 here: one layer's 2 x 2 heads x 8 x 4 B a position; four
    # layers' two rows of 64 x 4 B a sequence.
    assert kept["kv_cache_bytes_per_token"] == 128
    assert (kept["conv_layers"], kept["conv_state_bytes_per_row"]) == (
        4, 4 * 2 * 64 * 4)
    state, _ = token_trainer.optimizer._pstate
    assert set(state) == {"kv", "conv", "pos"}
    assert state_shapes(state) == ([CACHE] * 2, [CONV_STATE] * 4)
    # What the benchmark's two readers of the state make of it.
    caches = jax.tree.leaves(state["kv"])
    assert sum(c.nbytes for c in caches) / (4 * S) == 128
    assert sum(c.nbytes for c in jax.tree.leaves(state["conv"])) / 4 == 2048


def test_learner_stats_report_what_the_grouped_kernel_read(kernel_here):
    """The trainer on the fused Anakin path with the kernel form in its
    rollout and under its learner's bootstrap step: the one cache, three
    blocks of 8, fills from empty every rollout and is read 1/2 + block /
    2S of."""
    trainer = IMPALATrainer(config=token_trainer_config())
    try:
        result = trainer.train()
        assert np.isfinite(result["info"]["learner"]["total_loss"])
        kept = trainer.optimizer.learner_stats
        assert kept["decode_cache_read_share"] == pytest.approx(
            0.5 + 8 / (2 * S))
        # The host's counters are of the platform the trainer runs on.
        assert kept["decode_attention_kernel"] == 0.0
        assert kept["decode_cache_block"] == S
    finally:
        trainer.stop()


@pytest.mark.parametrize("cfg,match", [
    ({"n_routed_experts": 8}, "not lfm2_moe's"),
    ({"head_dim": 16}, "not lfm2_moe's"),
    ({"conv_bias": True}, "conv_bias"),
    ({"use_expert_bias": False}, "use_expert_bias"),
    ({"tie_embedding": False}, "tie_embedding"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"num_key_value_heads": 3}, "groups"),
    ({"layer_types": ["conv", "full_attention"]}, "layer_types has 2"),
    ({"layer_types": ["conv", "sliding_attention"] + TYPES[2:]},
     "sliding_attention"),
    ({"experts_held": 6, "first_expert_held": 4}, "not among"),
])
def test_custom_model_config_without_a_part_is_refused(cfg, match):
    with pytest.raises(ValueError, match=match):
        model = catalog.get_model(None, 96, {
            "custom_model": "lfm2_moe",
            "custom_model_config": dict(NET, **cfg)})
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
                   model.initial_state(1), jnp.zeros((1, 1)))


def test_a_tied_head_gives_as_many_logits_as_the_vocabulary_has_ids():
    with pytest.raises(ValueError, match="tied to the embedding"):
        model = transformer.lfm2_moe_from_config(50, NET)
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
                   model.initial_state(1), jnp.zeros((1, 1)))


def test_keys_left_out_have_the_published_model_s_values():
    """An empty description is LFM2-8B-A1B itself: 24 layers, 18 of them
    convolutions, the first two dense, 8.3 B parameters."""
    model = transformer.lfm2_moe_from_config(65536, {})
    kinds = [model.layer_kind(i) for i in range(24)]
    assert kinds.count("conv") == 18 and model.attention_layers == (
        2, 6, 10, 14, 18, 21)
    assert (model.dense_layers, model.num_experts, model.held,
            model.experts_per_token, model.head_width) == (2, 32, 32, 4, 64)
    variables = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
        jax.eval_shape(lambda: model.initial_state(1)),
        jax.ShapeDtypeStruct((1, 1), jnp.float32))
    count = sum(int(np.prod(v.shape))
                for v in jax.tree.leaves(variables["params"]))
    assert 8.2e9 < count < 8.5e9


def test_the_tuned_example_is_the_benchmark_s_cell():
    """`rllib train -f lfm2-token-impala.yaml` and the cell
    `lfm2_token_anakin_4k` are one trainer config, and the configuration's
    file holds every published number of its source but the ones it lists
    as reduced."""
    import json

    import yaml
    root = os.path.dirname(BENCH)
    with open(os.path.join(root, "ray_tpu", "rllib", "tuned_examples",
                           "lfm2-token-impala.yaml")) as f:
        (example,) = yaml.safe_load(f).values()
    with open(os.path.join(
            BENCH, "workloads", "lfm2_token_anakin_4k.json")) as f:
        cell = json.load(f)
    with open(os.path.join(
            BENCH, "configs", "impala_lfm2_8b_a1b.json")) as f:
        config = json.load(f)
    network = {k: v for k, v in config["network"].items()
               if k != "param_count"}
    want = dict(cell["trainer_config"], **config["trainer_config"])
    want["model"] = dict(want["model"], custom_model_config=network)
    want["num_tpus_for_learner"] = cell["chips"]
    assert example["run"] == config["trainer"]
    assert example["env"] == want.pop("env")
    assert example["config"] == want
    # The source's config (the catalog's row), the reduced keys apart.
    published = dict(transformer.LFM2_MOE_PUBLISHED, conv_bias=False,
                     use_expert_bias=True, model_type="lfm2_moe")
    reduced = {"num_hidden_layers": (24, 5), "num_dense_layers": (2, 1),
               "num_experts": (32, 8), "vocab_size": (65536, 16384),
               "max_position_embeddings": (128000, 4096)}
    for key, value in published.items():
        if key in reduced:
            assert (config["published"][key], config[key]) == reduced[key]
        else:
            assert config[key] == value, key
            if key in network and key != "layer_types":
                assert network[key] == value, key
    # The published layers 1-5.
    assert network["layer_types"] == config["layer_types"][1:6] == TYPES
    assert (network["num_experts"], network["experts_held"]) == (32, 8)
    assert config["reduced"] == list(reduced) + ["env"]
    assert set(config["reduced"]) == set(config["reduced_why"])
    # 507,822,209 trained parameters and four routers' 32 biases.
    assert config["network"]["param_count"] == 507_822_337
    model = transformer.lfm2_moe_from_config(16384, network)
    assert (model.hidden_size, model.num_heads, model.kv_heads,
            model.head_width, model.conv_taps, model.dense_width,
            model.expert_width, model.experts_per_token, model.rope_theta,
            model.rms_eps) == (2048, 32, 8, 64, 3, 7168, 1792, 4, 1000000,
                               1e-5)
