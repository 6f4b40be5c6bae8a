"""The `nemotron_h` token policy at a tiny size on the CPU: the model against
the plain reference (`benchmark/lib/reference_nemotron_h.py`, whose Mamba-2 is
the recurrence itself, one position at a time) in its causal form (chunk
terms by a masked cumulative sum, a scan over chunks that carries the
matrix) and in its decode through three kinds of state (a Mamba-2 layer's
matrix a head and its one convolution's last inputs, the attention layer's
grouped cache); layers that are ONE function each; experts without a gate
matrix; `ssd_chunked` alone against a loop of `ssd_step`, gradients too; a
decode that continues a causal pass; resets inside a chunk, at a chunk's
edge, and an episode one token long against separate passes; decays that
lose more than e^100 inside one chunk; the sixteen shares of an expert layer
against the uncut layer; each named wrong mathematics refused by the cell's
limits. V-trace's loss, its gradients, one update of the optimizer's own
against the reference's and the trainer on the fused Anakin path stand in
`tests/test_nemotron_h_update.py`, a file of its own so that the two run on
two workers.
"""

import json
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

from lib import reference_nemotron_h as reference  # noqa: E402

from ray_tpu.models import catalog, transformer  # noqa: E402
from ray_tpu.models.transformer import dropless_experts  # noqa: E402

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


def build(dtype, net=NET, bias_scale=None, tokens=S):
    """(model, seeded variables, tokens). The norms' weights and D are
    seeded too (one at initialisation), so that a norm's place shows."""
    model = catalog.get_model(None, net["vocab_size"], {
        "custom_model": "nemotron_h", "custom_model_config": net,
        "compute_dtype": dtype})
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (B, tokens), 0, net["vocab_size"])
    variables = model.init(jax.random.PRNGKey(0), tokens[:, :1],
                           model.initial_state(B), jnp.zeros((B, 1)))

    def seeded(path, a):
        if not path[-1].key.endswith(("norm", "ssm_d")):
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


def plain(variables, tokens, net=NET, experts=None, starts=None, **how):
    """The reference's forward, compiled (its scans run op by op
    otherwise)."""
    return jax.jit(lambda v, t, e, s: reference.forward(
        v, t, net, experts=e, starts=s, **how))(
            variables, tokens, experts, starts)


def judged(system, variables, tokens, net=NET, starts=None):
    """The system's (logits, values, experts) against the reference held
    to those experts: (outputs, routing)."""
    logits, values, experts = system
    held = plain(variables, tokens, net, experts, starts)
    return (reference.compare((logits, values),
                              (held["logits"], held["values"])),
            reference.routing_verdict(experts, held["experts"],
                                      held["select"]))


def within_bfloat16(system, variables, tokens, net=NET, starts=None):
    """Blocks in bfloat16, at these widths: the limits at the published
    widths are no measure here, where the reference itself, its blocks
    rounded to bfloat16, stands several per cent from its float32 self. The
    system is held to that: no further off than twice the rounded
    reference, and its routing within a tenth."""
    outputs, routing = judged(system, variables, tokens, net, starts)
    low = plain(variables, tokens, net, starts=starts,
                round_to=jnp.bfloat16)
    rounded, _ = judged((low["logits"], low["values"], low["experts"]),
                        variables, tokens, net, starts)
    assert routing["router_flips"] <= 0.1, routing
    for name, error in outputs["errors"].items():
        assert error <= 2 * rounded["errors"][name] < 0.3, (
            outputs, rounded)


def causal_routed(model, variables, tokens, reset=None):
    (logits, values, state), kept = jax.jit(
        lambda v, t, r: model.apply(v, t, None, r,
                                    mutable=["routing", "counters"]))(
            variables, tokens,
            jnp.zeros(tokens.shape) if reset is None else reset)
    return (logits, values, kept["routing"]["experts"][-1]), state, kept


def decode_routed(model, variables, tokens, reset=None, jit=True,
                  between=None):
    """Every position one token at a time from empty state:
    ((logits, values, experts), the last state, the counters a step).
    `between` alters the state after every step."""
    def step(token, state, reset):
        return model.apply(variables, token, state, reset, method="decode",
                           mutable=["routing", "counters"])
    if jit:
        step = jax.jit(step)
    if reset is None:
        reset = jnp.zeros(tokens.shape)
    state = model.initial_state(tokens.shape[0])
    logits, values, experts, counted = [], [], [], []
    for t in range(tokens.shape[1]):
        (step_l, step_v, state), kept = step(
            tokens[:, t], state, reset[:, t])
        if between is not None:
            state = between(state)
        logits.append(step_l)
        values.append(step_v)
        experts.append(kept["routing"]["experts"][-1])
        counted.append({k: float(v[-1])
                        for k, v in kept["counters"].items()})
    return (jnp.stack(logits, 1), jnp.stack(values, 1),
            jnp.stack(experts, 2)), state, counted


def state_shapes(state):
    return tuple([c.shape[1:] for c in jax.tree.leaves(state[key])]
                 for key in ("kv", "conv", "ssm"))


STATE_SHAPES = ([CACHE] * 2, [TAILS] * 3, [MATRIX] * 3)


# -- the model against the reference -----------------------------------
@pytest.mark.parametrize("tokens", [S, S - 3])
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_causal_pass_matches_reference(dtype, tokens):
    """A fragment of whole chunks and one that ends inside a chunk.
    float32 blocks: to float32 accuracy, the same experts in every layer.
    bfloat16 blocks: as near as the reference rounded where they round."""
    net = dict(NET, max_position_embeddings=tokens)
    model, variables, tokens = build(dtype, net, tokens=tokens)
    system, state, _ = causal_routed(model, variables, tokens)
    assert system[2].shape == (3, B, tokens.shape[1], 2)  # expert layers
    if dtype == "f32":
        held = plain(variables, tokens, net, system[2])
        assert np.array_equal(np.sort(system[2], -1),
                              np.sort(held["experts"], -1))
        for got, want in zip(system[:2], (held["logits"], held["values"])):
            assert reference.relative_error(got, want) < 1e-5
        # The matrix states the scan hands over are the recurrence's.
        for got, want in zip(jax.tree.leaves(state["ssm"]),
                             held["ssm_states"]):
            assert reference.relative_error(got, want) < 1e-5
    else:
        within_bfloat16(system, variables, tokens, net)
    # What the pass hands a decode: the one grouped cache, three layers'
    # convolution inputs, three layers' matrices, a key a kind; a layer
    # that is its feed-forward alone keeps nothing.
    cache = (tokens.shape[1], CACHE[1])
    assert state_shapes(state) == ([cache] * 2, [TAILS] * 3, [MATRIX] * 3)
    assert [len(kv) for kv in state["kv"]] == [0, 0, 0, 0, 0, 2, 0]
    assert [np.ndim(c) for c in state["conv"]] == [3, 1, 3, 1, 3, 1, 1]
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(state["ssm"]))
    assert np.all(np.asarray(state["pos"]) == tokens.shape[1])


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_decode_through_three_kinds_of_state_matches_reference(dtype):
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
            np.testing.assert_allclose(got, want, atol=2e-5)
    else:
        within_bfloat16(system, variables, tokens)
    assert state_shapes(state) == STATE_SHAPES
    # The matrix state is float32 whatever the blocks compute in; the
    # convolution's inputs and the cache are the blocks'.
    blocks = jnp.float32 if dtype == "f32" else jnp.bfloat16
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(state["ssm"]))
    assert all(a.dtype == blocks for a in jax.tree.leaves(
        (state["conv"], state["kv"])))
    # The attention layer alone reads a cache: off a TPU, all of it.
    assert counted[-1] == {"decode_cache_read_share": 1.0}


def test_a_layer_is_one_function_and_an_expert_has_no_gate():
    """The parameters say it: a mixer layer has the operator's norm and no
    feed-forward, an expert layer the feed-forward's norm, two matrices an
    expert and two for the shared one, and no operator."""
    _, variables, _ = build("f32")
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


def scalar_of(logits, values):
    weight = jax.random.normal(jax.random.PRNGKey(7), logits.shape)
    return jnp.sum(logits * weight) + jnp.sum(jnp.sin(values))


def model_gradients(variables, tokens, reset=None):
    """The gradient of one scalar of the outputs with respect to every
    parameter, through the system's scan over chunks and through the
    reference's recurrence."""
    model, _, _ = build("f32")

    def system(params):
        logits, values, _ = model.apply(
            dict(variables, params=params), tokens, None,
            jnp.zeros(tokens.shape) if reset is None else reset)
        return scalar_of(logits, values)

    def recurrence(params):
        out = reference.forward(dict(variables, params=params), tokens, NET,
                                starts=reset)
        return scalar_of(out["logits"], out["values"])
    return (jax.jit(jax.grad(system))(variables["params"]),
            jax.jit(jax.grad(recurrence))(variables["params"]))


_OPERATOR = {}  # compiled once for the fragment whole, once cut by resets


def operator_gradients(variables, layer, reset=None):
    """One Mamba-2 layer alone, x + Mamba2(RMSNorm(x)) of seeded x: (the
    outputs, the gradients of a scalar of them with respect to the layer's
    parameters) through the system's scan over chunks and through the
    reference's recurrence."""
    if (reset is None) not in _OPERATOR:
        model, _, _ = build("f32")
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
    _, variables, _ = build("f32")
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


@pytest.mark.parametrize("reset", [None, RESET], ids=["whole", "resets"])
def test_the_model_s_gradient_is_the_reference_s(reset):
    """Every parameter of the seven layers, through three scans, the
    attention layer and three expert layers without a gate matrix."""
    _, variables, tokens = build("f32")
    got, want = model_gradients(variables, tokens, reset)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert reference.relative_error(a, b) < 5e-5


def test_a_decode_continues_a_causal_pass_from_the_state_it_hands_over():
    """Prefixes shorter than the taps, at a chunk's edge, inside a chunk:
    the pass's state is the matrix after its last position and the
    convolution's last three inputs (zeros where the episode is shorter),
    and the decode goes on from it."""
    model, variables, tokens = build("f32")
    decode = jax.jit(lambda token, state, reset: model.apply(
        variables, token, state, reset))
    (full, _, _), _, _ = causal_routed(model, variables, tokens)
    for prefix in (2, 3, 5, 8, 13):
        _, state, _ = causal_routed(model, variables, tokens[:, :prefix])
        for t in range(prefix, S):
            step, _, state = decode(tokens[:, t:t + 1], state,
                                    jnp.zeros((B, 1)))
            assert reference.relative_error(
                step[:, 0], full[:, t]) < 1e-5, (prefix, t)


def test_resets_inside_a_chunk_at_its_edge_and_an_episode_one_token_long():
    """Four episodes in a fragment, the second one token long, the last
    beginning with a chunk: what separate passes give, in both forms and
    in the reference; the state handed over is the last episode's alone."""
    model, variables, tokens = build("f32")
    both, state, _ = causal_routed(model, variables, tokens, RESET)
    parts = []
    for a, b in EPISODES:
        if b - a > 1:
            parts.append(causal_routed(model, variables, tokens[:, a:b]))
        else:
            # A causal pass takes two tokens or more: the lone token as a
            # decode step from empty state.
            lone, _, _ = model.apply(variables, tokens[:, a:b],
                                     model.initial_state(B), jnp.ones((B, 1)))
            parts.append(((lone,), None, None))
    separate = jnp.concatenate([p[0][0] for p in parts], axis=1)
    assert reference.relative_error(both[0], separate) < 1e-5
    last = parts[-1][1]
    for key in ("conv", "ssm"):
        for got, want in zip(jax.tree.leaves(state[key]),
                             jax.tree.leaves(last[key])):
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(state["pos"]) == S - 16)
    outputs, routing = judged(both, variables, tokens, starts=RESET)
    assert max(outputs["errors"].values()) < 1e-5, outputs
    assert routing["router_flips"] == 0.0
    stepped, stepped_state, _ = decode_routed(model, variables, tokens, RESET)
    assert reference.relative_error(stepped[0], both[0]) < 1e-5
    for got, want in zip(jax.tree.leaves(stepped_state["ssm"]),
                         jax.tree.leaves(state["ssm"])):
        np.testing.assert_allclose(got, want, atol=1e-5)
    # A fragment that ends one token into an episode hands over one input
    # of the convolution, two zero rows, and a matrix of rank one a head.
    _, _, short = model.apply(
        variables, tokens[:, :13], None, RESET[:, :13])
    for held in jax.tree.leaves(short["conv"]):
        assert not np.any(np.asarray(held[:, :2]))
        assert np.any(np.asarray(held[:, 2]))
    for held in jax.tree.leaves(short["ssm"]):
        assert np.all(np.linalg.matrix_rank(np.asarray(held)) == 1)


def test_decays_that_lose_e100_inside_a_chunk_stay_finite_and_agree():
    """Every exponent of the scan is a sum of log decays: the whole model's
    outputs and gradients are finite and the recurrence's, which multiplies
    by exp(la) one position at a time."""
    model, variables, tokens = build("f32")
    variables = fast_decays(variables)
    lp = variables["params"]["layer_0"]
    assert CHUNK * float(jnp.min(jnp.exp(lp["ssm_a_log"]))) * 15.0 > 100.0
    for reset in (None, RESET):
        system, state, _ = causal_routed(model, variables, tokens, reset)
        assert all(np.isfinite(a).all() for a in system[:2])
        outputs, routing = judged(system, variables, tokens, starts=reset)
        assert max(outputs["errors"].values()) < 1e-5, outputs
        assert routing["router_flips"] == 0.0
    got, want = model_gradients(variables, tokens, RESET)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(a).all()
        assert reference.relative_error(a, b) < 2e-4
    stepped, _, _ = decode_routed(model, variables, tokens, RESET)
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


def read_by(run, operands):
    """(outputs, final state, gradients by x, B, C, la) of a scalar that
    reads every output and every entry of the final state."""
    def scalar(*operands):
        y, S = run(*operands)
        return (jnp.sum(jnp.sin(y) * jnp.arange(1, y.shape[1] + 1)[
            None, :, None, None]) + jnp.sum(jnp.cos(S))), (y, S)
    grads, (y, S) = jax.jit(jax.grad(
        scalar, argnums=(0, 1, 2, 3), has_aux=True))(*operands)
    return (y, S) + grads


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


# -- what the limits refuse ------------------------------------------------
@pytest.mark.parametrize("wrong", reference.MUTATIONS + ("float8_e4m3",))
def test_limits_refuse_wrong_mathematics(wrong):
    """The comparison fails each named error and blocks computed a
    precision lower: the reference, so altered, in the system's place
    against itself, by its outputs or by its routing. The fragment holds
    resets, so that a convolution that reaches across one shows."""
    _, variables, tokens = build("f32", bias_scale=0.2)
    # The one attention layer's softmax far enough from uniform, and its
    # output large enough beside the other layers', that a rotation shows
    # in the logits.
    params = dict(variables["params"])
    params["layer_5"] = dict(params["layer_5"],
                             wq=4.0 * params["layer_5"]["wq"],
                             wk=4.0 * params["layer_5"]["wk"],
                             wo=3.0 * params["layer_5"]["wo"])
    variables = dict(variables, params=params)
    if wrong == "float8_e4m3":
        got = plain(variables, tokens, starts=RESET, round_to=wrong)
    else:
        got = plain(variables, tokens, starts=RESET, mutate=wrong)
    outputs, routing = judged(
        (got["logits"], got["values"], got["experts"]), variables, tokens,
        starts=RESET)
    assert not (outputs["ok"] and routing["ok"]), (wrong, outputs, routing)


def test_a_bfloat16_matrix_state_is_refused_by_the_decode_s_limit():
    """The state is summed into at every step, so keeping it in bfloat16
    (rounded after every step; everything else float32) is no rounding of
    a block's output: its error is carried on and added to. Over a few
    hundred steps the logits leave the reference by more than the cell's
    limit, where the float32 state's stay at 1e-5."""
    steps = 384
    net = dict(NET, max_position_embeddings=steps)
    model, variables, tokens = build("f32", net, tokens=steps)
    # Decays slow enough that a state holds hundreds of positions.
    params = dict(variables["params"])
    for layer in SSM_LAYERS:
        params[layer] = dict(
            params[layer],
            ssm_a_log=params[layer]["ssm_a_log"] - jnp.log(16.0))
    variables = dict(variables, params=params)

    def rounded(state):
        return dict(state, ssm=jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
            state["ssm"]))
    kept, _, _ = decode_routed(model, variables, tokens)
    lost, _, _ = decode_routed(model, variables, tokens, between=rounded)
    outputs, _ = judged(kept, variables, tokens, net)
    assert max(outputs["errors"].values()) < 1e-5, outputs
    held = plain(variables, tokens, net, kept[2])
    wrong = reference.compare(lost[:2], (held["logits"], held["values"]))
    assert not wrong["ok"], wrong


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

    def share_of(first, size):
        return dict(lp, **{w: lp[w][first:first + size]
                           for w in ("w_up", "w_down")})

    def layer(first, size, shared=1):
        share = dict(net, experts_held=size, first_expert_held=first,
                     n_shared_experts=shared)
        with jax.default_matmul_precision("highest"):
            return reference._moe(share_of(first, size), bias, h, m, share,
                                  lambda a: a, None, None)
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
            s = share_of(first, held)
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


def test_a_causal_pass_over_the_landed_rows_is_the_batched_pass(
        grouped_pass_is_the_batched_pass):
    """The grouped form without a gate matrix, its `switch` over the row
    counts and their pullbacks of two products."""
    grouped_pass_is_the_batched_pass(*build("f32"))


# -- the cell, from its static shapes ---------------------------------------
def published_cut():
    with open(os.path.join(
            BENCH, "configs", "impala_nemotron_twotower_30b_a3b.json")) as f:
        net = json.load(f)["network"]
    return {k: v for k, v in net.items() if k != "param_count"}


def test_the_cell_s_program_is_known_from_its_static_shapes():
    """At the published widths a decode step of 128 rows sends 6 rows to a
    held expert, in the batched form; the learner's 8,192 rows go grouped,
    through a ladder of row counts; the one grouped cache takes the kernel
    (32 query heads over 2 cached ones: 256 lanes a position) and the
    causal pass the fused form; three matrix states of 2 MB a row; nothing
    but shapes is built."""
    net = published_cut()
    model = catalog.get_model(None, net["vocab_size"], {
        "custom_model": "nemotron_h", "custom_model_config": net})
    assert model.static_counters(128, 2048, "tpu") == {
        "decode_rows_per_expert": 6.0, "decode_experts_batched": 1.0,
        "decode_experts_sparse": 0.0, "decode_experts_read_share": 1.0,
        "decode_cache_block": 128, "decode_attention_kernel": 1.0,
        "causal_attention_fused": 1.0, "kv_cache_bytes_per_token": 1024.0,
        "rotation_fused_layers": 0.0, "kv_groups": 16, "conv_layers": 3,
        "conv_state_bytes_per_row": 110592, "ssm_layers": 3,
        "ssm_state_bytes_per_row": 6291456, "ssm_chunk": 128,
        "state_step_kernel": 0.0}
    off = model.static_counters(128, 2048, "cpu")
    assert (off["causal_attention_fused"], off["decode_cache_block"],
            off["decode_attention_kernel"], off["state_step_kernel"]) == (
                0.0, 2048, 0.0, 0.0)
    assert transformer.grouped_fused(2048, 2, 32, 128)
    assert not transformer.experts_batched(8192, 6, 128)
    assert transformer.dispatch_rows(8192, 6, 8, 128) == (
        3840, 6144, 12288, 49152)
    state = jax.eval_shape(lambda: model.initial_state(128))
    assert set(state) == {"kv", "conv", "ssm", "pos"}
    assert [a.shape for a in jax.tree.leaves(state["ssm"])] == [
        (128, 64, 64, 128)] * 3
    assert [a.shape for a in jax.tree.leaves(state["conv"])] == [
        (128, 3, 6144)] * 3
    assert [a.shape for a in jax.tree.leaves(state["kv"])] == [
        (128, 2048, 256)] * 2
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
        model.initial_state(1), jnp.zeros((1, 1))))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 528_095_809


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


@pytest.mark.parametrize("cfg,match", [
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
    (dict(mamba_num_heads=9), "groups"),
])
def test_custom_model_config_without_a_part_is_refused(cfg, match):
    net = dict(NET, **cfg)
    with pytest.raises(ValueError, match=match):
        model = catalog.get_model(None, 96, {
            "custom_model": "nemotron_h", "custom_model_config": net})
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
            model.initial_state(1), jnp.zeros((1, 1))))


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
    published = published_cut()
    assert transformer.nemotron_h_from_config(16384, published).held == 8


def test_the_tuned_example_is_the_benchmark_s_cell():
    import yaml
    root = os.path.dirname(BENCH)
    with open(os.path.join(root, "ray_tpu", "rllib", "tuned_examples",
                           "nemotron-h-token-impala.yaml")) as f:
        (example,) = yaml.safe_load(f).values()
    with open(os.path.join(BENCH, "workloads",
                           "nemotron_h_token_anakin_2k.json")) as f:
        cell = json.load(f)["trainer_config"]
    config = example["config"]
    assert config["model"]["custom_model"] == "nemotron_h"
    assert config["model"]["custom_model_config"] == published_cut()
    for key, value in cell.items():
        if key != "env":
            assert config[key] == value, key
    assert example["env"] == cell["env"]
