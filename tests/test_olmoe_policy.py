"""The OLMoE token policy at a tiny size on the CPU: the family's row, the
checks it shares with the other families (`tests/token_families.py`: the model
against the plain reference `benchmark/lib/reference_olmoe.py`, each named
wrong mathematics refused by the cell's limits) and what is its own: a wrong
router refused by its flips, decode through the cache in blocks against the
causal pass and the whole window, the learner's bootstrap step through it,
and the dropless dispatch under skewed routing in both forms of its product.
The loss and the loop: `tests/test_olmoe_update.py`.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from token_families import (  # noqa: F401: pytest collects what is named
    ROOT, Family, build, causal_routed, decode_routed, plain,
    test_causal_pass_matches_reference, test_limits_refuse_wrong_mathematics)

from lib import reference_olmoe as reference

from ray_tpu.models import catalog, transformer
from ray_tpu.models.transformer import dropless_experts, experts_batched

NET = dict(vocab_size=128, hidden_size=64, num_attention_heads=4,
           num_key_value_heads=4, num_hidden_layers=2, num_experts=8,
           num_experts_per_tok=2, intermediate_size=32,
           max_position_embeddings=16, rope_theta=10000.0,
           rms_norm_eps=1e-5, norm_topk_prob=False)
B, S = 3, 16


def forward(variables, tokens, net, experts=None, starts=None, **how):
    """`reference_olmoe.forward` under the later references' convention:
    it takes the parameters alone and returns a tuple; held to `experts` it
    returns them as its choice, so its own choice is a free pass's."""
    params = variables["params"]
    logits, values, own, probs = reference.forward(
        params, tokens, net, experts=experts, **how)
    if experts is not None:
        _, _, own, probs = reference.forward(params, tokens, net, **how)
    return {"logits": logits, "values": values, "experts": own,
            "select": probs}


FAMILY = Family(
    name="olmoe", net=NET, reference=reference, B=B, S=S, forward=forward,
    loss=lambda variables, batch, net, cfg: reference.vtrace_loss(
        variables["params"], batch, net, cfg),
    # A dropped expert, renormalised weights (and a block computed a
    # precision lower).
    mutations=("drop_last_expert", "renormalise"), envs=8)


@pytest.fixture
def blocks_of_4(monkeypatch):
    """The tiny window of 16 positions as four blocks of the decode's
    attention (the constant is read when a step is traced)."""
    monkeypatch.setattr(transformer, "DECODE_CACHE_BLOCK", 4)
    return 4


def test_a_wrong_router_is_refused_by_its_flips():
    """Experts chosen from probabilities that are off by more than a
    rounding are not near-ties of the reference's."""
    _, variables, tokens = build(FAMILY, "f32")
    want = plain(FAMILY, variables, tokens)
    probs = np.asarray(want["select"])
    noisy = probs * np.random.default_rng(0).uniform(0.7, 1.3, probs.shape)
    experts = np.argsort(-noisy, axis=-1)[..., :NET["num_experts_per_tok"]]
    verdict = reference.routing_verdict(
        experts, want["experts"], want["select"])
    assert not verdict["ok"] and verdict["max_flip_gap"] > 0.05
    same = reference.routing_verdict(
        want["experts"], want["experts"], want["select"])
    assert same == {"router_flips": 0.0, "max_flip_gap": 0.0, "ok": True}


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_decode_through_cache_matches_causal_pass(dtype, blocks_of_4):
    """Every position decoded one token at a time against the cache, the
    window four blocks of the decode's attention."""
    built = build(FAMILY, dtype, fresh=True)
    _, variables, tokens = built
    # One program a form in float32, for the time it saves. Not in
    # bfloat16: XLA:CPU then rounds elsewhere than the other form's ops
    # do, and one near-tie of the router moves a token's logits.
    jit = dtype == "f32"
    (logits, values, _), _, _ = causal_routed(
        built, variables, tokens, jit=jit)
    (got_l, got_v, _), state, counted = decode_routed(
        built, variables, tokens, jit=jit)
    assert S // blocks_of_4 >= 3
    assert [step["decode_cache_read_share"] for step in counted] == [
        (t // blocks_of_4 + 1) * blocks_of_4 / S for t in range(S)]
    tol = 1e-5 if dtype == "f32" else reference.TOLERANCE
    assert reference.relative_error(got_l, logits) <= tol
    assert reference.relative_error(got_v, values) <= tol
    assert np.all(np.asarray(state["pos"]) == S)


def test_prefill_then_decode_and_reset_inside_a_fragment(blocks_of_4):
    """A causal pass returns a cache a decode can continue from, and a
    reset inside a fragment starts a fresh episode: positions restart and
    nothing attends across the boundary (the decode then reads one block
    of a cache whose later blocks still hold the episode before)."""
    built = build(FAMILY, "f32", fresh=True)
    model, params, tokens = built
    decode = jax.jit(lambda token, state, reset: model.apply(
        params, token, state, reset, mutable=["counters"]))

    def causal(tokens, reset=None):
        (logits, _, _), state, _ = causal_routed(
            built, params, tokens, reset)
        return logits, state
    full, _ = causal(tokens)
    _, state = causal(tokens[:, :10])
    for t in range(10, S):
        (step, _, state), _ = decode(
            tokens[:, t:t + 1], state, jnp.zeros((B, 1)))
        assert reference.relative_error(step[:, 0], full[:, t]) < 1e-5
    # Two episodes of 8 in one fragment == the two halves on their own.
    reset = jnp.zeros((B, S)).at[:, 8].set(1.0)
    both, state = causal(tokens, reset)
    second, _ = causal(tokens[:, 8:])
    assert reference.relative_error(both[:, 8:], second) < 1e-5
    assert reference.relative_error(both[:, :8], full[:, :8]) < 1e-5
    assert np.all(np.asarray(state["pos"]) == 8)
    # ... and the decode honours the same reset.
    state = model.initial_state(B)
    for t in range(S):
        (step, _, state), _ = decode(
            tokens[:, t:t + 1], state, reset[:, t:t + 1])
        assert reference.relative_error(step[:, 0], both[:, t]) < 1e-5
    # ... also after a prefill, and in one row of the batch only: that row
    # restarts at position 0, the others go on from 10, and the blocks read
    # are those of the furthest row.
    _, state = causal(tokens[:, :10])
    one_row = jnp.zeros((B, 1)).at[0, 0].set(1.0)
    (step, _, state), kept = decode(tokens[:, 10:11], state, one_row)
    alone, _, _ = model.apply(params, tokens[:1, 10:11],
                              jnp.zeros((1, 1)), method="causal")
    assert reference.relative_error(step[:1, 0], alone[:, 0]) < 1e-5
    assert reference.relative_error(step[1:, 0], full[1:, 10]) < 1e-5
    assert np.all(np.asarray(state["pos"]) == [1, 11, 11])
    assert float(kept["counters"]["decode_cache_read_share"][-1]) == 12 / S


# -- the decode's attention, blocked -------------------------------------
def whole_window_attention(q, k_cache, v_cache, pos):
    """The plain form of `transformer.cached_attention` (the decode's
    arithmetic before the window was read in blocks): every position of the
    window scored, masked, and multiplied by its weight."""
    S = k_cache.shape[1]
    held = jnp.arange(S)[None, :] <= pos[:, None]
    scores = jnp.einsum(
        "bhd,bshd->bhs", q, k_cache,
        preferred_element_type=jnp.float32) * (q.shape[-1] ** -0.5)
    scores = jnp.where(held[:, None], scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,bshd->bhd", attn, v_cache), jnp.asarray(S)


# The published head shape (16 heads of 128) over the cell's window at a
# batch of 3, experts cut; and the rehearsal's tiny model, whose window of
# 16 is cut into blocks of 4 by the test.
PUBLISHED_HEADS = dict(NET, hidden_size=2048, num_attention_heads=16,
                       num_key_value_heads=16, num_hidden_layers=1,
                       max_position_embeddings=1024)
BLOCKED_SIZES = {"published_heads": (PUBLISHED_HEADS, None),
                 "rehearsal": (NET, 4)}
# Where each of the three rows stands, in terms of the block b and the
# window S (position p: the row holds p positions and this step writes p).
ROW_POSITIONS = {
    "pos_0": lambda b, S: [0, 0, 0],
    "pos_b-1": lambda b, S: [b - 1] * 3,
    "pos_b": lambda b, S: [b] * 3,
    "pos_S-1": lambda b, S: [S - 1] * 3,
    "rows_apart": lambda b, S: [0, 2 * b, b - 1],
    "rows_apart_to_the_end": lambda b, S: [S - 1, 0, b],
}


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("where", ROW_POSITIONS)
@pytest.mark.parametrize("size", BLOCKED_SIZES)
def test_blocked_decode_step_equals_whole_window(size, where, dtype,
                                                 monkeypatch):
    """A decode step reading the blocks up to the furthest row == the same
    step against the whole window, wherever the rows stand; positions past
    a row's own hold another episode's keys and values, not zeros."""
    net, block = BLOCKED_SIZES[size]
    if block:
        monkeypatch.setattr(transformer, "DECODE_CACHE_BLOCK", block)
    block, window = transformer.DECODE_CACHE_BLOCK, \
        net["max_position_embeddings"]
    assert window // block >= 3
    model, params, tokens = build(FAMILY, dtype, net, fresh=True)
    state = model.initial_state(B)
    keys = jax.random.split(jax.random.PRNGKey(2), 2 * len(state["kv"]))
    filled = [jax.random.normal(k, state["kv"][0][0].shape).astype(
        state["kv"][0][0].dtype) for k in keys]
    pos = jnp.asarray(ROW_POSITIONS[where](block, window), jnp.int32)
    state = {"kv": tuple(zip(filled[::2], filled[1::2])), "pos": pos}

    def step():
        return model.apply(params, tokens[:, 0], state, jnp.zeros(B),
                           method="decode", mutable=["counters"])
    (logits, values, after), kept = step()
    monkeypatch.setattr(transformer, "cached_attention",
                        whole_window_attention)
    (want_logits, want_values, want_after), plain = step()
    tol = 1e-5 if dtype == "f32" else reference.TOLERANCE
    assert reference.relative_error(logits, want_logits) <= tol
    assert reference.relative_error(values, want_values) <= tol
    for got, want in zip(jax.tree.leaves(after), jax.tree.leaves(want_after)):
        assert jnp.array_equal(got, want)  # the write at `pos`, pos + 1
    blocks = int(max(pos)) // block + 1
    assert float(kept["counters"]["decode_cache_read_share"][-1]) \
        == blocks * block / window
    assert float(plain["counters"]["decode_cache_read_share"][-1]) == 1.0


@pytest.mark.parametrize("reset", [0.0, 1.0])
def test_bootstrap_step_gradients_equal_whole_window(reset, blocks_of_4,
                                                     monkeypatch):
    """The learner's bootstrap step, one decode from the causal pass's
    cache under `jax.value_and_grad` with the parameters and the carry
    differentiated: it compiles, and value and gradients are those of the
    whole-window form (a loop whose trip count came from `pos` would have
    no transpose)."""
    model, params, tokens = build(FAMILY, "f32", fresh=True)
    weights = jax.random.normal(jax.random.PRNGKey(3),
                                (B, NET["vocab_size"]))

    def loss(p):
        _, _, carry = model.apply(p, tokens[:, :10], None,
                                  jnp.zeros((B, 10)))
        logits, value, _ = model.apply(
            p, tokens[:, 10:11], carry, jnp.full((B, 1), reset))
        return jnp.sum(value) + jnp.sum(logits[:, 0] * weights)

    got, got_grads = jax.jit(jax.value_and_grad(loss))(params)
    monkeypatch.setattr(transformer, "cached_attention",
                        whole_window_attention)
    want, want_grads = jax.jit(jax.value_and_grad(loss))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(got_grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-8
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * scale, path
    # Through the cache: without a reset the step attends the prefill's
    # keys, and only then has their projection a gradient.
    wk = np.asarray(got_grads["params"]["layer_1"]["wk"])
    assert np.any(wk != 0.0) == (reset == 0.0)


ROUTINGS = ["one_expert_takes_all", "one_expert_takes_none", "random"]
# The form is forced by the shape, as the program's is: (M, E) on either
# side of `experts_batched` at k = 2.
FORM_SHAPES = {"batched": (24, 4), "grouped": (1024, 8)}
# Of the output's scale. float32: summation order (both forms read under
# 1e-6). bfloat16: operands and intermediate products are rounded to 8 bits
# of mantissa, so the block's own limit against the float32 reference; both
# forms read 0.6-0.8 % forward and 0.7-1.3 % in the gradients here.
FORM_TOLERANCE = {"f32": 2e-4, "bf16": reference.TOLERANCE}
# The three routings in the batched form in float32 keep the ids they had
# before there were two forms.
FORM_CASES = [
    pytest.param(routing, form, dtype,
                 id=routing if (form, dtype) == ("batched", "f32")
                 else f"{routing}-{form}-{dtype}")
    for form in FORM_SHAPES for dtype in FORM_TOLERANCE
    for routing in ROUTINGS]


def expert_case(routing, form, dtype):
    """Seeded rows, weights and a routing for `dropless_experts`, in the
    shape that takes `form`; (n, top_p, top_i, w_gate, w_up, w_down)."""
    (M, E), (H, W, k) = FORM_SHAPES[form], (16, 8, 2)
    assert experts_batched(M, k, E) == (form == "batched")
    cd = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
    rng = np.random.default_rng(0)
    n = jnp.asarray(rng.normal(size=(M, H)), cd)
    w_gate, w_up = (jnp.asarray(rng.normal(size=(E, H, W)), cd)
                    for _ in range(2))
    w_down = jnp.asarray(rng.normal(size=(E, W, H)), cd)
    if routing == "one_expert_takes_all":
        top_i = np.stack([np.full(M, 2), rng.integers(0, 2, M)], axis=1)
    elif routing == "one_expert_takes_none":
        top_i = np.stack([rng.permutation(M) % 3, np.full(M, 0)], axis=1)
        top_i[:, 1] = (top_i[:, 0] + 1) % 3  # experts from 3 on get nothing
    else:
        top_i = np.stack([rng.permutation(E)[:k] for _ in range(M)])
    top_i = jnp.asarray(top_i, jnp.int32)
    top_p = jnp.asarray(rng.uniform(0.05, 0.5, size=(M, k)), jnp.float32)
    return n, top_p, top_i, w_gate, w_up, w_down


def dense_masked_loop(n, top_p, top_i, w_gate, w_up, w_down):
    """Every expert on every token times its weight or 0, in float32."""
    n, w_gate, w_up, w_down = (
        a.astype(jnp.float32) for a in (n, w_gate, w_up, w_down))
    want = jnp.zeros(n.shape)
    for e in range(w_gate.shape[0]):
        weight = jnp.sum(jnp.where(top_i == e, top_p, 0.0), axis=1)
        out = (jax.nn.silu(n @ w_gate[e]) * (n @ w_up[e])) @ w_down[e]
        want = want + weight[:, None] * out
    return want


@pytest.mark.parametrize("routing,form,dtype", FORM_CASES)
def test_dropless_dispatch_equals_dense_masked_loop(routing, form, dtype):
    """Both forms of the expert product (sort, grouped product, un-sort;
    batched products over all experts) == every expert on every token times
    a 0/1 mask, whatever the group sizes (a full group, an empty one)."""
    case = expert_case(routing, form, dtype)
    got, group_sizes, _ = dropless_experts(*case)
    assert got.dtype == case[0].dtype
    assert reference.relative_error(
        got.astype(jnp.float32), dense_masked_loop(*case)) \
        <= FORM_TOLERANCE[dtype]
    M, k = case[2].shape
    assert int(jnp.sum(group_sizes)) == M * k
    if routing == "one_expert_takes_all":
        assert int(group_sizes[2]) == M
    if routing == "one_expert_takes_none":
        assert int(jnp.sum(group_sizes[3:])) == 0


@pytest.mark.parametrize("dtype", FORM_TOLERANCE)
@pytest.mark.parametrize("form", FORM_SHAPES)
@pytest.mark.parametrize("routing", ROUTINGS)
def test_dropless_dispatch_gradients_equal_dense_masked_loop(
        routing, form, dtype):
    """Both forms are differentiable, and their gradients with respect to
    the rows and the three weight tensors are the dense masked loop's."""
    n, top_p, top_i, *weights = expert_case(routing, form, dtype)
    target = jnp.asarray(
        np.random.default_rng(1).normal(size=n.shape), jnp.float32)

    def loss(experts):
        return lambda n, *w: jnp.sum(
            experts(n, top_p, top_i, *w).astype(jnp.float32) * target)

    got = jax.grad(loss(lambda *a: dropless_experts(*a)[0]),
                   argnums=(0, 1, 2, 3))(n, *weights)
    want = jax.grad(loss(dense_masked_loop), argnums=(0, 1, 2, 3))(
        n, *weights)
    for name, g, w in zip(("n", "w_gate", "w_up", "w_down"), got, want):
        assert g.dtype == n.dtype
        assert reference.relative_error(g.astype(jnp.float32), w) \
            <= FORM_TOLERANCE[dtype], name


def test_the_form_is_chosen_from_the_static_shape():
    """At the published widths a decode step of 128 sequences (16 rows an
    expert) traces to no grouped product, the learner's causal pass over a
    minibatch of 8,192 tokens to three; nothing but shapes is built."""
    net = dict(NET, vocab_size=50304, hidden_size=2048,
               num_attention_heads=16, num_key_value_heads=16,
               num_hidden_layers=1, num_experts=64, num_experts_per_tok=8,
               intermediate_size=1024, max_position_embeddings=1024)
    model = catalog.get_model(None, net["vocab_size"], {
        "custom_model": "olmoe", "custom_model_config": net})
    assert experts_batched(128, 8, 64) and not experts_batched(8192, 8, 64)
    # No decode flag: a causal pass over 4 tokens is batched too, a decode
    # of 2,048 sequences grouped.
    assert experts_batched(4, 8, 64) and not experts_batched(2048, 8, 64)
    assert model.static_counters(128, 1024, "tpu") == {
        "decode_rows_per_expert": 16.0, "decode_experts_batched": 1.0,
        "decode_experts_sparse": 0.0, "decode_experts_read_share": 1.0,
        "decode_cache_block": transformer.DECODE_CACHE_BLOCK,
        "decode_attention_kernel": 0.0, "causal_attention_fused": 1.0,
        "rotation_fused_layers": float(net["num_hidden_layers"]),
        # K and V of 16 heads of 128 in bfloat16, a layer (PR 38: every
        # model with caches of a head's own says so).
        "kv_cache_bytes_per_token": 2 * 16 * 128 * 2.0 * net[
            "num_hidden_layers"]}
    assert model.static_counters(
        2048, 1024, "tpu")["decode_experts_batched"] == 0.0

    def shapes(b, t):
        return (jax.ShapeDtypeStruct((b, t), jnp.int32),
                jax.eval_shape(lambda: model.initial_state(b)),
                jax.ShapeDtypeStruct((b, t), jnp.float32))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), *shapes(1, 1))
    decode = str(jax.make_jaxpr(model.apply)(params, *shapes(128, 1)))
    learn = str(jax.make_jaxpr(model.apply)(params, *shapes(8, 1024)))
    # A grouped product at these widths is `grouped_product`'s two forms,
    # the kernel's and `ragged_dot`'s, under one name (PR 47).
    assert decode.count("= ragged_dot_general[") == 0
    assert decode.count("name=_fused_product") == 0
    assert learn.count("name=_fused_product") == 3


def test_policies_without_experts_never_import_the_transformer():
    """The Nature-CNN cells' entry point does not load the module."""
    subprocess.run(
        [sys.executable, "-c",
         "import ray_tpu.rllib.agents.impala, sys; "
         "assert 'ray_tpu.models.transformer' not in sys.modules"],
        check=True, timeout=300, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
