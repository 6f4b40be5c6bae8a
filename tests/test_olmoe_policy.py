"""The OLMoE token policy at a tiny size on the CPU: the model against the
plain reference (`benchmark/lib/reference_olmoe.py`), decode through the
cache against the causal pass, the V-trace loss and its gradients, the
dropless dispatch under skewed routing, V-trace from ACTION_LOGP, the
trainer on the fused Anakin path, and the Nature-CNN Anakin program's
outputs as they were before any of it.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import reference_olmoe as reference  # noqa: E402

from ray_tpu.models import catalog, transformer  # noqa: E402
from ray_tpu.models.transformer import (  # noqa: E402
    dropless_experts, experts_batched)
from ray_tpu.rllib import sample_batch as sb  # noqa: E402
from ray_tpu.rllib.agents.impala import IMPALATrainer  # noqa: E402
from ray_tpu.rllib.agents.impala.vtrace_policy import vtrace_loss  # noqa: E402

NET = dict(vocab_size=128, hidden_size=64, num_attention_heads=4,
           num_key_value_heads=4, num_hidden_layers=2, num_experts=8,
           num_experts_per_tok=2, intermediate_size=32,
           max_position_embeddings=16, rope_theta=10000.0,
           rms_norm_eps=1e-5, norm_topk_prob=False)
B, S = 3, 16


def build(dtype, net=NET):
    window = net["max_position_embeddings"]
    model = catalog.get_model(None, net["vocab_size"], {
        "custom_model": "olmoe", "custom_model_config": net,
        "compute_dtype": dtype})
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (B, window), 0, net["vocab_size"])
    params = model.init(jax.random.PRNGKey(0), tokens[:, :1],
                        model.initial_state(B), jnp.zeros((B, 1)))
    return model, params, tokens


@pytest.fixture
def blocks_of_4(monkeypatch):
    """The tiny window of 16 positions as four blocks of the decode's
    attention (the constant is read when a step is traced)."""
    monkeypatch.setattr(transformer, "DECODE_CACHE_BLOCK", 4)
    return 4


def token_trainer_config(**over):
    cfg = dict(
        env="TokenBigram-v0",
        env_config={"vocab_size": NET["vocab_size"], "episode_len": S},
        anakin=True, num_workers=0, num_envs_per_worker=8,
        rollout_fragment_length=S, train_batch_size=8 * S,
        sgd_minibatch_size=2 * S, num_sgd_iter=1,
        anakin_updates_per_call=1, min_iter_time_s=0, lr=6e-4, seed=3,
        model={"custom_model": "olmoe", "custom_model_config": NET})
    cfg.update(over)
    return cfg


# -- the model against the reference ------------------------------------
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_causal_pass_matches_reference(dtype):
    """float32 block: to float32 accuracy, same expert sets. bfloat16
    block: the limits written beside the reference (outputs against the
    reference held to the system's experts; its choice of experts against
    the reference's own)."""
    model, params, tokens = build(dtype)
    (logits, values, _), kept = model.apply(
        params, tokens, None, jnp.zeros((B, S)), mutable=["routing"])
    experts = kept["routing"]["experts"][-1]
    want = reference.forward(params["params"], tokens, NET)
    routing = reference.routing_verdict(experts, want[2], want[3])
    held = reference.forward(params["params"], tokens, NET, experts=experts)
    verdict = reference.compare((logits, values), held[:2])
    if dtype == "f32":
        assert routing["router_flips"] == 0.0
        assert max(verdict["errors"].values()) < 1e-5, verdict
    else:
        # 96 (token, layer) pairs: one flip is 1 %, and it is a near-tie.
        assert routing["router_flips"] <= 0.1
        assert routing["max_flip_gap"] <= reference.MAX_FLIP_GAP
        assert verdict["ok"], verdict


@pytest.mark.parametrize("wrong", ["drop_last_expert", "renormalise",
                                   "float8_e4m3"])
def test_limits_refuse_wrong_mathematics(wrong):
    """The comparison fails a dropped expert, renormalised weights and a
    block computed a precision lower (the reference, so altered, against
    itself; the router held to the same experts)."""
    _, params, tokens = build("f32")
    want = reference.forward(params["params"], tokens, NET)
    if wrong == "float8_e4m3":
        got = reference.forward(params["params"], tokens, NET,
                                round_to=wrong, experts=want[2])
    else:
        got = reference.forward(params["params"], tokens, NET, mutate=wrong)
    assert not reference.compare(got[:2], want[:2])["ok"]


def test_a_wrong_router_is_refused_by_its_flips():
    """Experts chosen from probabilities that are off by more than a
    rounding are not near-ties of the reference's."""
    _, params, tokens = build("f32")
    want = reference.forward(params["params"], tokens, NET)
    probs = np.asarray(want[3])
    noisy = probs * np.random.default_rng(0).uniform(0.7, 1.3, probs.shape)
    experts = np.argsort(-noisy, axis=-1)[..., :NET["num_experts_per_tok"]]
    verdict = reference.routing_verdict(experts, want[2], want[3])
    assert not verdict["ok"] and verdict["max_flip_gap"] > 0.05
    same = reference.routing_verdict(want[2], want[2], want[3])
    assert same == {"router_flips": 0.0, "max_flip_gap": 0.0, "ok": True}


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_decode_through_cache_matches_causal_pass(dtype, blocks_of_4):
    """Every position decoded one token at a time against the cache, the
    window four blocks of the decode's attention."""
    model, params, tokens = build(dtype)
    logits, values, _ = model.apply(params, tokens, None, jnp.zeros((B, S)))
    state, got_l, got_v, read = model.initial_state(B), [], [], []

    def decode(token, state):
        return model.apply(params, token, state, jnp.zeros(B),
                           method="decode", mutable=["counters"])
    if dtype == "f32":
        # One program a step, for the time it saves. Not in bfloat16:
        # XLA:CPU then rounds elsewhere than the causal pass's eager ops
        # do, and one near-tie of the router moves a token's logits.
        decode = jax.jit(decode)
    for t in range(S):
        (step_l, step_v, state), kept = decode(tokens[:, t], state)
        got_l.append(step_l)
        got_v.append(step_v)
        read.append(float(kept["counters"]["decode_cache_read_share"][-1]))
    assert S // blocks_of_4 >= 3
    assert read == [(t // blocks_of_4 + 1) * blocks_of_4 / S
                    for t in range(S)]
    tol = 1e-5 if dtype == "f32" else reference.TOLERANCE
    assert reference.relative_error(jnp.stack(got_l, 1), logits) <= tol
    assert reference.relative_error(jnp.stack(got_v, 1), values) <= tol
    assert np.all(np.asarray(state["pos"]) == S)


def test_prefill_then_decode_and_reset_inside_a_fragment(blocks_of_4):
    """A causal pass returns a cache a decode can continue from, and a
    reset inside a fragment starts a fresh episode: positions restart and
    nothing attends across the boundary (the decode then reads one block
    of a cache whose later blocks still hold the episode before)."""
    model, params, tokens = build("f32")
    decode = jax.jit(lambda token, state, reset: model.apply(
        params, token, state, reset, mutable=["counters"]))
    full, _, _ = model.apply(params, tokens, None, jnp.zeros((B, S)))
    _, _, state = model.apply(params, tokens[:, :10], None,
                              jnp.zeros((B, 10)))
    for t in range(10, S):
        (step, _, state), _ = decode(
            tokens[:, t:t + 1], state, jnp.zeros((B, 1)))
        assert reference.relative_error(step[:, 0], full[:, t]) < 1e-5
    # Two episodes of 8 in one fragment == the two halves on their own.
    reset = jnp.zeros((B, S)).at[:, 8].set(1.0)
    both, _, state = model.apply(params, tokens, None, reset)
    second, _, _ = model.apply(params, tokens[:, 8:], None,
                               jnp.zeros((B, 8)))
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
    _, _, state = model.apply(params, tokens[:, :10], None,
                              jnp.zeros((B, 10)))
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
    model, params, tokens = build(dtype, net)
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
    model, params, tokens = build("f32")
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
        check=True, timeout=300,
        cwd=os.path.dirname(BENCH), env=dict(os.environ, JAX_PLATFORMS="cpu"))


# -- the loss -------------------------------------------------------------
@pytest.fixture(scope="module")
def token_trainer():
    trainer = IMPALATrainer(config=token_trainer_config(
        model={"custom_model": "olmoe", "custom_model_config": NET,
               "compute_dtype": "f32"}))
    yield trainer
    trainer.stop()


def test_vtrace_minibatch_loss_and_gradients_match_reference(token_trainer):
    """One minibatch of whole episodes through the system's loss (packed
    rows, ACTION_LOGP, the bootstrap step from the final cache) and
    through `jax.grad` of the plain reference."""
    policy = token_trainer.get_policy()
    cfg = policy.config
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, NET["vocab_size"], size=(B, S))
    actions = rng.integers(0, NET["vocab_size"], size=(B, S))
    rewards = rng.integers(0, 2, size=(B, S)).astype(np.float32)
    behaviour_logp = rng.uniform(-5.5, -4.0, size=(B, S)).astype(np.float32)
    dones = np.zeros((B, S), np.float32)
    dones[:, -1] = 1.0
    batch = {
        sb.OBS: jnp.asarray(tokens.reshape(-1), jnp.int32),
        sb.ACTIONS: jnp.asarray(actions.reshape(-1), jnp.int32),
        sb.REWARDS: jnp.asarray(rewards.reshape(-1)),
        sb.DONES: jnp.asarray(dones.reshape(-1)),
        sb.ACTION_LOGP: jnp.asarray(behaviour_logp.reshape(-1)),
        sb.BOOTSTRAP_OBS: jnp.asarray(tokens[:, 0], jnp.int32),
    }
    params = jax.tree.map(jnp.asarray, policy.get_weights())

    def system(p):
        return vtrace_loss(policy, p, batch, None, {})

    (total, stats), grads = jax.value_and_grad(system, has_aux=True)(params)
    ref_batch = {"tokens": tokens, "actions": actions, "rewards": rewards,
                 "behaviour_logp": behaviour_logp}
    (want_total, parts), want_grads = jax.value_and_grad(
        lambda p: reference.vtrace_loss(p, ref_batch, NET, cfg),
        has_aux=True)(params["params"])
    np.testing.assert_allclose(total, want_total, rtol=1e-4)
    np.testing.assert_allclose(
        stats["entropy"] * B * S, parts["entropy"], rtol=1e-4)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads["params"])
    want_flat = jax.tree.leaves(want_grads)
    assert len(flat) == len(want_flat)
    for (path, got), want in zip(flat, want_flat):
        scale = float(jnp.max(jnp.abs(want))) + 1e-8
        assert float(jnp.max(jnp.abs(got - want))) <= 2e-3 * scale, path
    assert stats["expert_load_mean"] == B * S * 2 / NET["num_experts"]


def test_vtrace_from_logp_equals_vtrace_from_dist_inputs():
    """A 6-action batch: behaviour log-probabilities read from ACTION_LOGP
    give the loss, the stats and the gradients that the stored behaviour
    logits give."""
    trainer = IMPALATrainer(config=dict(
        env="SyntheticAtari-v0", num_workers=0, rollout_fragment_length=5,
        train_batch_size=20, min_iter_time_s=0, seed=2))
    try:
        policy = trainer.get_policy()
        assert policy.dist_dim == 6
        rng = np.random.default_rng(1)
        n, T = 20, 5
        logits = rng.normal(size=(n, 6)).astype(np.float32)
        actions = rng.integers(0, 6, size=n)
        logp = jax.nn.log_softmax(logits)[np.arange(n), actions]
        batch = {
            sb.OBS: jnp.asarray(rng.integers(
                0, 256, size=(n, 84, 84, 4)), jnp.uint8),
            sb.ACTIONS: jnp.asarray(actions),
            sb.REWARDS: jnp.asarray(rng.normal(size=n), jnp.float32),
            sb.DONES: jnp.asarray(rng.integers(0, 2, size=n), jnp.float32),
            sb.BOOTSTRAP_OBS: jnp.asarray(rng.integers(
                0, 256, size=(n // T, 84, 84, 4)), jnp.uint8),
        }
        with_logits = dict(batch, **{sb.ACTION_DIST_INPUTS: logits})
        with_logp = dict(batch, **{sb.ACTION_LOGP: logp})
        params = policy.params
        out = [jax.value_and_grad(
            lambda p, b=b: vtrace_loss(policy, p, b, None, {}),
            has_aux=True)(params) for b in (with_logits, with_logp)]
        ((loss_a, stats_a), grads_a), ((loss_b, stats_b), grads_b) = out
        np.testing.assert_allclose(loss_a, loss_b, rtol=1e-6)
        for key in stats_a:
            np.testing.assert_allclose(stats_a[key], stats_b[key],
                                       rtol=1e-5, atol=1e-7)
        for a, b in zip(jax.tree.leaves(grads_a), jax.tree.leaves(grads_b)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
        assert float(stats_a["is_ratio_max"]) != 1.0  # off-policy batch
    finally:
        trainer.stop()


# -- the loop -------------------------------------------------------------
def test_token_trainer_trains_on_the_fused_path(token_trainer):
    """`IMPALATrainer(anakin, TokenBigram-v0)` by config alone: two
    iterations, a finite loss, a rising count."""
    counts = []
    for _ in range(2):
        result = token_trainer.train()
        stats = result["info"]["learner"]
        assert np.isfinite(stats["total_loss"])
        counts.append(result["timesteps_total"])
    assert counts[1] > counts[0] > 0
    assert counts[1] - counts[0] == 8 * S
    # Four minibatches a rollout: the later ones are off-policy, so the
    # importance ratios have left 1.
    assert stats["is_ratio_max"] > 1.0
    assert stats["expert_load_max"] >= stats["expert_load_mean"] > 0
    # The rollout's decode step, from its static shape: 8 rows to 2 of 8
    # experts, multiplied in the batched form.
    kept = token_trainer.optimizer.learner_stats
    assert kept["decode_rows_per_expert"] == 2.0
    assert kept["decode_experts_batched"] == 1.0
    assert kept["experts_grouped_kernel"] == 0.0  # this is no TPU
    # Every expert is here: no share of the pairs to count.
    assert "experts_held_row_share" not in kept
    assert "dispatch_rows_share" not in kept
    # Its attention: the window of 16 is one block, read whole every step.
    assert kept["decode_cache_block"] == S
    assert kept["decode_cache_read_share"] == 1.0
    # The learner's attention: 16 tokens are no two tiles, and this is
    # no TPU.
    assert kept["causal_attention_fused"] == 0.0


@pytest.mark.parametrize("episode_len,share", [(S, 0.5 + 4 / (2 * S)),
                                               (1, 4 / S)])
def test_decode_cache_counters_in_learner_stats(episode_len, share,
                                                blocks_of_4):
    """`decode_cache_read_share` is reduced on the device from the value
    that selects the blocks: a window that fills from empty reads
    1/2 + b/(2S) of itself over a rollout, one held at position 0 (every
    step ends an episode) one block of four; `decode_cache_block` is the
    host's constant."""
    trainer = IMPALATrainer(config=token_trainer_config(
        env_config={"vocab_size": NET["vocab_size"],
                    "episode_len": episode_len}))
    try:
        trainer.train()
        kept = trainer.optimizer.learner_stats
        assert kept["decode_cache_block"] == blocks_of_4
        assert kept["decode_cache_read_share"] == pytest.approx(share)
    finally:
        trainer.stop()


def test_wide_action_space_keeps_logp_not_logits():
    """Decided from the action space's size: a 50,304-way policy's
    trajectory carries ACTION_LOGP and VF_PREDS, a 6-way one its logits."""
    import ray_tpu.rllib.policy.jax_policy as jp
    wide = dict(NET, vocab_size=jp.MAX_KEPT_DIST_INPUTS + 8)
    trainer = IMPALATrainer(config=token_trainer_config(
        env_config={"vocab_size": wide["vocab_size"], "episode_len": S},
        num_envs_per_worker=2, train_batch_size=2 * S,
        sgd_minibatch_size=S,
        model={"custom_model": "olmoe", "custom_model_config": wide}))
    try:
        policy = trainer.get_policy()
        assert not policy.keeps_dist_inputs
        seen = {}
        loss_fn = policy._loss_fn

        def spy(pol, params, batch, rng, loss_state):
            seen.update({k: v.shape for k, v in batch.items()
                         if hasattr(v, "shape")})
            return loss_fn(pol, params, batch, rng, loss_state)

        policy._loss_fn = spy
        trainer.optimizer._anakin_fn = trainer.optimizer._build_fn()
        result = trainer.train()
        assert np.isfinite(result["info"]["learner"]["total_loss"])
        assert sb.ACTION_DIST_INPUTS not in seen
        assert seen[sb.ACTION_LOGP] == (S,) and seen[sb.VF_PREDS] == (S,)
    finally:
        trainer.stop()


def test_context_window_policy_needs_whole_episodes():
    with pytest.raises(ValueError, match="whole episodes"):
        IMPALATrainer(config=token_trainer_config(
            env_config={"vocab_size": NET["vocab_size"], "episode_len": 12}))


@pytest.mark.parametrize("minibatch", [0, 40])
def test_lstm_policy_trains_on_the_fused_path(minibatch):
    """The LSTM's (c, h) is a case of the carried policy state: replayed
    from `state_in`, the one-update rollout is exactly on-policy."""
    trainer = IMPALATrainer(config=dict(
        env="CartPole-v0", anakin=True, num_workers=0,
        num_envs_per_worker=8, rollout_fragment_length=10,
        train_batch_size=80, sgd_minibatch_size=minibatch,
        anakin_updates_per_call=2, min_iter_time_s=0, seed=1,
        model={"use_lstm": True, "lstm_cell_size": 16,
               "fcnet_hiddens": [16]}))
    try:
        stats = trainer.train()["info"]["learner"]
        assert np.isfinite(stats["total_loss"])
        if minibatch == 0:
            assert stats["is_ratio_max"] == pytest.approx(1.0, abs=1e-5)
        else:
            assert stats["is_ratio_max"] > 1.0
    finally:
        trainer.stop()


def test_nature_cnn_anakin_outputs_unchanged():
    """The Nature-CNN path through the same functions is the program it
    was: for a fixed seed the stats of two calls are those of the parent
    commit (24c7a04, recorded from its tree on this CPU)."""
    trainer = IMPALATrainer(config=dict(
        env="SyntheticAtari-v0", env_config={"episode_len": 8},
        anakin=True, num_workers=0, num_envs_per_worker=4,
        rollout_fragment_length=4, train_batch_size=16,
        anakin_updates_per_call=2, min_iter_time_s=0, lr=6e-4,
        grad_clip=40.0, seed=7))
    want = [
        {"entropy": 1.79152250289917, "mean_kl_behaviour": 0.0,
         "policy_loss": -0.16051942110061646,
         "total_loss": -0.646298885345459, "vf_loss": 0.27608194947242737,
         "vtrace_mean_vs": 0.3140600919723511},
        {"entropy": 1.791407823562622, "mean_kl_behaviour": 0.0,
         "policy_loss": -0.019576922059059143,
         "total_loss": 0.47002220153808594, "vf_loss": 0.1337347775697708,
         "vtrace_mean_vs": 0.37526535987854004},
    ]
    try:
        assert trainer.get_policy().keeps_dist_inputs
        for expected in want:
            stats = trainer.train()["info"]["learner"]
            for key, value in expected.items():
                assert stats[key] == pytest.approx(value, rel=1e-4,
                                                   abs=1e-6), key
            assert stats["is_ratio_max"] == 1.0  # one update: on-policy
            assert not [k for k in stats if k.startswith("decode_")]
    finally:
        trainer.stop()
