"""The `sdar_moe` token policy, which GENERATES BY DIFFUSION OVER BLOCKS, at a
tiny size on the CPU: the family's row, the checks it shares with the other
families (`tests/token_families.py`: each named wrong mathematics refused by
the cell's limits, the cell's program from its shapes, the builder's
refusals, the tuned example) and what is its own: the rollout's block step
through the cache (S denoising passes and a commit pass a block) against the
plain reference (`benchmark/lib/reference_sdar_moe.py`) on the trace it
sampled, for S in {1, 2, 4} at a block of 4: log-probabilities, values, and
the order the positions were unmasked in; the learner's pass over the same
trace against the reference, and its log-probabilities equal to the rollout's
at unchanged parameters; the learner's last layer, whose clean stream stops
at its keys and values, against the whole form written out here (logits,
values, every parameter's gradient, the routing collection, the tiles the
fused mask visits); the reference's 2T form against the block-by-block
definition; a block of one position and one pass against a plain masked
forward; the eight shares of an expert layer against the uncut layer; the
block-level V-trace against a hand-rolled one. The loss and the loop:
`tests/test_sdar_update.py`.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from token_families import (  # noqa: F401: pytest collects what is named
    Family, build, plain, test_custom_model_config_without_a_part_is_refused,
    test_limits_refuse_wrong_mathematics,
    test_the_cell_s_program_is_known_from_its_static_shapes,
    test_the_tuned_example_is_the_benchmark_s_cell)

from lib import reference_sdar_moe as reference

from ray_tpu.models import transformer
from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.agents.impala import vtrace, vtrace_policy

# Two layers; 4 query heads over 2 cached ones of 16; 4 of 8 experts of 32
# held, 2 a token; an episode of 24 positions, the first given.
T, N = 24, 3
NET = dict(vocab_size=96, hidden_size=64, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
           num_experts=8, experts_held=4, first_expert_held=0,
           num_experts_per_tok=2, moe_intermediate_size=32,
           norm_topk_prob=True, max_position_embeddings=T,
           rope_theta=1000000, rms_norm_eps=1e-6, block_length=4,
           denoise_steps=2)
CFG = {"gamma": 0.99, "lambda": 1.0, "vf_loss_coeff": 0.5,
       "entropy_coeff": 0.01, "vtrace_clip_rho_threshold": 1.0,
       "vtrace_clip_pg_rho_threshold": 1.0}


def seeded(path, a):
    """The norms' weights are seeded too (one at initialisation), so that
    a norm's place shows."""
    if not path[-1].key.endswith("norm"):
        return a
    key = jax.random.fold_in(jax.random.PRNGKey(2), a.size + len(path))
    return a * (1.0 + 0.5 * jax.random.normal(key, a.shape))


def sdar(dtype="f32", **changed):
    """(model, seeded variables, net) of the tiny model, its description
    `changed`."""
    built = build(FAMILY, dtype, dict(NET, **changed))
    return built.model, built.variables, built.net


@functools.lru_cache(maxsize=None)
def rollout_of(model, seed, positions):
    """The rollout of one episode a row, compiled once a model."""
    first = jax.random.randint(jax.random.PRNGKey(seed), (N,), 0,
                               model.mask_id)

    def step(variables, carry, key):
        state, reset = carry
        (tokens, logp, steps, value, state), kept = model.apply(
            variables, first, state, reset, key, method="block_step",
            mutable=["routing", "counters"])
        commit = kept["routing"]["commit_experts"][-1]
        commit = jnp.concatenate(
            [commit, jnp.full((1,) + commit.shape[1:], -1)], axis=0)
        return (state, jnp.zeros_like(reset)), (
            tokens, logp, steps, value, commit,
            kept["routing"]["experts"][-1])
    keys = jax.random.split(jax.random.PRNGKey(seed + 1),
                            positions // model.block_len)
    return jax.jit(lambda variables: jax.lax.scan(
        functools.partial(step, variables),
        (model.initial_state(N), jnp.ones(N)), keys)[1])


def rollout(model, variables, net, seed=5, positions=T):
    """One episode a row by the model's own block steps from an empty
    cache: the trace (tokens, logp, steps [N, T], values [N, T / L]) and the
    experts each pass chose, in the learner's layout [layers, N, (S + 1) T,
    k] (the commit passes' first, whose last layer chooses none: -1)."""
    S = net["denoise_steps"]
    tokens, logp, steps, values, commit, noisy = rollout_of(
        model, seed, positions)(variables)

    def rows(x):
        """[blocks, N, L, ..] -> [N, T, ..]."""
        return jnp.swapaxes(x, 0, 1).reshape((N, positions) + x.shape[3:])
    # [blocks, layers, N, L, k] -> [layers, N, T, k], a stream at a time.
    streams = [commit] + [noisy[:, s] for s in range(S)]
    experts = jnp.concatenate([
        jnp.moveaxis(x, 0, 2).reshape(x.shape[1], N, positions, -1)
        for x in streams], axis=2)
    return {"tokens": rows(tokens), "logp": rows(logp),
            "steps": rows(steps), "values": values.T, "experts": experts}


FAMILY = Family(
    name="sdar_moe", net=NET, reference=reference, B=N, S=T,
    # The MASK id is the vocabulary's last: the policy has one output less.
    outputs=lambda net: net["vocab_size"] - 1, seeded=seeded,
    # The reference reads the trace the model's own rollout sampled.
    inputs=lambda built: rollout(built.model, built.variables, built.net),
    forward=lambda variables, trace, net, starts=None, **how:
        reference.forward(variables, trace["tokens"], trace["steps"], net,
                          **how),
    refused=(
        ({"num_shared_experts": 1}, 95, "not sdar_moe's"),
        ({"use_sliding_window": True}, 95, "use_sliding_window"),
        ({"tie_word_embeddings": True}, 95, "tie_word_embeddings"),
        ({}, 96, "MASK id"),
        ({"block_length": 4, "denoise_steps": 3}, 95, "block"),
        ({"block_length": 5}, 95, "block")),
    example="sdar-token-impala.yaml", cell="sdar_block_token_anakin_2k",
    config="impala_sdar_30b_a3b",
    program=dict(
        rows=64, fragment=2048, minibatch=(8192,),
        on_tpu={
            # 64 blocks of 4 rows, 8 of 128 experts each: 16 rows a held
            # expert.
            "decode_rows_per_expert": 16.0, "decode_experts_batched": 1.0,
            "decode_experts_sparse": 0.0, "decode_experts_read_share": 1.0,
            "decode_cache_block": 128, "decode_attention_kernel": 1.0,
            "causal_attention_fused": 1.0, "rotation_fused_layers": 5.0,
            "block_len": 4, "denoise_steps": 2,
            "decode_passes_per_token": 0.75,
            # Five layers of three streams, the last one's clean stream
            # left out.
            "learner_rows_per_token": 3 - 1 / 5,
            "block_attention_kernel": 1.0,
            "block_cache_writes_per_block": 1,
            "experts_grouped_kernel": 1.0,
            # 5 layers x (K and V) x 4 heads x 128 x 2 bytes.
            "kv_cache_bytes_per_token": 5 * 2048, "kv_groups": 8},
        off_tpu={
            "decode_cache_block": 2048, "decode_attention_kernel": 0.0,
            "causal_attention_fused": 0.0, "rotation_fused_layers": 0.0,
            "block_attention_kernel": 0.0, "experts_grouped_kernel": 0.0},
        state={"kv": [((64, 2048, 512), "bfloat16")] * 10},
        parameters=550987009))


def taken(logits, tokens):
    """log-probabilities [N, T] of `tokens` under logits over the real ids."""
    logp = jax.nn.log_softmax(logits[..., :NET["vocab_size"] - 1], axis=-1)
    return jnp.take_along_axis(logp, jnp.minimum(
        tokens, logp.shape[-1] - 1)[..., None], axis=-1)[..., 0]


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_block_step_through_the_cache_matches_reference(passes):
    model, variables, net = sdar(denoise_steps=passes)
    trace = rollout(model, variables, net)
    L = net["block_length"]
    steps = np.asarray(trace["steps"])
    # The first position of an episode is given; every other is unmasked at
    # one of the passes, L / S of them a pass.
    assert (steps[:, 0] == -1).all() and (steps[:, 1:] >= 0).all()
    assert steps.max() == passes - 1
    for block in steps[:, L:].reshape(N, -1, L).reshape(-1, L):
        assert sorted(block) == sorted(
            s for s in range(passes) for _ in range(L // passes))
    held = plain(FAMILY, variables, trace, net, trace["experts"])
    generated = steps >= 0
    np.testing.assert_allclose(
        np.asarray(taken(held["logits"], trace["tokens"]))[generated],
        np.asarray(trace["logp"])[generated], atol=2e-4)
    assert (np.asarray(trace["logp"])[~generated] == 0).all()
    np.testing.assert_allclose(held["values"], trace["values"], atol=2e-4)
    routing = reference.routing_verdict(
        trace["experts"], held["experts"], held["select"])
    assert routing["router_flips"] <= 0.01, routing


# A context of whole kernel blocks (four of 8 under `kernel_here`, a row a
# grid step).
T_KERNEL = 32


@pytest.mark.parametrize("passes", [1, 2])
def test_block_step_through_the_kernel_forms_matches_reference(
        kernel_here, passes):
    """The block entry's kernel, by the interpreter: the rollout is the
    reference's, at the limits of the plain form."""
    model, variables, net = sdar(
        denoise_steps=passes, max_position_embeddings=T_KERNEL)
    trace = rollout(model, variables, net, positions=T_KERNEL)
    held = plain(FAMILY, variables, trace, net, trace["experts"])
    generated = np.asarray(trace["steps"]) >= 0
    np.testing.assert_allclose(
        np.asarray(taken(held["logits"], trace["tokens"]))[generated],
        np.asarray(trace["logp"])[generated], atol=2e-4)
    np.testing.assert_allclose(held["values"], trace["values"], atol=2e-4)
    routing = reference.routing_verdict(
        trace["experts"], held["experts"], held["select"])
    assert routing["router_flips"] <= 0.01, routing


@pytest.mark.parametrize("form", ["plain", "kernel"])
@pytest.mark.parametrize("commit", [False, True])
def test_only_a_commit_pass_writes_and_only_its_block_s_rows(
        commit, form, request):
    """A denoising pass hands the caches back bit for bit; a commit pass
    changes rows [pos, pos + L) of each row's caches, in every layer, and
    nothing else. Both forms of the attention."""
    if form == "kernel":
        request.getfixturevalue("kernel_here")
    model, variables, net = sdar(max_position_embeddings=T_KERNEL)
    L = net["block_length"]
    pos = jnp.asarray([0, 12, T_KERNEL - L], jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (N, L), 0,
                                net["vocab_size"] - 1)
    caches = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(a.size), a.shape,
                                    a.dtype),
        model.initial_state(N)["kv"])
    _, after, _, reads = model.apply(
        variables, tokens, pos, caches, commit, method="_block_pass")
    own = np.asarray((jnp.arange(T_KERNEL)[None, :] >= pos[:, None])
                     & (jnp.arange(T_KERNEL)[None, :] < pos[:, None] + L))
    assert len(jax.tree.leaves(after)) == 2 * net["num_hidden_layers"]
    for was, now in zip(jax.tree.leaves(caches), jax.tree.leaves(after)):
        was, now = np.asarray(was), np.asarray(now)
        assert was.shape == now.shape and was.dtype == now.dtype
        np.testing.assert_array_equal(now[~own], was[~own])
        assert commit == bool((now[own] != was[own]).any())
        if commit:
            assert (now[own] != was[own]).mean() > 0.99
    # The fresh block is no read of the cache: the kernel fetches the blocks
    # up to the furthest row's last cached position, the plain form all.
    assert float(reads[0]) == pytest.approx(
        32.0 if form == "plain" else 8 * np.mean([1, 2, 4]))


@pytest.mark.parametrize("platform,kernel", [("tpu", 1.0), ("cpu", 0.0)])
def test_the_counters_say_which_form_a_block_step_takes(platform, kernel):
    model, _, _ = sdar(max_position_embeddings=256, head_dim=128)
    got = model.static_counters(4, 256, platform)
    assert got["block_attention_kernel"] == kernel
    assert got["decode_attention_kernel"] == kernel
    assert got["decode_cache_block"] == (128 if kernel else 256)
    # The commit pass's alone, of denoise_steps + 1 passes.
    assert got["block_cache_writes_per_block"] == 1
    narrow, _, _ = sdar(max_position_embeddings=256)
    assert narrow.static_counters(4, 256, "tpu")[
        "block_attention_kernel"] == 0.0


@pytest.mark.parametrize("passes", [2, 4])
def test_the_unmask_order_is_the_reference_s_top_probabilities(passes):
    """At pass s the positions unmasked are those of the still masked whose
    top probability, by the reference's own logits of that pass, is
    highest."""
    model, variables, net = sdar(denoise_steps=passes)
    trace = rollout(model, variables, net)
    L, per = net["block_length"], net["block_length"] // passes
    steps = np.asarray(trace["steps"])
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                     variables["params"])
    for s in range(passes - 1):  # the last pass takes what is left
        seen = reference.pass_inputs(trace["tokens"], trace["steps"], s, net)
        both = jnp.concatenate([trace["tokens"], seen], axis=1)
        with jax.default_matmul_precision("highest"):
            x, _, _ = reference._hidden(
                p, both, jnp.tile(jnp.arange(T), 2),
                lambda q, k: ((k < T) & ((k % T) // L < (q % T) // L)) | (
                    ((q >= T) == (k >= T)) & ((k % T) // L == (q % T) // L)),
                net, lambda a: a, None, None)
            logits, _ = reference._heads(p, x[:, T:], net)
        top = np.asarray(jnp.max(jax.nn.log_softmax(logits, -1), -1))
        for row in range(N):
            for b in range(T // L):
                at = np.arange(b * L, (b + 1) * L)
                masked = at[steps[row, at] >= s]
                chosen = set(at[steps[row, at] == s])
                want = sorted(masked, key=lambda i: (-top[row, i], i))[:per]
                margin = np.diff(np.sort(top[row, masked]))
                if margin.size and margin.min() < 1e-5:
                    continue  # a tie within float32's rounding
                assert chosen == set(want), (row, b, s)


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_learner_pass_matches_reference_and_the_rollout(dtype):
    model, variables, net = sdar(dtype)
    trace = rollout(model, variables, net)
    (logits, values), kept = jax.jit(lambda v, t, s: model.apply(
        v, t, s, jnp.zeros(t.shape), method="block_causal",
        mutable=["routing", "counters"]))(
            variables, trace["tokens"], trace["steps"])
    experts = kept["routing"]["experts"][-1]
    held = plain(FAMILY, variables, trace, net, experts)
    out = reference.compare((logits[..., :-1], values),
                            (held["logits"], held["values"]))
    routing = reference.routing_verdict(experts, held["experts"],
                                        held["select"])
    limit = 1e-4 if dtype == "f32" else reference.TOLERANCE
    assert max(out["errors"].values()) <= limit, out
    assert routing["router_flips"] <= (0.01 if dtype == "f32" else
                                       reference.MAX_ROUTER_FLIPS), routing
    # The MASK id has probability 0.
    assert float(jnp.max(jax.nn.softmax(logits, -1)[..., -1])) == 0.0
    # At unchanged parameters the learner's log-probabilities are the
    # rollout's: the importance ratio of every block is 1.
    generated = np.asarray(trace["steps"]) >= 0
    L = net["block_length"]
    ratio = np.exp(np.where(
        generated, np.asarray(taken(logits, trace["tokens"]))
        - np.asarray(trace["logp"]), 0.0).reshape(N, -1, L).sum(-1))
    np.testing.assert_allclose(
        ratio, 1.0, atol=5e-4 if dtype == "f32" else 0.15)
    if dtype == "f32":
        np.testing.assert_allclose(values, trace["values"], atol=2e-4)


def unmask_steps(rng, rows, positions, L, passes):
    """A seeded trace's passes [rows, positions]: in every block each pass
    unmasks L / passes positions, in an order of its own."""
    return np.stack([np.concatenate([
        rng.permutation(np.repeat(np.arange(passes), L // passes))
        for _ in range(positions // L)]) for _ in range(rows)])


def whole_block_causal(self, tokens, steps, reset):
    """`TokenDecoder.block_causal` as it stood before its last layer stopped
    at the clean stream's keys and values: every one of the (S + 1) T rows
    through every layer's `_attend_causal` and `_feed_forward`, the logits
    and values indexed in the S + 1 streams."""
    L, S = self.block_len, self.denoise_steps
    B, T_ = tokens.shape
    at = jnp.arange(T_)
    starts = (reset > 0).at[:, 0].set(True)
    episode = jnp.tile(jnp.cumsum(starts, axis=1), (1, S + 1))
    positions = jnp.tile(
        at - jax.lax.cummax(jnp.where(starts, at, 0), axis=1), (1, S + 1))
    inputs = jnp.concatenate([tokens] + [
        jnp.where(steps < s, tokens, self.mask_id) for s in range(S)], axis=1)
    x = self.embed[inputs].astype(self.compute_dtype)
    loads, experts = [], []
    for layer in self.layers:
        lp = layer()
        h, _ = self._attend_causal(
            lp, x, positions, episode, None, streams=S + 1)
        out, load, top_i = self._feed_forward(
            lp, h.reshape(B * (S + 1) * T_, -1))
        x = out.reshape(x.shape)
        loads.append(load)
        experts.append(top_i.reshape(B, (S + 1) * T_, -1))
    self._count(experts, loads)
    own = (1 + jnp.maximum(steps, 0)) * T_ + at
    logits, _ = self._token_logits(
        jnp.take_along_axis(x, own[..., None], axis=1))
    _, values = self._heads(x[:, T_:2 * T_:L])
    return logits, values


@functools.lru_cache(maxsize=None)
def both_forms(layers, passes):
    """{form: (logits, values, routing [layers, N, (S + 1) T, k], the
    gradient of `vtrace_loss`)} of `block_causal` and of the whole form above
    on one seeded trace, whose second row holds two episodes."""
    model, variables, net = sdar(
        num_hidden_layers=layers, denoise_steps=passes)
    L = net["block_length"]
    rng = np.random.default_rng(layers * 10 + passes)
    tokens = rng.integers(0, net["vocab_size"] - 1, (N, T))
    steps = unmask_steps(rng, N, T, L, passes)
    dones = np.zeros((N, T), np.float32)
    dones[:, -1] = dones[1, T // 2 - 1] = 1.0
    steps[:, 0] = steps[1, T // 2] = -1
    batch = {
        sb.OBS: jnp.asarray(tokens.reshape(-1), jnp.int32),
        sb.ACTIONS: jnp.asarray(tokens.reshape(-1), jnp.int32),
        sb.UNMASK_STEPS: jnp.asarray(steps.reshape(-1), jnp.int32),
        sb.REWARDS: jnp.asarray(rng.integers(0, 2, N * T), jnp.float32),
        sb.DONES: jnp.asarray(dones.reshape(-1)),
        sb.ACTION_LOGP: jnp.asarray(-np.log(net["vocab_size"]) + rng.uniform(
            -0.5, 0.5, N * T), jnp.float32)}
    reset = jnp.concatenate(
        [jnp.zeros((N, 1)), jnp.asarray(dones)[:, :-1]], axis=1)
    out = {}
    for form in ("block_causal", whole_block_causal):
        def apply(params, kept, form=form):
            return model.apply(
                dict(variables, params=params), jnp.asarray(tokens),
                jnp.asarray(steps), reset, method=form, mutable=kept)

        def apply_blocks(params, batch):
            held, kept = apply(params, ["counters", "losses"])
            return held, {k: v[-1] for k, v in kept["counters"].items()}, {}
        policy = types.SimpleNamespace(
            config=dict(CFG, rollout_fragment_length=T), block_len=L,
            apply_blocks=apply_blocks)
        (logits, values), kept = jax.jit(
            lambda p: apply(p, ["routing", "counters"]))(variables["params"])
        grads = jax.jit(jax.grad(lambda p: vtrace_policy.vtrace_loss(
            policy, p, batch, jax.random.PRNGKey(0), None)[0]))(
                variables["params"])
        out[form if isinstance(form, str) else "whole"] = (
            logits, values, kept["routing"]["experts"][-1], grads)
    return out


SHAPES = [(layers, passes) for layers in (1, 2, 3) for passes in (1, 2)]


@pytest.mark.parametrize("layers,passes", SHAPES)
def test_the_last_layer_s_clean_stream_was_owed_nothing(layers, passes):
    """The logits, the values and the gradient of `vtrace_loss` with respect
    to every parameter are the whole form's, to float32's rounding: the rows
    the last layer leaves out had a zero cotangent. (Products of other
    shapes sum in another order: the logits differ by 1.5e-7 at most, a
    gradient by 1.4e-6 of its largest entry over the six shapes.)"""
    got, want = (both_forms(layers, passes)[form]
                 for form in ("block_causal", "whole"))
    np.testing.assert_allclose(got[0], want[0], atol=1e-6)
    np.testing.assert_allclose(got[1], want[1], atol=1e-6)
    assert jax.tree.structure(got[3]) == jax.tree.structure(want[3])
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got[3])[0],
                            jax.tree.leaves(want[3])):
        scale = max(float(jnp.max(jnp.abs(w))), 1.0)
        assert float(jnp.max(jnp.abs(g - w))) <= 5e-6 * scale, (
            jax.tree_util.keystr(path))
    # The last layer's queries, output projection, router and experts do
    # learn: from the noisy rows.
    last = got[3][f"layer_{layers - 1}"]
    for name in ("wq", "wo", "router", "w_up", "wk", "wv"):
        assert float(jnp.max(jnp.abs(last[name]))) > 0, name


@pytest.mark.parametrize("layers,passes", SHAPES)
def test_the_routing_collection_keeps_its_shape_and_says_no_choice(
        layers, passes):
    """[layers, N, (S + 1) T, k]: the last layer's clean rows state -1, as
    the commit pass's last layer does in the rollout's trace; every other
    entry is the whole form's."""
    got, want = (np.asarray(both_forms(layers, passes)[form][2])
                 for form in ("block_causal", "whole"))
    assert got.shape == want.shape == (
        layers, N, (passes + 1) * T, NET["num_experts_per_tok"])
    assert (got[-1, :, :T] == -1).all() and (want >= 0).all()
    np.testing.assert_array_equal(got[:-1], want[:-1])
    np.testing.assert_array_equal(got[-1, :, T:], want[-1, :, T:])


@pytest.mark.parametrize("passes", [1, 2])
def test_the_noisy_queries_alone_are_the_square_form_s_rows(passes):
    """`block_stream_attention` with the noisy streams' queries against
    every stream's keys: rows [T:] of the square form, plain against plain,
    with two episodes in a row."""
    streams, heads, groups, d = passes + 1, 4, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(passes), 3)
    q = jax.random.normal(keys[0], (N, heads, streams * T, d))
    k, v = (jax.random.normal(key, (N, groups, streams * T, d))
            for key in keys[1:])
    episode = jnp.tile(
        jnp.ones((N, T), jnp.int32).at[1, T // 2:].set(2), (1, streams))
    square = transformer.block_stream_attention(
        q, k, v, episode, d ** -0.5, 4, streams)
    noisy = transformer.block_stream_attention(
        q[:, :, T:], k, v, episode, d ** -0.5, 4, streams)
    assert noisy.shape == (N, heads, passes * T, d)
    np.testing.assert_allclose(noisy, square[:, :, T:], atol=1e-6)


@pytest.mark.parametrize("queries,tiles", [(3, (38, 144)), (2, (28, 96))])
def test_the_fused_mask_s_tiles_are_known_from_the_static_shape(
        queries, tiles):
    """At the cell's learner shape (T 2,048, blocks of 4, a clean and two
    noisy streams, tiles of 512): the square mask visits 38 of 144 tiles,
    the noisy queries' rectangle 28 of 96 (the clean queries' 10, a causal
    pass's over T, go); `block_stream_tiles` says so, and the splash
    kernel's own reading of the mask it is given visits the same, forward
    and backward."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as masks, splash_attention_mask_info as info)
    t = transformer.CAUSAL_TILE
    assert transformer.block_stream_tiles(2048, 4, 3, queries) == tiles
    assert transformer.causal_window_tiles(2048, 0)[0] == 38 - 28
    mask = transformer._block_stream_mask(2048, 4, 3, queries)
    assert mask.shape == (queries * 2048, 3 * 2048)
    for process in (info.process_mask, info.process_mask_dkv):
        held, computed = process(masks.MultiHeadMask([mask]), (t, t))
        assert computed is not None  # computed in the kernel, not loaded
        assert int((np.asarray(held.block_mask) > 0).sum()) == tiles[0]
    # The rectangle is the square mask's rows [T:], read a block at a time
    # (whole blocks: what the kernel's set-up asks for) or a position.
    ids = np.arange(3 * 24)
    small = transformer._block_stream_mask(24, 4, 3, 2)
    want = transformer.block_stream_allowed(24, 4)(
        ids[24:, None], ids[None, :])
    for rows, columns in ((slice(None), slice(None)),
                          (slice(4, 12), slice(8, 28)),
                          (slice(3, 13), slice(5, 31))):
        np.testing.assert_array_equal(
            small[rows, columns], want[rows, columns])


@pytest.mark.parametrize("passes", [1, 2])
def test_the_2t_form_is_the_block_by_block_definition(passes):
    model, variables, net = sdar(denoise_steps=passes)
    trace = rollout(model, variables, net)
    whole = plain(FAMILY, variables, trace, net)
    by_blocks = jax.jit(lambda v, t, s: reference.forward_by_blocks(
        v, t, s, net))(variables, trace["tokens"], trace["steps"])
    np.testing.assert_allclose(whole["logits"], by_blocks["logits"],
                               atol=2e-5)
    np.testing.assert_allclose(whole["values"], by_blocks["values"],
                               atol=2e-5)


def test_a_block_of_one_and_one_pass_is_a_plain_masked_forward():
    """L 1, S 1: a step yields one token a row; position i's distribution
    comes from the MASK id at i reading the clean tokens before it (a
    causal forward over the whole length, whose position i reads nothing
    after it: one program for the 24 positions)."""
    model, variables, net = sdar(block_length=1, denoise_steps=1)
    trace = rollout(model, variables, net)
    assert trace["tokens"].shape == (N, T)
    steps = np.asarray(trace["steps"])
    assert (steps[:, 0] == -1).all() and (steps[:, 1:] == 0).all()
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                     variables["params"])

    @jax.jit
    def masked_forward(seen):
        with jax.default_matmul_precision("highest"):
            x, _, _ = reference._hidden(
                p, seen, jnp.arange(T), lambda q, k: k <= q, net,
                lambda a: a, None, None)
            return reference._heads(p, x, net)
    logits, values = [], []
    for i in range(T):
        seen = trace["tokens"]
        if i:
            seen = seen.at[:, i].set(net["vocab_size"] - 1)
        out = masked_forward(seen)
        logits.append(out[0][:, i])
        values.append(out[1][:, i])
    logp = taken(jnp.stack(logits, 1), trace["tokens"])
    np.testing.assert_allclose(
        np.asarray(logp)[:, 1:], np.asarray(trace["logp"])[:, 1:], atol=2e-4)
    np.testing.assert_allclose(jnp.stack(values, 1), trace["values"],
                               atol=2e-4)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """A chip's expert part is the sum over the experts it holds; the eight
    shares' parts sum to the layer with every expert, in the reference and
    in the system's dispatch alike."""
    E, k, H, W, M = 16, 4, 32, 24, 40
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    lp = {"w_gate": jax.random.normal(keys[0], (E, H, W)) / 6,
          "w_up": jax.random.normal(keys[1], (E, H, W)) / 6,
          "w_down": jax.random.normal(keys[2], (E, W, H)) / 5}
    m = jax.random.normal(keys[3], (M, H))
    probs = jax.nn.softmax(jax.random.normal(keys[4], (M, E)), -1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / top_p.sum(-1, keepdims=True)

    def share(first, held, fn):
        part = {name: w[first:first + held] for name, w in lp.items()}
        return fn(part, first)
    plain_part = lambda part, first: reference.moe(  # noqa: E731
        part, m, {}, lambda a: a, top_i, top_p, first=first)
    system_part = lambda part, first: transformer.dropless_experts(  # noqa
        m, top_p, top_i, part["w_gate"], part["w_up"], part["w_down"],
        first, E)[0]
    whole = share(0, E, plain_part)
    for fn in (plain_part, system_part):
        parts = [share(first, 2, fn) for first in range(0, E, 2)]
        np.testing.assert_allclose(sum(parts), whole, atol=1e-5)
        assert float(jnp.max(jnp.abs(parts[0] - whole))) > 0.01


def test_block_vtrace_is_the_library_s_over_blocks():
    """The reference's hand-rolled V-trace over blocks against
    `vtrace.from_importance_weights` at a bootstrap value of 0."""
    rng = np.random.default_rng(0)
    B, n = 3, 7
    log_rhos = jnp.asarray(rng.normal(0, 0.5, (B, n)), jnp.float32)
    rewards = jnp.asarray(rng.integers(0, 5, (B, n)), jnp.float32)
    values = jnp.asarray(rng.normal(0, 1, (B, n)), jnp.float32)
    discounts = jnp.full((B, n), 0.99).at[:, -1].set(0.0)
    vs, pg = reference.block_vtrace(log_rhos, discounts, rewards, values, CFG)
    want = vtrace.from_importance_weights(
        log_rhos=log_rhos.T, discounts=discounts.T, rewards=rewards.T,
        values=values.T, bootstrap_value=jnp.zeros(B),
        clip_rho_threshold=1.0, clip_pg_rho_threshold=1.0, lambda_=1.0)
    np.testing.assert_allclose(vs.T, want.vs, atol=1e-5)
    np.testing.assert_allclose(pg.T, want.pg_advantages, atol=1e-5)


def test_the_stream_mask_is_the_reference_s():
    """`block_stream_allowed` over S + 1 streams against the reference's
    own 2T mask, a pass at a time."""
    T_, L, S = 12, 4, 2
    ids = np.arange((S + 1) * T_)
    got = transformer.block_stream_allowed(T_, L)(ids[:, None], ids[None, :])
    for s in range(S):
        keep = np.concatenate([ids[:T_], ids[(1 + s) * T_:(2 + s) * T_]])
        q, k = np.arange(2 * T_)[:, None], np.arange(2 * T_)[None, :]
        want = ((k < T_) & ((k % T_) // L < (q % T_) // L)) | (
            ((q >= T_) == (k >= T_)) & ((k % T_) // L == (q % T_) // L))
        np.testing.assert_array_equal(got[np.ix_(keep, keep)], want)
    # A noisy stream never reads another noisy stream.
    assert not got[T_:2 * T_, 2 * T_:].any()
    assert not got[2 * T_:, T_:2 * T_].any()


def test_keys_left_out_have_the_published_model_s_values():
    model = transformer.sdar_moe_from_config(151935, {})
    assert (model.hidden_size, model.num_heads, model.num_kv_heads,
            model.head_dim, model.num_layers) == (2048, 32, 4, 128, 48)
    assert (model.num_experts, model.experts_per_token, model.expert_width,
            model.norm_topk_prob) == (128, 8, 768, True)
    assert (model.vocab_size, model.num_outputs, model.mask_id,
            model.context_len) == (151936, 151936, 151935, 32768)
    assert (model.block_len, model.denoise_steps, model.qk_norm,
            model.rope_theta, model.rms_eps) == (4, 2, "head", 1000000, 1e-6)
