"""The `smallthinker` token policy at a tiny size on the CPU: the model
against the plain reference (`benchmark/lib/reference_smallthinker.py`) with
fragments LONGER than the window, so that the window layers' rings turn;
grouped key/value heads in both forms of the attention; the decode through
a full cache and three rings against the causal pass, which keeps every
position; the expert layer that holds a share against the uncut layer; each
named wrong mathematics refused by the cell's limits; V-trace's loss, its
gradients and one update of the optimizer's own against the reference's;
and the trainer on the fused Anakin path.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import reference_glm4_moe_lite  # noqa: E402
from lib import reference_smallthinker as reference  # noqa: E402

from ray_tpu.models import catalog, transformer  # noqa: E402
from ray_tpu.models.transformer import dropless_experts  # noqa: E402
from ray_tpu.rllib import sample_batch as sb  # noqa: E402
from ray_tpu.rllib.agents.impala import IMPALATrainer  # noqa: E402
from ray_tpu.rllib.agents.impala.vtrace_policy import vtrace_loss  # noqa: E402

# One period: a full, position-free layer and three windowed rotary ones;
# 8 query heads in 2 groups, of a width that is not hidden / heads; 2 of 8
# experts held; a window of 8 under fragments of 24, so a ring turns twice.
WINDOW, S, B = 8, 24, 3
NET = dict(vocab_size=96, hidden_size=64, num_attention_heads=8,
           num_key_value_heads=2, head_dim=16, num_hidden_layers=4,
           sliding_window_size=WINDOW, sliding_window_layout=[0, 1, 1, 1],
           rope_layout=[0, 1, 1, 1], moe_num_primary_experts=8,
           experts_held=2, first_expert_held=0,
           moe_num_active_primary_experts=2, moe_ffn_hidden_size=32,
           norm_topk_prob=True, max_position_embeddings=S,
           rope_theta=1.5e6, rms_norm_eps=1e-6)
# Grouped heads' caches are stored flat: 2 cached heads of 16 a row.
CACHES = [(S, 2 * 16)] + [(WINDOW, 2 * 16)] * 3


def build(dtype, net=NET, sharp=1.0):
    model = catalog.get_model(None, net["vocab_size"], {
        "custom_model": "smallthinker", "custom_model_config": net,
        "compute_dtype": dtype})
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (B, S), 0, net["vocab_size"])
    variables = model.init(jax.random.PRNGKey(0), tokens[:, :1],
                           model.initial_state(B), jnp.zeros((B, 1)))
    if sharp != 1.0:
        # Queries and keys large enough that a softmax has a few heavy
        # terms, so that one position more or less in it shows.
        variables = jax.tree_util.tree_map_with_path(
            lambda path, a: a * sharp if path[-1].key in ("wq", "wk")
            else a, variables)
    return model, variables, tokens


def judged(system, variables, tokens, net=NET):
    """The system's (logits, values, experts) against the reference held
    to those experts: (outputs, routing)."""
    logits, values, experts = system
    held = reference.forward(variables, tokens, net, experts=experts)
    return (reference.compare((logits, values),
                              (held["logits"], held["values"])),
            reference.routing_verdict(experts, held["experts"],
                                      held["select"]))


def causal_routed(model, variables, tokens):
    (logits, values, state), kept = model.apply(
        variables, tokens, None, jnp.zeros(tokens.shape),
        mutable=["routing", "counters"])
    return (logits, values, kept["routing"]["experts"][-1]), state, kept


def decode_routed(model, variables, tokens, jit=True):
    """Every position one token at a time from an empty window:
    ((logits, values, experts), the last state, the counters a step)."""
    def step(token, state):
        return model.apply(variables, token, state, jnp.zeros(B),
                           method="decode", mutable=["routing", "counters"])
    if jit:
        step = jax.jit(step)
    state = model.initial_state(B)
    logits, values, experts, counted = [], [], [], []
    for t in range(tokens.shape[1]):
        (step_l, step_v, state), kept = step(tokens[:, t], state)
        logits.append(step_l)
        values.append(step_v)
        experts.append(kept["routing"]["experts"][-1])
        counted.append({k: float(v[-1])
                        for k, v in kept["counters"].items()})
    return (jnp.stack(logits, 1), jnp.stack(values, 1),
            jnp.stack(experts, 2)), state, counted


# -- the model against the reference -----------------------------------
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_causal_pass_matches_reference(dtype):
    """float32 blocks: to float32 accuracy, the same experts in every
    layer. bfloat16 blocks: the limits written beside the reference."""
    model, variables, tokens = build(dtype)
    system, state, _ = causal_routed(model, variables, tokens)
    assert system[2].shape == (4, B, S, 2)
    outputs, routing = judged(system, variables, tokens)
    if dtype == "f32":
        assert routing["router_flips"] == 0.0
        assert max(outputs["errors"].values()) < 1e-5, outputs
    else:
        # 288 (token, layer) pairs: a flip is 0.35 %, and a near-tie.
        assert routing["router_flips"] <= 0.1
        assert routing["max_flip_gap"] <= reference.MAX_FLIP_GAP
        assert outputs["ok"], outputs
    # What the pass hands a decode: the context's positions of the full
    # layer, a ring of the window of each window layer.
    assert [[c.shape[1:] for c in layer] for layer in state["kv"]] == [
        [shape] * 2 for shape in CACHES]
    assert np.all(np.asarray(state["pos"]) == S)


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_decode_through_a_full_cache_and_three_rings_matches_reference(dtype):
    """24 positions through rings of 8: every slot is overwritten twice.
    Against the reference, which has no cache; and, float32, against the
    causal pass, which keeps every position and masks the window."""
    model, variables, tokens = build(dtype)
    system, state, counted = decode_routed(model, variables, tokens,
                                           jit=dtype == "f32")
    outputs, routing = judged(system, variables, tokens)
    if dtype == "f32":
        assert routing["router_flips"] == 0.0
        assert max(outputs["errors"].values()) < 1e-5, outputs
        causal, _, _ = causal_routed(model, variables, tokens)
        assert reference.relative_error(system[0], causal[0]) < 1e-5
        assert np.array_equal(system[2], causal[2])
    else:
        assert routing["router_flips"] <= 0.1
        assert outputs["ok"], outputs
    assert [[c.shape[1:] for c in layer] for layer in state["kv"]] == [
        [shape] * 2 for shape in CACHES]
    # One block a cache at this size: the full layer reads its 24
    # positions, a ring its 8 of the context's 24.
    assert counted[-1] == {
        "decode_cache_read_share": pytest.approx((1 + 3 / 3) / 4),
        "decode_cache_read_share_full": 1.0,
        "decode_cache_read_share_window": pytest.approx(1 / 3)}


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_a_decode_through_the_kernel_form_is_the_causal_pass(
        dtype, kernel_here):
    """The grouped caches through the kernel form (`conftest.kernel_here`:
    blocks of 8 positions, interpreted): a window of 16 under 24
    positions, so the full cache is three blocks and a ring two, which
    turns at position 16 and holds every slot from then on. A step reads
    the blocks its rows hold; the logits are the causal pass's, which
    keeps every position and masks the window."""
    net = dict(NET, sliding_window_size=2 * WINDOW)
    model, variables, tokens = build(dtype, net)
    system, state, counted = decode_routed(model, variables, tokens,
                                           jit=dtype == "f32")
    for t, step in enumerate(counted):
        held = 8 * (t // 8 + 1)
        assert step["decode_cache_read_share_full"] == pytest.approx(
            held / S)
        assert step["decode_cache_read_share_window"] == pytest.approx(
            min(held, 2 * WINDOW) / S)
    assert [c.shape[1:] for c in jax.tree.leaves(state["kv"])] == (
        [(S, 32)] * 2 + [(2 * WINDOW, 32)] * 6)
    if dtype == "f32":
        causal, _, _ = causal_routed(model, variables, tokens)
        assert reference.relative_error(system[0], causal[0]) < 1e-5
        assert reference.relative_error(system[1], causal[1]) < 1e-5
        assert np.array_equal(system[2], causal[2])
    else:
        outputs, routing = judged(system, variables, tokens, net)
        assert routing["router_flips"] <= 0.1
        assert outputs["ok"], outputs


def test_grouped_caches_are_read_whole_and_ungrouped_rings_in_blocks(
        monkeypatch):
    """Blocks of 4 positions. Grouped heads take one whole-cache branch,
    whatever the block; with as many cached heads as query heads a ring of
    8 is two blocks, read whole once any row holds 4 positions and whole
    ever after, while the full cache of 24 goes on growing. Either way
    the causal pass's logits."""
    monkeypatch.setattr(transformer, "DECODE_CACHE_BLOCK", 4)
    for net in (NET, dict(NET, num_key_value_heads=8)):
        model, variables, tokens = build("f32", net)
        system, _, counted = decode_routed(model, variables, tokens)
        causal, _, _ = causal_routed(model, variables, tokens)
        assert reference.relative_error(system[0], causal[0]) < 1e-5
        grouped = net["num_key_value_heads"] != net["num_attention_heads"]
        for t, step in enumerate(counted):
            read = S if grouped else 4 * (t // 4 + 1)
            assert step["decode_cache_read_share_full"] == pytest.approx(
                read / S)
            assert step["decode_cache_read_share_window"] == pytest.approx(
                min(read, WINDOW) / S)


def test_prefill_then_ring_decode_and_a_reset_inside_a_fragment():
    """The causal pass returns rings a decode continues from, whether the
    prefix is shorter than the window, longer, or a multiple of it; and a
    reset inside a fragment starts a fresh episode in both forms."""
    model, variables, tokens = build("f32")
    decode = jax.jit(lambda token, state, reset: model.apply(
        variables, token, state, reset))
    full, _, _ = model.apply(variables, tokens, None, jnp.zeros((B, S)))
    for prefix in (5, WINDOW, 13, 2 * WINDOW):
        _, _, state = model.apply(variables, tokens[:, :prefix], None,
                                  jnp.zeros((B, prefix)))
        for t in range(prefix, S):
            step, _, state = decode(tokens[:, t:t + 1], state,
                                    jnp.zeros((B, 1)))
            assert reference.relative_error(
                step[:, 0], full[:, t]) < 1e-5, (prefix, t)
    reset = jnp.zeros((B, S)).at[:, 11].set(1.0)
    both, _, state = model.apply(variables, tokens, None, reset)
    second, _, _ = model.apply(variables, tokens[:, 11:], None,
                               jnp.zeros((B, S - 11)))
    assert reference.relative_error(both[:, 11:], second) < 1e-5
    assert reference.relative_error(both[:, :11], full[:, :11]) < 1e-5
    assert np.all(np.asarray(state["pos"]) == S - 11)
    state = model.initial_state(B)
    for t in range(S):
        step, _, state = decode(tokens[:, t:t + 1], state, reset[:, t:t + 1])
        assert reference.relative_error(step[:, 0], both[:, t]) < 1e-5, t


@pytest.mark.parametrize("wrong", reference.MUTATIONS + ("float8_e4m3",))
def test_limits_refuse_wrong_mathematics(wrong):
    """The comparison fails each named error and blocks computed a
    precision lower: the reference, so altered, in the system's place
    against itself, by its outputs or by its routing."""
    _, variables, tokens = build("f32", sharp=4.0)
    if wrong == "float8_e4m3":
        got = reference.forward(variables, tokens, NET, round_to=wrong)
    else:
        got = reference.forward(variables, tokens, NET, mutate=wrong)
    outputs, routing = judged(
        (got["logits"], got["values"], got["experts"]), variables, tokens)
    assert not (outputs["ok"] and routing["ok"]), (wrong, outputs, routing)
    if wrong == "router_reads_post_attention_norm":
        assert not routing["ok"]


# -- grouped heads, a kind a layer: the parts, one at a time -------------
@pytest.mark.parametrize("groups", [1, 2, 8])
@pytest.mark.parametrize("window", [0, 5])
def test_cached_attention_over_grouped_heads_is_the_plain_sum(groups, window):
    """`cached_attention` with 8 query heads over `groups` cached heads,
    rows at different positions, against the sum written out; with a
    window, the cache a ring of it that has turned."""
    heads, d, rows = 8, 16, 4
    length = window or 12
    keys = jax.random.split(jax.random.PRNGKey(groups), 3)
    q = jax.random.normal(keys[0], (rows, heads, d))
    k_all, v_all = (jax.random.normal(key, (rows, 12, groups, d))
                    for key in keys[1:])
    pos = jnp.asarray([0, 3, 7, 11])
    k_cache, v_cache = (jnp.zeros((rows, length, groups, d)),) * 2
    for t in range(12):  # written as a decode writes: slot t mod length
        live = (t <= pos)[:, None, None]
        k_cache = k_cache.at[:, t % length].set(
            jnp.where(live, k_all[:, t], k_cache[:, t % length]))
        v_cache = v_cache.at[:, t % length].set(
            jnp.where(live, v_all[:, t], v_cache[:, t % length]))
    got, read = transformer.cached_attention(q, k_cache, v_cache, pos)
    assert int(read) == length
    for b in range(rows):
        first = max(0, int(pos[b]) - window + 1) if window else 0
        span = slice(first, int(pos[b]) + 1)
        for h in range(heads):
            g = h // (heads // groups)
            a = jax.nn.softmax(k_all[b, span, g] @ q[b, h] / 4.0)
            np.testing.assert_allclose(
                got[b, h], a @ v_all[b, span, g], atol=2e-6)


def test_causal_window_tiles_are_counted_by_distance():
    # The cell: 16 tiles a side, 136 under the diagonal; a window of 8
    # tiles' positions reaches the tiles up to 8 apart: 16 + 15 + .. + 8.
    assert transformer.causal_window_tiles(8192, 4096) == (108, 136)
    assert transformer.causal_window_tiles(8192, 0) == (136, 136)
    assert transformer.causal_window_tiles(1024, 1) == (2, 3)
    assert transformer.causal_window_tiles(1024, 2) == (3, 3)
    assert transformer.causal_window_tiles(2048, 513) == (7, 10)
    assert transformer.causal_window_tiles(2048, 514) == (9, 10)


# -- the expert layer that holds a share ---------------------------------
def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four shares of 2 of the 8 experts: their parts add up to what the
    uncut reference gives for the whole layer (the reference's shares, and
    the system's in both forms of its product, ReLU in the gate)."""
    rng = np.random.default_rng(0)
    H, W, E, k, held = 64, 32, 8, 2, 2
    lp = jax.tree.map(jnp.asarray, {
        "attn_norm": np.ones(H, np.float32),
        "mlp_norm": np.ones(H, np.float32),
        "wq": rng.normal(size=(H, 128)).astype(np.float32) / 8,
        "wk": rng.normal(size=(H, 32)).astype(np.float32) / 8,
        "wv": rng.normal(size=(H, 32)).astype(np.float32) / 8,
        "wo": rng.normal(size=(128, H)).astype(np.float32) / 8,
        "router": rng.normal(size=(H, E)).astype(np.float32) / 4,
        "w_gate": rng.normal(size=(E, H, W)).astype(np.float32) / 8,
        "w_up": rng.normal(size=(E, H, W)).astype(np.float32) / 8,
        "w_down": rng.normal(size=(E, W, H)).astype(np.float32) / 6})
    x = jnp.asarray(rng.normal(size=(2, 12, H)), jnp.float32)

    def share_of(first, size):
        return dict(lp, **{w: lp[w][first:first + size]
                           for w in ("w_gate", "w_up", "w_down")})

    def layer(first, size, **other):
        net = dict(NET, experts_held=size, first_expert_held=first)
        with jax.default_matmul_precision("highest"):
            return reference._layer(dict(share_of(first, size), **other), x,
                                    net, 1, lambda a: a, None, None)
    whole, chosen, _ = layer(0, E)
    # h alone, x + attention: experts that give nothing.
    no_expert, _, _ = layer(0, E, w_down=jnp.zeros_like(lp["w_down"]))
    parts = sum(layer(first, held)[0] - no_expert
                for first in range(0, E, held))
    assert reference.relative_error(parts, whole - no_expert) < 1e-5

    # The system's shares of the same routing, in the form each shape
    # takes (24 rows batched, 64 times as many grouped).
    n = transformer.rms_norm(x.reshape(-1, H), lp["attn_norm"], 1e-6,
                             jnp.float32)
    top_p, top_i = transformer.route(n, lp["router"], k, True)
    assert np.array_equal(np.sort(top_i, -1),
                          np.sort(chosen.reshape(-1, k), -1))
    m = transformer.rms_norm(no_expert.reshape(-1, H), lp["mlp_norm"], 1e-6,
                             jnp.float32)
    for reps in (1, 64):
        rows, p, i = (jnp.tile(a, (reps, 1)) for a in (m, top_p, top_i))
        routed, landed = jnp.zeros_like(rows), 0
        for first in range(0, E, held):
            s = share_of(first, held)
            part, sizes, _ = dropless_experts(
                rows, p, i, s["w_gate"], s["w_up"], s["w_down"], first, E,
                jax.nn.relu)
            routed, landed = routed + part, landed + int(jnp.sum(sizes))
        assert landed == rows.shape[0] * k
        assert reference.relative_error(
            routed[:m.shape[0]], (whole - no_expert).reshape(-1, H)) < 1e-4
    assert transformer.experts_batched(m.shape[0], k, E)
    assert not transformer.experts_batched(64 * m.shape[0], k, E)


def test_a_causal_pass_over_the_landed_rows_is_the_batched_pass(
        grouped_pass_is_the_batched_pass):
    grouped_pass_is_the_batched_pass(*build("f32"))


def test_the_cell_s_program_is_known_from_its_static_shapes():
    """At the published widths: 656.5 M parameters; a full cache of 8,192
    positions and three rings of 4,096, 5,120 bytes a position of the
    context where caches that kept every position would hold 8,192; 108
    of a window layer's 136 causal tiles visited; nothing but shapes is
    built."""
    net = dict(NET, vocab_size=37984, hidden_size=2560,
               num_attention_heads=28, num_key_value_heads=4, head_dim=128,
               sliding_window_size=4096, moe_num_primary_experts=64,
               experts_held=16, moe_num_active_primary_experts=6,
               moe_ffn_hidden_size=768, max_position_embeddings=8192)
    model = catalog.get_model(None, net["vocab_size"], {
        "custom_model": "smallthinker", "custom_model_config": net})
    assert model.static_counters(16, 8192, "tpu") == {
        # Under two rows a held expert: a rollout's step reads the chosen
        # ones' matrices alone, and counts their share itself.
        "decode_rows_per_expert": 1.5, "decode_experts_batched": 0.0,
        "decode_experts_sparse": 1.0,
        "decode_cache_block": 128, "decode_attention_kernel": 1.0,
        "causal_attention_fused": 1.0, "window_layers": 3, "kv_groups": 7,
        # The three window layers rotate, the full one is position-free.
        "rotation_fused_layers": 3.0,
        "kv_cache_bytes_per_token": 5120.0,
        "causal_window_tiles_kept": 108 / 136}
    # Off a TPU the caches are read whole, by XLA's products.
    off = model.static_counters(16, 8192, "cpu")
    assert (off["decode_cache_block"], off["decode_attention_kernel"]) == (
        8192, 0.0)
    state = jax.eval_shape(lambda: model.initial_state(16))
    assert [c.shape for c in jax.tree.leaves(state["kv"])] == (
        [(16, 8192, 4 * 128)] * 2 + [(16, 4096, 4 * 128)] * 6)
    variables = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
        jax.eval_shape(lambda: model.initial_state(1)),
        jax.ShapeDtypeStruct((1, 1), jnp.float32))
    assert set(variables) == {"params"}
    count = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(variables))
    attention = 2 * 2560 * 28 * 128 + 2 * 2560 * 4 * 128
    layer = attention + 2560 * 64 + 16 * 3 * 2560 * 768 + 2 * 2560
    assert count == 4 * layer + 2 * 37984 * 2560 + 2560 + 2560 + 1
    assert count == 656_532_481


# -- the loss and the loop ------------------------------------------------
def token_trainer_config(**over):
    cfg = dict(
        env="TokenBigram-v0",
        env_config={"vocab_size": NET["vocab_size"], "episode_len": S},
        anakin=True, num_workers=0, num_envs_per_worker=4,
        rollout_fragment_length=S, train_batch_size=4 * S,
        sgd_minibatch_size=2 * S, num_sgd_iter=1,
        anakin_updates_per_call=1, min_iter_time_s=0, lr=6e-4, seed=3,
        model={"custom_model": "smallthinker", "custom_model_config": NET,
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
    rows, ACTION_LOGP, the bootstrap step differentiated through the
    rings) and through `jax.grad` of the plain reference."""
    policy = token_trainer.get_policy()
    batch, ref_batch = seeded_batch(B, 5)
    variables = jax.tree.map(jnp.asarray, policy.get_weights())
    assert set(variables) == {"params"}
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
    (want_loss, _), grads = jax.value_and_grad(
        lambda p: reference.vtrace_loss(
            {"params": p}, ref_batch, NET, cfg,
            mutate=wrong.get("mutate")), has_aux=True)(before["params"])
    count = int(adam.count)
    assert count > 0
    want_change, norm = reference_glm4_moe_lite.adam_update(
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
    "silu_in_the_gradient": dict(mutate="silu_for_relu"),
    "window_layers_without_rope": dict(mutate="no_rope_on_a_window_layer"),
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


def test_smallthinker_token_trainer_trains_on_the_fused_path(token_trainer):
    """`IMPALATrainer(anakin, TokenBigram-v0, smallthinker)` by config
    alone: two iterations, a finite loss, a rising count, a policy state
    whose caches differ in length by layer, the new counters in
    `learner_stats`."""
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
    assert kept["decode_cache_block"] == S
    # One block a cache: the full layer's 24 positions, a ring's 8 of 24.
    assert kept["decode_cache_read_share_full"] == 1.0
    assert kept["decode_cache_read_share_window"] == pytest.approx(1 / 3)
    assert kept["decode_cache_read_share"] == pytest.approx(0.5)
    assert kept["causal_attention_fused"] == 0.0
    assert (kept["window_layers"], kept["kv_groups"]) == (3, 4)
    # float32 here: 2 x 2 heads x 16 x 4 B a position a layer.
    assert kept["kv_cache_bytes_per_token"] == 256 * (S + 3 * WINDOW) / S
    state, _ = token_trainer.optimizer._pstate
    assert [c.shape for c in jax.tree.leaves(state["kv"])] == [
        (4,) + shape for shape in CACHES for _ in range(2)]


def test_learner_stats_report_what_the_grouped_kernel_read(kernel_here):
    """The trainer on the fused Anakin path with the kernel form in its
    rollout and under its learner's bootstrap step, a layer at a time: the
    full cache, three blocks of 8, fills from empty and is read 1/2 +
    block / 2S of; a ring of one block takes no kernel and is read
    whole."""
    trainer = IMPALATrainer(config=token_trainer_config())
    try:
        result = trainer.train()
        assert np.isfinite(result["info"]["learner"]["total_loss"])
        kept = trainer.optimizer.learner_stats
        assert kept["decode_cache_read_share_full"] == pytest.approx(
            0.5 + 8 / (2 * S))
        assert kept["decode_cache_read_share_window"] == pytest.approx(
            WINDOW / S)
        # The host's counters are of the platform the trainer runs on.
        assert kept["decode_attention_kernel"] == 0.0
        assert kept["decode_cache_block"] == S
    finally:
        trainer.stop()


@pytest.mark.parametrize("cfg,match", [
    ({"num_experts": 8}, "not smallthinker's"),
    ({"intermediate_size": 96}, "not smallthinker's"),
    ({"hidden_act": "silu"}, "hidden_act"),
    ({"moe_primary_router_apply_softmax": False}, "apply_softmax"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"num_key_value_heads": 3}, "groups"),
    ({"sliding_window_layout": [0, 1]}, "window layout"),
    ({"rope_layout": [1]}, "rope layout"),
    ({"experts_held": 6, "first_expert_held": 4}, "not among"),
])
def test_custom_model_config_without_a_part_is_refused(cfg, match):
    with pytest.raises(ValueError, match=match):
        model = catalog.get_model(None, 96, {
            "custom_model": "smallthinker",
            "custom_model_config": dict(NET, **cfg)})
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
                   model.initial_state(1), jnp.zeros((1, 1)))


def test_a_layout_longer_than_the_depth_is_read_from_its_head():
    """A cut in depth keeps the leading layers' kinds: the published
    layouts of 52 entries under four layers."""
    model = catalog.get_model(None, 96, {
        "custom_model": "smallthinker", "custom_model_config": dict(
            NET, sliding_window_layout=[0, 1, 1, 1] * 13,
            rope_layout=[0, 1, 1, 1] * 13)})
    assert [model.layer_kind(i)[:2] for i in range(4)] == [
        (0, False), (WINDOW, True), (WINDOW, True), (WINDOW, True)]
    assert [model.cache_len(i) for i in range(4)] == [S, 8, 8, 8]


def test_the_tuned_example_is_the_benchmark_s_cell():
    """`rllib train -f smallthinker-token-impala.yaml` and the cell
    `smallthinker_token_anakin_8k` are one trainer config, and the
    configuration's file holds every published number of its source but
    the ones it lists as reduced."""
    import json

    import yaml
    root = os.path.dirname(BENCH)
    with open(os.path.join(root, "ray_tpu", "rllib", "tuned_examples",
                           "smallthinker-token-impala.yaml")) as f:
        (example,) = yaml.safe_load(f).values()
    with open(os.path.join(
            BENCH, "workloads", "smallthinker_token_anakin_8k.json")) as f:
        cell = json.load(f)
    with open(os.path.join(
            BENCH, "configs", "impala_smallthinker_21b_a3b.json")) as f:
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
    published = {
        "head_dim": 128, "hidden_size": 2560, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "norm_topk_prob": True,
        "moe_primary_router_apply_softmax": True,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-6, "rope_theta": 1500000,
        "sliding_window_size": 4096, "rope_scaling": None,
        "tie_word_embeddings": False,
        "rope_layout": [0, 1, 1, 1] * 13,
        "sliding_window_layout": [0, 1, 1, 1] * 13}
    for key, value in published.items():
        assert config[key] == value, key
        if key in network:
            # The network's layouts are the four leading entries.
            assert network[key] == (
                value[:4] if key.endswith("layout") else value), key
    assert config["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "vocab_size": 151936, "max_position_embeddings": 16384}
    assert (config["moe_num_primary_experts"],
            network["moe_num_primary_experts"],
            network["experts_held"]) == (16, 64, 16)
    assert config["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size",
        "max_position_embeddings", "env"]
    assert set(config["reduced"]) == set(config["reduced_why"])
    assert config["network"]["param_count"] == 656_532_481
