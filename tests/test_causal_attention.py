"""The learner's causal attention (`transformer.causal_attention`): its
fused form, the library's TPU kernel run by the Pallas interpreter on the
CPU, against its plain form, forward and gradients, under every way an
episode can fall across the kernel's tiles; the rule that chooses between
them; and a whole `TokenDecoder.causal` pass on the fused form against the
same pass on the plain one, for both head layouts.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import catalog, transformer
from ray_tpu.models.transformer import CAUSAL_TILE, causal_fused
from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.agents.impala import IMPALATrainer
from ray_tpu.rllib.agents.impala.vtrace_policy import vtrace_loss


@pytest.fixture
def interpreted(monkeypatch):
    """The fused form's kernels run by the Pallas interpreter, and the
    row-wise passes' in front of them and behind (`models/rowwise.py`: a
    fragment and a head that are whole tiles for the one are for the
    other)."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash
    from ray_tpu.models import rowwise
    for make in ("make_splash_mha", "make_splash_mqa"):
        monkeypatch.setattr(splash, make, functools.partial(
            getattr(splash, make), interpret=True))
    for kernel in ("rotate_kernel", "gate_kernel"):
        monkeypatch.setattr(rowwise, kernel, functools.partial(
            getattr(rowwise, kernel), interpret=True))


@pytest.fixture
def fused_here(interpreted, monkeypatch):
    """A program lowered for this CPU takes the branch a TPU's would."""
    monkeypatch.setattr(
        jax.lax, "platform_dependent",
        lambda *args, tpu, default: tpu(*args))


# -- the two forms of the one sum ------------------------------------------
# (d_qk, d_v, scale): full heads (the scale already on q), and the latent
# layout decompressed (192 + 64 against 256, 1/16).
LAYOUTS = {"heads_128": (128, 128, 1.0), "latent_256": (256, 256, 0.0625)}
B, HEADS = 2, 2


def episodes(case, T):
    """`starts` [B, T]: where an episode begins, beside step 0."""
    starts = np.zeros((B, T), bool)
    if case == "reset_in_mid_tile":
        starts[:, CAUSAL_TILE + 300] = True
    elif case == "reset_on_a_tile_boundary":
        starts[:, CAUSAL_TILE] = True
    elif case == "rows_with_different_episodes":
        starts[0, [100, CAUSAL_TILE + 188]] = True
        starts[1, CAUSAL_TILE + 1] = True
    elif case == "a_row_of_resets":
        starts[0, :] = True
    else:
        assert case == "no_reset"
    starts[:, 0] = True
    return jnp.cumsum(jnp.asarray(starts), axis=1)


EPISODES = ["no_reset", "reset_in_mid_tile", "reset_on_a_tile_boundary",
            "rows_with_different_episodes", "a_row_of_resets"]
# bfloat16's: the forms differ by where the probabilities are rounded
# (the chip read 0.016 and 0.38 % at the cells' shapes, PERF.md section 5).
FORWARD_LIMIT = 0.04
GRADIENT_LIMIT = 0.01


def both_forms(layout, T, episode):
    d_qk, d_v, scale = LAYOUTS[layout]
    keys = jax.random.split(jax.random.PRNGKey(T + d_qk), 4)
    q, k = (jax.random.normal(key, (B, HEADS, T, d_qk), jnp.bfloat16)
            for key in keys[:2])
    v = jax.random.normal(keys[2], (B, HEADS, T, d_v), jnp.bfloat16)
    weight = jax.random.normal(keys[3], (B, HEADS, T, d_v), jnp.float32)

    def run(form):
        def loss(q, k, v):
            out = form(q, k, v, episode, scale)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return [np.asarray(a, np.float32) for a in (out,) + grads]
    return run(transformer._causal_fused), run(transformer._causal_plain), v


@pytest.mark.parametrize("case", EPISODES)
@pytest.mark.parametrize("tiles", [2, 4])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_fused_form_is_the_plain_form(layout, tiles, case, interpreted):
    episode = episodes(case, tiles * CAUSAL_TILE)
    fused, plain, v = both_forms(layout, tiles * CAUSAL_TILE, episode)
    assert all(np.isfinite(a).all() for a in fused)
    assert np.max(np.abs(fused[0] - plain[0])) <= FORWARD_LIMIT
    for got, want in zip(fused[1:], plain[1:]):
        assert np.linalg.norm(got - want) <= (
            GRADIENT_LIMIT * np.linalg.norm(want))
    if case == "a_row_of_resets":
        # Each step of row 0 attends to itself alone: its output is its
        # value, and no score moves it (a probability of 1 has gradient
        # dp - sum(o do) = 0 to the rounding of the two sums).
        for form in (fused, plain):
            np.testing.assert_array_equal(
                form[0][0], np.asarray(v[0], np.float32))
            assert np.max(np.abs(form[1][0])) <= 1e-4
            assert np.max(np.abs(form[2][0])) <= 1e-4


@pytest.mark.parametrize("groups,window", [
    (1, 0), (2, 0), (4, 700), (1, CAUSAL_TILE), (2, 1), (4, 3 * CAUSAL_TILE)])
def test_fused_form_over_grouped_heads_and_a_window_is_the_plain_form(
        groups, window, interpreted):
    """4 query heads over `groups` key/value heads, within `window`
    positions (0: the whole episode; one tile, a tile and a part, a
    single position, more than the fragment), an episode that starts in
    mid tile: forward and gradients."""
    T, heads, d = 3 * CAUSAL_TILE, 4, 128
    keys = jax.random.split(jax.random.PRNGKey(groups + window), 4)
    q = jax.random.normal(keys[0], (B, heads, T, d), jnp.bfloat16)
    k, v = (jax.random.normal(key, (B, groups, T, d), jnp.bfloat16)
            for key in keys[1:3])
    weight = jax.random.normal(keys[3], (B, heads, T, d), jnp.float32)
    episode = episodes("reset_in_mid_tile", T)

    def run(form):
        def loss(q, k, v):
            out = form(q, k, v, episode, d ** -0.5, window)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return [np.asarray(a, np.float32) for a in (out,) + grads]
    fused, plain = run(transformer._causal_fused), run(
        transformer._causal_plain)
    assert all(np.isfinite(a).all() for a in fused)
    assert np.max(np.abs(fused[0] - plain[0])) <= FORWARD_LIMIT
    for got, want in zip(fused[1:], plain[1:]):
        # (A probability of 1 has no gradient: to the sums' rounding.)
        assert np.linalg.norm(got - want) <= max(
            GRADIENT_LIMIT * np.linalg.norm(want), 1e-3)
    if window == 1:  # each step attends to itself alone
        per = heads // groups
        np.testing.assert_array_equal(
            plain[0], np.repeat(np.asarray(v, np.float32), per, axis=1))


def test_the_limits_refuse_a_window_layer_computed_as_a_full_one(
        interpreted):
    """A window layer computed as a full one, or with another window,
    is outside the limits that the two forms agree within."""
    T, d = 2 * CAUSAL_TILE, 128
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(key, (1, 2, T, d), jnp.bfloat16)
               for key in keys)
    episode = jnp.ones((1, T), jnp.int32)
    out = {window: np.asarray(transformer._causal_fused(
        q, k, v, episode, d ** -0.5, window), np.float32)
        for window in (0, 600, 601)}
    assert np.max(np.abs(out[0] - out[600])) > 4 * FORWARD_LIMIT
    # One position more in a window of 600 moves the late rows alone.
    assert np.array_equal(out[600][:, :, :600], out[601][:, :, :600])
    assert np.max(np.abs(out[600] - out[601])) > 0


def test_the_limits_refuse_attention_across_an_episode_s_start(interpreted):
    T = 2 * CAUSAL_TILE
    fused, _, _ = both_forms(
        "heads_128", T, episodes("reset_in_mid_tile", T))
    _, plain, _ = both_forms("heads_128", T, episodes("no_reset", T))
    assert np.max(np.abs(fused[0] - plain[0])) > 10 * FORWARD_LIMIT
    assert np.linalg.norm(fused[3] - plain[3]) > (
        10 * GRADIENT_LIMIT * np.linalg.norm(plain[3]))


# -- the rule ----------------------------------------------------------------
@pytest.mark.parametrize("T,d_qk,d_v,fused", [
    (1024, 256, 256, True),    # the second token cell's learner
    (1024, 128, 128, True),    # the first one's
    (2048, 192, 128, False),   # a width the MXU does not take whole
    (16, 128, 128, False),     # a rehearsal's or a test's fragment
    (512, 128, 128, False),    # one tile
    (1000, 128, 128, False),   # no whole tiles
    (1024, 64, 64, True),      # the fourth one's: half a lane tile
    (1024, 96, 96, False),
    (1536, 128, 256, True),
])
def test_causal_fused_is_a_rule_of_the_static_shape(T, d_qk, d_v, fused):
    assert CAUSAL_TILE == 512
    assert causal_fused(T, d_qk, d_v) == fused


@pytest.mark.parametrize("platform,T,kernel", [
    ("cpu", 1024, False), ("tpu", 1024, True), ("tpu", 1000, False)])
def test_the_form_follows_the_platform_the_program_is_lowered_for(
        platform, T, kernel):
    shape = jax.ShapeDtypeStruct((1, 2, T, 128), jnp.bfloat16)
    episode = jax.ShapeDtypeStruct((1, T), jnp.int32)
    lowered = jax.jit(functools.partial(
        transformer.causal_attention, scale=1.0)).trace(
            shape, shape, shape, episode).lower(
                lowering_platforms=(platform,))
    assert ("tpu_custom_call" in lowered.as_text()) == kernel


# -- a whole causal pass ------------------------------------------------------
FRAGMENT = 2 * CAUSAL_TILE
NETS = {
    "olmoe": dict(
        vocab_size=96, hidden_size=256, num_attention_heads=2,
        num_key_value_heads=2, num_hidden_layers=2, num_experts=4,
        num_experts_per_tok=2, intermediate_size=32,
        max_position_embeddings=FRAGMENT, rope_theta=10000.0,
        rms_norm_eps=1e-5, norm_topk_prob=False),
    "glm4_moe_lite": dict(
        vocab_size=96, hidden_size=64, num_attention_heads=2,
        num_key_value_heads=2, num_hidden_layers=2, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=64, qk_rope_head_dim=64,
        v_head_dim=128, first_k_dense_replace=1, intermediate_size=96,
        n_routed_experts=8, experts_held=2, first_expert_held=0,
        num_experts_per_tok=2, moe_intermediate_size=32,
        n_shared_experts=1, topk_method="noaux_tc", n_group=1, topk_group=1,
        norm_topk_prob=True, routed_scaling_factor=1.8,
        num_nextn_predict_layers=1, max_position_embeddings=FRAGMENT,
        rope_theta=1e6, rms_norm_eps=1e-5),
}
# (the mean difference and a difference, as shares of a leaf's largest
# magnitude; the share of the elements that may pass the latter). float32: the
# two forms are one sum to rounding. bfloat16: their roundings differ, and
# where a token's experts tie within that, one of them changes (0.4-0.9 %
# of the tokens here) and that position's logits with it.
PASS_LIMITS = {"f32": (2e-5, 2e-4, 0.0), "bf16": (0.004, 0.05, 0.02)}


@pytest.mark.parametrize("dtype", PASS_LIMITS)
@pytest.mark.parametrize("family", NETS)
def test_a_causal_pass_on_the_fused_form_is_the_pass_on_the_plain_one(
        family, dtype, fused_here, monkeypatch):
    net = NETS[family]
    model = catalog.get_model(None, net["vocab_size"], {
        "custom_model": family, "custom_model_config": net,
        "compute_dtype": dtype})
    rows = 2
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (rows, FRAGMENT), 0, net["vocab_size"])
    # Row 1 starts a second episode in the middle of the second tile.
    reset = jnp.zeros((rows, FRAGMENT)).at[1, CAUSAL_TILE + 77].set(1.0)
    variables = model.init(jax.random.PRNGKey(0), tokens[:, :1],
                           model.initial_state(rows), jnp.zeros((rows, 1)))
    assert model.static_counters(rows, FRAGMENT, "tpu")[
        "causal_attention_fused"] == 1.0
    assert model.static_counters(rows, FRAGMENT, "cpu")[
        "causal_attention_fused"] == 0.0

    def run():
        (logits, values, state), kept = model.apply(
            variables, tokens, None, reset, mutable=["losses", "counters"])
        return logits, values, state, kept.get("losses", {})
    fused = run()
    monkeypatch.undo()  # the program is lowered for what it runs on
    plain = run()
    mean, most, share = PASS_LIMITS[dtype]
    for got, want in zip(jax.tree.leaves(fused), jax.tree.leaves(plain)):
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        scale = np.max(np.abs(want)) + 1e-8
        assert np.mean(np.abs(got - want)) <= mean * scale
        assert np.mean(np.abs(got - want) > most * scale) <= share
    np.testing.assert_array_equal(fused[2]["pos"], plain[2]["pos"])


def test_vtrace_gradients_through_the_fused_form_of_the_latent_layout(
        fused_here, monkeypatch):
    """The learner's loss (V-trace plus the module's term, through the
    recomputed blocks that keep the kernel's output and log-sum-exp) and
    its gradient for every parameter, fused form against plain."""
    net = NETS["glm4_moe_lite"]
    rows = 2
    trainer = IMPALATrainer(config=dict(
        env="TokenBigram-v0",
        env_config={"vocab_size": net["vocab_size"], "episode_len": FRAGMENT},
        anakin=True, num_workers=0, num_envs_per_worker=rows,
        rollout_fragment_length=FRAGMENT, train_batch_size=rows * FRAGMENT,
        sgd_minibatch_size=rows * FRAGMENT, num_sgd_iter=1,
        anakin_updates_per_call=1, min_iter_time_s=0, lr=1e-6, seed=3,
        model={"custom_model": "glm4_moe_lite", "custom_model_config": net,
               "compute_dtype": "f32"}))
    try:
        policy = trainer.get_policy()
        rng = np.random.default_rng(5)
        tokens = rng.integers(0, net["vocab_size"], size=(rows, FRAGMENT))
        dones = np.zeros((rows, FRAGMENT), np.float32)
        dones[:, -1] = 1.0
        batch = {
            sb.OBS: jnp.asarray(tokens.reshape(-1), jnp.int32),
            sb.ACTIONS: jnp.asarray(rng.integers(
                0, net["vocab_size"], size=rows * FRAGMENT), jnp.int32),
            sb.REWARDS: jnp.asarray(rng.integers(
                0, 2, size=rows * FRAGMENT).astype(np.float32)),
            sb.DONES: jnp.asarray(dones.reshape(-1)),
            sb.ACTION_LOGP: jnp.asarray(rng.uniform(
                -5.5, -4.0, size=rows * FRAGMENT).astype(np.float32)),
            sb.BOOTSTRAP_OBS: jnp.asarray(tokens[:, 0], jnp.int32),
        }
        variables = jax.tree.map(jnp.asarray, policy.get_weights())

        def run():
            (total, stats), grads = jax.value_and_grad(
                lambda v: vtrace_loss(policy, v, batch, None, {}),
                has_aux=True)(variables)
            return total, stats, grads["params"]
        total, stats, grads = run()
        monkeypatch.undo()
        want_total, want_stats, want_grads = run()
    finally:
        trainer.stop()
    np.testing.assert_allclose(total, want_total, rtol=1e-5)
    np.testing.assert_allclose(
        stats["mtp_loss"], want_stats["mtp_loss"], rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(want))) + 1e-8
        assert float(jnp.max(jnp.abs(got - want))) <= 2e-3 * scale, path
