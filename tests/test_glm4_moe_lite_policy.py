"""The `glm4_moe_lite` token policy at a tiny size on the CPU: the family's
row, the checks it shares with the other families (`tests/token_families.py`:
each named wrong mathematics refused by the cell's limits, by the outputs, the
routing or the module's loss; the grouped form of the expert product; the
builder's refusals; the tuned example) and what is its own: the model and its
next-next-token module's loss against the plain reference
(`benchmark/lib/reference_glm4_moe_lite.py`), latent attention absorbed
through its cache against the decompressed causal pass, the expert layer that
holds a share against the uncut layer and its dispatch over the landed rows,
the module's loss and where its gradient goes. The loss and the loop:
`tests/test_glm4_moe_lite_update.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from token_families import (  # noqa: F401: pytest collects what is named
    Family, build, causal_routed, configuration, decode_routed, judged,
    test_a_causal_pass_over_the_landed_rows_is_the_batched_pass,
    test_custom_model_config_without_a_part_is_refused
    as test_unknown_custom_model_config_keys_are_refused,
    test_limits_refuse_wrong_mathematics,
    test_the_tuned_example_is_the_benchmark_s_cell)

from lib import reference_glm4_moe_lite as reference

from ray_tpu.models import catalog, transformer
from ray_tpu.models.transformer import dropless_experts, experts_batched

# One dense layer, two expert layers holding 2 of 8 routed experts beside a
# shared one, the module, a vocabulary of 96: the published keys.
NET = dict(vocab_size=96, hidden_size=64, num_attention_heads=4,
           num_key_value_heads=4, num_hidden_layers=3, q_lora_rank=24,
           kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8,
           v_head_dim=16, first_k_dense_replace=1, intermediate_size=96,
           n_routed_experts=8, experts_held=2, first_expert_held=0,
           num_experts_per_tok=2, moe_intermediate_size=32,
           n_shared_experts=1, topk_method="noaux_tc", n_group=1,
           topk_group=1, norm_topk_prob=True, routed_scaling_factor=1.8,
           num_nextn_predict_layers=1, max_position_embeddings=16,
           rope_theta=1e6, rms_norm_eps=1e-5)
B, S = 3, 16
LATENT = NET["kv_lora_rank"] + NET["qk_rope_head_dim"]

FAMILY = Family(
    name="glm4_moe_lite", net=NET, reference=reference, B=B, S=S,
    collections=frozenset({"params", "constants"}),
    # The module's cross-entropy by position is judged beside the outputs.
    kept=("losses",), module_loss=("nextn_nll", "nextn_nll_by_position"),
    # A selection bias as large as the scores' own spread, so that what it
    # is let into shows.
    limits_build=dict(bias_scale=0.2),
    refused_by={
        # The trunk is as it was; the module's loss and its router differ.
        "module_without_norms": lambda verdicts: (
            not verdicts["loss"]["ok"] and verdicts["outputs"]["ok"]),
        "bias_left_out_of_choice": lambda verdicts:
            not verdicts["routing"]["ok"]},
    envs=8,
    wrong_updates={
        "module_s_weight_dropped": dict(
            patch=("NEXTN_LOSS_WEIGHT", 0.0), by="loss_error"),
        "no_shared_expert_in_the_gradient": dict(
            mutate="no_shared_expert", by="update_error"),
        "sum_over_part_of_the_batch": dict(
            part_of_the_batch=True, by=("loss_error", "update_error")),
        "vf_coeff_doubled": dict(cfg={"vf_loss_coeff": 1.0},
                                 by="loss_error"),
        "ten_times_the_entropy_coeff": dict(cfg={"entropy_coeff": 0.1},
                                            by="loss_error"),
        "no_clip": dict(cfg={"grad_clip": None}, by="update_error"),
        "ten_times_the_lr": dict(cfg={"lr": 6e-3}, by="update_error"),
        "adam_s_state_not_read": dict(fresh_moments=True,
                                      by="update_error")},
    refused=(
        ({"num_experts": 8}, "not glm4_moe_lite's"),
        ({"compute_path": "absorbed"}, "not glm4_moe_lite's"),
        ({"topk_method": "greedy"}, "noaux_tc"),
        ({"n_group": 2}, "n_group"),
        ({"num_key_value_heads": 2}, "key/value heads"),
        ({"experts_held": 6, "first_expert_held": 4}, "not among")),
    example="glm47-flash-token-impala.yaml",
    cell="glm47_flash_token_anakin", config="impala_glm_4_7_flash")


# -- the model against the reference ------------------------------------
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_causal_pass_and_module_loss_match_reference(dtype):
    """float32 blocks: to float32 accuracy, the same experts in every
    layer. bfloat16 blocks: the limits written beside the reference."""
    built = build(FAMILY, dtype)
    _, variables, tokens = built
    system, _, kept = causal_routed(built, variables, tokens)
    # Two expert layers and the module's: the dense layer routes nothing.
    assert system[2].shape == (3, B, S, NET["num_experts_per_tok"])
    verdicts, _ = judged(FAMILY, system, variables, tokens)
    outputs, routing, loss = (
        verdicts[name] for name in ("outputs", "routing", "loss"))
    if dtype == "f32":
        assert routing["router_flips"] == 0.0
        assert max(outputs["errors"].values()) < 1e-5, outputs
        assert loss["error_by_position"] < 1e-4, loss
    else:
        # 144 (token, layer) pairs: one flip is 0.7 %, and a near-tie.
        assert routing["router_flips"] <= 0.1
        assert routing["max_flip_gap"] <= reference.MAX_FLIP_GAP
        assert outputs["ok"] and loss["ok"], (outputs, loss)
    # The weighted sum the objective adds, and the mean the stats show.
    nll = kept["losses"]["next_next_token"][-1] / reference.NEXTN_LOSS_WEIGHT
    np.testing.assert_allclose(nll, jnp.sum(system[3]), rtol=1e-6)
    np.testing.assert_allclose(nll / (B * (S - 2)),
                               kept["counters"]["mtp_loss"][-1], rtol=1e-6)


# -- latent attention: absorbed through the cache == decompressed --------
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_absorbed_decode_through_latent_cache_matches_causal_pass(dtype):
    """Every position decoded one token at a time, W_UK absorbed into the
    query and W_UV applied to the weighted latents, against the causal
    pass's decompressed keys and values; the latent window read whole."""
    built = build(FAMILY, dtype)
    model, variables, tokens = built
    # One program a form in float32, for the time it saves. Not in
    # bfloat16: XLA:CPU then rounds elsewhere than the other form's ops
    # do, and one near-tie of a router moves a token's logits.
    jit = dtype == "f32"
    (logits, values, _, _), _, _ = causal_routed(
        built, variables, tokens, jit=jit)
    # The state: one latent row a position a layer, and nothing wider.
    assert [[c.shape for c in layer]
            for layer in model.initial_state(B)["kv"]] == [
        [(B, S, LATENT)]] * NET["num_hidden_layers"]
    (got_l, got_v, _), state, counted = decode_routed(
        built, variables, tokens, jit=jit)
    assert [step["decode_cache_read_share"] for step in counted] == [1.0] * S
    tol = 1e-5 if dtype == "f32" else reference.TOLERANCE
    assert reference.relative_error(got_l, logits) <= tol
    assert reference.relative_error(got_v, values) <= tol
    assert np.all(np.asarray(state["pos"]) == S)
    assert [[c.shape for c in layer] for layer in state["kv"]] == [
        [(B, S, LATENT)]] * NET["num_hidden_layers"]
    assert model.static_counters(B, S, "cpu")["latent_cache_bytes_per_token"] == (
        NET["num_hidden_layers"] * LATENT * (4 if dtype == "f32" else 2))


def test_prefill_then_absorbed_decode_and_a_reset_inside_a_fragment():
    """The causal pass returns the latents a decode continues from, and
    a reset inside a fragment starts a fresh episode in both forms."""
    built = build(FAMILY, "f32")
    model, variables, tokens = built

    def causal(tokens, reset=None):
        (logits, _, _, _), state, _ = causal_routed(
            built, variables, tokens, reset)
        return logits, state
    full, _ = causal(tokens)
    _, state = causal(tokens[:, :10])
    assert state["kv"][0][0].shape == (B, S, LATENT)
    for t in range(10, S):
        step, _, state = built.decode(
            variables, tokens[:, t:t + 1], state, jnp.zeros((B, 1)))
        assert reference.relative_error(step[:, 0], full[:, t]) < 1e-5
    reset = jnp.zeros((B, S)).at[:, 8].set(1.0)
    both, state = causal(tokens, reset)
    second, _ = causal(tokens[:, 8:])
    assert reference.relative_error(both[:, 8:], second) < 1e-5
    assert reference.relative_error(both[:, :8], full[:, :8]) < 1e-5
    assert np.all(np.asarray(state["pos"]) == 8)
    state = model.initial_state(B)
    for t in range(S):
        step, _, state = built.decode(
            variables, tokens[:, t:t + 1], state, reset[:, t:t + 1])
        assert reference.relative_error(step[:, 0], both[:, t]) < 1e-5


# -- the expert layer that holds a share ---------------------------------
def test_the_shares_add_up_to_the_uncut_layer():
    """Eight shares of 1 of the 8 routed experts: their routed parts, with
    what every chip computes alike (the shared expert) counted once, add up
    to what the uncut reference gives for the whole layer. The system's
    layer (both forms of its product) and the reference's, share by
    share."""
    rng = np.random.default_rng(0)
    H, W, E, k, held = 64, 32, 8, 2, 1
    uncut = dict(NET, experts_held=E, rms_norm_eps=1e-5)
    lp = {
        "mlp_norm": np.ones(H, np.float32),
        "router": rng.normal(size=(H, E)).astype(np.float32) / 8,
        "w_gate": rng.normal(size=(E, H, W)).astype(np.float32) / 8,
        "w_up": rng.normal(size=(E, H, W)).astype(np.float32) / 8,
        "w_down": rng.normal(size=(E, W, H)).astype(np.float32) / 6,
        "shared_gate": rng.normal(size=(H, W)).astype(np.float32) / 8,
        "shared_up": rng.normal(size=(H, W)).astype(np.float32) / 8,
        "shared_down": rng.normal(size=(W, H)).astype(np.float32) / 6,
    }
    lp = jax.tree.map(jnp.asarray, lp)
    bias = jnp.asarray(rng.normal(size=E) * 0.05, jnp.float32)
    h = jnp.asarray(rng.normal(size=(2, 12, H)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _, _ = reference._moe(lp, bias, h, uncut, lambda x: x, None,
                                     None)
        no_shared, _, _ = reference._moe(
            lp, bias, h, uncut, lambda x: x, "no_shared_expert", None)
    shared_once = (whole - h) - (no_shared - h)

    def share_of(lp, first):
        return dict(lp, **{w: lp[w][first:first + held]
                           for w in ("w_gate", "w_up", "w_down")})

    # The reference's shares.
    routed = jnp.zeros_like(h)
    for first in range(0, E, held):
        net = dict(uncut, experts_held=held, first_expert_held=first)
        with jax.default_matmul_precision("highest"):
            part, _, _ = reference._moe(
                share_of(lp, first), bias, h, net, lambda x: x,
                "no_shared_expert", None)
        routed = routed + (part - h)
    assert reference.relative_error(routed + shared_once, whole - h) < 1e-5

    # The system's shares, in the form each shape takes.
    n = transformer.rms_norm(h.reshape(-1, H), lp["mlp_norm"], 1e-5,
                             jnp.float32)
    top_p, top_i = transformer.route(n, lp["router"], k, True, bias, 1.8)
    for rows in (n, jnp.tile(n, (64, 1))):
        M = rows.shape[0]
        reps = M // n.shape[0]
        p, i = jnp.tile(top_p, (reps, 1)), jnp.tile(top_i, (reps, 1))
        routed, landed = jnp.zeros_like(rows), 0
        for first in range(0, E, held):
            s = share_of(lp, first)
            part, sizes, _ = dropless_experts(
                rows, p, i, s["w_gate"], s["w_up"], s["w_down"], first, E)
            routed, landed = routed + part, landed + int(jnp.sum(sizes))
        assert landed == M * k  # every pair lands on exactly one share
        want = (whole - h).reshape(-1, H) - shared_once.reshape(-1, H)
        assert reference.relative_error(
            routed[:n.shape[0]], want) < 1e-4, M
    assert experts_batched(n.shape[0], k, E)
    assert not experts_batched(64 * n.shape[0], k, E)


HELD_SHAPES = {"batched": 24, "grouped": 4096}
HELD_ROUTINGS = ["every_pair_lands_here", "no_pair_lands_here", "random"]


def held_case(routing, form, dtype):
    """Seeded rows, a routing over 8 experts and the weights of experts
    2-4; (n, top_p, top_i, w_gate, w_up, w_down, first, E)."""
    M, (H, W, k, E, first, held) = HELD_SHAPES[form], (16, 8, 2, 8, 2, 3)
    assert experts_batched(M, k, E) == (form == "batched")
    cd = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
    rng = np.random.default_rng(0)
    n = jnp.asarray(rng.normal(size=(M, H)), cd)
    w_gate, w_up = (jnp.asarray(rng.normal(size=(held, H, W)), cd)
                    for _ in range(2))
    w_down = jnp.asarray(rng.normal(size=(held, W, H)), cd)
    if routing == "every_pair_lands_here":
        top_i = np.stack([np.full(M, 3), 2 + 2 * rng.integers(0, 2, M)], 1)
    elif routing == "no_pair_lands_here":
        top_i = np.stack([rng.integers(0, 2, M), rng.integers(5, 8, M)], 1)
    else:
        top_i = np.stack([rng.permutation(E)[:k] for _ in range(M)])
    top_i = jnp.asarray(top_i, jnp.int32)
    top_p = jnp.asarray(rng.uniform(0.05, 0.5, size=(M, k)), jnp.float32)
    return n, top_p, top_i, w_gate, w_up, w_down, first, E


def held_masked_loop(n, top_p, top_i, w_gate, w_up, w_down, first, E):
    """Every held expert on every token times its weight or 0, float32;
    an absent expert adds nothing."""
    n, w_gate, w_up, w_down = (
        a.astype(jnp.float32) for a in (n, w_gate, w_up, w_down))
    want = jnp.zeros(n.shape)
    for e in range(w_gate.shape[0]):
        weight = jnp.sum(jnp.where(top_i == first + e, top_p, 0.0), axis=1)
        out = (jax.nn.silu(n @ w_gate[e]) * (n @ w_up[e])) @ w_down[e]
        want = want + weight[:, None] * out
    return want


HELD_TOLERANCE = {"f32": 2e-4, "bf16": reference.TOLERANCE}


@pytest.mark.parametrize("dtype", HELD_TOLERANCE)
@pytest.mark.parametrize("form", HELD_SHAPES)
@pytest.mark.parametrize("routing", HELD_ROUTINGS)
def test_held_expert_dispatch_equals_dense_masked_loop(routing, form, dtype):
    """Both forms of the product over a share of the experts == the held
    experts on every token times a 0/1 mask, values and gradients,
    whatever lands here (everything: the static worst case; nothing)."""
    case = held_case(routing, form, dtype)
    n, top_p, top_i, *weights, first, E = case
    got, group_sizes, _ = dropless_experts(*case)
    want = held_masked_loop(*case)
    assert got.dtype == n.dtype and group_sizes.shape == (3,)
    here = (np.asarray(top_i) >= first) & (np.asarray(top_i) < first + 3)
    assert int(jnp.sum(group_sizes)) == int(here.sum())
    if routing == "every_pair_lands_here":
        assert int(here.sum()) == top_i.size
    if routing == "no_pair_lands_here":
        assert int(here.sum()) == 0 and not np.any(np.asarray(got))
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    assert reference.relative_error(
        got.astype(jnp.float32), want, scale=scale) <= HELD_TOLERANCE[dtype]

    target = jnp.asarray(
        np.random.default_rng(1).normal(size=n.shape), jnp.float32)

    def loss(experts):
        return lambda n, *w: jnp.sum(experts(
            n, top_p, top_i, *w, first, E).astype(jnp.float32) * target)

    got_g = jax.grad(loss(lambda *a: dropless_experts(*a)[0]),
                     argnums=(0, 1, 2, 3))(n, *weights)
    want_g = jax.grad(loss(held_masked_loop), argnums=(0, 1, 2, 3))(
        n, *weights)
    for name, g, w in zip(("n", "w_gate", "w_up", "w_down"), got_g, want_g):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        assert g.dtype == n.dtype and bool(jnp.all(jnp.isfinite(g)))
        assert reference.relative_error(
            g.astype(jnp.float32), w, scale=scale) \
            <= HELD_TOLERANCE[dtype], name


def ragged_dot_that_computes_no_row_past_the_groups(fill):
    """`jax.lax.ragged_dot` as XLA:TPU runs it: a row in no group is not
    computed, in the product and in its transposes, and holds what the
    memory held (`fill`). XLA:CPU leaves 0 there, so on the CPU the
    grouped form's gradients were right with the absent pairs' rows
    unmasked, and on a v5e they were not."""
    real = jax.lax.ragged_dot

    def past(x, sizes):
        return (jnp.arange(x.shape[0]) >= jnp.sum(sizes))[:, None]

    @jax.custom_vjp
    def dot(lhs, rhs, sizes):
        return jnp.where(past(lhs, sizes), fill, real(lhs, rhs, sizes))

    def forward(lhs, rhs, sizes):
        return dot(lhs, rhs, sizes), (lhs, rhs, sizes)

    def backward(kept, dy):
        lhs, rhs, sizes = kept
        _, transposes = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = transposes(jnp.where(past(dy, sizes), 0, dy))
        return (jnp.where(past(lhs, sizes), fill, d_lhs).astype(lhs.dtype),
                d_rhs, np.zeros(sizes.shape, jax.dtypes.float0))
    dot.defvjp(forward, backward)
    return dot


@pytest.mark.parametrize("fill", [jnp.nan, 3e4])
@pytest.mark.parametrize("routing", HELD_ROUTINGS)
def test_grouped_form_reads_no_row_that_no_product_computed(
        routing, fill, monkeypatch):
    """The grouped form over a share, values and gradients, with the rows
    past the last group holding NaN or a large number wherever a
    `ragged_dot` or its transpose left them: the same as the masked loop."""
    monkeypatch.setattr(
        jax.lax, "ragged_dot",
        ragged_dot_that_computes_no_row_past_the_groups(fill))
    case = held_case(routing, "grouped", "f32")
    n, top_p, top_i, *weights, first, E = case
    target = jnp.asarray(
        np.random.default_rng(1).normal(size=n.shape), jnp.float32)

    def loss(experts):
        return lambda n, top_p, *w: jnp.sum(
            experts(n, top_p, top_i, *w, first, E) * target)
    got = jax.value_and_grad(loss(lambda *a: dropless_experts(*a)[0]),
                             argnums=(0, 1, 2, 3, 4))(n, top_p, *weights)
    want = jax.value_and_grad(loss(held_masked_loop),
                              argnums=(0, 1, 2, 3, 4))(n, top_p, *weights)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(g)))
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        assert reference.relative_error(g, w, scale=scale) <= 2e-4


# The grouped shape's ladder (4,096 rows x 2 of 8 experts, 3 held: 3,072
# pairs expected, 8,192 in all) and how many pairs the routing lands here:
# nothing, under the shortest size, on each size's edge and one over it,
# every pair.
LADDER = (3840, 6144, 8192)
LANDED = {"nothing": (0, 3840), "under_the_shortest": (3000, 3840),
          "on_the_first_edge": (3840, 3840),
          "one_over_the_first": (3841, 6144),
          "on_the_second_edge": (6144, 6144),
          "one_over_the_second": (6145, 8192), "every_pair": (8192, 8192)}
# The same with one short size, (3840, 8192).
OF_TWO = {"on_the_edge_of_two": (3840, 3840), "one_over_of_two": (3841, 8192),
          "most_of_two": (7681, 8192), "every_pair_of_two": (8192, 8192)}


def landing(count):
    """`held_case`'s grouped shape with a routing built so that exactly
    `count` of its 8,192 (row, expert) pairs land on experts 2-4, a row's
    two experts distinct, the rows shuffled."""
    n, top_p, _, *rest = held_case("random", "grouped", "f32")
    M = n.shape[0]
    rng = np.random.default_rng(count)
    first = np.arange(M) < min(count, M)
    second = np.arange(M) < count - M
    top_i = np.stack([
        np.where(first, rng.integers(2, 4, M), rng.integers(0, 2, M)),
        np.where(second, 4, rng.integers(5, 8, M))], 1)
    return (n, top_p, jnp.asarray(rng.permutation(top_i), jnp.int32), *rest)


def test_the_ladder_is_a_function_of_the_static_shape():
    """Multiples of the expected landed count in whole tiles, the whole
    last; one size where every expert is here, where the shape takes the
    batched form, and where the first multiple already reaches M k."""
    rows = transformer.dispatch_rows
    assert rows(4096, 2, 3, 8) == LADDER
    assert all(R % transformer.DISPATCH_TILE == 0 for R in LADDER)
    # The four cells' minibatches (PERF.md section 5).
    assert rows(8192, 4, 8, 64) == (5120, 8192, 16384, 32768)
    assert rows(8192, 4, 8, 32) == (10240, 16384, 32768)
    assert rows(8192, 6, 16, 64) == (15360, 24576, 49152)
    assert rows(8192, 8, 8, 256) == (2560, 4096, 8192, 65536)
    assert rows(8192, 8, 64, 64) == (65536,)  # OLMoE: all held
    assert rows(128, 4, 8, 64) == (512,)  # a decode step: batched
    assert rows(4096, 2, 5, 8) == (6400, 8192)  # 2 x 5,120 is past M k
    assert rows(4096, 2, 7, 8) == (8192,)
    for count, R in LANDED.values():
        index = int(transformer.dispatch_index(jnp.int32(count), LADDER))
        assert LADDER[index] == R


@pytest.mark.parametrize("landed", [*LANDED, *OF_TWO])
def test_a_share_s_dispatch_over_the_landed_rows_equals_the_masked_loop(
        landed, monkeypatch):
    """The grouped form over a share, under `jit` with the landed count a
    traced value, whichever size of the ladder the count takes: values and
    gradients (rows, weights of the sum, the three matrices) are the dense
    masked loop's, with NaN in every row of a `ragged_dot` and of its
    transpose that lies between the landed count and the size."""
    monkeypatch.setattr(
        jax.lax, "ragged_dot",
        ragged_dot_that_computes_no_row_past_the_groups(jnp.nan))
    if landed in OF_TWO:
        monkeypatch.setattr(transformer, "DISPATCH_MULTIPLES", (1.25,))
    count, R = {**LANDED, **OF_TWO}[landed]
    n, top_p, top_i, *weights, first, E = landing(count)
    assert transformer.dispatch_rows(*top_i.shape, 3, E) == (
        LADDER if landed in LANDED else (LADDER[0], LADDER[-1]))
    target = jnp.asarray(
        np.random.default_rng(1).normal(size=n.shape), jnp.float32)

    def loss(experts):
        def of(top_i, n, top_p, *w):
            y = experts(n, top_p, top_i, *w, first, E)
            y, *load = y if isinstance(y, tuple) else (y, None, None)
            return jnp.sum(y * target), load
        return jax.jit(jax.value_and_grad(
            of, argnums=(1, 2, 3, 4, 5), has_aux=True))
    (got, (sizes, gathered)), got_g = loss(dropless_experts)(
        top_i, n, top_p, *weights)
    (want, _), want_g = loss(held_masked_loop)(top_i, n, top_p, *weights)
    assert (int(jnp.sum(sizes)), int(gathered)) == (count, R)
    for g, w in zip((got, *got_g), (want, *want_g)):
        assert bool(jnp.all(jnp.isfinite(g)))
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        assert reference.relative_error(g, w, scale=scale) <= 2e-4
    if not count:
        assert not any(np.any(np.asarray(g)) for g in (got, *got_g))


# (M, k, E) -> the form: the shapes the OLMoE tests list and the sweep's
# crossing (PERF.md section 5), as before the layer could hold a share; and
# the new cell's decode step and update, whose held count (8 of 64) scales
# both sides of the rule alike.
FORMS = [
    (128, 8, 64, True), (8192, 8, 64, False), (4, 8, 64, True),
    (2048, 8, 64, False), (24, 2, 4, True), (1024, 2, 8, False),
    (512, 8, 64, True), (768, 8, 64, False), (1024, 8, 64, False),
    (128, 4, 64, True), (8192, 4, 64, False)]


@pytest.mark.parametrize("M,k,E,batched", FORMS)
def test_experts_batched_is_the_rule_it_was(M, k, E, batched):
    assert experts_batched(M, k, E) == batched == (
        M * E <= transformer.GROUP_COST_ROWS * E
        + transformer.GROUPED_ROW_COST * M * k)
    for held in (1, E // 2, E):  # what a share changes: nothing
        assert (M * held <= transformer.GROUP_COST_ROWS * held
                + transformer.GROUPED_ROW_COST * M * k * held / E) == batched


def test_the_cell_s_forms_are_chosen_from_the_static_shape():
    """At the published widths a decode step of 128 sequences (8 rows a
    held expert) traces to no grouped product, the learner's causal pass
    over 8,192 tokens to three an expert layer; the
    latent cache is 5,760 bytes a position; nothing but shapes is built."""
    net = dict(NET, vocab_size=19360, hidden_size=2048,
               num_attention_heads=20, num_key_value_heads=20,
               num_hidden_layers=5, q_lora_rank=768, kv_lora_rank=512,
               qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
               intermediate_size=10240, n_routed_experts=64, experts_held=8,
               num_experts_per_tok=4, moe_intermediate_size=1536,
               max_position_embeddings=1024)
    model = catalog.get_model(None, net["vocab_size"], {
        "custom_model": "glm4_moe_lite", "custom_model_config": net})
    assert model.static_counters(128, 1024, "tpu") == {
        "decode_rows_per_expert": 8.0, "decode_experts_batched": 1.0,
        "decode_experts_sparse": 0.0, "decode_experts_read_share": 1.0,
        "decode_cache_block": 128, "decode_attention_kernel": 1.0,
        "latent_cache_bytes_per_token": 5760, "causal_attention_fused": 1.0,
        "rotation_fused_layers": 0.0}
    assert model.static_counters(128, 1024, "cpu")[
        "decode_cache_block"] == 1024

    def shapes(b, t):
        return (jax.ShapeDtypeStruct((b, t), jnp.int32),
                jax.eval_shape(lambda: model.initial_state(b)),
                jax.ShapeDtypeStruct((b, t), jnp.float32))
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                               *shapes(1, 1))
    count = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(variables))
    assert count == 706_520_897
    assert jax.tree.leaves(shapes(128, 1)[1]["kv"])[0].shape == (
        128, 1024, 576)
    decode = str(jax.make_jaxpr(model.apply)(variables, *shapes(128, 1)))
    learn = str(jax.make_jaxpr(
        lambda v, *a: model.apply(v, *a, mutable=["losses"]))(
            variables, *shapes(8, 1024)))
    assert decode.count("= ragged_dot_general[") == 0
    assert decode.count("name=_fused_product") == 0
    # (The printer shows a recomputed block's body once for all its uses.)
    # A grouped product at these widths is `grouped_product`'s two forms,
    # the kernel's and `ragged_dot`'s, under one name (PR 47).
    grouped = learn.count("name=_fused_product")
    assert grouped > 0 and grouped % 3 == 0


# -- the module's loss: where its gradient goes -------------------------
def test_module_loss_gradients_match_reference_and_stop_at_the_trunk():
    """d(module's loss) against `jax.grad` of the reference's; it reaches
    the module's own parameters alone: none into the trunk, the embedding
    or the head (it follows the policy, it does not move it), and none
    into any router's selection bias."""
    model, variables, tokens = build(FAMILY, "f32")

    def system(v):
        _, kept = model.apply(v, tokens, None, jnp.zeros((B, S)),
                              mutable=["losses"])
        return kept["losses"]["next_next_token"][-1]

    def plain(v):
        return reference.NEXTN_LOSS_WEIGHT * reference.forward(
            v, tokens, NET)["nextn_nll"]

    got, grads = jax.jit(jax.value_and_grad(system))(variables)
    want, want_grads = jax.jit(jax.value_and_grad(plain))(variables)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, g in grads["params"].items():
        moved = any(bool(jnp.any(leaf != 0)) for leaf in jax.tree.leaves(g))
        assert moved == (name == "nextn_0"), name
    assert not any(bool(jnp.any(leaf != 0))
                   for leaf in jax.tree.leaves(grads["constants"]))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads["params"]["nextn_0"])
    for (path, g), w in zip(
            flat, jax.tree.leaves(want_grads["params"]["nextn_0"])):
        scale = float(jnp.max(jnp.abs(w))) + 1e-8
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-3 * scale, path


def test_the_configuration_s_file_holds_its_source_s_published_numbers():
    """Every published number of the source (catalog row 81) but the ones
    the file lists as reduced."""
    _, _, config, network = configuration(FAMILY)
    published = {
        "hidden_size": 2048, "intermediate_size": 10240,
        "moe_intermediate_size": 1536, "num_attention_heads": 20,
        "num_key_value_heads": 20, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
        "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "rope_theta": 1000000,
        "rms_norm_eps": 1e-5, "norm_topk_prob": True, "n_group": 1,
        "topk_group": 1, "topk_method": "noaux_tc"}
    for key, value in published.items():
        assert config[key] == value, key
        assert network[key] == value, key
    assert config["published"] == {
        "num_hidden_layers": 47, "n_routed_experts": 64,
        "vocab_size": 154880, "max_position_embeddings": 202752}
    assert (config["n_routed_experts"], network["n_routed_experts"],
            network["experts_held"]) == (8, 64, 8)
