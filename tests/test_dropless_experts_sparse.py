"""A rollout's step reads the held experts its rows chose, and no others.

`dropless_experts(.., rollout=True)` at a shape the static rule takes
(`experts_sparse`, `expert_step.whole_tiles`) in a program lowered for a TPU
is `expert_step.chosen_kernel`: the batched form's sum over the held experts
with a row alone. Here the kernel runs by the Pallas interpreter and the
program takes the branch a TPU's would (`chosen_here`).

* the kernel against the batched form, at the three cells' kinds of expert
  (gated SiLU, gated ReLU, un-gated squared ReLU) over a held share that
  starts past expert 0, under routings that leave some held experts without
  a row, give ONE expert every row, and land NO pair (the sum is exactly 0);
* the rule, from the benchmark's own files: the three cells whose steps bring
  under two rows a held expert take the form, the other five token cells, a
  block model's passes, every learner's minibatch and every caller that does
  not say `rollout` do not;
* the learner's bootstrap step (one position through `apply`, under
  `value_and_grad`) keeps the batched form, value and gradient, while
  `step_state` of the same policy takes the kernel;
* `decode_experts_read_share` against a hand count on a seeded step, and
  1.0 where the same step's program is lowered for a CPU.
"""

import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import catalog, expert_step, transformer
from ray_tpu.models.transformer import dropless_experts, experts_sparse

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

# The cells whose rollout step brings under two rows a held expert, and the
# other token cells.
SPARSE = ("qwen3_next_token_anakin_4k", "kimi_linear_token_anakin_4k",
          "smallthinker_token_anakin_8k")
BATCHED = ("glm47_flash_token_anakin", "lfm2_token_anakin_4k",
           "nemotron_h_token_anakin_2k", "olmoe_token_anakin",
           "sdar_block_token_anakin_2k")


@pytest.fixture
def chosen_here(monkeypatch):
    """A program lowered for this CPU takes the branch a TPU's would, the
    chosen experts' kernel runs by the Pallas interpreter, and its tiles
    are a test's; the calls it took are counted."""
    calls = []

    def kernel(*operands, **options):
        calls.append(operands[0].shape)
        return real(*operands, **options, interpret=True)
    real = expert_step.chosen_kernel
    monkeypatch.setattr(expert_step, "chosen_kernel", kernel)
    monkeypatch.setattr(expert_step, "whole_tiles",
                        lambda M, H, W, dtype=None: M % 2 == 0)
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    return calls


# (rows, hidden, width, experts, held, first, k, gated, activation): the
# three cells' experts at a rehearsal's size, each a held share that starts
# past expert 0; and the un-gated kind.
KINDS = {
    "qwen3_next": (32, 128, 128, 512, 32, 64, 10, True, "silu"),
    "kimi_linear": (32, 256, 128, 256, 8, 16, 8, True, "silu"),
    "smallthinker": (16, 128, 256, 64, 16, 32, 6, True, "relu"),
    "un_gated": (16, 128, 128, 64, 8, 8, 6, False, "relu2"),
}


def routed(kind, routing, dtype, seed=0):
    M, H, W, E, held, first, k, gated, act = KINDS[kind]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = jax.random.normal(ks[0], (M, H), dtype)
    top_p = jax.nn.softmax(jax.random.normal(ks[1], (M, k), jnp.float32))
    if routing == "some_empty":
        _, top_i = jax.lax.top_k(jax.random.gumbel(ks[2], (M, E)), k)
    elif routing == "one_has_every_row":
        # Every row's first choice is one held expert, its others absent.
        top_i = jnp.broadcast_to(jnp.arange(k), (M, k)).at[:, 0].set(
            first + 3)
    else:  # no pair landed: every choice is an absent expert
        top_i = jnp.broadcast_to(first + held + jnp.arange(k), (M, k))
    w_gate = jax.random.normal(ks[3], (held, H, W), dtype) * H ** -0.5
    w_up = jax.random.normal(ks[4], (held, H, W), dtype) * H ** -0.5
    w_down = jax.random.normal(ks[5], (held, W, H), dtype) * W ** -0.5
    return (n, top_p, top_i.astype(jnp.int32), w_gate if gated else None,
            w_up, w_down, first, E, transformer.ACTIVATIONS[act])


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize(
    "routing", ["some_empty", "one_has_every_row", "no_pair_landed"])
@pytest.mark.parametrize("kind", KINDS)
def test_the_chosen_form_is_the_batched_form_s_sum(
        kind, routing, dtype, chosen_here):
    case = routed(kind, routing, jnp.dtype(dtype))
    M, H, W, E, held, first, k, gated, _ = KINDS[kind]
    assert experts_sparse(M, k, E, H, W, case[0].dtype)
    want, want_sizes, _ = dropless_experts(*case)
    assert not chosen_here  # a caller that is no rollout's step
    got, sizes, gathered = dropless_experts(*case, rollout=True)
    assert chosen_here == [(M, H)]
    assert got.dtype == case[0].dtype and int(gathered) == M * k
    np.testing.assert_array_equal(sizes, want_sizes)
    with_a_row = int(jnp.sum(sizes > 0))
    if routing == "some_empty":
        assert 0 < with_a_row < held
    elif routing == "one_has_every_row":
        assert with_a_row == 1 and int(sizes[3]) == M
    else:
        assert with_a_row == 0
        assert not np.any(np.asarray(got, np.float32))
    # To the operands' rounding: the two forms round at the same places and
    # add a row's experts in float32 in another order.
    want, got = (np.asarray(a, np.float32) for a in (want, got))
    scale = np.max(np.abs(want)) + 1e-9
    limit = 2e-2 if dtype == "bfloat16" else 1e-5
    assert np.max(np.abs(got - want)) <= limit * scale


@pytest.mark.parametrize("M, H, W, dtype, whole", [
    (32, 2048, 512, "bfloat16", True),    # qwen3_next's step
    (32, 2304, 1024, "bfloat16", True),   # Kimi Linear's
    (16, 2560, 768, "bfloat16", True),    # SmallThinker's
    (128, 2048, 1536, "bfloat16", False),  # wider than any the kernel ran
    (32, 2048, 1792, "bfloat16", False),
    (8, 2048, 512, "bfloat16", False),    # rows under a tile
    (32, 2048, 448, "bfloat16", False),   # a width of no whole lane tiles
    (32, 2000, 512, "bfloat16", False),
    (32, 2048, 512, "float32", False),
])
def test_the_kernel_s_tiles_are_a_matter_of_the_static_shape(
        M, H, W, dtype, whole):
    """An expert's width is one block of the kernel: the three cells'
    widths, and none past the widest of them."""
    assert expert_step.whole_tiles(M, H, W, jnp.dtype(dtype)) == whole


def cell_files(cell):
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        workload = json.load(f)
    with open(os.path.join(BENCH, "configs",
                           workload["config"] + ".json")) as f:
        return json.load(f), workload


def cell_model(cell):
    config, workload = cell_files(cell)
    net = {k: v for k, v in config["network"].items() if k != "param_count"}
    model = catalog.get_model(
        None, net["vocab_size"] - bool(net.get("block_length")), {
            "custom_model": config["trainer_config"]["model"]["custom_model"],
            "custom_model_config": net})
    return model, workload["trainer_config"]


@pytest.mark.parametrize("cell", SPARSE + BATCHED)
def test_the_rule_takes_the_cells_whose_steps_leave_experts_empty(cell):
    """From the benchmark's own files, nothing but shapes built."""
    model, trainer = cell_model(cell)
    rows = trainer["num_envs_per_worker"]
    fragment, minibatch = (trainer["rollout_fragment_length"],
                           trainer["sgd_minibatch_size"])
    k, E, held = model.experts_per_token, model.num_experts, model.held
    step_rows = rows * (model.block_len or 1)
    taken = cell in SPARSE
    assert model.decode_sparse(step_rows) == taken
    assert ((1 - k / E) ** step_rows >= transformer.SPARSE_EMPTY_SHARE) \
        == taken
    counters = model.static_counters(rows, fragment, "tpu", minibatch)
    assert counters["decode_experts_sparse"] == float(taken)
    assert counters["decode_experts_batched"] == float(not taken)
    # Where the step reads every held expert the share is said here; where
    # it does not, the step counts it.
    assert counters.get("decode_experts_read_share") == (
        None if taken else 1.0)
    # No program for a CPU takes the kernel.
    assert model.static_counters(rows, fragment, "cpu", minibatch)[
        "decode_experts_sparse"] == 0.0
    # No learner's minibatch, of this cell or another's size.
    assert not any(model.decode_sparse(m)
                   for m in (minibatch, 3 * minibatch, 512))

    def pallas(**said):
        shapes = (
            jax.ShapeDtypeStruct((step_rows, model.hidden_size),
                                 jnp.bfloat16),
            jax.ShapeDtypeStruct((step_rows, k), jnp.float32),
            jax.ShapeDtypeStruct((step_rows, k), jnp.int32),
            *(jax.ShapeDtypeStruct((held, *io), jnp.bfloat16) for io in (
                (model.hidden_size, model.expert_width),) * 2 + (
                (model.expert_width, model.hidden_size),)))
        return "pallas_call" in str(jax.make_jaxpr(
            lambda *a: dropless_experts(
                *a, model.first_expert_held, E, **said))(*shapes))
    # The step's shape takes the kernel where the caller is the rollout's
    # step, and in no cell where it does not say so.
    assert pallas(rollout=True) == taken
    assert not pallas()
    # A bootstrap step's rows (a minibatch's sequences) never do.
    assert not model.decode_sparse(minibatch // fragment) or taken


@pytest.fixture(scope="module")
def sessions():
    """The three cells' trainers at their rehearsal sizes, opened once."""
    opened = {}

    def session(cell):
        if cell not in opened:
            config, workload = cell_files(cell)
            driver = importlib.import_module("drivers." + workload["driver"])
            opened[cell] = driver.open_session(config, workload, 7, 1, True)
        return opened[cell]
    yield session
    for s in opened.values():
        s.close()


def close(got, want, limit=1e-4):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = (np.asarray(a, np.float32) for a in (g, w))
        assert np.max(np.abs(g - w)) <= limit * (np.max(np.abs(w)) + 1e-8)


@pytest.mark.parametrize("cell", SPARSE)
def test_the_rollout_s_step_takes_the_form_and_counts_what_it_read(
        cell, sessions, chosen_here):
    """`step_state` of the cell's policy at its rehearsal size: every expert
    layer's products are the kernel's, the step's outputs the batched
    form's, and `decode_experts_read_share` the held experts with a row,
    counted by hand from the experts the step chose."""
    session = sessions(cell)
    policy, opt = session.policy, session.optimizer
    model = policy.model
    state, reset = opt._pstate
    obs = opt._obs
    rows = obs.shape[0]
    assert model.decode_sparse(rows)
    want = policy.apply(policy.params, obs[:, None], state, reset[:, None])
    assert not chosen_here  # `apply` is no rollout's step
    logits, value, after, counted = policy.step_state(
        policy.params, obs, state, reset)
    close((logits[:, None], value[:, None], after), want)
    # The hand count, from the experts the same step chose.
    _, kept = policy.apply(policy.params, obs[:, None], state,
                           reset[:, None], mutable=["routing"])
    experts = np.asarray(kept["routing"]["experts"][-1])  # [layers, rows, k]
    assert len(chosen_here) == len(experts) > 0  # every expert layer's
    first, held = model.first_expert_held, model.held
    shares = [len({int(e) for e in layer.reshape(-1)
                   if first <= e < first + held}) / held
              for layer in experts]
    assert 0 < np.mean(shares) < 1
    np.testing.assert_allclose(
        float(counted["decode_experts_read_share"]), np.mean(shares),
        rtol=1e-6)


@pytest.mark.parametrize("cell", SPARSE)
def test_off_a_tpu_the_step_says_it_read_every_held_expert(
        cell, sessions, monkeypatch):
    """Where the static rule holds and the program is lowered for a CPU, the
    products are the batched form's (`jax.lax.platform_dependent`'s
    default), and the step's counter says what that form read: all. (Both
    branches are traced; the kernel's, which a CPU cannot lower, is a stub
    whose sum would show.)"""
    def stub(n, *operands, **options):
        return jnp.full(n.shape, jnp.nan, jnp.float32)
    monkeypatch.setattr(expert_step, "chosen_kernel", stub)
    monkeypatch.setattr(expert_step, "whole_tiles", lambda *a: True)
    session = sessions(cell)
    policy, opt = session.policy, session.optimizer
    state, reset = opt._pstate
    obs = opt._obs
    assert policy.model.decode_sparse(obs.shape[0])
    want = policy.apply(policy.params, obs[:, None], state, reset[:, None])
    logits, value, after, counted = jax.jit(policy.step_state)(
        policy.params, obs, state, reset)
    close((logits[:, None], value[:, None], after), want)
    assert float(counted["decode_experts_read_share"]) == 1.0


@pytest.mark.parametrize("cell", SPARSE)
def test_the_bootstrap_step_keeps_the_batched_form(
        cell, sessions, monkeypatch):
    """One position through `apply` under `value_and_grad`, as the learner
    takes its bootstrap value: where a TPU's branch would be taken and the
    rule's shapes are a test's, the value and every parameter's gradient are
    what they are without the rule, and the kernel (which has no
    derivative) is never asked."""
    session = sessions(cell)
    policy, opt = session.policy, session.optimizer
    state, reset = opt._pstate
    obs = opt._obs

    def bootstrap():
        def value(params):
            _, values, _ = policy.apply(params, obs[:, None], state,
                                        reset[:, None])
            return jnp.sum(values)
        return jax.value_and_grad(value)(policy.params)
    want = bootstrap()

    def never(*operands, **options):
        raise AssertionError("the bootstrap step asked for the kernel")
    monkeypatch.setattr(expert_step, "chosen_kernel", never)
    monkeypatch.setattr(expert_step, "whole_tiles", lambda *a: True)
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    close(bootstrap(), want, limit=0.0)
    with pytest.raises(AssertionError, match="asked for the kernel"):
        policy.step_state(policy.params, obs, state, reset)
