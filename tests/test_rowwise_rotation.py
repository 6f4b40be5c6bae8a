"""A learner's row-wise passes over a head's rows (`models/rowwise.py`: the
rotation with the softmax's scale folded in, and the attention's gate), run
by the Pallas interpreter on the CPU, against the forms they replace in a
program lowered for a TPU, as those forms stand: `transformer.rope`,
`TokenDecoder._rotate` and `TokenDecoder._gated`. Values AND gradients (of a
seeded scalar), over the shapes the nine token configurations bring: the
whole head rotated, half of it (Laguna's full layers, under YaRN) and a
quarter (Qwen3-Next); the softmax's scale folded in and not; 48 / 8, 64 / 8,
32 / 4, 28 / 4 and 16 / 2 heads; heads of 128 and 256 (and of 64, which the
rule refuses); positions that restart inside a fragment; a gate a head and a
gate a value. The rule of the static shape, the host value that says how
many layers take the pass, and two whole models' causal passes (Laguna's
five layers, Qwen3-Next's four) with the passes engaged against themselves
without. The kernels' compiles for a described v5e are in
`tests/test_decode_attention.py`, the one file that loads the chip's
compiler.
"""

import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from ray_tpu.models import catalog, rowwise, transformer  # noqa: E402
from ray_tpu.models.transformer import Rotation, TokenDecoder  # noqa: E402

# Positions a grid step takes under `rows_here`, and in a test's fragment.
ROWS, T = 16, 48
YARN = (8.0, 16, 1.0, 0.05, 1.2079441541679836)
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}
# What two orders of the same float32 arithmetic may differ by: a rounding
# of the result's dtype.
CLOSE = {"f32": 2e-6, "bf16": 2.0 ** -7}


def engage(patch, rows=ROWS):
    """Under `patch` (a MonkeyPatch) a program lowered for this CPU takes
    the branch a TPU's would, the row-wise kernels run by the Pallas
    interpreter over tiles of `rows` positions; the kernels' calls,
    counted."""
    calls = {"rotate": 0, "gate": 0}

    def counted(name, kernel):
        def run(*args, **kwargs):
            calls[name] += 1
            return kernel(*args, **kwargs, interpret=True)
        return run
    patch.setattr(rowwise, "ROWS", rows)
    patch.setattr(rowwise, "rotate_kernel",
                  counted("rotate", rowwise.rotate_kernel))
    patch.setattr(rowwise, "gate_kernel",
                  counted("gate", rowwise.gate_kernel))
    patch.setattr(jax.lax, "platform_dependent",
                  lambda *args, tpu, default: tpu(*args))
    return calls


def both(monkeypatch, fn, *args, rows=ROWS, compiled=False):
    """(value, aux, gradients in every argument) of `fn` as the program
    lowers off a TPU, the same through the kernels, and the kernels' calls
    the second took. `compiled`: each as one program (a whole model's ops
    one by one take minutes), traced anew under what `engage` patches."""
    def run():
        grad = jax.value_and_grad(
            fn, argnums=tuple(range(len(args))), has_aux=True)
        (value, aux), grads = (jax.jit(grad) if compiled else grad)(*args)
        return value, aux, grads
    plain = run()
    with monkeypatch.context() as patch:
        calls = engage(patch, rows)
        return plain, run(), calls


def positions(B, restart):
    """[B, T]: a row's positions count from 0; with `restart`, an episode
    ends inside every row but the first and the count starts again."""
    rows = [jnp.arange(T)] + [
        jnp.concatenate([jnp.arange(cut), jnp.arange(T - cut)])
        if restart else jnp.arange(T) for cut in (11, 29, 40)[:B - 1]]
    return jnp.stack(rows)


def seeded(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


def close(got, want, dtype):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= CLOSE[dtype] * max(
        np.max(np.abs(want)), 1.0)


ROTATIONS = [
    # heads, d, share, scaling, folded: the cells' layers.
    pytest.param(48, 128, 0.5, YARN, True, id="laguna_full_queries"),
    pytest.param(64, 128, 1.0, (), True, id="laguna_window_queries"),
    pytest.param(8, 128, 0.5, YARN, False, id="laguna_full_keys"),
    pytest.param(8, 128, 1.0, (), False, id="laguna_window_keys"),
    pytest.param(32, 128, 1.0, (), True, id="sdar_queries"),
    pytest.param(4, 128, 1.0, (), False, id="sdar_keys"),
    pytest.param(28, 128, 1.0, (), True, id="smallthinker_queries"),
    pytest.param(16, 256, 0.25, (), True, id="qwen3_next_queries"),
    pytest.param(2, 256, 0.25, (), False, id="qwen3_next_keys"),
    pytest.param(16, 128, 1.0, YARN, True, id="whole_head_under_yarn"),
]


def rotated(x, where, rotation, scale, w):
    out = TokenDecoder._rotate(None, x, where, rotation, scale,
                               head_major=True)
    return jnp.sum(out.astype(jnp.float32) * w), out


@pytest.mark.parametrize("restart", [False, True], ids=["whole", "restart"])
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("heads, d, share, scaling, folded", ROTATIONS)
def test_the_rotation_is_rope_s_values_and_gradients(
        heads, d, share, scaling, folded, dtype, restart, monkeypatch):
    """One pass over [B, heads, T, d] gives `_rotate`'s values, and its
    pullback (the same pass, the sine negated) autodiff's gradient of
    `rope`'s chain, to a rounding of the operand's dtype."""
    B = 2
    rotation = Rotation(10000.0, share, scaling)
    scale = d ** -0.5 if folded else 1.0
    x, w = seeded(0, (B, heads, T, d), DTYPES[dtype]), seeded(
        1, (B, heads, T, d))
    where = positions(B, restart)
    (_, want, (d_want,)), (_, got, (d_got,)), calls = both(
        monkeypatch, lambda x: rotated(x, where, rotation, scale, w), x)
    # Forward and pullback: the one kernel twice.
    assert calls == {"rotate": 2, "gate": 0}
    assert got.dtype == want.dtype == d_got.dtype == DTYPES[dtype]
    close(got, want, dtype)
    close(d_got, d_want, dtype)
    # The values past the rotated share are the operand's, scaled.
    kept = int(d * share)
    if kept < d:
        close(got[..., kept:], (x[..., kept:].astype(jnp.float32) * scale
                                ).astype(x.dtype), dtype)


def test_the_plain_form_is_rope_as_it_stands():
    """Off a TPU, and for every shape the rule refuses, `_rotate` is
    `transformer.rope` itself (of the rotated share, the rest beside it)."""
    x, where = seeded(0, (2, 4, T, 128), jnp.bfloat16), positions(2, True)
    assert jnp.array_equal(
        TokenDecoder._rotate(None, x, where, Rotation(100.0, 1.0, YARN),
                             0.25, head_major=True),
        transformer.rope(x, where, 100.0, 0.25, True, YARN))
    half = TokenDecoder._rotate(None, x, where, Rotation(100.0, 0.5, ()),
                                head_major=True)
    assert jnp.array_equal(half[..., :64], transformer.rope(
        x[..., :64], where, 100.0, head_major=True))
    assert jnp.array_equal(half[..., 64:], x[..., 64:])


@pytest.mark.parametrize("form, shape, where", [
    ("a rollout's step: rows by head", (2, 4, 128), (2,)),
    ("a block step: positions by head", (2, ROWS, 4, 128), (2, ROWS)),
    ("heads of 64: half a lane tile", (2, 4, T, 64), (2, T)),
    ("a fragment of no whole tiles", (2, 4, ROWS + 8, 128), (2, ROWS + 8)),
])
def test_the_other_forms_keep_rope(form, shape, where, monkeypatch):
    """The choice is by the static form: rows that are not head-major, a
    head that is no whole lane tiles and a fragment that is no whole tiles
    of positions take no kernel in a program for a TPU either."""
    calls = engage(monkeypatch)
    head_major = len(shape) == 4 and shape[2] != 4
    x = seeded(0, shape, jnp.bfloat16)
    at = jnp.broadcast_to(jnp.arange(where[-1]), where)
    got = TokenDecoder._rotate(None, x, at, Rotation(10000.0, 1.0, ()),
                               0.5, head_major=head_major)
    assert calls == {"rotate": 0, "gate": 0}
    assert jnp.array_equal(got, transformer.rope(
        x, at, 10000.0, 0.5, head_major))


@pytest.mark.parametrize("T, d, rotated, takes", [
    (8192, 128, 128, True),     # Laguna's window layers, SmallThinker, OLMoE
    (8192, 128, 64, True),      # Laguna's full layers: half a head
    (6144, 128, 128, True),     # SDAR: three streams of 2,048
    (4096, 128, 128, True),     # SDAR's last layer: the noisy streams ask
    (4096, 256, 64, True),      # Qwen3-Next: a quarter of 256
    (4096, 64, 64, False),      # LFM2: a head is half a lane tile
    (4096, 192, 64, False),     # a latent layout's width
    (1000, 128, 128, False),    # no whole tiles of positions
    (32, 128, 128, False),      # every rehearsal's fragment
    (8192, 128, 0, False),      # nothing to rotate
    (8192, 128, 6, True),       # any even share
    (8192, 128, 7, False),      # halves that are no whole values
])
def test_whole_tiles_is_a_rule_of_the_static_shape(T, d, rotated, takes):
    assert rowwise.whole_tiles(T, d, rotated) == takes
    # The gate's rule is the rotation's without a share.
    assert rowwise.whole_tiles(T, d) == (T % 512 == 0 and d % 128 == 0)


@pytest.mark.parametrize("heads, d, a_step", [
    (64, 128, 8), (48, 128, 8), (8, 128, 8), (32, 128, 8), (4, 128, 4),
    (28, 128, 7), (16, 256, 4), (2, 256, 2), (16, 128, 8), (5, 2048, 1)])
def test_a_grid_step_takes_heads_that_divide_the_layer_s(heads, d, a_step):
    """The most heads a step that divide the layer's and keep a block at
    1,024 values a position (1 MB of bfloat16 at 512 positions)."""
    assert rowwise.heads_a_step(heads, d) == a_step


GATES = [
    # heads, d, a gate a head or a value
    pytest.param(64, 128, True, id="laguna_window"),
    pytest.param(48, 128, True, id="laguna_full"),
    pytest.param(28, 128, True, id="a_head_of_seven_a_step"),
    pytest.param(16, 256, False, id="qwen3_next"),
    pytest.param(16, 128, False, id="a_value_of_128"),
]


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("heads, d, a_head", GATES)
def test_the_gate_is_gated_s_values_and_gradients(heads, d, a_head, dtype,
                                                  monkeypatch):
    """One pass over o gives o * sigmoid(gate) as `_gated` computes it, a
    gate a head lying [B, T, heads]; its pullback both gradients, the
    gate's a sum over a row's lanes where a head has one gate."""
    B = 2
    me = types.SimpleNamespace(compute_dtype=DTYPES[dtype])
    o, w = seeded(0, (B, heads, T, d), DTYPES[dtype]), seeded(
        1, (B, heads, T, d))
    gate = seeded(2, (B, T, heads) if a_head else o.shape, DTYPES[dtype])

    def gated(o, gate):
        out = TokenDecoder._gated(me, o, gate, True)
        return jnp.sum(out.astype(jnp.float32) * w), out
    (_, want, d_want), (_, got, d_got), calls = both(
        monkeypatch, gated, o, gate)
    assert calls == {"rotate": 0, "gate": 2}
    assert got.dtype == DTYPES[dtype]
    close(got, want, dtype)
    for a, b in zip(d_got, d_want):
        assert a.dtype == b.dtype == DTYPES[dtype]
        close(a, b, dtype)
    # As the parent's form has it: the gate by head, a trailing 1.
    if a_head:
        close(want, TokenDecoder._gated(
            me, o, jnp.swapaxes(gate, 1, 2)[..., None]), dtype)


# -- whole models -------------------------------------------------------------
def laguna():
    from test_laguna import FAMILY
    from token_families import build
    return build(FAMILY, "f32")


def qwen3_next():
    from test_qwen3_next_policy import FAMILY
    from token_families import build
    return build(FAMILY, "f32")


@pytest.mark.parametrize("build, rotations, gates", [
    # Laguna: five layers rotate (q and k), each gated a head.
    (laguna, 10, 5),
    # Qwen3-Next: its one softmax layer rotates a quarter, gated a value.
    (qwen3_next, 2, 1),
])
def test_a_causal_pass_through_the_kernels_is_the_causal_pass(
        build, rotations, gates, monkeypatch):
    """A whole model's causal pass and its parameters' gradients with the
    row-wise passes engaged (a head of 16 a lane tile here, 8 positions a
    tile: fragments of 32 and 24) against the same pass without."""
    model, variables, tokens = build()
    monkeypatch.setattr(rowwise, "LANES", 16)

    def loss(params):
        logits, values, _ = model.apply(
            dict(variables, params=params), tokens, None,
            jnp.zeros(tokens.shape))
        return jnp.sum(jnp.sin(logits)) + jnp.sum(values), (logits, values)
    (_, want, (d_want,)), (_, got, (d_got,)), calls = both(
        monkeypatch, loss, variables["params"], rows=8, compiled=True)
    # Traced for every layer's forward pass at the least (a pullback is
    # traced once a shape).
    assert calls["rotate"] > rotations and calls["gate"] > gates
    for a, b in zip(jax.tree.leaves((got, d_got)),
                    jax.tree.leaves((want, d_want))):
        scale = float(jnp.max(jnp.abs(b))) + 1e-8
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * scale


@pytest.mark.parametrize("cell, layers", [
    ("olmoe_token_anakin", None), ("glm47_flash_token_anakin", 0),
    ("smallthinker_token_anakin_8k", 3), ("lfm2_token_anakin_4k", 0),
    ("kimi_linear_token_anakin_4k", 0), ("nemotron_h_token_anakin_2k", 0),
    ("sdar_block_token_anakin_2k", 5), ("qwen3_next_token_anakin_4k", 1),
    ("laguna_token_anakin_8k", 5),
])
def test_the_host_value_counts_the_layers_that_take_the_pass(cell, layers):
    """`rotation_fused_layers` of each token cell at its own shapes, from
    the cell's files: the layers that rotate, where a head is whole lane
    tiles and the learner's fragment whole tiles of positions, in a
    program for a TPU; none in a program for anything else."""
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        workload = json.load(f)
    with open(os.path.join(BENCH, "configs",
                           workload["config"] + ".json")) as f:
        config = json.load(f)
    trainer = workload["trainer_config"]
    network = {k: v for k, v in config["network"].items()
               if k != "param_count"}
    kind = config["trainer_config"]["model"]["custom_model"]
    # A block model's last id is the MASK's, which the env never draws.
    model = catalog.get_model(
        None, network["vocab_size"] - (kind == "sdar_moe"),
        {"custom_model": kind, "custom_model_config": network,
         "compute_dtype": "bf16"})
    shape = (trainer["num_envs_per_worker"],
             trainer["rollout_fragment_length"])
    on_tpu = model.static_counters(*shape, "tpu")
    if layers is None:
        layers = network["num_hidden_layers"]
    assert on_tpu["rotation_fused_layers"] == layers
    assert (on_tpu["rotation_fused_layers"] > 0) <= bool(
        on_tpu["causal_attention_fused"])
    assert model.static_counters(*shape, "cpu")[
        "rotation_fused_layers"] == 0
