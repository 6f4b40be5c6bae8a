"""How a token family is checked, written once.

Nine transformers stand behind `models/catalog.py` as token policies. What
their tests share is HOW a family is checked: how a tiny model of it is built,
how its outputs are held against its plain reference
(`benchmark/lib/reference_<family>.py`), how a batch is seeded, how one update
by the optimizer's own step is taken and judged. That stands here, as
functions of a `Family`; what differs by family is a row of one, which
stands at the head of the family's policy file beside the reasons for its
shapes.

A family's two files, `tests/test_<family>_policy.py` and
`tests/test_<family>_update.py`, name the row `FAMILY`, import the shared
checks they bind (pytest collects what a module names, under the name it
has there) and hold the tests of what is the family's alone, written with
the helpers below. `conftest.py` gives a shared check its family
(`family`), its family's cases (`pytest_generate_tests` reads the mark that
`cases` leaves) and the family's trainer on the fused path (`token_trainer`).

What is built and compiled is kept: a tiny model, its variables and tokens
by (family, dtype, description, seeding); its causal pass and decode step as
one program each; the reference's forward by what it is asked; the
optimizer's step and the reference's loss and gradient by family. A test
that alters a constant a TRACE reads (`kernel_here`, a block's length)
builds `fresh`, so that no program traced without it is served.

Not collected: the file's name has no `test_` prefix.
"""

import dataclasses
import functools
import json
import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import reference_glm4_moe_lite  # noqa: E402

from ray_tpu.models import catalog, transformer  # noqa: E402
from ray_tpu.rllib import sample_batch as sb  # noqa: E402
from ray_tpu.rllib.agents.impala.vtrace_policy import vtrace_loss  # noqa: E402


@dataclasses.dataclass(eq=False)
class Family:
    """What differs between two families' checks. `eq=False`: a row is
    itself, and keys what is kept for it."""
    name: str                 # `custom_model`
    net: dict                 # the tiny `custom_model_config`
    reference: object         # its module under benchmark/lib
    B: int                    # rows of a batch of fragments
    S: int                    # positions of a fragment
    # The kinds of state a decode carries beside "pos", and the shapes a
    # row's leaves of each kind have after a fragment of S positions.
    state_kinds: tuple = ("kv",)
    state_shapes: object = None          # S -> a list of shapes a kind
    state_layers: dict = None            # kind -> leaves a layer
    matrix_kind: str = None   # the kind that is summed into: float32 always
    collections: frozenset = frozenset({"params"})
    expert_layers: int = None            # layers that route, experts a token
    experts_per_token: int = None
    # Seeding beyond `model.init`: (path, leaf) -> leaf over the parameters.
    seeded: object = None
    # What `sharp` multiplies, so that a softmax has a few heavy terms.
    sharp_keys: tuple = ()
    # How `test_limits_refuse_wrong_mathematics` builds, and what it does to
    # the variables so that every named error shows in the outputs.
    limits_build: dict = dataclasses.field(default_factory=dict)
    shown: object = None
    mutations: tuple = None   # default: the reference's MUTATIONS
    # wrong -> verdicts -> bool: which limit refuses it, where that is known.
    refused_by: dict = dataclasses.field(default_factory=dict)
    # Resets inside a fragment [B, S] (the limits' fragment holds them, so
    # that a state that reaches across one shows), and its episodes.
    reset: object = None
    episodes: tuple = ()
    # None: bfloat16 blocks are held to the limits written beside the
    # reference. (times, ceiling, flips): the limits at the published widths
    # are no measure at these; the system is held to the reference with its
    # blocks rounded to bfloat16, no further from the float32 reference
    # than `times` it, which itself stays under `ceiling`, and its routing
    # within `flips`.
    bfloat16: tuple = None
    # The other lengths of fragment the causal pass is checked at.
    other_lengths: tuple = ()
    length_key: str = "max_position_embeddings"
    # The counters a decode's last step states.
    decode_counters: dict = None
    # How far a decode's state may stand from the one a causal pass hands
    # over (None: rings and caches, compared by what is decoded from them).
    handed_atol: float = None
    prefixes: tuple = ()      # where a decode takes over from a causal pass
    # A matrix state rounded to bfloat16 after every step: variables ->
    # variables whose decays are slow enough that a state holds hundreds
    # of positions (None: as drawn), and (the rounded state's comparison,
    # the float32 state's) -> whether the carried error shows as it must.
    long_lived: object = None
    carried_error: object = None
    # What the model keeps beside "routing" and "counters", and the names
    # (the system's in "routing", the reference's) of a module's loss by
    # position (glm4_moe_lite's next-next-token module).
    kept: tuple = ()
    module_loss: tuple = None
    # The reference's forward and loss where they predate the convention
    # (variables, inputs, net, experts=, starts=, round_to=, mutate=).
    forward: object = None
    loss: object = None
    inputs: object = None     # built -> what the reference reads (tokens)
    outputs: object = None    # net -> the policy's number of outputs
    envs: int = 4             # the trainer's environments a worker
    wrong_updates: dict = dataclasses.field(default_factory=dict)
    # (description's change, [outputs,] what the refusal says).
    refused: tuple = ()
    # The tuned example, the cell and the configuration it is the example
    # of, and what the cell's program is from its static shapes.
    example: str = None
    cell: str = None
    config: str = None
    program: dict = None

    def __post_init__(self):
        reference = self.reference
        if self.forward is None:
            self.forward = lambda variables, inputs, net, starts=None, **how: \
                reference.forward(variables, inputs, net, **how, **(
                    {} if starts is None else {"starts": starts}))
        if self.loss is None:
            self.loss = reference.vtrace_loss
        if self.mutations is None:
            self.mutations = reference.MUTATIONS
        if self.inputs is None:
            self.inputs = lambda built: built.tokens
        if self.outputs is None:
            self.outputs = lambda net: net["vocab_size"]

    def of_length(self, tokens):
        return self.net if tokens in (None, self.S) else dict(
            self.net, **{self.length_key: tokens})


def cases(names, of):
    """A shared check's cases are its family's: `of(family)` under `names`
    (`conftest.pytest_generate_tests` reads the mark)."""
    def mark(check):
        check.family_cases = getattr(check, "family_cases", ()) + (
            (names, of),)
        return check
    return mark


def noise(path, a, seed=2):
    """Seeded unit normals of a leaf's shape, by the leaf's path."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), zlib.crc32(
        jax.tree_util.keystr(path).encode()) % 2 ** 31)
    return jax.random.normal(key, a.shape)


def seeded_norms(*also):
    """The norms' weights (one at initialisation; a norm with unit weights
    commutes with RoPE, and a per-head norm's place would not show), and
    the leaves named `also`, seeded about what they were."""
    def seeded(path, a):
        if not path[-1].key.endswith(("norm",) + also):
            return a
        return a * (1.0 + 0.5 * noise(path, a))
    return seeded


# -- a tiny model, built once ------------------------------------------------
class Built:
    """(model, variables, tokens) of a family at a tiny size, with the two
    programs the checks apply: the causal pass and a decode step, each
    compiled once for this model and taking the variables as an argument,
    so that a test that alters them compiles nothing."""

    def __init__(self, family, dtype, net, tokens, sharp, bias_scale):
        self.family, self.net = family, net
        self.model = model = catalog.get_model(None, family.outputs(net), {
            "custom_model": family.name, "custom_model_config": net,
            "compute_dtype": dtype})
        B = family.B
        self.tokens = jax.random.randint(
            jax.random.PRNGKey(1), (B, tokens or family.S), 0,
            net["vocab_size"])
        variables = model.init(jax.random.PRNGKey(0), self.tokens[:, :1],
                               model.initial_state(B), jnp.zeros((B, 1)))
        if family.seeded is not None:
            variables = dict(
                variables, params=jax.tree_util.tree_map_with_path(
                    family.seeded, variables["params"]))
        if sharp != 1.0:
            # Queries and keys large enough that a softmax has a few heavy
            # terms, so that one position more or less in it, or another
            # rotation, shows.
            variables = jax.tree_util.tree_map_with_path(
                lambda path, a: a * sharp
                if path[-1].key in family.sharp_keys else a, variables)
        if bias_scale is not None:
            # A selection bias as large as the scores' own spread, so that
            # choosing by score + bias and weighing by score differ.
            variables = dict(variables, constants=jax.tree.map(
                lambda b: b * (bias_scale / transformer.ROUTER_BIAS_SCALE),
                variables["constants"]))
        self.variables = variables
        kept = ["routing", "counters", *family.kept]
        self.causal = jax.jit(lambda v, t, r: model.apply(
            v, t, None, r, mutable=kept))
        self.step = jax.jit(lambda v, token, state, reset: model.apply(
            v, token, state, reset, method="decode",
            mutable=["routing", "counters"]))
        # A step of one position, [B, 1], as the learner's bootstrap takes.
        self.decode = jax.jit(lambda v, token, state, reset: model.apply(
            v, token, state, reset))

    def __iter__(self):
        return iter((self.model, self.variables, self.tokens))


_BUILT = {}


def build(family, dtype="f32", net=None, tokens=None, sharp=1.0,
          bias_scale=None, fresh=False):
    """The family's tiny model: `(model, variables, tokens) = build(..)`,
    and the `Built` itself for `causal_routed` and `decode_routed`. Kept by
    what it was asked; `fresh` builds anew and keeps nothing (for a test
    under a patch that a trace reads)."""
    net = family.net if net is None else net
    if fresh:
        return Built(family, dtype, net, tokens, sharp, bias_scale)
    key = (family, dtype, json.dumps(net, sort_keys=True), tokens, sharp,
           bias_scale)
    if key not in _BUILT:
        _BUILT[key] = Built(family, dtype, net, tokens, sharp, bias_scale)
    return _BUILT[key]


@functools.lru_cache(maxsize=None)
def _forward(family, net, how):
    net, how = json.loads(net), dict(how)
    return jax.jit(lambda v, inputs, experts, starts: family.forward(
        v, inputs, net, experts=experts, starts=starts, **how))


def plain(family, variables, inputs, net=None, experts=None, starts=None,
          **how):
    """The reference's forward, compiled (its scans, and a layer's eager
    ops, run one by one otherwise), once for what it is asked."""
    net = family.net if net is None else net
    return _forward(family, json.dumps(net, sort_keys=True),
                    tuple(sorted(how.items(), key=str)))(
                        variables, inputs, experts, starts)


def judged(family, system, variables, inputs, net=None, starts=None):
    """The system's (logits, values, experts[, the module's loss by
    position]) against the reference held to those experts: the verdicts
    {"outputs", "routing"[, "loss"]}, and what the reference gave."""
    reference = family.reference
    held = plain(family, variables, inputs, net, experts=system[2],
                 starts=starts)
    verdicts = {
        "outputs": reference.compare(
            system[:2], (held["logits"], held["values"])),
        "routing": reference.routing_verdict(
            system[2], held["experts"], held["select"])}
    if len(system) > 3:
        verdicts["loss"] = reference.compare_loss(
            system[3], held[family.module_loss[1]])
    return verdicts, held


def system_of(family, out):
    """A forward of the reference in the system's place."""
    return (out["logits"], out["values"], out["experts"]) + (
        (out[family.module_loss[1]],) if family.module_loss else ())


def held_to_reference(family, dtype, system, variables, inputs, net=None,
                      starts=None, near_ties=False):
    """float32 blocks: to float32 accuracy, the same experts in every
    layer. bfloat16 blocks: the limits written beside the reference
    (`near_ties`: a flip is a near-tie of the reference's), or, where the
    family says so, the reference rounded where the blocks round. Returns
    what the reference gave."""
    verdicts, held = judged(family, system, variables, inputs, net, starts)
    outputs, routing = verdicts["outputs"], verdicts["routing"]
    if dtype == "f32":
        assert routing["router_flips"] == 0.0, routing
        assert max(outputs["errors"].values()) < 1e-5, outputs
    elif family.bfloat16 is None:
        assert routing["router_flips"] <= 0.1, routing
        if near_ties:
            assert routing["max_flip_gap"] <= family.reference.MAX_FLIP_GAP
        assert outputs["ok"], outputs
    else:
        times, ceiling, flips = family.bfloat16
        low = plain(family, variables, inputs, net, starts=starts,
                    round_to=jnp.bfloat16)
        rounded, _ = judged(family, system_of(family, low), variables,
                              inputs, net, starts)
        assert routing["router_flips"] <= flips, routing
        for name, error in outputs["errors"].items():
            assert error <= times * rounded["outputs"]["errors"][name] \
                < ceiling, (outputs, rounded)
    return held


def causal_routed(built, variables, tokens, reset=None, jit=True):
    """The causal pass: ((logits, values, experts[, the module's loss by
    position]), the state it hands over, what it kept)."""
    reset = jnp.zeros(tokens.shape) if reset is None else reset
    if jit:
        (logits, values, state), kept = built.causal(
            variables, tokens, reset)
    else:
        (logits, values, state), kept = built.model.apply(
            variables, tokens, None, reset,
            mutable=["routing", "counters", *built.family.kept])
    system = (logits, values, kept["routing"]["experts"][-1])
    if built.family.module_loss:
        system += (kept["routing"][built.family.module_loss[0]][-1],)
    return system, state, kept


def decode_routed(built, variables, tokens, reset=None, jit=True,
                  between=None):
    """Every position one token at a time from empty state: ((logits,
    values, experts), the last state, the counters a step). `between`
    alters the state after every step."""
    if jit:
        step = functools.partial(built.step, variables)
    else:
        def step(token, state, reset):
            return built.model.apply(
                variables, token, state, reset, method="decode",
                mutable=["routing", "counters"])
    if reset is None:
        reset = jnp.zeros(tokens.shape)
    state = built.model.initial_state(tokens.shape[0])
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


def state_shapes(family, state):
    """A row's leaves by kind, in `family.state_kinds`' order."""
    return tuple([c.shape[1:] for c in jax.tree.leaves(state[kind])]
                 for kind in family.state_kinds)


def state_is_the_family_s(family, state, dtype, positions):
    """A state's kinds, which layers keep each, the shapes a row has of
    each after `positions`, and what each is stored in: the kind that is
    summed into float32 whatever the blocks compute in, the rest the
    blocks'."""
    assert set(state) == {*family.state_kinds, "pos"}
    assert state_shapes(family, state) == tuple(
        family.state_shapes(positions))
    assert {kind: [len(jax.tree.leaves(layer)) for layer in state[kind]]
            for kind in family.state_kinds} == family.state_layers
    blocks = jnp.float32 if dtype == "f32" else jnp.bfloat16
    for kind in family.state_kinds:
        want = jnp.float32 if kind == family.matrix_kind else blocks
        assert all(a.dtype == want for a in jax.tree.leaves(state[kind]))


def scalar_of(logits, values):
    weight = jax.random.normal(jax.random.PRNGKey(7), logits.shape)
    return jnp.sum(logits * weight) + jnp.sum(jnp.sin(values))


_GRADIENTS = {}


def model_gradients(family, variables, tokens, reset=None):
    """The gradient of one scalar of the outputs with respect to every
    parameter, through the system's causal pass and through the
    reference's forward (a recurrence, where the system scans chunks)."""
    model = build(family, "f32").model
    if family not in _GRADIENTS:
        def system(params, variables, tokens, reset):
            logits, values, _ = model.apply(
                dict(variables, params=params), tokens, None, reset)
            return scalar_of(logits, values)

        def recurrence(params, variables, tokens, reset):
            out = family.forward(dict(variables, params=params), tokens,
                                 family.net, starts=reset)
            return scalar_of(out["logits"], out["values"])
        _GRADIENTS[family] = tuple(
            jax.jit(jax.grad(f)) for f in (system, recurrence))
    # No reset as a fragment of zeros: one program for both.
    reset = jnp.zeros(tokens.shape) if reset is None else reset
    return tuple(f(variables["params"], variables, tokens, reset)
                 for f in _GRADIENTS[family])


def read_by(run, operands):
    """(outputs, final state, the gradients by every operand) of a scalar
    that reads every output of `run(*operands)` and every entry of the
    final state it returns."""
    def scalar(*operands):
        o, S = run(*operands)
        return (jnp.sum(jnp.sin(o) * jnp.arange(1, o.shape[1] + 1)[
            None, :, None, None]) + jnp.sum(jnp.cos(S))), (o, S)
    grads, (o, S) = jax.jit(jax.grad(
        scalar, argnums=tuple(range(len(operands))), has_aux=True))(
            *operands)
    return (o, S) + grads


def share_of(lp, first, size, names=("w_gate", "w_up", "w_down")):
    """A layer's parameters with the matrices of `size` of its experts,
    from `first` on: what a chip that holds that share has. `first` may be
    traced, so that the shares of one size are one program."""
    return dict(lp, **{w: jax.lax.dynamic_slice_in_dim(lp[w], first, size)
                       for w in names})


def shapes_of(model):
    """The variables' shapes: nothing is built."""
    return jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
        jax.eval_shape(lambda: model.initial_state(1)),
        jax.ShapeDtypeStruct((1, 1), jnp.float32))


def count(tree):
    return sum(int(np.prod(v.shape)) for v in jax.tree.leaves(tree))


def configuration(family):
    """The family's tuned example, its cell and its configuration's file
    as they stand in the tree, and the network the cell builds."""
    import yaml
    with open(os.path.join(ROOT, "ray_tpu", "rllib", "tuned_examples",
                           family.example)) as f:
        (example,) = yaml.safe_load(f).values()
    with open(os.path.join(BENCH, "workloads", family.cell + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(BENCH, "configs", family.config + ".json")) as f:
        config = json.load(f)
    network = {k: v for k, v in config["network"].items()
               if k != "param_count"}
    return example, cell, config, network


# -- the shared checks: the model against its reference ----------------------
def _causal_cases(family):
    return [pytest.param(dtype, tokens, id=dtype if not family.other_lengths
                         else f"{dtype}-{tokens}")
            for tokens in (family.S, *family.other_lengths)
            for dtype in ("f32", "bf16")]


@cases("dtype,tokens", _causal_cases)
def test_causal_pass_matches_reference(family, dtype, tokens):
    """A fragment of the family's length (and, where its causal form works
    in chunks, one that ends inside a chunk). float32 blocks: to float32
    accuracy, the same experts in every layer. bfloat16 blocks: the limits
    written beside the reference, or as near as the reference rounded
    where they round. What the pass hands a decode is the family's state:
    its kinds, a key a kind, which layers keep each, and their shapes."""
    net = family.of_length(tokens)
    built = build(family, dtype, net, tokens=tokens)
    _, variables, tokens = built
    system, state, _ = causal_routed(built, variables, tokens)
    length = tokens.shape[1]
    if family.expert_layers is not None:
        assert system[2].shape == (family.expert_layers, family.B, length,
                                   family.experts_per_token)
    held = held_to_reference(family, dtype, system, variables, tokens, net,
                             near_ties=True)
    if family.matrix_kind and dtype == "f32":
        # The matrix states the scan hands over are the recurrence's.
        for got, want in zip(jax.tree.leaves(state[family.matrix_kind]),
                             held[family.matrix_kind + "_states"]):
            assert family.reference.relative_error(got, want) < 1e-5
    if family.state_shapes is not None:
        state_is_the_family_s(family, state, dtype, length)
    assert np.all(np.asarray(state["pos"]) == length)


@cases("dtype", lambda family: ["f32", "bf16"])
def test_decode_through_every_kind_of_state_matches_reference(family, dtype):
    """Every position decoded one token at a time from empty state,
    against the reference, which has neither cache nor state; and,
    float32, against the causal pass, which keeps every position and masks
    a window, and (where a state is carried, not a ring) against the state
    it hands over."""
    relative_error = family.reference.relative_error
    built = build(family, dtype)
    _, variables, tokens = built
    system, state, counted = decode_routed(built, variables, tokens)
    held_to_reference(family, dtype, system, variables, tokens)
    if dtype == "f32":
        causal, handed, _ = causal_routed(built, variables, tokens)
        assert relative_error(system[0], causal[0]) < 1e-5
        assert relative_error(system[1], causal[1]) < 1e-5
        assert np.array_equal(system[2], causal[2])
        if family.handed_atol:
            for got, want in zip(jax.tree.leaves(state),
                                 jax.tree.leaves(handed)):
                np.testing.assert_allclose(got, want,
                                           atol=family.handed_atol)
    state_is_the_family_s(family, state, dtype, family.S)
    assert counted[-1] == family.decode_counters


def test_a_decode_continues_a_causal_pass_from_the_state_it_hands_over(
        family):
    """Prefixes shorter than a convolution's taps, as long, at a chunk's
    edge and inside a chunk: the pass's state is what a decode would have
    carried there (zeros where the episode is shorter than the taps), and
    the decode goes on from it."""
    built = build(family, "f32")
    _, variables, tokens = built
    (full, _, _), _, _ = causal_routed(built, variables, tokens)
    for prefix in family.prefixes:
        _, state, _ = causal_routed(built, variables, tokens[:, :prefix])
        for t in range(prefix, family.S):
            step, _, state = built.decode(
                variables, tokens[:, t:t + 1], state,
                jnp.zeros((family.B, 1)))
            assert family.reference.relative_error(
                step[:, 0], full[:, t]) < 1e-5, (prefix, t)


def test_resets_inside_a_chunk_at_its_edge_and_an_episode_one_token_long(
        family):
    """Four episodes in a fragment, the second one token long, the last
    beginning with a chunk: what separate passes give, in both forms and
    in the reference; the state handed over is the last episode's alone."""
    relative_error = family.reference.relative_error
    built = build(family, "f32")
    model, variables, tokens = built
    B, S, matrix = family.B, family.S, family.matrix_kind
    both, state, _ = causal_routed(built, variables, tokens, family.reset)
    parts = []
    for a, b in family.episodes:
        if b - a > 1:
            parts.append(causal_routed(built, variables, tokens[:, a:b]))
        else:
            # A causal pass takes two tokens or more: the lone token as a
            # decode step from empty state.
            lone, value, _ = built.decode(
                variables, tokens[:, a:b], model.initial_state(B),
                jnp.ones((B, 1)))
            parts.append(((lone, value), None, None))
    for got, alone in zip(both[:2], zip(*(p[0][:2] for p in parts))):
        assert relative_error(got, jnp.concatenate(alone, axis=1)) < 1e-5
    last = parts[-1][1]
    for kind in ("conv", matrix):
        for got, want in zip(jax.tree.leaves(state[kind]),
                             jax.tree.leaves(last[kind])):
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(state["pos"]) == S - family.episodes[-1][0])
    held_to_reference(family, "f32", both, variables, tokens,
                      starts=family.reset)
    stepped, stepped_state, _ = decode_routed(
        built, variables, tokens, family.reset)
    assert relative_error(stepped[0], both[0]) < 1e-5
    assert relative_error(stepped[1], both[1]) < 1e-5
    for got, want in zip(jax.tree.leaves(stepped_state[matrix]),
                         jax.tree.leaves(state[matrix])):
        np.testing.assert_allclose(got, want, atol=2e-5)
    # A fragment that ends one token into an episode hands over one input
    # of a convolution, zero rows before it, and a matrix of rank one a
    # head.
    first = family.episodes[2][0]
    _, short, _ = causal_routed(built, variables, tokens[:, :first + 1],
                                family.reset[:, :first + 1])
    for held in jax.tree.leaves(short["conv"]):
        assert not np.any(np.asarray(held[:, :-1]))
        assert np.any(np.asarray(held[:, -1]))
    for held in jax.tree.leaves(short[matrix]):
        assert np.all(np.linalg.matrix_rank(np.asarray(held)) == 1)


def test_a_bfloat16_matrix_state_is_refused_by_the_decode_s_limit(family):
    """The state is summed into at every step, so keeping it in bfloat16
    (rounded after every step; everything else float32) is no rounding of
    a block's output: its error is carried on and added to. Over a few
    hundred steps the logits leave the reference by more than the cell's
    limit, where the float32 state's stay at 1e-5."""
    steps, kind = 384, family.matrix_kind
    net = family.of_length(steps)
    built = build(family, "f32", net, tokens=steps)
    _, variables, tokens = built
    if family.long_lived is not None:
        variables = family.long_lived(variables)

    def rounded(state):
        return dict(state, **{kind: jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
            state[kind])})
    kept, _, _ = decode_routed(built, variables, tokens)
    lost, _, _ = decode_routed(built, variables, tokens, between=rounded)
    verdicts, held = judged(family, kept, variables, tokens, net)
    outputs = verdicts["outputs"]
    assert max(outputs["errors"].values()) < 1e-5, outputs
    wrong = family.reference.compare(
        lost[:2], (held["logits"], held["values"]))
    assert family.carried_error(wrong, outputs), wrong


@cases("reset", lambda family: [pytest.param(None, id="whole"),
                                pytest.param(family.reset, id="resets")])
def test_the_model_s_gradient_is_the_reference_s(family, reset):
    """Every parameter of every block, through the scans over chunks and
    the attention layer, the fragment whole and cut by resets: what
    float32 leaves after the blocks' worth of sums in two orders (a
    chunked operator alone agrees with its recurrence to 1e-5: the
    family's own test)."""
    _, variables, tokens = build(family, "f32")
    got, want = model_gradients(family, variables, tokens, reset)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        assert np.isfinite(a).all(), path
        assert family.reference.relative_error(a, b) < 5e-5, path


@cases("wrong", lambda family: family.mutations + ("float8_e4m3",))
def test_limits_refuse_wrong_mathematics(family, wrong):
    """The comparison fails each named error and blocks computed a
    precision lower: the reference, so altered, in the system's place
    against itself held to the experts it chose, by its outputs, by its
    routing or (where the model has a term of its own) by that loss. Where
    the family's fragments hold resets this one does, so that a state that
    reaches across one shows."""
    built = build(family, "f32", **family.limits_build)
    variables, inputs = built.variables, family.inputs(built)
    if family.shown is not None:
        variables = family.shown(variables)
    how = {"round_to": wrong} if wrong == "float8_e4m3" else {
        "mutate": wrong}
    got = plain(family, variables, inputs, starts=family.reset, **how)
    verdicts, _ = judged(family, system_of(family, got), variables, inputs,
                         starts=family.reset)
    assert not all(v["ok"] for v in verdicts.values()), (wrong, verdicts)
    if wrong in family.refused_by:
        assert family.refused_by[wrong](verdicts), (wrong, verdicts)


def test_a_causal_pass_over_the_landed_rows_is_the_batched_pass(
        family, grouped_pass_is_the_batched_pass):
    """The grouped form of the expert product (where the experts have no
    gate matrix too: its `switch` over the row counts and their
    pullbacks)."""
    grouped_pass_is_the_batched_pass(*build(family, "f32"))


def test_the_cell_s_program_is_known_from_its_static_shapes(family):
    """The benchmark's cell from shapes alone, at the configuration's
    network (the published widths): every counter a TPU's program states
    (which kernels it takes, what its caches and states hold a token and a
    row), what changes off a TPU, the state a rollout carries, and the
    parameters counted; nothing but shapes is built."""
    program = family.program
    _, _, config, network = configuration(family)
    model = catalog.get_model(None, family.outputs(network), {
        "custom_model": family.name, "custom_model_config": network})
    shape = (program["rows"], program["fragment"])
    minibatch = program.get("minibatch", ())
    assert model.static_counters(*shape, "tpu", *minibatch) \
        == program["on_tpu"]
    off = model.static_counters(*shape, "cpu", *minibatch)
    assert {k: off[k] for k in program["off_tpu"]} == program["off_tpu"]
    state = jax.eval_shape(lambda: model.initial_state(program["rows"]))
    assert {kind: [(c.shape, c.dtype.name) for c in jax.tree.leaves(held)]
            for kind, held in state.items() if kind != "pos"} \
        == program["state"]
    variables = shapes_of(model)
    assert set(variables) == family.collections
    assert count(variables) == config["network"]["param_count"] \
        == program["parameters"]


def _refusals(family):
    """(cfg, outputs or None, match), under the ids pytest gives the
    family's own tuples."""
    return [pytest.param(cfg, outputs[0] if outputs else None, match,
                         id="-".join([f"cfg{i}", *map(str, outputs), match]))
            for i, (cfg, *outputs, match) in enumerate(family.refused)]


@cases("cfg,outputs,match", _refusals)
def test_custom_model_config_without_a_part_is_refused(
        family, cfg, outputs, match):
    """A description with a key that is not the family's, or a value of a
    part the model does not have, is refused by name when the model is
    traced; nothing is built."""
    net = dict(family.net, **cfg)
    with pytest.raises(ValueError, match=match):
        model = catalog.get_model(
            None, outputs or family.outputs(family.net), {
                "custom_model": family.name, "custom_model_config": net})
        shapes_of(model)


def test_the_tuned_example_is_the_benchmark_s_cell(family):
    """`rllib train -f <the family's yaml>` and the family's cell are one
    trainer config: the cell's traffic under the configuration's trainer,
    its network the model's description, its chips the learner's."""
    example, cell, config, network = configuration(family)
    want = dict(cell["trainer_config"], **config["trainer_config"])
    want["model"] = dict(want["model"], custom_model_config=network)
    want["num_tpus_for_learner"] = cell["chips"]
    assert example["run"] == config["trainer"]
    assert example["env"] == want.pop("env")
    assert example["config"] == want
    assert set(config["reduced"]) == set(config["reduced_why"])


# -- the shared checks: the loss and the loop --------------------------------
def token_trainer_config(family, **over):
    net, S = family.net, family.S
    cfg = dict(
        env="TokenBigram-v0",
        env_config={"vocab_size": net["vocab_size"], "episode_len": S},
        anakin=True, num_workers=0, num_envs_per_worker=family.envs,
        rollout_fragment_length=S, train_batch_size=family.envs * S,
        sgd_minibatch_size=2 * S, num_sgd_iter=1,
        anakin_updates_per_call=1, min_iter_time_s=0, lr=6e-4, seed=3,
        model={"custom_model": family.name, "custom_model_config": net,
               "compute_dtype": "f32"})
    cfg.update(over)
    return cfg


def seeded_batch(family, frags, seed):
    """`frags` whole episodes of a walk (`TokenBigram-v0`: the action
    taken is the next observation), as the learner's packed batch and as
    the reference's."""
    S = family.S
    rng = np.random.default_rng(seed)
    walk = rng.integers(0, family.net["vocab_size"], size=(frags, S + 1))
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


# Compiled once a family: the optimizer's step, and the reference's loss
# and gradient by what its loss depends on (the planted error, the loss's
# coefficients, a constant of the reference's that a test has patched; a
# clip or a learning rate changes Adam's side alone).
_COMPILED = {}


def reference_loss_and_gradient(family, cfg, mutate=None, patch=None):
    key = (family, mutate, patch, tuple(sorted(
        (k, v) for k, v in cfg.items()
        if k in ("vf_loss_coeff", "entropy_coeff"))))
    if key not in _COMPILED:
        how = {} if mutate is None else {"mutate": mutate}
        _COMPILED[key] = jax.jit(jax.value_and_grad(
            lambda p, rest, ref_batch: family.loss(
                dict(rest, params=p), ref_batch, family.net, cfg, **how),
            has_aux=True))
    return _COMPILED[key]


def test_vtrace_minibatch_loss_and_gradients_match_reference(
        family, token_trainer):
    """One minibatch of whole episodes through the system's loss (packed
    rows, ACTION_LOGP, the bootstrap step differentiated through every
    kind of state the family carries) and through `jax.grad` of the plain
    reference: every parameter's gradient to 2e-3 of its largest entry
    (float32: two orders of the same sums; a bfloat16 block anywhere reads
    1e-1). A router's selection bias has no gradient and no optimizer
    state: Adam's moments exist for the parameters alone."""
    policy = token_trainer.get_policy()
    B, S = family.B, family.S
    batch, ref_batch = seeded_batch(family, B, 5)
    variables = jax.tree.map(jnp.asarray, policy.get_weights())
    assert set(variables) == family.collections
    (total, stats), grads = jax.jit(jax.value_and_grad(
        lambda v: vtrace_loss(policy, v, batch, None, {}),
        has_aux=True))(variables)
    rest = {k: v for k, v in variables.items() if k != "params"}
    (want_total, parts), want_grads = reference_loss_and_gradient(
        family, policy.config)(variables["params"], rest, ref_batch)
    np.testing.assert_allclose(total, want_total, rtol=1e-4)
    np.testing.assert_allclose(
        stats["entropy"] * B * S, parts["entropy"], rtol=1e-4)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads["params"])
    want_flat = jax.tree.leaves(want_grads)
    assert len(flat) == len(want_flat)
    for (path, got), want in zip(flat, want_flat):
        scale = float(jnp.max(jnp.abs(want))) + 1e-8
        assert float(jnp.max(jnp.abs(got - want))) <= 2e-3 * scale, path
    assert not any(bool(jnp.any(g != 0)) for g in jax.tree.leaves(
        {k: grads[k] for k in rest}))
    moments = [leaf for leaf in jax.tree.leaves(policy.opt_state)
               if leaf.dtype == jnp.float32]
    assert len(moments) == 2 * len(jax.tree.leaves(variables["params"]))
    assert stats["expert_load_mean"] > 0
    if "experts_held" in family.net:
        assert 0.0 < stats["experts_held_row_share"] < 1.0
    else:
        # Every expert is here: each of a token's k lands.
        assert stats["expert_load_mean"] == B * S * family.net[
            "num_experts_per_tok"] / family.net["num_experts"]


def one_update(family, trainer, seed=7, **wrong):
    """One update of seeded whole episodes by the optimizer's own step
    (`AnakinOptimizer.learn`, the body of the fused program's learner)
    from the trainer's parameters and optimizer state, against the
    reference's loss, gradients and Adam: what the benchmark's driver does
    at the cell's minibatch. `wrong` plants a fault in the reference's
    side: its `mutate`, a `cfg` of its own, a constant of its module's
    that the caller has `patch`ed (name, value), a loss summed over
    `part_of_the_batch`, Adam from `fresh_moments`."""
    reference = family.reference
    policy, opt = trainer.get_policy(), trainer.optimizer
    cfg = dict(policy.config, **wrong.get("cfg", {}))
    batch, ref_batch = seeded_batch(family, opt.minibatch // opt.T, seed)

    def flat(tree):
        return {jax.tree_util.keystr(path): np.asarray(leaf)
                for path, leaf in
                jax.tree_util.tree_flatten_with_path(tree)[0]}
    before = policy.params
    (adam,) = [s for s in jax.tree.leaves(
        policy.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
        if hasattr(s, "mu")]
    if (family, "learn") not in _COMPILED:
        _COMPILED[family, "learn"] = jax.jit(opt.learn)
    after, _, stats = _COMPILED[family, "learn"](
        before, policy.opt_state, batch, jax.random.PRNGKey(0))
    rest = {k: v for k, v in before.items() if k != "params"}
    assert jax.tree.all(jax.tree.map(
        jnp.array_equal, {k: after[k] for k in rest}, rest))
    if wrong.get("part_of_the_batch"):
        ref_batch = {k: v[:-1] for k, v in ref_batch.items()}
    (want_loss, _), grads = reference_loss_and_gradient(
        family, cfg, wrong.get("mutate"), wrong.get("patch"))(
            before["params"], rest, ref_batch)
    count, mu, nu = int(adam.count), flat(adam.mu["params"]), \
        flat(adam.nu["params"])
    assert count > 0
    if wrong.get("fresh_moments"):
        count, mu, nu = 0, *(
            {k: np.zeros_like(v) for k, v in m.items()} for m in (mu, nu))
    want_change, norm = reference_glm4_moe_lite.adam_update(
        flat(grads), mu, nu, count, cfg)
    assert norm > 0
    old, new = flat(before["params"]), flat(after["params"])
    return reference.compare_update(stats["total_loss"], want_loss, {
        name: float(reference_glm4_moe_lite.change_error(
            old[name], new[name], want))
        for name, want in want_change.items()})


def test_one_update_by_the_optimizer_s_own_step_matches_reference(
        family, token_trainer):
    found = one_update(family, token_trainer)
    assert found["ok"], found
    assert found["loss_error"] < 1e-5 and found["update_error"] < 1e-2, found


@cases("wrong", lambda family: list(family.wrong_updates))
def test_update_limits_refuse_a_wrong_update(
        family, wrong, token_trainer, monkeypatch):
    """The comparison of one update fails each named error, planted in
    the reference's side: by the limits the fault names (the loss's, the
    worst parameter's change's, or both), or by either."""
    fault = dict(family.wrong_updates[wrong])
    by = fault.pop("by", ())
    if "patch" in fault:
        monkeypatch.setattr(family.reference, *fault["patch"])
    found = one_update(family, token_trainer, **fault)
    assert not found["ok"], found
    limits = {"loss_error": family.reference.UPDATE_LOSS_TOLERANCE,
              "update_error": family.reference.UPDATE_TOLERANCE}
    for name in (by,) if isinstance(by, str) else by:
        assert found[name] > limits[name], found


def two_iterations(family, trainer):
    """`IMPALATrainer(anakin, TokenBigram-v0, <family>)` by config alone:
    two iterations, a finite loss, a count that rises by a rollout's steps;
    (the last stats, the counters the optimizer kept)."""
    counts = []
    for _ in range(2):
        result = trainer.train()
        stats = result["info"]["learner"]
        assert np.isfinite(stats["total_loss"])
        counts.append(result["timesteps_total"])
    assert counts[1] - counts[0] == family.envs * family.S and counts[0] > 0
    kept = trainer.optimizer.learner_stats
    assert kept["expert_load_max"] >= kept["expert_load_mean"] > 0
    assert kept["causal_attention_fused"] == 0.0  # this is no TPU
    return stats, kept
