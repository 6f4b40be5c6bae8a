"""Driver for the cells whose token policy is `laguna` (attention whose
geometry is a layer kind's: full layers of 48 query heads under YaRN over
half a head beside window layers of 64 under the default rotation, all over
8 cached heads of 128, a sigmoid gate a head; a leading dense layer, then a
share of sigmoid-routed experts beside a shared one; an untied head; no
constants). Everything but the comparison with the reference is
`rllib_token_trainer.TokenSession`'s, and so `rllib_trainer.Session`'s.

`check_outputs`, on the stopped trainer, at the widths and in the state the
trainer ran to, outside the window: `check.sequences` seeded sequences of
one episode's length (8,192 positions in the cell: sixteen windows, twice
YaRN's original positions), and

  (a) the system's causal pass (the learner's form and the learner's
      shape, one sequence a pass: window layers masked, tiles outside the
      window skipped, both kinds' heads through the fused form), logits and
      values at every position,
  (b) the system's decode of the same sequences from an empty state, every
      position one token at a time through the two full caches and the
      three rings (the rollout's form), as rows of a batch as wide as the
      rollout's (`num_envs_per_worker`; the other rows decode seeded
      sequences of their own), so that every position beyond the window
      comes through rings that have turned,
  (c) the system's choice of experts against the reference's own, a layer
      at a time: the reference is held to the system's choices, so the
      layers before a layer are the system's on both sides
      (`router_flips`, `max_flip_gap`, `flips_by_layer`),
  (d) the parameter count,
  (e) one update by the optimizer's own step (`AnakinOptimizer.learn`, the
      body of the fused program's learner: V-trace, its gradient through
      the recomputed blocks, the fused attention's backward kernels at both
      head counts, the held experts' dispatch, the bootstrap step through
      the caches and the rings, the clip, Adam) on one seeded minibatch of
      the cell's size, from the parameters and the optimizer state the
      window left: the loss it reports and the change of every parameter,
      against `jax.grad` of the reference's `vtrace_loss` put through the
      reference's `adam_change`, the reference held to the experts the
      system's causal pass chooses for the minibatch, as in (a): the step
      is `rllib_qwen3_next_token_trainer`'s own, read with this file's
      reference.

(a) and (b) against `lib/reference_laguna.py`'s full forward (float32, no
cache, the window a mask on the whole score matrix, YaRN by its formulas,
the same share of the experts and the vocabulary) held to the experts the
system's pass chose, a sequence at a time. Logits are compared, never
sampled tokens; an output's scale is the largest value of the reference
over the sequences of a pass. The reference with its blocks rounded to
float8_e4m3 (the nearest precision below the stated bfloat16) goes through
(a) and (c) in the system's place and is printed beside it: it has to be
refused.

Written for its seconds as its siblings are
(`rllib_kimi_linear_token_trainer`, whose `Seconds` and
`REFERENCE_OPTIONS` it takes): every program takes what a seed changes as
an argument, the reference's own programs are compiled at XLA's least
effort, and the check holds no more of the device than the window did.
"""

from __future__ import annotations

import types

import numpy as np

from drivers import rllib_token_trainer  # `benchmark/` is on the path
from drivers.rllib_kimi_linear_token_trainer import REFERENCE_OPTIONS, Seconds
from drivers.rllib_qwen3_next_token_trainer import Qwen3NextTokenSession
from lib import reference_laguna as reference


def _with_this_reference(method):
    """A sibling driver's method whose module names its reference
    `reference`, as every driver's does, reading this file's instead: the
    sibling's code, not a copy of it."""
    return types.FunctionType(
        method.__code__, dict(method.__globals__, reference=reference),
        method.__name__, method.__defaults__, method.__closure__)


def _merged(found: list, scales: tuple) -> dict:
    """The verdicts of a pass's sequences as one: each output's largest
    difference over its scale, the largest gap, the mean share of flips."""
    errors = {name: max(f["errors"][name] for f in found) / scale
              for name, scale in zip(("logits", "value"), scales)}
    flips = float(np.mean([f["router_flips"] for f in found]))
    gap = max(f["max_flip_gap"] for f in found)
    return {
        "errors": errors, "router_flips": flips, "max_flip_gap": gap,
        "flips_by_layer": [float(x) for x in np.mean(
            [f["flips_by_layer"] for f in found], axis=0)],
        "ok": bool(max(errors.values()) <= reference.TOLERANCE
                   and flips <= reference.MAX_ROUTER_FLIPS
                   and gap <= reference.MAX_FLIP_GAP)}


class LagunaTokenSession(rllib_token_trainer.TokenSession):
    _check_update = _with_this_reference(Qwen3NextTokenSession._check_update)

    def check_outputs(self, seed: int) -> dict:
        import jax
        import jax.numpy as jnp

        self._stop_trainer()
        seconds = Seconds()
        policy, net, opt = self.policy, self.network, self.optimizer
        # The rollout's caches are not needed any more, and the optimizer's
        # state, which (e) begins from, waits on the host meanwhile: the
        # check holds no more of the device than the window did.
        opt_state = jax.device_get(policy.opt_state)
        for leaf in jax.tree.leaves((opt._pstate, policy.opt_state)):
            leaf.delete()
        seconds.lap("to_host")
        S, rows = net["sequence_length"], opt.num_envs
        n = self.workload["check"]["sequences"]
        rng = np.random.default_rng(seed)
        tokens = jnp.asarray(rng.integers(
            0, net["vocab_size"], size=(n, S)), jnp.int32)
        others = jnp.asarray(rng.integers(
            0, net["vocab_size"], size=(rows - n, S)), jnp.int32)
        params = policy.params

        def causal(params, tokens):
            """One sequence [1, S], the learner's minibatch in the cell:
            (logits, values, experts [L, 1, S, k])."""
            (logits, values, _), kept = policy.apply(
                params, tokens, None, jnp.zeros(tokens.shape),
                mutable=["routing", "counters"])
            return logits, values, kept["routing"]["experts"][-1]

        def decode(params, tokens, others):
            """Every position of `tokens`, the first `n` rows of a batch
            of `rows`, from an empty state as the rollout begins:
            (logits [n, S, V], values [n, S], experts [L, n, S, k])."""
            def step(carry, token):
                state, reset = carry
                (logits, value, state), kept = policy.apply(
                    params, token[:, None], state, reset[:, None],
                    mutable=["routing"])
                return (state, jnp.zeros_like(reset)), (
                    logits[:n, 0], value[:n, 0],
                    kept["routing"]["experts"][-1][:, :n])
            _, (logits, values, experts) = jax.lax.scan(
                step, (policy.initial_state(rows),
                       jnp.ones(rows, jnp.float32)),
                jnp.concatenate([tokens, others]).T)
            return (jnp.swapaxes(logits, 0, 1), values.T,
                    jnp.moveaxis(experts, 0, 2))

        def held_reference(params, tokens, experts):
            """The float32 reference of one sequence [1, S] held to
            `experts` [L, 1, S, k]."""
            return reference.forward(params, tokens, net, experts=experts)

        def low_reference(params, tokens):
            return reference.forward(params, tokens, net,
                                     round_to="float8_e4m3")

        one = tokens[:1]
        causal = seconds.compiled("causal", causal, params, one)
        chosen = seconds.ran("causal", causal(params, one))[2]
        held_reference = seconds.compiled(
            "reference", held_reference, params, one, chosen,
            options=REFERENCE_OPTIONS)

        def judged(passes):
            """A pass's sequences, each (logits, values, experts) of one
            [1, S], in the system's place against the float32 reference
            held to the experts that pass chose: the merged verdict, and the
            outputs' scales."""
            found, scales = [], (0.0, 0.0)
            for i, (logits, values, experts) in enumerate(passes):
                held = seconds.ran("reference", held_reference(
                    params, tokens[i:i + 1], experts))
                wanted = (held["logits"], held["values"])
                # Differences as they are: the scale is the pass's.
                out = reference.compare((logits, values), wanted, (1.0, 1.0))
                found.append({"errors": out["errors"],
                              **reference.routing_verdict(
                                  experts, held["experts"],
                                  held["select"])})
                scales = tuple(max(a, b) for a, b in zip(
                    scales, reference.output_scales(wanted)))
            seconds.lap("judge")
            return _merged(found, scales), scales

        verdict = {
            "tolerance": reference.TOLERANCE,
            "max_router_flips": reference.MAX_ROUTER_FLIPS,
            "max_flip_gap": reference.MAX_FLIP_GAP,
            "update_loss_tolerance": reference.UPDATE_LOSS_TOLERANCE,
            "update_tolerance": reference.UPDATE_TOLERANCE,
            "decode_rows": rows, "positions": S}
        verdict["causal"], verdict["output_scales"] = judged(
            seconds.ran("causal", causal(params, tokens[i:i + 1]))
            for i in range(n))

        decode = seconds.compiled("decode", decode, params, tokens, others)
        logits, values, experts = seconds.ran(
            "decode", decode(params, tokens, others))
        verdict["decode"], _ = judged(
            (logits[i:i + 1], values[i:i + 1], experts[:, i:i + 1])
            for i in range(n))
        del logits, values, experts, decode

        # What the limits have to refuse: the same forward a precision
        # lower (float8_e4m3 block activations), in the system's place.
        low_reference = seconds.compiled(
            "fp8_reference", low_reference, params, one,
            options=REFERENCE_OPTIONS)

        def low(i):
            out = seconds.ran("fp8_reference", low_reference(
                params, tokens[i:i + 1]))
            return out["logits"], out["values"], out["experts"]
        verdict["fp8_reference"], _ = judged(low(i) for i in range(n))
        verdict["fp8_reference"]["refused"] = \
            not verdict["fp8_reference"].pop("ok")
        del low_reference, held_reference

        verdict["param_count"] = policy.num_params()
        # The minibatch is one sequence: the causal pass's program serves.
        verdict["update"] = self._check_update(
            seed, opt_state, seconds, lambda tokens: causal(
                params, jnp.asarray(tokens, jnp.int32))[2])
        verdict["seconds"] = dict(seconds, total=sum(seconds.values()))
        verdict["ok"] = bool(
            verdict["causal"]["ok"] and verdict["decode"]["ok"]
            and verdict["update"]["ok"]
            and (self.rehearse or verdict["param_count"]
                 == self.config["network"]["param_count"]))
        return verdict


def open_session(config: dict, workload: dict, seed: int, chips: int,
                 rehearse: bool) -> LagunaTokenSession:
    return LagunaTokenSession(config, workload, seed, chips, rehearse)
