"""Driver for cells whose RLlib trainer trains a token policy (observations
and actions are token ids; the model is a transformer with a key/value
cache). Everything but the comparison with the reference is
`rllib_trainer.Session`'s.

The configuration's `network` block (the published `config.json` keys) is
what the policy is built from: it goes into the trainer's
`model.custom_model_config` unless the cell's rehearsal gives a tiny one.

The weights are the configuration's, the traffic is the run's. The run's
`--seed` reaches the trainer as its `seed`, which draws the policy's
parameters as well as the env's state and the rollout's sampling keys. A
configuration that names `weights_seed` has its parameters drawn again from
that seed (`TokenSession._draw_weights`: the policy's own initialiser and
the key the trainer would have used under `seed: weights_seed`, so the
parameters are those of a run at `--seed <weights_seed>` bit for bit) before
the first call of the trainer's program; everything else stays the run's. A
cell whose rate follows the routers' draw then reads the same rate at every
seed. A configuration that names none runs as it always has.

`check_outputs`, on the stopped trainer, at the widths the trainer ran,
outside the window: `check.sequences` seeded sequences of one episode's
length, and

  (a) the system's causal pass (the learner's form), logits and values at
      every position, and
  (b) the system's decode through its cache for the last
      `check.decode_positions` positions after a prefill of the rest (the
      rollout's form),

both against `lib/reference_olmoe.py`'s full forward held to the experts the
system's pass chose, and the system's choice of experts against the
reference's own (`router_flips`, `max_flip_gap`). Logits are compared, never
sampled tokens. It also runs the reference with its block rounded to
float8_e4m3 (the nearest precision below the stated bfloat16) through the same
two checks and prints the result beside the system's: it has to be refused.
"""

from __future__ import annotations

import time

import numpy as np

from drivers import rllib_trainer  # `benchmark/` is on the path (run.py)
from lib import reference_olmoe as reference


class TokenSession(rllib_trainer.Session):
    def __init__(self, config: dict, workload: dict, seed: int,
                 chips: int, rehearse: bool):
        published = {k: v for k, v in config["network"].items()
                     if k != "param_count"}
        config = rllib_trainer.merge(config, {"trainer_config": {
            "model": {"custom_model_config": published}}})
        super().__init__(config, workload, seed, chips, rehearse)
        self.rehearse = rehearse
        # The shapes the trainer really ran, for the readers and the check.
        model = self.trainer.config["model"]["custom_model_config"]
        self.network = dict(
            model, sequence_length=self.trainer.config[
                "rollout_fragment_length"])
        if config.get("weights_seed") is not None:
            self._draw_weights(int(config["weights_seed"]))

    def _draw_weights(self, weights_seed: int) -> None:
        """The policy's parameters drawn anew from `weights_seed`, as
        `JaxPolicy` draws them from the trainer's seed: `model.init` under
        `jax.jit`, the first key of `PRNGKey(seed)`'s counter, the same
        dummies. The first draw is freed before the second is made (Adam's
        zero moments stand beside them), so the chip never holds both."""
        import jax

        policy = self.policy
        key = jax.random.fold_in(
            jax.random.PRNGKey(weights_seed % rllib_trainer.SEED_SPACE),
            np.uint32(1))
        observation = np.zeros((1, 1) + tuple(policy.preprocessor.shape),
                               policy.preprocessor.dtype)
        for leaf in jax.tree.leaves(policy.params):
            leaf.delete()
        policy.set_weights(jax.jit(policy.model.init)(
            key, observation, policy.model.initial_state(1),
            np.zeros((1, 1), np.float32)))

    def check_outputs(self, seed: int) -> dict:
        import jax
        import jax.numpy as jnp

        self._stop_trainer()
        t0 = time.perf_counter()
        policy, net = self.policy, self.network
        # The optimizer's state and the rollout's cache are not needed any
        # more; the reference needs the room.
        for leaf in jax.tree.leaves((policy.opt_state,
                                     self.optimizer._pstate)):
            leaf.delete()
        check = self.workload["check"]
        S = net["sequence_length"]
        n_decode = min(check["decode_positions"], S // 2)
        rng = np.random.default_rng(seed)
        tokens = jnp.asarray(rng.integers(
            0, net["vocab_size"], size=(check["sequences"], S)), jnp.int32)
        zeros = jnp.zeros(tokens.shape, jnp.float32)
        params = policy.params

        def routed(out):
            """(logits, values, experts [layers, B, T, k]) of an apply
            that kept the "routing" collection."""
            (logits, values, state), kept = out
            return logits, values, kept["routing"]["experts"][-1], state

        @jax.jit
        def causal(params, tokens):
            return routed(policy.apply(
                params, tokens, None, zeros, mutable=["routing"]))[:3]

        @jax.jit
        def prefill_decode(params, tokens):
            head, tail = tokens[:, :S - n_decode], tokens[:, S - n_decode:]
            _, _, head_experts, state = routed(policy.apply(
                params, head, None, zeros[:, :S - n_decode],
                mutable=["routing"]))

            def step(state, token):
                logits, value, experts, state = routed(policy.apply(
                    params, token[:, None], state, zeros[:, :1],
                    mutable=["routing"]))
                return state, (logits[:, 0], value[:, 0], experts)
            _, (logits, values, experts) = jax.lax.scan(step, state, tail.T)
            experts = jnp.concatenate(
                [head_experts, jnp.moveaxis(experts, 0, 2)], axis=2)
            return jnp.swapaxes(logits, 0, 1), values.T, experts

        programs = {}

        def reference_of(experts=None, round_to=None):
            """The reference's forward, its router free or held to
            `experts`."""
            key = (experts is None, round_to)
            if key not in programs:
                programs[key] = jax.jit(lambda p, t, e: reference.forward(
                    p, t, net, round_to=round_to, experts=e))
            return programs[key](params["params"], tokens, experts)

        # (0) the reference on its own: the outputs' scales, its routing.
        want_logits, want_values, want_experts, want_probs = reference_of()
        scales = reference.output_scales((want_logits, want_values))
        t_ref = time.perf_counter()
        verdict = {"tolerance": reference.TOLERANCE, "output_scales": scales}
        ok = True
        # (a) the causal pass, (b) prefill + cached decode: each against
        # the reference held to the experts that pass chose, and its
        # choice against the reference's own.
        for name, run, tail in (("causal", causal, S),
                                ("decode", prefill_decode, n_decode)):
            logits, values, experts = run(params, tokens)
            routing = reference.routing_verdict(
                experts, want_experts, want_probs)
            held = reference_of(experts)
            outputs = reference.compare(
                (logits, values),
                (held[0][:, S - tail:], held[1][:, S - tail:]), scales)
            verdict[name] = {"errors": outputs["errors"], **routing}
            ok = ok and outputs["ok"] and routing["ok"]
            del logits, values, held
        t_sys = time.perf_counter()

        # What the limits have to refuse: the same forward a precision
        # lower (float8_e4m3 block activations), against itself in float32.
        low = reference_of(round_to="float8_e4m3")
        low_routing = reference.routing_verdict(
            low[2], want_experts, want_probs)
        low_held = reference_of(want_experts, round_to="float8_e4m3")
        low_outputs = reference.compare(
            low_held[:2], (want_logits, want_values), scales)
        verdict["fp8_reference"] = {
            "errors": low_outputs["errors"], **low_routing,
            "refused": not (low_outputs["ok"] and low_routing["ok"])}
        del low, low_held

        verdict["param_count"] = policy.num_params()
        verdict["seconds"] = {
            "reference": t_ref - t0, "system": t_sys - t_ref,
            "fp8_reference": time.perf_counter() - t_sys}
        verdict["ok"] = bool(
            ok and (self.rehearse or verdict["param_count"]
                    == self.config["network"]["param_count"]))
        return verdict


def open_session(config: dict, workload: dict, seed: int, chips: int,
                 rehearse: bool) -> TokenSession:
    return TokenSession(config, workload, seed, chips, rehearse)
