"""Driver for the cells whose token policy is `qwen3_next` (Gated DeltaNet
layers whose state is a float32 matrix a value head under ONE decay a head
and the last three inputs of one short convolution, beside one gated
grouped-head attention layer with a partial rotation and a cache; every
layer a share of softmax-routed experts beside a gated shared one; an untied
head; no constants). Everything but the comparison with the reference is
`rllib_token_trainer.TokenSession`'s, and so `rllib_trainer.Session`'s.

`check_outputs`, on the stopped trainer, at the widths and in the state the
trainer ran to, outside the window: `check.sequences` seeded sequences of
one episode's length (4,096 positions in the cell), and

  (a) the system's causal pass (the learner's form and, with the cell's
      two sequences as one pass, the learner's shape: Gated DeltaNet by the
      chunked scan, the attention fused), logits and values at every
      position, and the matrix states it hands over after the last position
      against the reference's recurrence there (`gdn_state_drift`, a
      layer's largest difference over its largest value; printed, not
      judged),
  (b) the system's decode of the same sequences from empty state, every
      position one token at a time through the matrix states, the
      convolution's inputs and the key/value cache (the rollout's form), as
      rows of a batch as wide as the rollout's (`num_envs_per_worker`; the
      other rows decode seeded sequences of their own),
  (c) the system's choice of experts against the reference's own, a layer
      at a time: the reference is held to the system's choices, so the
      layers before a layer are the system's on both sides
      (`router_flips`, `max_flip_gap`, `flips_by_layer`),
  (d) the parameter count,
  (e) one update by the optimizer's own step (`AnakinOptimizer.learn`, the
      body of the fused program's learner: V-trace, its gradient through
      the recomputed blocks, the chunked scan's backward pass, the fused
      attention's backward kernels, the held experts' dispatch, the
      bootstrap step through every kind of state, the clip, Adam) on one
      seeded minibatch of the cell's size, from the parameters and the
      optimizer state the window left: the loss it reports and the change
      of every parameter, against `jax.grad` of the reference's
      `vtrace_loss` (through its one-position recurrence) put through the
      reference's `adam_change`, the reference held to the experts the
      system's causal pass chooses for the minibatch, as in (a) (why:
      `rllib_kimi_linear_token_trainer`'s docstring), and ties are (c)'s to
      judge.

(a) and (b) against `lib/reference_qwen3_next.py`'s full forward (float32,
no cache, no chunk, Gated DeltaNet as the recurrence one position at a
time, the same share of the experts and the vocabulary) held to the experts
the system's pass chose, the sequences as one batch. Logits are compared,
never sampled tokens; an output's scale is the largest value of the
reference held to the causal pass's experts. The reference with its blocks
rounded to float8_e4m3 (the nearest precision below the stated bfloat16)
goes through (a) and (c) in the system's place and is printed beside it: it
has to be refused.

A run of the cell has a time limit that set-up, window and this check
share, so the check is written for its seconds as its sibling's is
(`rllib_kimi_linear_token_trainer`, whose `Seconds`, `REFERENCE_OPTIONS`
and `_flat` it takes): every program takes what a seed changes as an
argument, the reference's own programs are compiled at XLA's least effort,
and the check holds no more of the device than the window did.
"""

from __future__ import annotations

import numpy as np

from drivers import rllib_token_trainer  # `benchmark/` is on the path
from drivers.rllib_kimi_linear_token_trainer import (
    REFERENCE_OPTIONS, Seconds, _flat)
from lib import reference_qwen3_next as reference


class Qwen3NextTokenSession(rllib_token_trainer.TokenSession):
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
            """The sequences [n, S] as one pass (the learner's minibatch
            in the cell): (logits, values, experts [L, n, S, k], the Gated
            DeltaNet layers' matrix states after the last position [layers,
            n, ..])."""
            (logits, values, state), kept = policy.apply(
                params, tokens, None, jnp.zeros(tokens.shape),
                mutable=["routing", "counters"])
            return (logits, values, kept["routing"]["experts"][-1],
                    jnp.stack(jax.tree.leaves(state["gdn"])))

        def decode(params, tokens, others):
            """Every position of `tokens`, the first `n` rows of a batch
            of `rows`, from an empty window as the rollout begins:
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
            """The float32 reference held to `experts` [L, n, S, k]."""
            return reference.forward(params, tokens, net, experts=experts)

        def low_reference(params, tokens):
            return reference.forward(params, tokens, net,
                                     round_to="float8_e4m3")

        causal = seconds.compiled("causal", causal, params, tokens)
        logits, values, experts, states = seconds.ran(
            "causal", causal(params, tokens))
        held_reference = seconds.compiled(
            "reference", held_reference, params, tokens, experts,
            options=REFERENCE_OPTIONS)
        held = seconds.ran("reference",
                           held_reference(params, tokens, experts))
        scales = reference.output_scales((held["logits"], held["values"]))
        verdict = {
            "tolerance": reference.TOLERANCE,
            "max_router_flips": reference.MAX_ROUTER_FLIPS,
            "max_flip_gap": reference.MAX_FLIP_GAP,
            "update_loss_tolerance": reference.UPDATE_LOSS_TOLERANCE,
            "update_tolerance": reference.UPDATE_TOLERANCE,
            "decode_rows": rows, "positions": S, "output_scales": scales}

        def judge(logits, values, experts, held):
            """One pass's outputs, in the system's place, against the
            float32 reference held to the experts that pass chose."""
            out = reference.compare(
                (logits, values), (held["logits"], held["values"]), scales)
            routing = reference.routing_verdict(
                experts, held["experts"], held["select"])
            return {"errors": out["errors"], **routing,
                    "ok": bool(out["ok"] and routing["ok"])}

        verdict["causal"] = judge(logits, values, experts, held)
        # How far each matrix state is from the recurrence's after the
        # last position.
        verdict["causal"]["gdn_state_drift"] = [
            reference.relative_error(got, want)
            for got, want in zip(states, held["gdn_states"])]
        del logits, values, experts, states, held
        seconds.lap("judge")

        decode = seconds.compiled("decode", decode, params, tokens, others)
        logits, values, experts = seconds.ran(
            "decode", decode(params, tokens, others))
        held = seconds.ran("reference",
                           held_reference(params, tokens, experts))
        verdict["decode"] = judge(logits, values, experts, held)
        del logits, values, experts, held, decode
        seconds.lap("judge")

        # What the limits have to refuse: the same forward a precision
        # lower (float8_e4m3 block activations), in the system's place.
        low_reference = seconds.compiled(
            "fp8_reference", low_reference, params, tokens,
            options=REFERENCE_OPTIONS)
        low = seconds.ran("fp8_reference", low_reference(params, tokens))
        held = seconds.ran("reference",
                           held_reference(params, tokens, low["experts"]))
        verdict["fp8_reference"] = judge(
            low["logits"], low["values"], low["experts"], held)
        verdict["fp8_reference"]["refused"] = \
            not verdict["fp8_reference"].pop("ok")
        del low, held, low_reference, held_reference
        seconds.lap("judge")

        verdict["param_count"] = policy.num_params()
        # `check.sequences` is the minibatch's: one program serves both.
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

    def _check_update(self, seed: int, opt_state, seconds: Seconds,
                      experts_of) -> dict:
        """(e) of the module docstring, from `opt_state`, the optimizer
        state the window left, on the host; `experts_of(tokens)` are the
        experts [L, B, S, k] the system's causal pass chooses. Last of the
        checks: the step
        is given the policy's parameters and that state to overwrite, as
        the fused program is, and nothing reads them afterwards. In this
        order, so that the device never holds more than three trees the
        parameters' size, as the window did: the reference's gradients
        beside the parameters; the parameters to the host and the moments
        back, and from them the change the gradients ask for; that change
        to the host and the parameters back; the step; the errors, a
        program over the three trees."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.rllib import sample_batch as sb

        policy, net, opt = self.policy, self.network, self.optimizer
        S, frags = net["sequence_length"], opt.minibatch // opt.T
        rng = np.random.default_rng(seed + 1)
        # `TokenBigram-v0`: the action taken is the next observation.
        walk = rng.integers(0, net["vocab_size"], size=(frags, S + 1))
        ref_batch = {
            "tokens": walk[:, :S], "actions": walk[:, 1:],
            "rewards": rng.integers(0, 2, size=(frags, S)).astype(
                np.float32),
            "behaviour_logp": (
                -np.log(net["vocab_size"])
                + rng.uniform(-0.5, 0.5, size=(frags, S))).astype(
                    np.float32)}
        dones = np.zeros((frags, S), np.float32)
        dones[:, -1] = 1.0
        batch = {
            sb.OBS: jnp.asarray(ref_batch["tokens"].reshape(-1), jnp.int32),
            sb.ACTIONS: jnp.asarray(
                ref_batch["actions"].reshape(-1), jnp.int32),
            sb.REWARDS: jnp.asarray(ref_batch["rewards"].reshape(-1)),
            sb.DONES: jnp.asarray(dones.reshape(-1)),
            sb.ACTION_LOGP: jnp.asarray(
                ref_batch["behaviour_logp"].reshape(-1)),
            sb.VF_PREDS: jnp.zeros(frags * S, jnp.float32),
            sb.BOOTSTRAP_OBS: jnp.asarray(walk[:, S], jnp.int32)}
        cfg = policy.config
        params = policy.params

        def adam_of(opt_state):
            (adam,) = [s for s in jax.tree.leaves(
                opt_state, is_leaf=lambda s: hasattr(s, "mu"))
                if hasattr(s, "mu")]
            return adam
        count = int(adam_of(opt_state).count)

        # The reference: a sequence at a time (the loss is a sum over
        # sequences), float32, its gradients added up where they are.
        def loss_and_grad(variables, one, total):
            loss, grads = jax.value_and_grad(
                lambda p: reference.vtrace_loss(
                    dict(variables, params=p), one, net, cfg)[0])(
                        variables["params"])
            return loss, jax.tree.map(jnp.add, total, grads)
        experts = seconds.ran("causal", experts_of(ref_batch["tokens"]))
        ones = [dict({k: jnp.asarray(v[i:i + 1])
                      for k, v in ref_batch.items()},
                     experts=experts[:, i:i + 1]) for i in range(frags)]
        grads = jax.tree.map(jnp.zeros_like, params["params"])
        loss_and_grad = seconds.compiled(
            "reference_gradient", loss_and_grad, params, ones[0], grads,
            options=REFERENCE_OPTIONS, donate_argnums=(2,))
        want_loss = 0.0
        for one in ones:
            loss, grads = loss_and_grad(params, one, grads)
            want_loss += float(loss)
        seconds.ran("reference_gradient", grads)
        scale, norm = reference.clip_scale(_flat(grads), cfg)
        on_host = jax.device_get(params)
        for leaf in jax.tree.leaves(params):
            leaf.delete()
        old = on_host["params"]
        opt_state = jax.device_put(opt_state)
        adam = adam_of(opt_state)
        want = jax.jit(
            lambda g, m, v, scale: jax.tree.map(
                lambda g, m, v: reference.adam_change(
                    g, m, v, count, cfg, scale), g, m, v),
            donate_argnums=(0,))(
                grads, adam.mu["params"], adam.nu["params"],
                jnp.float32(scale))
        want = jax.device_get(want)
        del grads, adam
        params = jax.device_put(on_host)
        seconds.lap("to_host")

        step = seconds.compiled(
            "step", lambda p, o, b: opt.learn(
                p, o, b, jax.random.PRNGKey(0)),
            params, opt_state, batch, donate_argnums=(0, 1))
        after, opt_state, stats = step(params, opt_state, batch)
        loss = float(stats["total_loss"])
        for leaf in jax.tree.leaves(opt_state):
            leaf.delete()
        del step, opt_state
        seconds.lap("step")
        errors = jax.jit(lambda old, new, want: jax.tree.map(
            reference.change_error, old, new, want))(
                old, after["params"], want)
        errors = {name: float(e) for name, e in _flat(errors).items()}
        seconds.lap("errors")
        found = reference.compare_update(loss, want_loss, errors)
        found.update(tokens=frags * S, updates_before=count, grad_norm=norm,
                     largest_errors=dict(sorted(
                         errors.items(), key=lambda kv: -kv[1])[:6]),
                     ok=bool(found["ok"]))
        return found


def open_session(config: dict, workload: dict, seed: int, chips: int,
                 rehearse: bool) -> Qwen3NextTokenSession:
    return Qwen3NextTokenSession(config, workload, seed, chips, rehearse)
