"""Driver for the cells whose token policy is `sdar_moe`, which GENERATES BY
DIFFUSION OVER BLOCKS (every layer grouped-head attention with QK-norm over
each head and a share of softmax-routed SwiGLU experts; a rollout step yields
a block of positions a sequence in S denoising passes and a commit pass; the
learner replays the sampler's trace over a clean and S noisy streams and
V-trace runs over blocks). Everything but the comparison with the reference
is `rllib_token_trainer.TokenSession`'s, and so `rllib_trainer.Session`'s;
the clock, the reference's compile options and the flattening are the
`kimi_linear` driver's.

`check_outputs`, on the stopped trainer, at the widths and in the state the
trainer ran to, outside the window: `check.sequences` seeded episodes of one
fragment's positions (4 of 2,048 in the cell), and

  (a) the ROLLOUT's own block steps from empty caches (the timed path's
      form: S denoising passes against the caches the commit passes wrote),
      as rows of a batch as wide as the rollout's (`num_envs_per_worker`;
      the other rows generate episodes of their own): the tokens it drew,
      the pass each was unmasked at, its log-probabilities there and a value
      a block: the TRACE that everything below is evaluated on;
  (b) the LEARNER's pass over the same tokens and trace (the cell's
      minibatch shape: one clean and S noisy streams, the fused attention
      under the stream mask): logits at every position, a value a block;
  (c) the system's choice of experts against the reference's own, a layer
      at a time, in both: the reference is held to the system's choices, so
      the layers before a layer are the system's on both sides
      (`router_flips`, `max_flip_gap`, `flips_by_layer`);
  (d) the parameter count;
  (e) one update by the optimizer's own step (`AnakinOptimizer.learn`, the
      body of the fused program's learner: the block-level V-trace, its
      gradient through the recomputed layers and the fused attention's
      backward kernels, the held experts' dispatch, the clip, Adam) on that
      trace as one minibatch, with seeded rewards and the behaviour's
      log-probabilities moved off the rollout's by a seeded amount (so that
      the importance ratios are not 1), from the parameters and the
      optimizer state the window left: the loss it reports and the change
      of every parameter, against `jax.grad` of the reference's
      `vtrace_loss` put through the reference's `adam_change`, the
      reference held to the experts the system's pass chooses, as in (b).

(a) and (b) against `lib/reference_sdar_moe.py`'s forward on the trace
(float32, no cache, no kernel, the clean and a noisy stream as one sequence
of 2T positions under a mask written out on the score matrix, the same share
of the experts and the vocabulary) held to the experts that pass chose. Of
(a) the log-probabilities of the drawn tokens are compared (the rollout
keeps no logits), of (b) the logits; both as a share of the largest
reference logit, values of the largest reference value. The reference with
its blocks rounded to float8_e4m3 (the nearest precision below the stated
bfloat16) goes through (b) and (c) in the system's place and is printed
beside it: it has to be refused.

The check is written for its seconds and for the device's room as the
`kimi_linear` driver's is: every program takes what a seed changes as an
argument, the reference's own programs are compiled at XLA's least effort,
and the check holds no more of the device than the window did.
"""

from __future__ import annotations

import numpy as np

from drivers import rllib_token_trainer  # `benchmark/` is on the path
from drivers.rllib_kimi_linear_token_trainer import (
    REFERENCE_OPTIONS, Seconds, _flat)
from lib import reference_sdar_moe as reference


class SdarTokenSession(rllib_token_trainer.TokenSession):
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
        T, rows = net["sequence_length"], opt.num_envs
        L, S = net["block_length"], net["denoise_steps"]
        n = self.workload["check"]["sequences"]
        first = jnp.asarray(np.random.default_rng(seed).integers(
            0, net["vocab_size"] - 1, size=rows), jnp.int32)
        key = jax.random.PRNGKey(seed % 2 ** 30)
        params = policy.params
        model = policy.model

        def generate(params, first, key):
            """One episode a row by the policy's own block steps from empty
            caches; of the first `n` rows: tokens, log-probabilities, unmask
            steps [n, T], values [n, T / L], and the experts every pass
            chose in the learner's layout [layers, n, (S + 1) T, k] (the
            commit passes' first; their last layer chooses none: -1)."""
            def step(carry, key):
                state, reset = carry
                (tokens, logp, steps, value, state), kept = model.apply(
                    params, first, state, reset, key, method="block_step",
                    mutable=["routing", "counters"])
                commit = kept["routing"]["commit_experts"][-1][:, :n]
                commit = jnp.concatenate(
                    [commit, jnp.full((1,) + commit.shape[1:], -1)], axis=0)
                return (state, jnp.zeros_like(reset)), (
                    tokens[:n], logp[:n], steps[:n], value[:n], commit,
                    kept["routing"]["experts"][-1][:, :, :n])
            _, (tokens, logp, steps, values, commit, noisy) = jax.lax.scan(
                step, (policy.initial_state(rows),
                       jnp.ones(rows, jnp.float32)),
                jax.random.split(key, T // L))

            def positions(x):
                """[blocks, n, L, ..] -> [n, T, ..]."""
                return jnp.swapaxes(x, 0, 1).reshape((n, T) + x.shape[3:])
            # [blocks, layers, n, L, k] -> [layers, n, T, k] a stream.
            experts = jnp.concatenate([
                jnp.moveaxis(x, 0, 2).reshape(x.shape[1], n, T, -1)
                for x in [commit] + [noisy[:, s] for s in range(S)]], axis=2)
            return (positions(tokens), positions(logp), positions(steps),
                    values.T, experts)

        def learner(params, tokens, steps):
            """The trace [n, T] as one pass of the learner's (the cell's
            minibatch): (logits over the ids below the MASK id, values,
            experts [layers, n, (S + 1) T, k])."""
            (logits, values), kept = model.apply(
                params, tokens, steps, jnp.zeros(tokens.shape),
                method="block_causal", mutable=["routing", "counters"])
            return logits[..., :-1], values, kept["routing"]["experts"][-1]

        def held_reference(params, tokens, steps, experts):
            return reference.forward(params, tokens, steps, net,
                                     experts=experts)

        def low_reference(params, tokens, steps):
            return reference.forward(params, tokens, steps, net,
                                     round_to="float8_e4m3")

        def taken(logits, tokens):
            """The log-probabilities of `tokens` under `logits`."""
            logp = jax.nn.log_softmax(logits, axis=-1)
            return jnp.take_along_axis(logp, jnp.minimum(
                tokens, logits.shape[-1] - 1)[..., None], axis=-1)[..., 0]

        generate = seconds.compiled("rollout", generate, params, first, key)
        tokens, logp, steps, values, experts = seconds.ran(
            "rollout", generate(params, first, key))
        del generate
        generated = np.asarray(steps) >= 0
        trace = {
            "given_rows": int((~generated).sum()),
            "unmasked_at": [int((np.asarray(steps) == s).sum())
                            for s in range(S)],
            "mean_logp": float(np.asarray(logp)[generated].mean())}
        held_reference = seconds.compiled(
            "reference", held_reference, params, tokens, steps, experts,
            options=REFERENCE_OPTIONS)
        held = seconds.ran(
            "reference", held_reference(params, tokens, steps, experts))
        scales = reference.output_scales((held["logits"], held["values"]))
        verdict = {
            "tolerance": reference.TOLERANCE,
            "max_router_flips": reference.MAX_ROUTER_FLIPS,
            "max_flip_gap": reference.MAX_FLIP_GAP,
            "update_loss_tolerance": reference.UPDATE_LOSS_TOLERANCE,
            "update_tolerance": reference.UPDATE_TOLERANCE,
            "rollout_rows": rows, "positions": T, "output_scales": scales,
            "trace": trace}

        def judge(got, want, experts, held):
            """One form's outputs, in the system's place, against the
            float32 reference held to the experts that form chose."""
            out = reference.compare(got, want, scales)
            routing = reference.routing_verdict(
                experts, held["experts"], held["select"])
            return {"errors": out["errors"], **routing,
                    "ok": bool(out["ok"] and routing["ok"])}

        # (a): the drawn tokens' log-probabilities, where generated.
        want_logp = np.where(
            generated, np.asarray(taken(held["logits"], tokens)), 0.0)
        verdict["rollout"] = judge(
            (np.where(generated, np.asarray(logp), 0.0), values),
            (want_logp, held["values"]), experts, held)
        del held, experts
        seconds.lap("judge")

        # (b): the learner's pass on the same trace.
        learner = seconds.compiled("learner", learner, params, tokens, steps)
        logits, learned_values, experts = seconds.ran(
            "learner", learner(params, tokens, steps))
        held = seconds.ran(
            "reference", held_reference(params, tokens, steps, experts))
        verdict["learner"] = judge(
            (logits, learned_values), (held["logits"], held["values"]),
            experts, held)
        # At unchanged parameters the learner's log-probabilities are the
        # rollout's: a block's importance ratio is 1 (printed, not judged).
        ratio = np.exp(np.where(
            generated, np.asarray(taken(logits, tokens)) - np.asarray(logp),
            0.0).reshape(n, T // L, L).sum(-1))
        verdict["learner"]["is_ratio"] = [float(ratio.min()),
                                          float(ratio.mean()),
                                          float(ratio.max())]
        del logits, learned_values, experts, held
        seconds.lap("judge")

        # What the limits have to refuse: the same forward a precision
        # lower (float8_e4m3 block activations), in the system's place.
        low_reference = seconds.compiled(
            "fp8_reference", low_reference, params, tokens, steps,
            options=REFERENCE_OPTIONS)
        low = seconds.ran("fp8_reference",
                          low_reference(params, tokens, steps))
        held = seconds.ran("reference", held_reference(
            params, tokens, steps, low["experts"]))
        verdict["fp8_reference"] = judge(
            (low["logits"], low["values"]),
            (held["logits"], held["values"]), low["experts"], held)
        verdict["fp8_reference"]["refused"] = \
            not verdict["fp8_reference"].pop("ok")
        del low, held, low_reference, held_reference
        seconds.lap("judge")

        verdict["param_count"] = policy.num_params()
        verdict["update"] = self._check_update(
            seed, opt_state, seconds, np.asarray(tokens), np.asarray(steps),
            np.asarray(logp), lambda: learner(params, tokens, steps)[2])
        verdict["seconds"] = dict(seconds, total=sum(seconds.values()))
        verdict["ok"] = bool(
            verdict["rollout"]["ok"] and verdict["learner"]["ok"]
            and verdict["update"]["ok"]
            and (self.rehearse or verdict["param_count"]
                 == self.config["network"]["param_count"]))
        return verdict

    def _check_update(self, seed: int, opt_state, seconds: Seconds,
                      tokens, steps, logp, experts_of) -> dict:
        """(e) of the module docstring, from `opt_state`, the optimizer
        state the window left, on the host, on the trace (`tokens`, `steps`,
        the rollout's `logp`, [frags, T]); `experts_of()` are the experts
        the system's learner pass chooses on it. Last of the checks: the
        step is given the policy's parameters and that state to overwrite,
        as the fused program is, and nothing reads them afterwards. In the
        `nemotron_h` driver's order, so that the device never holds more
        than three trees the parameters' size, as the window did."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.rllib import sample_batch as sb

        policy, net, opt = self.policy, self.network, self.optimizer
        frags, T = tokens.shape
        assert frags == opt.minibatch // opt.T, (frags, opt.minibatch, opt.T)
        rng = np.random.default_rng(seed + 1)
        generated = steps >= 0
        ref_batch = {
            "tokens": tokens, "steps": steps,
            "rewards": np.where(generated, rng.integers(
                0, 2, size=tokens.shape), 0).astype(np.float32),
            "behaviour_logp": np.where(generated, logp + rng.uniform(
                -0.25, 0.25, size=tokens.shape), 0.0).astype(np.float32)}
        dones = np.zeros(tokens.shape, np.float32)
        dones[:, -1] = 1.0
        batch = {
            sb.OBS: jnp.asarray(tokens.reshape(-1), jnp.int32),
            sb.ACTIONS: jnp.asarray(tokens.reshape(-1), jnp.int32),
            sb.UNMASK_STEPS: jnp.asarray(steps.reshape(-1), jnp.int32),
            sb.REWARDS: jnp.asarray(ref_batch["rewards"].reshape(-1)),
            sb.DONES: jnp.asarray(dones.reshape(-1)),
            sb.ACTION_LOGP: jnp.asarray(
                ref_batch["behaviour_logp"].reshape(-1)),
            sb.VF_PREDS: jnp.zeros(frags * T, jnp.float32),
            sb.BOOTSTRAP_OBS: jnp.zeros(frags, jnp.int32)}
        cfg = policy.config
        params = policy.params

        def adam_of(opt_state):
            (adam,) = [s for s in jax.tree.leaves(
                opt_state, is_leaf=lambda s: hasattr(s, "mu"))
                if hasattr(s, "mu")]
            return adam
        count = int(adam_of(opt_state).count)

        # The reference: an episode at a time (the loss is a sum over
        # episodes), float32, its gradients added up where they are.
        def loss_and_grad(variables, one, total):
            loss, grads = jax.value_and_grad(
                lambda p: reference.vtrace_loss(
                    dict(variables, params=p), one, net, cfg)[0])(
                        variables["params"])
            return loss, jax.tree.map(jnp.add, total, grads)
        experts = seconds.ran("learner", experts_of())
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
        del experts, ones
        scale, norm = reference.clip_scale(_flat(grads), cfg)
        old = jax.device_get(params)
        for leaf in jax.tree.leaves(params):
            leaf.delete()
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
        params = jax.device_put(old)
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
                old["params"], after["params"], want)
        errors = {name: float(e) for name, e in _flat(errors).items()}
        seconds.lap("errors")
        found = reference.compare_update(loss, want_loss, errors)
        found.update(positions=frags * T, updates_before=count,
                     grad_norm=norm,
                     is_ratio=[float(stats["is_ratio_mean"]),
                               float(stats["is_ratio_max"])],
                     largest_errors=dict(sorted(
                         errors.items(), key=lambda kv: -kv[1])[:6]))
        return found


def open_session(config: dict, workload: dict, seed: int, chips: int,
                 rehearse: bool) -> SdarTokenSession:
    return SdarTokenSession(config, workload, seed, chips, rehearse)
