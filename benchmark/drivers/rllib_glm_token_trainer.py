"""Driver for the cells whose token policy is `glm4_moe_lite` (latent
attention, a share of the routed experts beside a shared one, a leading
dense layer, a next-next-token module). Everything but the comparison with
the reference is `rllib_token_trainer.TokenSession`'s, and so
`rllib_trainer.Session`'s.

`check_outputs`, on the stopped trainer, at the widths and in the state the
trainer ran to, outside the window: `check.sequences` seeded sequences of
one episode's length, and

  (a) the system's causal pass (the learner's form, latent attention
      decompressed), logits and values at every position,
  (b) the system's decode of the same sequences from an empty window,
      every position one token at a time through its latent cache (the
      rollout's form, latent attention absorbed), as rows of a batch as
      wide as the rollout's (`num_envs_per_worker`; the other rows decode
      seeded sequences of their own),
  (c) the system's choice of experts against the reference's own, a layer
      at a time: the reference is held to the system's choices, so the
      layers before a layer are the system's on both sides
      (`router_flips`, `max_flip_gap`, `flips_by_layer`),
  (d) the next-next-token module's cross-entropy on the same sequences,
      position by position,
  (e) the parameter count,
  (f) one update by the optimizer's own step (`AnakinOptimizer.learn`, the
      body of the fused program's learner: V-trace plus the module's term,
      its gradient through the recomputed blocks and the held experts'
      dispatch, the clip, Adam with the selection bias frozen) on one
      seeded minibatch of the cell's size, from the parameters and the
      optimizer state the window left: the loss it reports and the change
      of every parameter, against `jax.grad` of the reference's
      `vtrace_loss` put through the reference's `adam_change`; the
      selection bias must not have moved.

(a), (b) and (d) against `lib/reference_glm4_moe_lite.py`'s full forward
(float32, decompressed, no cache, the same share of the experts and the
vocabulary) held to the experts the system's pass chose. Logits are
compared, never sampled tokens. The reference with its blocks rounded to
float8_e4m3 (the nearest precision below the stated bfloat16) goes through
(a), (c) and (d) in the system's place and is printed beside it: it has to
be refused.
"""

from __future__ import annotations

import time

import numpy as np

from drivers import rllib_token_trainer  # `benchmark/` is on the path
from lib import reference_glm4_moe_lite as reference


class GlmTokenSession(rllib_token_trainer.TokenSession):
    def check_outputs(self, seed: int) -> dict:
        import jax
        import jax.numpy as jnp

        self._stop_trainer()
        t0 = time.perf_counter()
        policy, net, opt = self.policy, self.network, self.optimizer
        # The rollout's cache is not needed any more; the reference needs
        # the room. (The optimizer's state is: (f) begins from it.)
        for leaf in jax.tree.leaves(opt._pstate):
            leaf.delete()
        check = self.workload["check"]
        S, rows, n = net["sequence_length"], opt.num_envs, check["sequences"]
        rng = np.random.default_rng(seed)
        tokens = jnp.asarray(rng.integers(
            0, net["vocab_size"], size=(n, S)), jnp.int32)
        others = jnp.asarray(rng.integers(
            0, net["vocab_size"], size=(rows - n, S)), jnp.int32)
        zeros = jnp.zeros(tokens.shape, jnp.float32)
        params = policy.params

        @jax.jit
        def causal(params, tokens):
            (logits, values, _), kept = policy.apply(
                params, tokens, None, zeros,
                mutable=["routing", "counters", "losses"])
            return (logits, values, kept["routing"]["experts"][-1],
                    kept["routing"]["nextn_nll"][-1])

        @jax.jit
        def decode(params, tokens):
            """Every position of `tokens`, the first `n` rows of a batch
            of `rows`, from an empty window as the rollout begins."""
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
                    jnp.moveaxis(experts, 0, 2), None)

        programs = {}

        def reference_of(experts=None, round_to=None):
            """The reference's forward, its router free or held to
            `experts` (the trunk's layers alone, or the module's too)."""
            key = (None if experts is None else len(experts), round_to)
            if key not in programs:
                programs[key] = jax.jit(lambda p, t, e: reference.forward(
                    p, t, net, round_to=round_to, experts=e))
            return programs[key](params, tokens, experts)

        def judge(logits, values, experts, nll, scales):
            """One pass's outputs, in the system's place, against the
            float32 reference held to the experts that pass chose."""
            held = reference_of(experts)
            out = reference.compare(
                (logits, values), (held["logits"], held["values"]), scales)
            routing = reference.routing_verdict(
                experts, held["experts"], held["select"])
            found = {"errors": out["errors"], **routing}
            ok = out["ok"] and routing["ok"]
            if nll is not None:
                found["nextn_loss"] = reference.compare_loss(
                    nll, held["nextn_nll_by_position"])
                ok = ok and found["nextn_loss"]["ok"]
            found["ok"] = bool(ok)
            return found

        # (0) the reference on its own: the outputs' scales.
        free = reference_of()
        scales = reference.output_scales((free["logits"], free["values"]))
        t_ref = time.perf_counter()
        verdict = {
            "tolerance": reference.TOLERANCE,
            "max_router_flips": reference.MAX_ROUTER_FLIPS,
            "max_flip_gap": reference.MAX_FLIP_GAP,
            "update_loss_tolerance": reference.UPDATE_LOSS_TOLERANCE,
            "update_tolerance": reference.UPDATE_TOLERANCE,
            "decode_rows": rows,
            "output_scales": scales,
            "nextn_loss": float(free["nextn_loss"])}
        ok = True
        for name, run in (("causal", causal), ("decode", decode)):
            verdict[name] = judge(*run(params, tokens), scales)
            ok = ok and verdict[name]["ok"]
        t_sys = time.perf_counter()

        # What the limits have to refuse: the same forward a precision
        # lower (float8_e4m3 block activations), in the system's place.
        low = reference_of(round_to="float8_e4m3")
        verdict["fp8_reference"] = judge(
            low["logits"], low["values"], low["experts"],
            low["nextn_nll_by_position"], scales)
        verdict["fp8_reference"]["refused"] = \
            not verdict["fp8_reference"].pop("ok")
        del low, free
        programs.clear()
        t_low = time.perf_counter()

        verdict["param_count"] = policy.num_params()
        verdict["update"] = self._check_update(seed)
        verdict["seconds"] = {
            "reference": t_ref - t0, "system": t_sys - t_ref,
            "fp8_reference": t_low - t_sys,
            "update": time.perf_counter() - t_low}
        verdict["ok"] = bool(
            ok and verdict["update"]["ok"]
            and (self.rehearse or verdict["param_count"]
                 == self.config["network"]["param_count"]))
        return verdict

    def _check_update(self, seed: int) -> dict:
        """(f) of the module docstring. Last of the checks: the step is
        given the policy's parameters and optimizer state to overwrite, as
        the fused program is, and nothing reads them afterwards."""
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

        def flat(tree):
            return {jax.tree_util.keystr(path): np.asarray(leaf)
                    for path, leaf in
                    jax.tree_util.tree_flatten_with_path(tree)[0]}

        # What the update begins with, on the host: the reference's side.
        before = jax.device_get(policy.params)
        (adam,) = [s for s in jax.tree.leaves(
            policy.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
            if hasattr(s, "mu")]
        count, mu, nu = (int(adam.count), flat(adam.mu["params"]),
                         flat(adam.nu["params"]))
        step = jax.jit(
            lambda p, o, b: opt.learn(p, o, b, jax.random.PRNGKey(0)),
            donate_argnums=(0, 1))
        after, opt_state, stats = step(policy.params, policy.opt_state,
                                       batch)
        loss = float(stats["total_loss"])
        for leaf in jax.tree.leaves(opt_state):
            leaf.delete()
        after = jax.device_get(after)
        del step, opt_state
        bias_moved = any(
            not np.array_equal(a, b) for a, b in zip(
                jax.tree.leaves(after["constants"]),
                jax.tree.leaves(before["constants"])))

        # The reference: a sequence at a time (the loss is a sum over
        # sequences), float32, nothing recomputed.
        cfg = policy.config
        constants = before["constants"]
        grad = jax.jit(jax.value_and_grad(
            lambda p, b: reference.vtrace_loss(
                {"params": p, "constants": constants}, b, net, cfg)[0]))
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                      donate_argnums=(0,))
        ref_params = jax.device_put(before["params"])
        want_loss, grads = 0.0, None
        for i in range(frags):
            one, g = grad(ref_params,
                          {k: v[i:i + 1] for k, v in ref_batch.items()})
            want_loss += float(one)
            grads = g if grads is None else add(grads, g)
        del ref_params
        # A parameter at a time, on the device: its moments and the
        # system's two copies go up from the host, an error comes back.
        grads = {jax.tree_util.keystr(path): leaf for path, leaf in
                 jax.tree_util.tree_flatten_with_path(grads)[0]}
        scale, norm = reference.clip_scale(grads, cfg)
        error = jax.jit(lambda old, new, g, m, v: reference.change_error(
            old, new, reference.adam_change(g, m, v, count, cfg, scale)))
        old, new = flat(before["params"]), flat(after["params"])
        errors = {name: float(error(old[name], new[name], g, mu[name],
                                    nu[name]))
                  for name, g in grads.items()}
        found = reference.compare_update(loss, want_loss, errors)
        found.update(
            tokens=frags * S, updates_before=count, grad_norm=norm,
            mtp_loss=float(stats["mtp_loss"]), bias_moved=bias_moved,
            ok=bool(found["ok"] and not bias_moved))
        return found


def open_session(config: dict, workload: dict, seed: int, chips: int,
                 rehearse: bool) -> GlmTokenSession:
    return GlmTokenSession(config, workload, seed, chips, rehearse)
