"""Driver for the cells whose token policy is `lfm2_moe` (gated short
convolutions whose state is two rows a layer beside one grouped-head
attention layer with a cache; per-head QK-norm; a leading dense layer, then
a share of sigmoid-routed experts; the head tied to the embedding).
Everything but the comparison with the reference is
`rllib_token_trainer.TokenSession`'s, and so `rllib_trainer.Session`'s.

`check_outputs`, on the stopped trainer, at the widths and in the state the
trainer ran to, outside the window: `check.sequences` seeded sequences of
one episode's length (4,096 positions in the cell), and

  (a) the system's causal pass (the learner's form and, with the cell's
      two sequences as one pass, the learner's shape: the convolutions as
      shifted products over the fragment, the attention fused), logits and
      values at every position,
  (b) the system's decode of the same sequences from empty state, every
      position one token at a time through the convolutions' states and the
      attention's cache (the rollout's form), as rows of a batch as wide as
      the rollout's (`num_envs_per_worker`; the other rows decode seeded
      sequences of their own),
  (c) the system's choice of experts against the reference's own, an expert
      layer at a time: the reference is held to the system's choices, so
      the layers before a layer are the system's on both sides
      (`router_flips`, `max_flip_gap`, `flips_by_layer`),
  (d) the parameter count,
  (e) one update by the optimizer's own step (`AnakinOptimizer.learn`, the
      body of the fused program's learner: V-trace, its gradient through
      the recomputed blocks, the convolutions' shifted products, the fused
      attention's backward kernels, the held experts' dispatch, the tied
      head, the bootstrap step through both kinds of state, the clip, Adam)
      on one seeded minibatch of the cell's size, from the parameters and
      the optimizer state the window left: the loss it reports and the
      change of every parameter, against `jax.grad` of the reference's
      `vtrace_loss` put through the reference's `adam_change`; and the
      routers' selection biases, which no update may move.

(a) and (b) against `lib/reference_lfm2_moe.py`'s full forward (float32, no
cache, no state, the convolution as three shifted products, the same share
of the experts and the vocabulary) held to the experts the system's pass
chose, a sequence at a time. Logits are compared, never sampled tokens. The
reference with its blocks rounded to float8_e4m3 (the nearest precision
below the stated bfloat16) goes through (a) and (c) in the system's place
and is printed beside it: it has to be refused.
"""

from __future__ import annotations

import time

import numpy as np

from drivers import rllib_token_trainer  # `benchmark/` is on the path
from lib import reference_lfm2_moe as reference


def _merged(found: list) -> dict:
    """The verdicts of the sequences as one: the largest error of each
    output and the largest gap, the mean share of flips."""
    errors = {name: max(f["errors"][name] for f in found)
              for name in found[0]["errors"]}
    flips = float(np.mean([f["router_flips"] for f in found]))
    gap = max(f["max_flip_gap"] for f in found)
    return {
        "errors": errors, "router_flips": flips, "max_flip_gap": gap,
        "flips_by_layer": [float(x) for x in np.mean(
            [f["flips_by_layer"] for f in found], axis=0)],
        "ok": bool(max(errors.values()) <= reference.TOLERANCE
                   and flips <= reference.MAX_ROUTER_FLIPS
                   and gap <= reference.MAX_FLIP_GAP)}


class Lfm2TokenSession(rllib_token_trainer.TokenSession):
    def check_outputs(self, seed: int) -> dict:
        import jax
        import jax.numpy as jnp

        self._stop_trainer()
        t0 = time.perf_counter()
        policy, net, opt = self.policy, self.network, self.optimizer
        # The rollout's caches are not needed any more, and the check's
        # own need the room; the optimizer's state, which (e) begins from
        # and compares with on the host anyway, waits there meanwhile.
        opt_state = jax.device_get(policy.opt_state)
        for leaf in jax.tree.leaves((opt._pstate, policy.opt_state)):
            leaf.delete()
        S, rows = net["sequence_length"], opt.num_envs
        n = self.workload["check"]["sequences"]
        rng = np.random.default_rng(seed)
        tokens = jnp.asarray(rng.integers(
            0, net["vocab_size"], size=(n, S)), jnp.int32)
        others = jnp.asarray(rng.integers(
            0, net["vocab_size"], size=(rows - n, S)), jnp.int32)
        params = policy.params

        @jax.jit
        def causal(params, tokens):
            """The sequences [n, S] as one pass (the learner's minibatch
            in the cell): (logits, values, experts [n, L, S, k])."""
            (logits, values, _), kept = policy.apply(
                params, tokens, None, jnp.zeros(tokens.shape),
                mutable=["routing", "counters"])
            return logits, values, jnp.swapaxes(
                kept["routing"]["experts"][-1], 0, 1)

        @jax.jit
        def decode(params, tokens):
            """Every position of `tokens`, the first `n` rows of a batch
            of `rows`, from an empty window as the rollout begins:
            (logits [S, n, V], values [S, n], experts [S, L, n, k])."""
            def step(carry, token):
                state, reset = carry
                (logits, value, state), kept = policy.apply(
                    params, token[:, None], state, reset[:, None],
                    mutable=["routing"])
                return (state, jnp.zeros_like(reset)), (
                    logits[:n, 0], value[:n, 0],
                    kept["routing"]["experts"][-1][:, :n])
            _, out = jax.lax.scan(
                step, (policy.initial_state(rows),
                       jnp.ones(rows, jnp.float32)),
                jnp.concatenate([tokens, others]).T)
            return out

        programs = {}

        def reference_of(i, experts=None, round_to=None):
            """The reference's forward of sequence `i`, its router free
            or held to `experts` [L, S, k]."""
            key = (experts is None, round_to)
            if key not in programs:
                programs[key] = jax.jit(lambda p, t, e: jax.tree.map(
                    lambda a: a[:, 0] if a.ndim == 4 else a[0],
                    reference.forward(
                        p, t[None], net, round_to=round_to,
                        experts=None if e is None else e[:, None])))
            return programs[key](params, tokens[i], experts)

        def judge(i, logits, values, experts, scales):
            """One pass's outputs of sequence `i`, in the system's place,
            against the float32 reference held to the experts that pass
            chose."""
            held = reference_of(i, experts)
            out = reference.compare(
                (logits, values), (held["logits"], held["values"]), scales)
            routing = reference.routing_verdict(
                experts[:, None], held["experts"][:, None],
                held["select"][:, None])
            return {"errors": out["errors"], **routing}

        # (0) the reference on its own: the outputs' scales.
        scales = (0.0, 0.0)
        for i in range(n):
            free = reference_of(i)
            scales = tuple(max(a, b) for a, b in zip(
                scales, reference.output_scales(
                    (free["logits"], free["values"]))))
            del free
        t_ref = time.perf_counter()
        verdict = {
            "tolerance": reference.TOLERANCE,
            "max_router_flips": reference.MAX_ROUTER_FLIPS,
            "max_flip_gap": reference.MAX_FLIP_GAP,
            "update_loss_tolerance": reference.UPDATE_LOSS_TOLERANCE,
            "update_tolerance": reference.UPDATE_TOLERANCE,
            "decode_rows": rows, "positions": S, "output_scales": scales}
        logits, values, experts = causal(params, tokens)
        found = [judge(i, logits[i], values[i], experts[i], scales)
                 for i in range(n)]
        del logits, values, experts
        verdict["causal"] = _merged(found)
        logits, values, experts = decode(params, tokens)
        found = [judge(i, logits[:, i], values[:, i],
                       jnp.swapaxes(experts[:, :, i], 0, 1), scales)
                 for i in range(n)]
        del logits, values, experts
        verdict["decode"] = _merged(found)
        t_sys = time.perf_counter()

        # What the limits have to refuse: the same forward a precision
        # lower (float8_e4m3 block activations), in the system's place.
        found = []
        for i in range(n):
            low = reference_of(i, round_to="float8_e4m3")
            found.append(judge(i, low["logits"], low["values"],
                               low["experts"], scales))
            del low
        verdict["fp8_reference"] = _merged(found)
        verdict["fp8_reference"]["refused"] = \
            not verdict["fp8_reference"].pop("ok")
        programs.clear()
        t_low = time.perf_counter()

        verdict["param_count"] = policy.num_params()
        verdict["update"] = self._check_update(seed, opt_state)
        verdict["seconds"] = {
            "reference": t_ref - t0, "system": t_sys - t_ref,
            "fp8_reference": t_low - t_sys,
            "update": time.perf_counter() - t_low}
        verdict["ok"] = bool(
            verdict["causal"]["ok"] and verdict["decode"]["ok"]
            and verdict["update"]["ok"]
            and (self.rehearse or verdict["param_count"]
                 == self.config["network"]["param_count"]))
        return verdict

    def _check_update(self, seed: int, opt_state) -> dict:
        """(e) of the module docstring, from `opt_state`, the optimizer
        state the window left, on the host. Last of the checks: the step
        is given the policy's parameters and that state to overwrite, as
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
            opt_state, is_leaf=lambda s: hasattr(s, "mu"))
            if hasattr(s, "mu")]
        count, mu, nu = (int(adam.count), flat(adam.mu["params"]),
                         flat(adam.nu["params"]))
        step = jax.jit(
            lambda p, o, b: opt.learn(p, o, b, jax.random.PRNGKey(0)),
            donate_argnums=(0, 1))
        after, opt_state, stats = step(
            policy.params, jax.device_put(opt_state), batch)
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
        # sequences), float32.
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
        found.update(tokens=frags * S, updates_before=count, grad_norm=norm,
                     largest_errors=dict(sorted(
                         errors.items(), key=lambda kv: -kv[1])[:6]),
                     bias_moved=bias_moved,
                     ok=bool(found["ok"] and not bias_moved))
        return found


def open_session(config: dict, workload: dict, seed: int, chips: int,
                 rehearse: bool) -> Lfm2TokenSession:
    return Lfm2TokenSession(config, workload, seed, chips, rehearse)
