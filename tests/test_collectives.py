"""The in-mesh collective plane: quantized gradient all-reduce, error
feedback, bf16 compute with f32 masters, and the byte/latency accounting.

Covers ISSUE 17: the learner's gradient exchange as an explicit
EQuARX-style q8 block-quantized all-reduce (`parallel/collectives.py`),
selectable per-trainer, at equal learning curves and >=3.5x fewer
exchange bytes than the implicit fp32 psum.
"""

import numpy as np
import pytest

from ray_tpu._private import metrics, serialization
from ray_tpu.parallel import collectives


def _mesh(n=8):
    import jax

    from ray_tpu.parallel import mesh as mesh_lib
    devices = jax.devices()[:n]
    if len(devices) < n:
        pytest.skip(f"need {n} devices, have {len(jax.devices())}")
    return mesh_lib.make_mesh(devices=devices, axis_names=("dp",))


# ---------------------------------------------------------------------
# the numpy quantizer satellites (zero-amax clamp) + jnp bit parity
# ---------------------------------------------------------------------
class TestQ8Quantizer:
    def test_all_zero_vector_round_trips_finite(self):
        """Satellite fix: all-zero blocks used to hit scale==0; the
        Q8_SCALE_EPS clamp must keep scales positive and the round trip
        exactly zero with no NaN/Inf anywhere."""
        for n in (1, 7, serialization.Q8_BLOCK, 3 * serialization.Q8_BLOCK + 5):
            vec = np.zeros(n, np.float32)
            q, scales = serialization.q8_quantize(vec)
            assert np.all(scales > 0.0)
            assert np.all(np.isfinite(scales))
            out = serialization.q8_dequantize(q, scales)
            assert out.shape == (n,)
            assert np.all(out == 0.0)

    def test_mixed_zero_and_live_blocks(self):
        """A zero block next to a live block: the live block keeps its
        amax/127 scale, the zero block gets the epsilon clamp."""
        B = serialization.Q8_BLOCK
        vec = np.zeros(2 * B, np.float32)
        vec[B:] = np.linspace(-1.0, 1.0, B, dtype=np.float32)
        q, scales = serialization.q8_quantize(vec)
        assert scales[0] == np.float32(serialization.Q8_SCALE_EPS)
        assert scales[1] == np.float32(1.0) / np.float32(127.0)
        out = serialization.q8_dequantize(q, scales)
        assert np.all(out[:B] == 0.0)
        assert np.max(np.abs(out[B:] - vec[B:])) <= 1.0 / 254.0 + 1e-7

    def test_single_element_tails(self):
        """Single-element vectors and ragged tail blocks (n % B != 0)
        round-trip finite and within the per-block bound."""
        rng = np.random.default_rng(3)
        for n in (1, 2, serialization.Q8_BLOCK + 1,
                  2 * serialization.Q8_BLOCK + 17):
            vec = rng.standard_normal(n).astype(np.float32)
            q, scales = serialization.q8_quantize(vec)
            out = serialization.q8_dequantize(q, scales)
            assert np.all(np.isfinite(out))
            bound = np.abs(vec).max() / 254.0 + 1e-7
            assert np.max(np.abs(out - vec)) <= bound

    def test_tiny_values_denormal_safe(self):
        """Values near the float32 floor: the epsilon clamp must not
        produce Inf scales-reciprocals or NaN outputs."""
        vec = np.full(5, 1e-38, np.float32)
        q, scales = serialization.q8_quantize(vec)
        out = serialization.q8_dequantize(q, scales)
        assert np.all(np.isfinite(out))

    def test_jnp_encoder_bitwise_matches_numpy(self):
        """collectives.q8_encode (inside the jitted update) and the host
        q8_quantize (weight-sync wire) are the SAME codec: identical int8
        codes and f32 scales for the same input."""
        rng = np.random.default_rng(0)
        for n in (1, 5, serialization.Q8_BLOCK, 5000):
            vec = rng.standard_normal(n).astype(np.float32)
            qj, sj = collectives.q8_encode(vec)
            qn, sn = serialization.q8_quantize(vec)
            np.testing.assert_array_equal(
                np.asarray(qj).reshape(-1)[:n], qn)
            np.testing.assert_array_equal(np.asarray(sj), sn)
            out = collectives.q8_decode(qj, sj, (n,))
            np.testing.assert_array_equal(
                np.asarray(out), serialization.q8_dequantize(qn, sn))


# ---------------------------------------------------------------------
# the quantized all-reduce itself (8 virtual devices, shard_map)
# ---------------------------------------------------------------------
class TestQuantizedAllReduce:
    def _make_allreduce(self, mesh):
        """One jitted q8 all-reduce over stacked[ndev, n] per-device
        values (built ONCE per test — jax.jit caches on fn identity)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def per_replica(v, e):
            out, ne = collectives.psum_quantized(v[0], e[0], "dp")
            return out[None], ne[None]

        fn = jax.jit(jax.shard_map(
            per_replica, mesh=mesh, in_specs=(P("dp"), P("dp")),
            out_specs=(P("dp"), P("dp")), check_vma=False))
        sh = NamedSharding(mesh, P("dp"))

        def run(stacked, ef_stacked):
            out, ne = fn(jax.device_put(stacked, sh),
                         jax.device_put(ef_stacked, sh))
            return np.asarray(out), np.asarray(ne)

        return run

    def test_matches_fp32_psum_within_block_bound(self):
        mesh = _mesh(8)
        rng = np.random.default_rng(1)
        n = 2 * serialization.Q8_BLOCK + 100  # ragged tail
        vals = rng.standard_normal((8, n)).astype(np.float32)
        out, _ = self._make_allreduce(mesh)(
            vals, np.zeros((8, n), np.float32))
        exact = vals.sum(axis=0)
        # Every replica computes the same sum of dequantized payloads.
        for d in range(8):
            np.testing.assert_array_equal(out[d], out[0])
        # Per-element error <= sum over senders of that sender's
        # per-block quantization bound (amax/254).
        B = serialization.Q8_BLOCK
        nb = -(-n // B)
        padded = np.zeros((8, nb * B), np.float32)
        padded[:, :n] = vals
        amax = np.abs(padded.reshape(8, nb, B)).max(axis=2)  # [8, nb]
        bound = (amax / 254.0).sum(axis=0)                   # [nb]
        err = np.abs(out[0] - exact)
        for b in range(nb):
            blk = err[b * B:(b + 1) * B]
            assert blk.max() <= bound[b] + 1e-6, (b, blk.max(), bound[b])

    def test_error_feedback_telescopes_no_drift(self):
        """100 steps of a CONSTANT gradient: with error feedback the
        cumulative applied update tracks the cumulative true update to
        within one single-step quantization bound — the error telescopes
        instead of accumulating linearly."""
        mesh = _mesh(8)
        rng = np.random.default_rng(2)
        n = serialization.Q8_BLOCK
        g = rng.standard_normal((8, n)).astype(np.float32)
        ef = np.zeros((8, n), np.float32)
        total = np.zeros(n, np.float64)
        steps = 100
        allreduce = self._make_allreduce(mesh)
        for _ in range(steps):
            out, ef = allreduce(g, ef)
            total += out[0]
        exact_total = steps * g.sum(axis=0, dtype=np.float64)
        one_step_bound = (np.abs(g).max(axis=1) / 254.0).sum() + 1e-4
        drift = np.abs(total - exact_total).max()
        assert drift <= 2 * one_step_bound, (drift, one_step_bound)
        # Residuals themselves stay bounded by one block scale.
        assert np.abs(ef).max() <= (np.abs(g).max() / 254.0) * 1.01 + 1e-6

    def test_payload_ratio_exceeds_3p5x(self):
        """Analytic wire bytes on a real model tree: q8 must be >=3.5x
        smaller than fp32 (1 byte/elem + amortized scales vs 4)."""
        import jax

        from ray_tpu.models.networks import FullyConnectedNetwork
        model = FullyConnectedNetwork(num_outputs=4, hiddens=(64, 64))
        params = model.init(jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.float32))
        f32 = collectives.payload_bytes(params, "fp32")
        q8 = collectives.payload_bytes(params, "q8")
        assert f32 / q8 >= 3.5, (f32, q8)

    def test_probe_returns_positive_seconds(self):
        mesh = _mesh(8)
        tree = {"w": np.zeros((32, 32), np.float32)}
        for codec in collectives.CODECS:
            s = collectives.allreduce_probe_s(tree, mesh, codec,
                                              iters=1)
            assert s > 0.0

    def test_resolve_codec_validates(self):
        assert collectives.resolve_codec("fp32") == "fp32"
        assert collectives.resolve_codec("q8") == "q8"
        with pytest.raises(ValueError):
            collectives.resolve_codec("int4")
        with pytest.raises(ValueError):
            collectives.resolve_compute_dtype("fp8")


# ---------------------------------------------------------------------
# policy integration: codec + compute dtype through PPOJaxPolicy
# ---------------------------------------------------------------------
def _ppo_policy(mesh, overrides=None, hiddens=(16, 16)):
    from ray_tpu.rllib.agents.ppo.ppo import DEFAULT_CONFIG, PPOJaxPolicy
    from ray_tpu.rllib.env.spaces import Box, Discrete
    config = dict(DEFAULT_CONFIG)
    config.update({
        "_mesh": mesh,
        "model": {"fcnet_hiddens": list(hiddens)},
        "num_sgd_iter": 2,
        "sgd_minibatch_size": 16,
        "train_batch_size": 32,
    })
    config.update(overrides or {})
    return PPOJaxPolicy(
        Box(low=-np.inf, high=np.inf, shape=(8,), dtype=np.float32),
        Discrete(4), config)


def _ppo_batch(n):
    import __graft_entry__
    return __graft_entry__._synthetic_ppo_batch(n, (8,), 4)


class TestPolicyCodecs:
    def test_q8_policy_tracks_fp32_loss(self):
        mesh = _mesh(8)
        fp = _ppo_policy(mesh, {"allreduce_codec": "fp32"})
        q8 = _ppo_policy(mesh, {"allreduce_codec": "q8"})
        assert q8.allreduce_codec == "q8"
        q8.set_weights(fp.get_weights())
        batch = _ppo_batch(32)
        before = metrics.snapshot()["counters"].get("allreduce_bytes", 0.0)
        fs = fp.sgd_learn(batch, num_sgd_iter=2, minibatch_size=16)
        qs = q8.sgd_learn(batch, num_sgd_iter=2, minibatch_size=16)
        fl, ql = fs["total_loss"], qs["total_loss"]
        assert np.isfinite(ql)
        assert abs(ql - fl) < 1e-2 * (1.0 + abs(fl)), (fl, ql)
        after = metrics.snapshot()["counters"].get("allreduce_bytes", 0.0)
        assert after > before
        hists = metrics.snapshot()["hists"]
        assert "learner_allreduce_s.q8" in hists
        assert "learner_allreduce_s.fp32" in hists

    def test_q8_accounting_is_3p5x_smaller(self):
        mesh = _mesh(8)
        fp = _ppo_policy(mesh, {"allreduce_codec": "fp32"})
        q8 = _ppo_policy(mesh, {"allreduce_codec": "q8"})
        assert fp._allreduce_payload / q8._allreduce_payload >= 3.5

    def test_fsdp_layout_falls_back_to_fp32(self):
        """q8 needs replicated params (each sender quantizes the full
        local gradient) — the fsdp layout must fall back with a warning,
        not crash or silently mis-reduce."""
        mesh = _mesh(8)
        p = _ppo_policy(mesh, {"allreduce_codec": "q8",
                               "param_sharding": "fsdp"},
                        hiddens=(32, 32))
        assert p.allreduce_codec == "fp32"
        stats = p.sgd_learn(_ppo_batch(32), num_sgd_iter=2,
                            minibatch_size=16)
        assert np.isfinite(stats["total_loss"])

    def test_bf16_compute_keeps_f32_masters(self):
        """bf16 compute dtype: the flax trunk runs in bfloat16 but the
        master params and every float optax slot stay float32, and the
        loss is finite without loss scaling."""
        import jax
        import jax.numpy as jnp
        mesh = _mesh(8)
        p = _ppo_policy(mesh, {"compute_dtype": "bf16"})
        assert p.compute_dtype == jnp.bfloat16
        assert p.model.compute_dtype == jnp.bfloat16
        stats = p.sgd_learn(_ppo_batch(32), num_sgd_iter=2,
                            minibatch_size=16)
        assert np.isfinite(stats["total_loss"])
        for leaf in jax.tree.leaves(p.params):
            assert leaf.dtype == jnp.float32
        for leaf in jax.tree.leaves(p.opt_state):
            if hasattr(leaf, "dtype") and jnp.issubdtype(
                    leaf.dtype, jnp.floating):
                assert leaf.dtype == jnp.float32

    def test_bf16_with_q8_compose(self):
        """The two knobs compose: bf16 loss/grad math feeding the
        quantized all-reduce (grads arrive f32 from the cast transpose)."""
        mesh = _mesh(8)
        p = _ppo_policy(mesh, {"compute_dtype": "bf16",
                               "allreduce_codec": "q8"})
        assert p.allreduce_codec == "q8"
        stats = p.sgd_learn(_ppo_batch(32), num_sgd_iter=2,
                            minibatch_size=16)
        assert np.isfinite(stats["total_loss"])

    def test_default_model_dtype_unchanged(self):
        """At the default f32 the FC trunk stays f32 (no silent bf16)."""
        import jax.numpy as jnp
        mesh = _mesh(8)
        p = _ppo_policy(mesh)
        assert p.compute_dtype == jnp.float32
        assert p.model.compute_dtype == jnp.float32


# ---------------------------------------------------------------------
# sgd runner integration
# ---------------------------------------------------------------------
class TestSGDTrainerCodecs:
    def _creators(self):
        import flax.linen as nn
        import optax

        class Linear(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Dense(1)(x)

        def model_creator(config):
            return Linear()

        def data_creator(config):
            rng = np.random.default_rng(0)
            x = rng.standard_normal((512, 4)).astype(np.float32)
            w = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
            y = x @ w + 0.1
            return (x, y), (x[:64], y[:64])

        def optimizer_creator(config):
            return optax.sgd(config.get("lr", 0.5))

        def loss_creator(config):
            def loss_fn(out, target):
                return ((out - target) ** 2).mean()
            return loss_fn

        return model_creator, data_creator, optimizer_creator, loss_creator

    def _run(self, **cfg):
        from ray_tpu.sgd.jax_trainer import JaxTrainer
        mc, dc, oc, lc = self._creators()
        trainer = JaxTrainer(
            model_creator=mc, data_creator=dc, optimizer_creator=oc,
            loss_creator=lc, num_replicas=0, batch_size=64,
            num_devices_per_replica=4, config=cfg)
        for _ in range(12):
            stats = trainer.train()
        val = trainer.validate()
        trainer.shutdown()
        return stats, val

    def test_q8_trainer_converges_and_accounts(self):
        before = metrics.snapshot()["counters"].get("allreduce_bytes", 0.0)
        stats, val = self._run(allreduce_codec="q8")
        assert val["validation_loss"] < 0.01, val
        after = metrics.snapshot()["counters"].get("allreduce_bytes", 0.0)
        assert after > before
        assert "learner_allreduce_s.q8" in metrics.snapshot()["hists"]

    def test_bf16_trainer_converges(self):
        stats, val = self._run(compute_dtype="bf16")
        assert val["validation_loss"] < 0.01, val


# ---------------------------------------------------------------------
# end-to-end learning-curve parity: PPO CartPole fp32 vs q8
# ---------------------------------------------------------------------
class TestLearningCurveParity:
    def _run(self, codec, iters=3):
        from ray_tpu.rllib.agents.ppo import PPOTrainer
        before = metrics.snapshot()["counters"]
        t = PPOTrainer(config={
            "env": "CartPole-v0",
            "num_workers": 0,
            "num_envs_per_worker": 2,
            "train_batch_size": 128,
            "sgd_minibatch_size": 32,
            "num_sgd_iter": 2,
            "rollout_fragment_length": 64,
            "lr": 3e-4,
            "model": {"fcnet_hiddens": [16, 16]},
            "seed": 0,
            "num_tpus_for_learner": 4,
            "allreduce_codec": codec,
        })
        rewards = []
        for _ in range(iters):
            r = t.train()
            if np.isfinite(r.get("episode_reward_mean", np.nan)):
                rewards.append(r["episode_reward_mean"])
        t.stop()
        after = metrics.snapshot()["counters"]
        bytes_delta = after.get("allreduce_bytes", 0.0) \
            - before.get("allreduce_bytes", 0.0)
        return rewards, bytes_delta

    def test_q8_matches_fp32_curve_at_fewer_bytes(self, ray_start):
        """Same-seed CartPole PPO on a 4-device learner mesh, implicit
        fp32 psum vs explicit q8 all-reduce: the q8 arm must account
        >=3.5x fewer gradient-exchange bytes and learn comparably (error
        feedback keeps it on the fp32 trajectory up to sampling noise)."""
        fp_rewards, fp_bytes = self._run("fp32")
        q8_rewards, q8_bytes = self._run("q8")
        assert fp_bytes > 0 and q8_bytes > 0
        assert fp_bytes / q8_bytes >= 3.5, (fp_bytes, q8_bytes)
        assert fp_rewards and q8_rewards
        best_fp, best_q8 = max(fp_rewards), max(q8_rewards)
        assert best_q8 >= 0.5 * best_fp - 10, (fp_rewards, q8_rewards)
