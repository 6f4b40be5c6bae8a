"""The learners' numerics knobs: the q8 block quantizer weight sync
ships deltas with (`_private/serialization.py`), bf16 compute with f32
masters (`parallel/precision.py`), and what is left of the q8 gradient
exchange that ISSUE 44 removed: its config key is refused by name, and a
checkpoint that carries its residuals still loads.
"""

import numpy as np
import pytest

from conftest import cpu_mesh, ppo_batch, ppo_policy
from ray_tpu._private import serialization
from ray_tpu.parallel import precision


# ---------------------------------------------------------------------
# the numpy quantizer satellites (zero-amax clamp)
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


# ---------------------------------------------------------------------
# policy integration: compute dtype through PPOJaxPolicy
# ---------------------------------------------------------------------
class TestPolicyCodecs:
    def test_bf16_compute_keeps_f32_masters(self):
        """bf16 compute dtype: the flax trunk runs in bfloat16 but the
        master params and every float optax slot stay float32, and the
        loss is finite without loss scaling."""
        import jax
        import jax.numpy as jnp
        mesh = cpu_mesh(8)
        p = ppo_policy(mesh, {"compute_dtype": "bf16"})
        assert p.compute_dtype == jnp.bfloat16
        assert p.model.compute_dtype == jnp.bfloat16
        stats = p.sgd_learn(ppo_batch(32), num_sgd_iter=2,
                            minibatch_size=16)
        assert np.isfinite(stats["total_loss"])
        for leaf in jax.tree.leaves(p.params):
            assert leaf.dtype == jnp.float32
        for leaf in jax.tree.leaves(p.opt_state):
            if hasattr(leaf, "dtype") and jnp.issubdtype(
                    leaf.dtype, jnp.floating):
                assert leaf.dtype == jnp.float32

    def test_default_model_dtype_unchanged(self):
        """At the default f32 the FC trunk stays f32 (no silent bf16)."""
        import jax.numpy as jnp
        mesh = cpu_mesh(8)
        p = ppo_policy(mesh)
        assert p.compute_dtype == jnp.float32
        assert p.model.compute_dtype == jnp.float32

    def test_unknown_compute_dtype_is_refused(self):
        with pytest.raises(ValueError, match="fp8"):
            precision.resolve_compute_dtype("fp8")

    def test_allreduce_codec_key_is_refused_by_name(self):
        """`deep_merge` takes unknown keys silently, so the key that
        selected the removed exchange is refused where the policy reads
        its config, whatever its value."""
        for value in ("q8", "fp32", "auto"):
            with pytest.raises(ValueError, match="allreduce_codec.*XLA"):
                ppo_policy(cpu_mesh(2), {"allreduce_codec": value})

    def test_state_has_no_residuals_and_an_old_one_still_loads(self):
        """`get_state` writes no `ef_state`; a parent's checkpoint that
        holds one (q8 was on) restores the rest and trains on."""
        import jax
        mesh = cpu_mesh(2)
        src, dst = ppo_policy(mesh), ppo_policy(mesh, {"seed": 7})
        batch = ppo_batch(32)
        src.sgd_learn(batch, num_sgd_iter=2, minibatch_size=16)
        state = src.get_state()
        assert sorted(state) == ["global_timestep", "loss_state",
                                 "opt_state", "weights"]
        state["ef_state"] = jax.tree.map(
            lambda w: np.zeros((2,) + w.shape, np.float32),
            state["weights"])
        dst.set_state(state)
        assert dst.global_timestep == src.global_timestep == 32
        for mine, theirs in ((dst.params, src.params),
                             (dst.opt_state, src.opt_state)):
            for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        stats = dst.learn_on_batch(batch)
        assert np.isfinite(stats["total_loss"])
        assert dst.global_timestep == 64


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

    def test_bf16_trainer_converges(self):
        stats, val = self._run(compute_dtype="bf16")
        assert val["validation_loss"] < 0.01, val

    def test_allreduce_codec_key_is_refused_by_name(self):
        with pytest.raises(ValueError, match="allreduce_codec.*XLA"):
            self._run(allreduce_codec="q8")
