"""jax.distributed (DCN) bootstrap tests — VERDICT r2 item #4.

Two layers, mirroring how the reference proves its torch.distributed
plane (`sgd/tests` + `distributed_pytorch_runner.py:47`):

- raw 2-process world: subprocesses federate via gloo CPU collectives
  into one 2x4-device global mesh and run jitted SGD steps whose
  gradient all-reduce crosses processes;
- the Ray-SGD surface: `JaxTrainer(use_jax_distributed=True)` runner
  ACTORS join one world, train in SPMD lockstep, and hold byte-identical
  replicas with no driver-side weight averaging.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env(n_devices: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "0"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


RAW_WORLD_SCRIPT = textwrap.dedent("""
    import sys
    rank, coordinator = int(sys.argv[1]), sys.argv[2]
    from ray_tpu.parallel import distributed as dist
    dist.initialize(coordinator, num_processes=2, process_id=rank)
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 8, len(jax.devices())
    mesh = dist.global_mesh()
    repl = NamedSharding(mesh, P())
    bshard = NamedSharding(mesh, P("dp"))

    # Linear regression y = 3x - 1, SGD over the global batch.
    w = dist.process_local_batch(repl, np.zeros(2, np.float32))

    def step(w, x, y):
        def loss_fn(w):
            pred = w[0] * x + w[1]
            return jnp.mean((pred - y) ** 2)
        loss, g = jax.value_and_grad(loss_fn)(w)
        return w - 0.1 * g, loss

    jstep = jax.jit(step, in_shardings=(repl, bshard, bshard),
                    out_shardings=(repl, repl))
    rng = np.random.RandomState(rank)
    first = last = None
    for i in range(60):
        x = rng.uniform(-1, 1, size=4).astype(np.float32)
        y = 3 * x - 1
        w, loss = jstep(w, dist.process_local_batch(bshard, x),
                        dist.process_local_batch(bshard, y))
        loss = float(loss)
        first = loss if first is None else first
        last = loss
    wv = np.asarray(w)
    assert last < first * 0.1, (first, last)
    assert abs(wv[0] - 3) < 0.3 and abs(wv[1] + 1) < 0.3, wv
    print(f"rank{rank} OK w={wv}")
    dist.shutdown()
""")


class TestRawWorld:
    def test_two_process_global_mesh_sgd(self, tmp_path):
        from ray_tpu.parallel.distributed import reserve_coordinator_port
        coordinator = reserve_coordinator_port()
        script = tmp_path / "world.py"
        script.write_text(RAW_WORLD_SCRIPT)
        procs = [
            subprocess.Popen(
                [sys.executable, str(script), str(rank), coordinator],
                env=_child_env(4), stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT)
            for rank in (0, 1)]
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out.decode())
        for rank, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank{rank} failed:\n{out[-2000:]}"
            assert f"rank{rank} OK" in out


def _model_creator(config):
    import flax.linen as nn

    class Linear(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(1)(x)

    return Linear()


def _data_creator(config):
    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, size=(256, 3)).astype(np.float32)
    w = np.array([[2.0], [-1.0], [0.5]], np.float32)
    y = x @ w + 0.25
    return (x, y), (x[:64], y[:64])


def _optimizer_creator(config):
    import optax
    return optax.sgd(config.get("lr", 0.2))


def _loss_creator(config):
    def mse(pred, y):
        import jax.numpy as jnp
        return jnp.mean((pred - y) ** 2)
    return mse


class TestJaxTrainerDistributed:
    def test_runner_actors_form_one_world(self):
        ray_tpu.init(num_cpus=3)
        try:
            from ray_tpu.sgd.jax_trainer import JaxTrainer
            trainer = JaxTrainer(
                _model_creator, _data_creator, _optimizer_creator,
                _loss_creator,
                config={"lr": 0.2, "seed": 0},
                num_replicas=2, batch_size=32,
                use_jax_distributed=True,
                runner_env={
                    "JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS":
                        "--xla_force_host_platform_device_count=2",
                })
            s1 = trainer.train()
            s3 = None
            for _ in range(4):
                s3 = trainer.train()
            assert s3["train_loss"] < s1["train_loss"] * 0.5, (s1, s3)
            val = trainer.validate()
            assert val["validation_loss"] < s1["train_loss"]
            # Replicas are identical WITHOUT driver-side averaging.
            w0, w1 = ray_tpu.get(
                [r.get_weights.remote() for r in trainer.runners])
            import jax
            jax.tree.map(np.testing.assert_array_equal, w0, w1)
            trainer.shutdown()
        finally:
            ray_tpu.shutdown()

    def test_rejects_inprocess_distributed(self):
        from ray_tpu.sgd.jax_trainer import JaxTrainer
        with pytest.raises(ValueError, match="num_replicas"):
            JaxTrainer(_model_creator, _data_creator,
                       _optimizer_creator, _loss_creator,
                       num_replicas=0, use_jax_distributed=True)
