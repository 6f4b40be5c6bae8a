"""Data-parallel supervised training (the Ray SGD equivalent).

Parity: `python/ray/experimental/sgd/pytorch/pytorch_trainer.py:23`
(`PyTorchTrainer`) + `distributed_pytorch_runner.py` — N runner actors,
synchronized data-parallel SGD, fault-tolerant `train(max_retries)` that
shrinks the world after an actor death, `save`/`restore` of model +
optimizer state.

TPU re-architecture: the reference's NCCL allreduce
(`pytorch_trainer.py:90`, `distributed_pytorch_runner.py:47,62`) splits
into two planes:

- **Intra-host (the fast path)**: each runner jits ONE donated-buffer
  train step over its device mesh; the batch is sharded on the "dp" axis
  and XLA inserts the gradient psum over ICI. With `num_replicas=0`
  everything runs in-process on the full mesh — this is the TPU-native
  replacement for DDP on a single machine.
- **Inter-host**: two modes. Default: runner actors exchange gradients
  through the object store (driver-averaged, synchronous). With
  `use_jax_distributed=True`, the runners join ONE `jax.distributed`
  world (`parallel/distributed.py`): every runner jits the same train
  step over the GLOBAL mesh spanning all runners' devices, feeds its
  process-local batch shard, and XLA inserts the cross-process gradient
  all-reduce (DCN) — the true TPU-pod replacement for
  `init_process_group` + DDP (`distributed_pytorch_runner.py:47,62`).
"""

from __future__ import annotations

import logging
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

import ray_tpu
from ray_tpu.exceptions import RayError

from ..parallel import mesh as mesh_lib
from ..parallel import precision

logger = logging.getLogger(__name__)


class JaxRunner:
    """One data-parallel worker: model replica + data shard.

    Parity: `distributed_pytorch_runner.py` — created as an actor by
    JaxTrainer (or used inline for num_replicas=0).
    """

    def __init__(self, model_creator: Callable, data_creator: Callable,
                 optimizer_creator: Callable, loss_creator: Callable,
                 config: Optional[dict] = None,
                 batch_size: int = 64,
                 num_devices: int = 0):
        self.config = dict(config or {})
        self.batch_size = batch_size
        self.model_creator = model_creator
        self.data_creator = data_creator
        self.optimizer_creator = optimizer_creator
        self.loss_creator = loss_creator
        self.num_devices = num_devices
        self.epoch = 0

    def setup(self, world_size: int = 1, world_rank: int = 0,
              coordinator: Optional[str] = None):
        """Build model/opt/data; shard the dataset by rank (parity:
        DistributedSampler in `distributed_pytorch_runner.py:62`).

        With `coordinator`, first join the jax.distributed world: the
        mesh then spans every runner's devices and the jitted step's
        gradient psum crosses processes (DCN)."""
        self.world_size = world_size
        self.world_rank = world_rank
        self.distributed = coordinator is not None
        if self.distributed:
            from ..parallel import distributed as dist
            dist.initialize(coordinator, num_processes=world_size,
                            process_id=world_rank)
            self.mesh = dist.global_mesh()
        else:
            self.mesh = mesh_lib.make_mesh(
                num_devices=self.num_devices or None)
        n_dev = self.mesh.devices.size
        self._repl = mesh_lib.replicated(self.mesh)
        self._bshard = mesh_lib.batch_sharded(self.mesh)
        # Param/opt-state layout resolves through the shared SpecLayout
        # rule table (config "param_sharding" -> RAY_TPU_PARAM_SHARDING;
        # same layer jax_policy uses). Distributed mode keeps the
        # replicated layout: its globals assemble from process-local
        # copies.
        from ray_tpu._private import spec_layout
        table = self.config.get("param_sharding")
        self.layout = spec_layout.SpecLayout.from_config(
            self.mesh, None if table in (None, "auto") else table)
        if self.distributed and not self.layout.is_replicated():
            raise ValueError(
                "param_sharding tables other than 'replicate' are not "
                "supported with use_jax_distributed yet")

        # Same knob as the rllib policy stack (parallel/precision.py).
        self.compute_dtype = precision.resolve_compute_dtype(
            self.config.get("compute_dtype", "auto"))

        self.model = self.model_creator(self.config)
        self.optimizer = self.optimizer_creator(self.config)
        self.loss_fn = self.loss_creator(self.config)

        data = self.data_creator(self.config)
        if isinstance(data, tuple) and len(data) == 2:
            train_data, val_data = data
        else:
            train_data, val_data = data, None
        # Shard rows rank::world_size (DistributedSampler semantics).
        self._n_total = len(np.asarray(train_data[0]))
        self.train_x, self.train_y = [
            np.asarray(a)[self.world_rank::self.world_size]
            for a in train_data]
        self.val = None
        if val_data is not None:
            self.val = tuple(np.asarray(a) for a in val_data)

        rng = jax.random.PRNGKey(self.config.get("seed", 0))
        dummy = self.train_x[:1]
        host_params = self.model.init(rng, jnp.asarray(dummy))
        if self.distributed:
            # Same seed everywhere -> identical replicas; assembled as
            # global replicated arrays over the multi-process mesh.
            from ..parallel import distributed as dist
            self.params = self._put_repl_global(host_params)
            self.opt_state = self._put_repl_global(
                self.optimizer.init(host_params))
            self._param_sh = self._opt_sh = self._repl
        else:
            host_opt = self.optimizer.init(host_params)
            self._param_sh = self.layout.shardings(host_params)
            self._opt_sh = self.layout.shardings(host_opt)
            self.params = jax.device_put(host_params, self._param_sh)
            self.opt_state = jax.device_put(host_opt, self._opt_sh)

        # bf16 compute casts the f32 master params at the loss boundary
        # only; autodiff transposes the cast so grads/optax stay f32.
        cdt = self.compute_dtype

        def local_loss_grad(params, x, y):
            def batch_loss(p):
                if cdt != jnp.float32:
                    p = precision.cast_float_tree(p, cdt)
                pred = self.model.apply(p, x)
                return self.loss_fn(pred, y)
            return jax.value_and_grad(batch_loss)(params)

        def train_step(params, opt_state, x, y):
            loss, grads = local_loss_grad(params, x, y)
            updates, opt_state = self.optimizer.update(
                grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        # Donated params/opt + dp-sharded batch: XLA inserts the gradient
        # all-reduce over the mesh (ICI), replacing NCCL. Params/opt
        # take the layout-resolved shardings (replicated by default;
        # fsdp shards the weight update across the mesh).
        self._train_step = jax.jit(
            train_step, donate_argnums=(0, 1),
            in_shardings=(self._param_sh, self._opt_sh,
                          self._bshard, self._bshard),
            out_shardings=(self._param_sh, self._opt_sh, self._repl))

        def grad_step(params, x, y):
            loss, grads = local_loss_grad(params, x, y)
            return grads, loss

        self._grad_step = jax.jit(
            grad_step,
            in_shardings=(self._param_sh, self._bshard, self._bshard),
            out_shardings=(self._repl, self._repl))

        def eval_step(params, x, y):
            if cdt != jnp.float32:
                params = precision.cast_float_tree(params, cdt)
            pred = self.model.apply(params, x)
            return self.loss_fn(pred, y)

        self._eval_step = jax.jit(
            eval_step,
            in_shardings=(self._param_sh, self._bshard, self._bshard),
            out_shardings=self._repl)
        self._perm_rng = np.random.RandomState(
            self.config.get("seed", 0) + self.world_rank)
        return n_dev

    def _put_repl_global(self, tree):
        """Host tree -> fully-replicated global arrays on the
        multi-process mesh (every process contributes its identical
        copy)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        sh = NamedSharding(self.mesh, PartitionSpec())
        return jax.tree.map(
            lambda a: jax.make_array_from_process_local_data(
                sh, np.asarray(a)), tree)

    # -- local (intra-host) training -------------------------------------
    def _batches(self):
        n = len(self.train_x)
        if self.distributed:
            # Global batch split evenly across processes; the step count
            # derives from the TOTAL length so every rank runs the same
            # number of collective steps (SPMD lockstep — a rank with one
            # extra local batch would deadlock the others).
            per_global = mesh_lib.pad_to_multiple(
                self.batch_size, self.mesh.devices.size)
            per = per_global // self.world_size
            n_min = self._n_total // self.world_size
            idx = self._perm_rng.permutation(n)[:n_min]
            for start in range(0, n_min - per + 1, per):
                sel = idx[start:start + per]
                yield self.train_x[sel], self.train_y[sel]
            return
        per = mesh_lib.pad_to_multiple(
            self.batch_size, self.mesh.devices.size)
        idx = self._perm_rng.permutation(n)
        for start in range(0, n - per + 1, per):
            sel = idx[start:start + per]
            yield self.train_x[sel], self.train_y[sel]

    def train_epoch(self) -> Dict:
        """One pass over the local shard, all-reducing over the local
        mesh (parity: `train` in distributed_pytorch_runner)."""
        losses = []
        t0 = time.time()
        count = 0
        for x, y in self._batches():
            if self.distributed:
                from ..parallel import distributed as dist
                x = dist.process_local_batch(self._bshard, np.asarray(x))
                y = dist.process_local_batch(self._bshard, np.asarray(y))
            else:
                x, y = jnp.asarray(x), jnp.asarray(y)
            self.params, self.opt_state, loss = self._train_step(
                self.params, self.opt_state, x, y)
            if self.distributed:
                # Scalar readback per step: replicated output, and a
                # natural SPMD sync point. Count only this process's
                # rows (x is the GLOBAL array here).
                losses.append(float(loss))
                count += x.shape[0] // self.world_size
            else:
                # Lazy device arrays: keep async dispatch pipelined;
                # one reduction per epoch.
                losses.append(loss)
                count += len(x)
        self.epoch += 1
        mean_loss = float(np.mean([float(l) for l in losses])) \
            if losses else 0.0
        return {"train_loss": mean_loss, "epoch": self.epoch,
                "num_samples": count,
                "time_s": round(time.time() - t0, 3)}

    # -- cross-host gradient exchange ------------------------------------
    def compute_gradients(self, weights) -> tuple:
        """Grads for one minibatch at the given weights (driver-averaged
        synchronous data parallelism across runners)."""
        if weights is not None:
            self.set_weights(weights)
        n = len(self.train_x)
        per = mesh_lib.pad_to_multiple(
            self.batch_size, self.mesh.devices.size)
        sel = self._perm_rng.randint(0, n, size=per)
        grads, loss = self._grad_step(
            self.params, jnp.asarray(self.train_x[sel]),
            jnp.asarray(self.train_y[sel]))
        return jax.tree.map(np.asarray, grads), float(loss)

    def apply_gradients(self, grads):
        updates, self.opt_state = self.optimizer.update(
            jax.tree.map(jnp.asarray, grads), self.opt_state, self.params)
        self.params = optax.apply_updates(self.params, updates)

    # -- evaluation / state ----------------------------------------------
    def validate(self) -> Dict:
        if self.val is None:
            return {}
        x, y = self.val
        if self.distributed:
            import jax
            from ..parallel import distributed as dist
            n_local_dev = len(jax.local_devices())
            n_min = len(x) // self.world_size
            n_keep = n_min - (n_min % max(1, n_local_dev))
            if n_keep == 0:
                return {}
            sel = slice(self.world_rank, None, self.world_size)
            x_loc = np.asarray(x)[sel][:n_keep]
            y_loc = np.asarray(y)[sel][:n_keep]
            loss = float(self._eval_step(
                self.params,
                dist.process_local_batch(self._bshard, x_loc),
                dist.process_local_batch(self._bshard, y_loc)))
            return {"validation_loss": loss}
        # The sharded eval program needs rows to tile the mesh exactly.
        n_keep = len(x) - len(x) % self.mesh.devices.size
        if n_keep == 0:
            return {}
        loss = float(self._eval_step(
            self.params, jnp.asarray(np.asarray(x)[:n_keep]),
            jnp.asarray(np.asarray(y)[:n_keep])))
        return {"validation_loss": loss}

    def get_weights(self):
        return jax.tree.map(np.asarray, self.params)

    def set_weights(self, weights):
        if getattr(self, "distributed", False):
            self.params = self._put_repl_global(weights)
        else:
            self.params = jax.device_put(weights, self._param_sh)

    # -- sharded weight exchange (the cross-replica update sharding) ----
    def get_weights_shard(self, shard_index: int, shard_count: int):
        """One equal byte-range slice of the flattened f32 parameter
        vector (spec_layout.shard_bounds semantics) — the unit the
        sharded averaging step moves, so no process ever gathers the
        full N-replica weight stack."""
        from ray_tpu._private import weight_sync
        from ray_tpu._private.spec_layout import shard_bounds
        vec, _aux = weight_sync.flatten_f32(self.get_weights())
        start, stop = shard_bounds(vec.size, shard_count)[shard_index]
        return vec[start:stop]

    def apply_weights_shard(self, shard_index: int, shard_count: int,
                            shard_vec) -> None:
        """Overwrite one shard slice with the averaged values."""
        from ray_tpu._private import weight_sync
        from ray_tpu._private.spec_layout import shard_bounds
        host = self.get_weights()
        vec, aux = weight_sync.flatten_f32(host)
        start, stop = shard_bounds(vec.size, shard_count)[shard_index]
        vec[start:stop] = np.asarray(shard_vec, np.float32)
        self.set_weights(weight_sync.unflatten_f32(host, vec, aux))

    def get_state(self) -> Dict:
        return {"params": self.get_weights(),
                "opt_state": jax.tree.map(np.asarray, self.opt_state),
                "epoch": self.epoch}

    def set_state(self, state: Dict):
        self.set_weights(state["params"])
        if getattr(self, "distributed", False):
            self.opt_state = self._put_repl_global(state["opt_state"])
        else:
            self.opt_state = jax.device_put(
                jax.tree.map(jnp.asarray, state["opt_state"]),
                self._opt_sh)
        self.epoch = state["epoch"]

    def ping(self):
        return "ok"


class JaxTrainer:
    """Parity: `PyTorchTrainer` (`pytorch_trainer.py:23`).

    num_replicas=0: in-process training over the full device mesh (the
    TPU path). num_replicas>=1: runner actors, one shard each, synchronous
    weight-averaged epochs, elastic recovery on actor death. Runners claim
    no TPU, so the head starts them on CPU JAX (one process drives all of
    a host's chips); `runner_env` overrides their environment.
    """

    def __init__(self,
                 model_creator: Callable,
                 data_creator: Callable,
                 optimizer_creator: Callable,
                 loss_creator: Callable,
                 config: Optional[dict] = None,
                 num_replicas: int = 0,
                 batch_size: int = 64,
                 num_devices_per_replica: int = 0,
                 use_jax_distributed: bool = False,
                 runner_env: Optional[dict] = None,
                 weight_sync_shards: Optional[int] = None):
        self._ctor_args = (model_creator, data_creator, optimizer_creator,
                           loss_creator)
        self.config = dict(config or {})
        mesh_lib.refuse_allreduce_codec(self.config)
        self.batch_size = batch_size
        self.num_replicas = num_replicas
        self.num_devices_per_replica = num_devices_per_replica
        # Sharded synchronous averaging: with S > 1 the flattened f32
        # weight vector averages/broadcasts in S independent slices, so
        # the driver holds one slice-stack at a time instead of every
        # replica's full tree at once (PAPERS: "Automatic Cross-Replica
        # Sharding of Weight Update in Data-Parallel Training").
        if weight_sync_shards is None:
            from ray_tpu._private import config as config_mod
            weight_sync_shards = config_mod.get("RAY_TPU_WEIGHT_SHARDS")
        self.weight_sync_shards = max(1, int(weight_sync_shards))
        # jax.distributed mode: runners form ONE global device world;
        # gradient all-reduce happens inside XLA across processes (DCN)
        # instead of through the object store.
        self.use_jax_distributed = use_jax_distributed
        self.runner_env = dict(runner_env or {})
        if use_jax_distributed and num_replicas <= 0:
            raise ValueError(
                "use_jax_distributed needs num_replicas >= 1 runner "
                "processes (in-process training already spans the local "
                "mesh)")
        if num_replicas <= 0:
            self.local_runner = JaxRunner(
                *self._ctor_args, config=self.config,
                batch_size=batch_size,
                num_devices=num_devices_per_replica)
            self.local_runner.setup(1, 0)
            self.runners: List = []
        else:
            self.local_runner = None
            self._start_runners(num_replicas)

    def _start_runners(self, n: int):
        RemoteRunner = ray_tpu.remote(JaxRunner)
        self.runners = [
            RemoteRunner.options(
                num_cpus=1, env_vars=self.runner_env).remote(
                *self._ctor_args, config=self.config,
                batch_size=self.batch_size,
                num_devices=self.num_devices_per_replica)
            for _ in range(n)]
        coordinator = None
        if self.use_jax_distributed:
            # Coordinator lives in rank 0's process; the port is reserved
            # on this host (single-host clusters / CI; a multi-host
            # deployment passes the rank-0 host address via config).
            from ..parallel import distributed as dist
            coordinator = self.config.get("coordinator_address") \
                or dist.reserve_coordinator_port()
        ray_tpu.get([r.setup.remote(n, i, coordinator=coordinator)
                     for i, r in enumerate(self.runners)])

    # ------------------------------------------------------------------
    def train(self, max_retries: int = 0) -> Dict:
        """One epoch. With actors: each runner trains its shard, then
        weights average (synchronous model averaging per epoch); actor
        death shrinks the world and retries (parity:
        `pytorch_trainer.py:167` train/max_retries)."""
        for attempt in range(max_retries + 1):
            try:
                return self._train_once()
            except RayError:
                if attempt >= max_retries:
                    raise
                logger.warning("runner failure; shrinking world and "
                               "retrying (%d/%d)", attempt + 1,
                               max_retries)
                self._recover()
        raise RuntimeError("unreachable")

    def _train_once(self) -> Dict:
        if self.local_runner is not None:
            return self.local_runner.train_epoch()
        stats = ray_tpu.get([r.train_epoch.remote() for r in self.runners])
        if not self.use_jax_distributed:
            # jax.distributed runners share gradients in-graph; their
            # replicas are identical by construction.
            self._average_weights()
        else:
            # A runner death wedges its peers inside a collective, so
            # recovery cannot pull state from survivors (unlike the
            # object-store mode): snapshot after each good epoch.
            self._last_state = ray_tpu.get(
                self.runners[0].get_state.remote())
        out = {k: float(np.mean([s[k] for s in stats]))
               for k in ("train_loss", "time_s")}
        out["epoch"] = int(max(s["epoch"] for s in stats))
        out["num_samples"] = int(sum(s["num_samples"] for s in stats))
        return out

    def _average_weights(self):
        if self.weight_sync_shards > 1 and len(self.runners) > 1:
            self._average_weights_sharded()
            return
        all_w = ray_tpu.get([r.get_weights.remote() for r in self.runners])
        mean_w = jax.tree.map(
            lambda *xs: np.mean(np.stack(xs), axis=0), *all_w)
        ref = ray_tpu.put(mean_w)
        ray_tpu.get([r.set_weights.remote(ref) for r in self.runners])

    def _average_weights_sharded(self):
        """Per-shard synchronous averaging: shard i gathers, averages,
        and broadcasts independently — peak driver residency is one
        slice-stack (total/S x replicas) instead of the whole tree from
        every replica, and every broadcast object is 1/S of the blob."""
        from ray_tpu._private import metrics
        S = self.weight_sync_shards
        for i in range(S):
            slices = ray_tpu.get(
                [r.get_weights_shard.remote(i, S) for r in self.runners])
            mean_slice = np.mean(np.stack(slices), axis=0)
            metrics.inc("weight_sync_bytes", int(mean_slice.nbytes))
            ref = ray_tpu.put(mean_slice)
            ray_tpu.get([r.apply_weights_shard.remote(i, S, ref)
                         for r in self.runners])

    def _recover(self):
        if self.use_jax_distributed:
            # Survivors are wedged in a cross-process collective waiting
            # on the dead peer — they can neither answer pings nor hand
            # over state. Kill the whole fleet, rebuild one size smaller,
            # restore from the last post-epoch snapshot.
            n = max(1, len(self.runners) - 1)
            for r in self.runners:
                try:
                    ray_tpu.kill(r)
                except Exception:
                    pass
            self._start_runners(n)
            state = getattr(self, "_last_state", None)
            if state is not None:
                ref = ray_tpu.put(state)
                ray_tpu.get([r.set_state.remote(ref)
                             for r in self.runners])
            else:
                logger.warning(
                    "no snapshot yet; distributed fleet restarted from "
                    "initial weights")
            return
        alive = []
        for r in self.runners:
            try:
                ray_tpu.get(r.ping.remote(), timeout=10)
                alive.append(r)
            except Exception:
                # Dead runners are expected here — this probe decides
                # which survived — but note each exclusion for the
                # post-mortem.
                logger.info("runner %r unresponsive; excluding from "
                            "recovery", r)
        if not alive:
            raise RuntimeError("all runners died")
        state = ray_tpu.get(alive[0].get_state.remote())
        for r in self.runners:
            try:
                ray_tpu.kill(r)
            except Exception:
                pass
        # Shrunk world: re-create the fleet at the surviving size
        # (reference shrinks then re-grows when resources return).
        self._start_runners(len(alive))
        ref = ray_tpu.put(state)
        ray_tpu.get([r.set_state.remote(ref) for r in self.runners])

    # ------------------------------------------------------------------
    def validate(self) -> Dict:
        if self.local_runner is not None:
            return self.local_runner.validate()
        stats = ray_tpu.get([r.validate.remote() for r in self.runners])
        stats = [s for s in stats if s]
        if not stats:
            return {}
        return {"validation_loss": float(
            np.mean([s["validation_loss"] for s in stats]))}

    def get_model_weights(self):
        if self.local_runner is not None:
            return self.local_runner.get_weights()
        return ray_tpu.get(self.runners[0].get_weights.remote())

    def save(self, path: str) -> str:
        import pickle
        state = self.local_runner.get_state() if self.local_runner \
            else ray_tpu.get(self.runners[0].get_state.remote())
        with open(path, "wb") as f:
            pickle.dump(state, f)
        return path

    def restore(self, path: str):
        import pickle
        with open(path, "rb") as f:
            state = pickle.load(f)
        if self.local_runner is not None:
            self.local_runner.set_state(state)
        else:
            ref = ray_tpu.put(state)
            ray_tpu.get([r.set_state.remote(ref) for r in self.runners])

    def shutdown(self):
        for r in self.runners:
            try:
                ray_tpu.kill(r)
            except Exception:
                pass
        self.runners = []
