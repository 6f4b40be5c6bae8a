"""Multi-process (DCN) device runtime: the `jax.distributed` bootstrap.

The reference's cross-host gradient plane is torch.distributed NCCL/gloo
(`python/ray/experimental/sgd/pytorch/pytorch_trainer.py:90`,
`distributed_pytorch_runner.py:47` `init_process_group`). The TPU-native
equivalent (SURVEY.md §5.8) is a `jax.distributed` world: every
participating process joins one global runtime, `jax.devices()` spans
ALL hosts' chips, and a single jitted program with sharded inputs runs
SPMD across the pod — XLA inserting cross-host collectives over ICI/DCN
exactly as it inserts them over a local mesh.

Rules this module encodes (learned the hard way on this platform):
- Backend-selection env (JAX_PLATFORMS / XLA_FLAGS) must be set before
  the PROCESS starts — the runtime's worker spawn path does that via
  per-actor env_vars; setting os.environ after interpreter start is too
  late.
- `initialize()` must run before anything touches a jax backend in the
  process. Worker processes never import jax during boot, so a runner
  actor's ctor is a safe place.
- CPU backends federate through gloo (`jax_cpu_collectives_implementation`)
  — which is also what makes multi-host semantics testable on CI's
  virtual-device mesh (the fake-topology trick of SURVEY §4.2, extended
  across processes).
"""

from __future__ import annotations

import logging
import socket
from typing import Optional, Sequence

logger = logging.getLogger(__name__)


def reserve_coordinator_port(host: str = "127.0.0.1") -> str:
    """Pick a free port for the jax.distributed coordinator (rank 0
    binds it during `initialize`). Small bind-then-release race window,
    same trade-off the reference makes for its service ports."""
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind((host, 0))
    port = s.getsockname()[1]
    s.close()
    return f"{host}:{port}"


def initialize(coordinator_address: str, num_processes: int,
               process_id: int,
               local_device_ids: Optional[Sequence[int]] = None) -> None:
    """Join this process to a jax.distributed world.

    Must run before the first backend use in this process. On CPU
    backends the gloo collectives implementation is enabled so the
    global mesh actually federates (without it each process silently
    keeps a 1-process view).
    """
    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    kwargs = {}
    if local_device_ids is not None:
        kwargs["local_device_ids"] = list(local_device_ids)
    jax.distributed.initialize(
        coordinator_address, num_processes=num_processes,
        process_id=process_id, **kwargs)
    logger.info(
        "jax.distributed world joined: rank %d/%d, coordinator %s",
        process_id, num_processes, coordinator_address)


def shutdown() -> None:
    import jax
    try:
        jax.distributed.shutdown()
    except Exception:
        pass


def global_mesh(axis_name: str = "dp"):
    """A 1-D mesh over every device in the distributed world (all
    processes). Call after `initialize`."""
    from . import mesh as mesh_lib
    return mesh_lib.make_mesh(axis_names=(axis_name,))


def process_local_batch(sharding, local_array):
    """Assemble a global batch-sharded array from this process's shard
    (each process contributes rows for its own devices)."""
    import jax
    return jax.make_array_from_process_local_data(sharding, local_array)
