"""Session/node bring-up: the `ray_tpu.init()` backend.

Parity: `python/ray/node.py` — the process supervisor that creates the
session directory, starts node services, and connects the driver. Our head
services (scheduler + GCS + monitor) run as threads in the driver process;
worker processes are spawned on demand by the head (`head.py`).
"""

from __future__ import annotations

import atexit
import datetime
import glob
import os
import shutil
import tempfile
import threading
from typing import Dict, Optional

from .head import HeadServer
from .runtime import Runtime
from . import worker_state

_lock = threading.Lock()
_node: Optional["Node"] = None


def default_resources() -> Dict[str, float]:
    ncpu = os.cpu_count() or 1
    # Scheduling here gates *process concurrency*, not raw FLOPs; workers are
    # mostly I/O- or device-bound, so allow a sane minimum of parallelism
    # even on tiny CI hosts.
    return {"CPU": float(max(ncpu, 4))}


def detect_tpus() -> float:
    """Count this host's TPU chips without opening them.

    A chip belongs to the first process that initialises a jax backend on
    it, and the driver is rarely the process that should (under `rllib
    train` the trial actor is), so the count comes from the accelerator
    device files — `/dev/accel<N>` or one `/dev/vfio/<N>` group per chip,
    depending on the TPU VM image — never from `jax.devices()`.
    """
    return float(len(glob.glob("/dev/accel[0-9]*"))
                 + len(glob.glob("/dev/vfio/[0-9]*")))


class Node:
    def __init__(self, resources: Dict[str, float], num_initial_workers: int,
                 session_root: Optional[str] = None,
                 worker_env: Optional[dict] = None,
                 enable_tcp: bool = False):
        ts = datetime.datetime.now().strftime("%Y%m%d-%H%M%S")
        self.session_name = f"{ts}-{os.getpid()}-{os.urandom(2).hex()}"
        from .debug import install_signal_dump
        install_signal_dump()
        # Note: deliberately NOT "<tmp>/ray_tpu" — a directory named like the
        # package next to a user's cwd would shadow the real package as a
        # namespace package.
        root = session_root or os.path.join(tempfile.gettempdir(),
                                            "ray-tpu-sessions")
        self.session_dir = os.path.join(root, f"session_{self.session_name}")
        os.makedirs(self.session_dir, exist_ok=True)
        self.head = HeadServer(self.session_dir, self.session_name, resources,
                               worker_env=worker_env, enable_tcp=enable_tcp)
        if num_initial_workers > 0:
            self.head.start_pool_workers(num_initial_workers)
        # In a multi-node (TCP) session the driver dials the head over TCP
        # so its own server binds TCP too — workers on other nodes must be
        # able to push results back to the driver.
        head_addr = self.head.tcp_addr if enable_tcp else self.head.sock_path
        self.runtime = Runtime(self.session_dir, self.session_name,
                               head_addr, role="driver")

    def shutdown(self):
        try:
            self.runtime.shutdown()
        finally:
            self.head.shutdown()
            self.runtime.shm.cleanup_session()
            shutil.rmtree(self.session_dir, ignore_errors=True)


class AttachedSession:
    """A driver attached to an EXISTING head over TCP (parity: `ray.init
    (redis_address=...)` joining a `ray start`ed cluster). Shutdown only
    detaches — the cluster outlives the driver."""

    def __init__(self, address: str):
        from . import protocol
        probe = protocol.connect(address, f"probe-{os.getpid()}",
                                 lambda c, m: None,
                                 hello_extra={"role": "probe"})
        info = probe.request({"kind": "session_info"}, timeout=30)
        probe.close()
        self.session_name = info["session_name"]
        self.session_dir = info["session_dir"]
        self.head = None
        self.runtime = Runtime(self.session_dir, self.session_name,
                               address, role="driver")

    def shutdown(self):
        self.runtime.shutdown()


def init(resources: Optional[Dict[str, float]] = None,
         num_cpus: Optional[float] = None,
         num_tpus: Optional[float] = None,
         num_initial_workers: int = 0,
         worker_env: Optional[dict] = None,
         enable_tcp: bool = False,
         address: Optional[str] = None):
    global _node
    with _lock:
        if _node is not None:
            raise RuntimeError("ray_tpu.init() called twice; call "
                               "ray_tpu.shutdown() first")
        if address is not None:
            session = AttachedSession(address)
            _node = session
            worker_state.set_runtime(session.runtime,
                                     worker_state.SCRIPT_MODE)
            atexit.register(_atexit_shutdown)
            return session
        res = default_resources()
        if num_cpus is not None:
            res["CPU"] = float(num_cpus)
        tpus = num_tpus if num_tpus is not None else detect_tpus()
        if tpus:
            res["TPU"] = float(tpus)
        if resources:
            res.update({k: float(v) for k, v in resources.items()})
        node = Node(res, num_initial_workers, worker_env=worker_env,
                    enable_tcp=enable_tcp)
        _node = node
        worker_state.set_runtime(node.runtime, worker_state.SCRIPT_MODE)
        atexit.register(_atexit_shutdown)
        return node


def _atexit_shutdown():
    try:
        shutdown()
    except Exception:
        pass


def shutdown():
    global _node
    with _lock:
        node = _node
        _node = None
    worker_state.clear()
    if node is not None:
        node.shutdown()


def is_initialized() -> bool:
    return _node is not None or worker_state.get_runtime_or_none() is not None


def current_node() -> Optional[Node]:
    return _node
