"""ray_tpu: a TPU-native distributed execution and ML training framework.

Capability parity with the Ray 0.9 reference (tasks, actors, distributed
object store, cluster scheduling, RL/tuning/data-parallel training
libraries), re-architected TPU-first: JAX/XLA for all device compute, XLA
collectives over ICI for gradient exchange, and a direct-call host runtime.

Public surface (parity: `python/ray/__init__.py` + `worker.py`):

    import ray_tpu

    ray_tpu.init()

    @ray_tpu.remote
    def f(x): return x * 2

    ray_tpu.get(f.remote(2))  # -> 4

    @ray_tpu.remote
    class Counter:
        def __init__(self): self.n = 0
        def inc(self): self.n += 1; return self.n

    c = Counter.remote()
    ray_tpu.get(c.inc.remote())  # -> 1
"""

from __future__ import annotations

import inspect as _inspect
import os as _os
from typing import Optional as _Optional

from . import exceptions
from ._private import node as _node
from ._private import worker_state as _ws
from ._private.object_ref import ObjectRef
from ._private.ids import ActorID, JobID, ObjectID, TaskID
from .actor import ActorClass, ActorHandle, exit_actor, get_actor, method
from .remote_function import RemoteFunction
from .exceptions import (ActorDiedError, ActorUnavailableError,
                         GetTimeoutError, ObjectLostError,
                         RayActorError, RayError, RayTaskError, TaskError,
                         WorkerCrashedError)

__version__ = "0.1.0"

_LOCAL_RUNTIME = None
_CHAOS_ENV_SET = False


def init(num_cpus: _Optional[float] = None,
         num_tpus: _Optional[float] = None,
         resources: _Optional[dict] = None,
         local_mode: bool = False,
         num_initial_workers: int = 0,
         worker_env: _Optional[dict] = None,
         address: _Optional[str] = None,
         chaos: _Optional[str] = None):
    """Start the runtime (parity: `ray.init`, `python/ray/worker.py:525`).

    With `address="tcp://host:port"` the driver attaches to an existing
    head started by `python -m ray_tpu.scripts start --head` (parity:
    `ray.init(redis_address=...)`); shutdown then only detaches.
    In a worker process this is a no-op (the worker is already connected).

    `chaos` arms the deterministic fault-injection plane for the whole
    session (equivalent to exporting ``RAY_TPU_CHAOS=<spec>`` before
    start; spawned workers and node agents inherit the schedule). See
    README "Fault tolerance & chaos testing" for the spec grammar.
    """
    global _LOCAL_RUNTIME, _CHAOS_ENV_SET
    if _ws.mode() == _ws.WORKER_MODE:
        return None
    if _ws.get_runtime_or_none() is not None:
        raise RuntimeError("ray_tpu.init() called twice; call "
                           "ray_tpu.shutdown() first")
    if chaos:
        from ._private import chaos as _chaos
        from ._private import config as _config
        _chaos.parse_spec(chaos)  # fail fast on a bad spec
        _config.set_override("RAY_TPU_CHAOS", chaos)
        _CHAOS_ENV_SET = True
    if address is None:
        # `ray_tpu.scripts exec` injects the cluster address (parity:
        # `ray exec` / RAY_ADDRESS).
        address = _os.environ.get("RAY_TPU_ADDRESS") or None
    if local_mode:
        from ._private.local_mode import LocalRuntime
        _LOCAL_RUNTIME = LocalRuntime()
        _ws.set_runtime(_LOCAL_RUNTIME, _ws.LOCAL_MODE)
        return _LOCAL_RUNTIME
    rt = _node.init(resources=resources, num_cpus=num_cpus,
                    num_tpus=num_tpus,
                    num_initial_workers=num_initial_workers,
                    worker_env=worker_env, address=address)
    from ._private import config as _config
    if _config.get("RAY_TPU_FLIGHT_RECORDER"):
        _install_flight_recorder_hook()
    return rt


_FLIGHT_HOOK_INSTALLED = False


def _install_flight_recorder_hook():
    """Chain a sys.excepthook that writes the flight-recorder bundle
    before a driver-fatal error kills the process — the postmortem of
    record when nobody was watching the dashboard. Fires at most once
    per process; a failure to dump never masks the original error."""
    global _FLIGHT_HOOK_INSTALLED
    if _FLIGHT_HOOK_INSTALLED:
        return
    _FLIGHT_HOOK_INSTALLED = True
    import sys as _sys
    prev_hook = _sys.excepthook

    def _hook(exc_type, exc, tb):
        try:
            path = debug_dump()
            _sys.stderr.write(
                f"ray_tpu: flight recorder dump written to {path} "
                f"(pretty-print: python -m ray_tpu.scripts dump "
                f"{path})\n")
        except Exception:
            pass
        prev_hook(exc_type, exc, tb)

    _sys.excepthook = _hook


def shutdown():
    """Stop the runtime and clean up the session (parity: `ray.shutdown`)."""
    global _LOCAL_RUNTIME, _CHAOS_ENV_SET
    if _CHAOS_ENV_SET:
        # A schedule armed via init(chaos=...) dies with the session.
        from ._private import chaos as _chaos
        from ._private import config as _config
        _config.clear_override("RAY_TPU_CHAOS")
        _CHAOS_ENV_SET = False
        _chaos.uninstall()
    if _LOCAL_RUNTIME is not None:
        _LOCAL_RUNTIME.shutdown()
        _LOCAL_RUNTIME = None
        _ws.clear()
        return
    _node.shutdown()


def is_initialized() -> bool:
    return _ws.get_runtime_or_none() is not None


def put(value) -> ObjectRef:
    """Store a value in the object store (parity: `ray.put`,
    `worker.py:1505`)."""
    return _ws.get_runtime().put(value)


def get(refs, timeout: _Optional[float] = None):
    """Fetch object values, blocking until available (parity: `ray.get`,
    `worker.py:1440`). Accepts one ref or a list."""
    if isinstance(refs, list):
        bad = [r for r in refs if not isinstance(r, ObjectRef)]
        if bad:
            raise TypeError(f"ray_tpu.get expects ObjectRefs, got {type(bad[0])}")
    elif not isinstance(refs, ObjectRef):
        raise TypeError(f"ray_tpu.get expects an ObjectRef or a list of them, "
                        f"got {type(refs)}")
    return _ws.get_runtime().get(refs, timeout=timeout)


def wait(refs, num_returns: int = 1, timeout: _Optional[float] = None):
    """Return (ready, not_ready) (parity: `ray.wait`, `worker.py:1540`)."""
    if isinstance(refs, ObjectRef):
        refs = [refs]
    return _ws.get_runtime().wait(refs, num_returns=num_returns,
                                  timeout=timeout)


def kill(actor: ActorHandle, no_restart: bool = True):
    """Forcefully terminate an actor (parity: `ray.kill`)."""
    _ws.get_runtime().kill_actor(actor._actor_id, no_restart=no_restart)


def free(refs):
    """Release object values from the store (explicit eviction; parity:
    `ray.experimental.free`)."""
    if isinstance(refs, ObjectRef):
        refs = [refs]
    _ws.get_runtime().free(refs)


def remote(*args, **kwargs):
    """The `@ray_tpu.remote` decorator for functions and classes (parity:
    `ray.remote`, `worker.py:1697`).

    Supported options: num_returns, num_cpus, resources, max_retries
    (functions); num_cpus, num_tpus, resources, max_restarts,
    max_concurrency (classes). Only an actor can claim a TPU: a chip
    belongs to one process, and tasks share CPU pool workers.
    """
    _FN_OPTS = {"num_returns", "num_cpus", "resources", "max_retries"}
    _CLS_OPTS = {"num_cpus", "num_tpus", "resources", "max_restarts",
                 "max_concurrency"}

    def make(target):
        allowed = _CLS_OPTS if _inspect.isclass(target) else _FN_OPTS
        unknown = set(kwargs) - allowed
        if unknown:
            kind = "class" if _inspect.isclass(target) else "function"
            raise TypeError(
                f"unknown @ray_tpu.remote option(s) for a {kind}: "
                f"{sorted(unknown)}; allowed: {sorted(allowed)}")
        if _inspect.isclass(target):
            return ActorClass(
                target,
                num_cpus=kwargs.get("num_cpus"),
                num_tpus=kwargs.get("num_tpus"),
                resources=kwargs.get("resources"),
                max_restarts=kwargs.get("max_restarts", 0),
                max_concurrency=kwargs.get("max_concurrency"))
        return RemoteFunction(
            target,
            num_returns=kwargs.get("num_returns", 1),
            num_cpus=kwargs.get("num_cpus"),
            resources=kwargs.get("resources"),
            max_retries=kwargs.get("max_retries", 3))

    if len(args) == 1 and callable(args[0]) and not kwargs:
        return make(args[0])
    if args:
        raise TypeError("@ray_tpu.remote takes keyword options only")
    return make


def profile(event_name=None, extra_data: _Optional[dict] = None, *,
            duration_s: _Optional[float] = None, target: str = "all",
            hz: _Optional[float] = None):
    """Two instruments behind one name.

    With a string, a user-level profiling span recorded into the
    cluster timeline (parity: `ray.profile`,
    `python/ray/profiling.py:17`):

        with ray_tpu.profile("preprocess"):
            ...

    With a number (or `duration_s=`), a coordinated cluster-wide
    capture: the head fans a bounded window to every selected process
    (head, drivers, node agents, workers); each runs a stack-sampling
    profiler at RAY_TPU_PROFILE_HZ (device-owning processes also run a
    `jax.profiler` trace), and the merged bundle comes back with
    flamegraph-ready folded stacks per process plus Chrome-trace
    events aligned with the span timeline:

        bundle = ray_tpu.profile(2.0)                  # whole cluster
        bundle = ray_tpu.profile(2.0, target="learner")  # device procs

    `target`: "all" | "head" | "workers" | "drivers" | "nodes" |
    "learner" | an explicit process addr. Same plane as
    `python -m ray_tpu.scripts profile --duration 2`.
    """
    if duration_s is None and isinstance(event_name, (int, float)) \
            and not isinstance(event_name, bool):
        duration_s, event_name = float(event_name), None
    if duration_s is not None:
        if event_name is not None:
            raise TypeError("ray_tpu.profile: pass either a span name "
                            "or a capture duration, not both")
        return _ws.get_runtime().profile_capture(
            duration_s, target=target, hz=hz)
    rt = _ws.get_runtime()
    return rt.profiler.span("user", event_name, extra_data)


def timeline(filename: _Optional[str] = None):
    """Cluster-wide Chrome trace of task/actor/user spans (parity:
    `ray.timeline` / `GlobalState.chrome_tracing_dump`, state.py:672).
    Returns the trace event list, or writes JSON to `filename` for
    chrome://tracing / Perfetto. Submit and exec spans carry flow
    events (`ph:"s"/"f"` keyed by task id) so viewers draw causality
    arrows across processes/nodes; a metadata record reports how many
    spans were dropped to buffer bounds."""
    from ._private import profiling as _prof
    dump = _ws.get_runtime().profile_dump()
    if filename is not None:
        return _prof.dump_chrome_trace(dump["events"], filename,
                                       dropped=dump["dropped"])
    return _prof.chrome_trace(dump["events"], dropped=dump["dropped"])


def tasks(state: _Optional[str] = None, name: _Optional[str] = None,
          limit: int = 100):
    """Task-lifecycle records from the head's bounded event ring
    (parity: the reference state API's `ray list tasks`). Each record
    carries the task's current state (SUBMITTED/QUEUED/LEASED/RUNNING/
    FINISHED/FAILED), per-state durations, node, worker pid, submitting
    caller, parent task, and the error for failed tasks."""
    return _ws.get_runtime().list_tasks(state=state, name=name,
                                        limit=limit)


def task_summary():
    """Per-state task counts grouped by function/method name (parity:
    `ray summary tasks`). Also shown by `ray_tpu stat --tasks` and the
    dashboard's state-summary row."""
    return _ws.get_runtime().task_summary()


def xla_profile(logdir: str):
    """Capture THIS process's device-side XLA trace (compiled program
    execution, HBM transfers, fusion timing) into a TensorBoard/
    Perfetto-loadable profile directory — the device-level complement
    to `timeline()`'s host-span view (SURVEY.md §5.1: the runtime
    timeline + XLA profiler integration). Run it around the hot loop
    in the process that owns the device (the learner):

        with ray_tpu.xla_profile("/tmp/prof"):
            trainer.train()

    View with `tensorboard --logdir /tmp/prof` (profile plugin) or
    Perfetto on the generated .trace files.

    Raises RuntimeError when THIS process has no XLA device to trace —
    a driver steering remote learners holds no device; capture those
    processes with `ray_tpu.profile(duration_s, target="learner")`
    (or `scripts profile --target learner`), which runs the same
    jax.profiler window inside each device-owning process.
    """
    try:
        import jax
    except ImportError as e:
        raise RuntimeError(
            "ray_tpu.xla_profile requires jax in the calling process; "
            "to capture remote device-owning processes use "
            "ray_tpu.profile(duration_s, target='learner')") from e
    try:
        devices = jax.local_devices()
    except Exception:
        devices = []
    if not devices:
        raise RuntimeError(
            "ray_tpu.xla_profile: no XLA device is attached to this "
            "process. xla_profile() only traces the CALLING process; "
            "to capture the learner/worker processes that do own "
            "devices, use ray_tpu.profile(duration_s, "
            "target='learner') or `python -m ray_tpu.scripts profile "
            "--target learner`.")
    return jax.profiler.trace(logdir)


def cluster_resources() -> dict:
    return _ws.get_runtime().cluster_info()["total_resources"]


def available_resources() -> dict:
    return _ws.get_runtime().cluster_info()["available_resources"]


def cluster_info() -> dict:
    return _ws.get_runtime().cluster_info()


def cluster_metrics() -> dict:
    """Cluster-aggregated metric counters/gauges/histograms (parity:
    the reference's Prometheus metrics plane, `src/ray/stats/`). The
    aggregate carries `quantiles` (p50/p95/p99 per histogram) and
    `rates` (trailing-window counter rates from the head's rate ring).
    Also exposed via `ray_tpu stat --metrics` / `--rates` and, when
    RAY_TPU_METRICS_PORT is set, as Prometheus text on
    http://127.0.0.1:<port>/metrics."""
    return _ws.get_runtime().cluster_metrics()


def cluster_rates() -> dict:
    """Trailing-window per-second rates of every cluster counter
    (tasks/s, wire bytes/s, weight syncs/s, ...), computed from the
    head's bounded rate ring of periodic counter snapshots — live
    activity instead of lifetime totals. Window and cadence are the
    RAY_TPU_RATE_WINDOW_S / RAY_TPU_RATE_RING_INTERVAL_S knobs."""
    return _ws.get_runtime().cluster_rates()


def debug_dump(path: _Optional[str] = None) -> str:
    """Flight recorder: write one postmortem JSON bundling the task-
    ring tail, the metrics + histogram aggregate, recent profiling
    spans, and per-node health. Returns the written path (default:
    RAY_TPU_FLIGHT_RECORDER_PATH or <session>/logs/flight_recorder
    .json). Installed automatically on driver-fatal errors when
    RAY_TPU_FLIGHT_RECORDER is on; pretty-print with
    `python -m ray_tpu.scripts dump <path>`."""
    return _ws.get_runtime().debug_dump(path)


__all__ = [
    "ActorClass", "ActorDiedError", "ActorHandle",
    "ActorUnavailableError", "GetTimeoutError",
    "ObjectLostError", "ObjectRef", "RayActorError", "RayError",
    "RayTaskError", "TaskError", "WorkerCrashedError", "available_resources",
    "cluster_info", "cluster_metrics", "cluster_rates",
    "cluster_resources", "debug_dump", "exceptions",
    "exit_actor", "free",
    "get", "get_actor", "init", "is_initialized", "kill", "method",
    "profile", "put", "remote", "shutdown", "task_summary", "tasks",
    "timeline", "wait", "xla_profile",
]
