"""Run one cell of the benchmark once, as a fresh process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This file holds no cell, configuration, driver or metric: it finds them by
name (`benchmark/README.md`). The cell is `workloads/<cell>.json`; that
names its `config` (`configs/<config>.json`) and its `driver`
(`drivers/<driver>.py`); each per-layer metric `<base>.<suffix>` is read by
`layer_metrics/<base>.py`; units come from `BENCHMARK.json`.

The process owns the chip. Without a TPU (or with fewer chips than the
cell asks for) it exits non-zero and prints no result. `--rehearse` is for
the builder: the same files at the cell's tiny rehearsal size on the CPU,
no metric values printed.

The last line of stdout is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device`, and `breakdown` in a traced run. Lines
before it that start with `#` are for people.
"""

import time

T_START = time.perf_counter()  # process start, to within the interpreter's

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)  # the system under test
sys.path.insert(0, HERE)  # `lib`, shared by drivers and readers

NO_ACCELERATOR = 3


def note(key: str, value) -> None:
    print(f"# {key}: {json.dumps(value, default=str)}", flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class CompileCounter:
    """Counts the programs this process lowers. Every program that was
    not ready is lowered once, whether XLA then compiles it or loads it
    from the persistent cache, so either shows."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1


def device_memory_peak(device) -> int:
    """Peak HBM the process held on one chip: the allocator's high-water
    mark (arrays) plus what the runtime reserved for loaded programs, which
    is where XLA's temporaries live and which the allocator's mark leaves
    out (an Anakin program with 3.7 GB tensors inside read 0.22 GB)."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def run_window(session, seconds: float, annotate, compiles,
               min_iterations: int = 1) -> dict:
    """Whole iterations from a boundary until `seconds` have passed, and
    `min_iterations` of them at the least."""
    attempted = failed = 0
    reasons = []
    steps0, lowered0 = session.steps_trained(), compiles.count
    t0 = time.perf_counter()
    while True:
        with annotate("bench.train"):
            out = session.iterate()
        attempted += 1
        if not out["ok"]:
            failed += 1
            reasons.append(out["why"])
        now = time.perf_counter()
        if now - t0 >= seconds and attempted >= min_iterations:
            break
    return {"attempted": attempted, "failed": failed, "reasons": reasons[:3],
            "seconds": now - t0, "steps": session.steps_trained() - steps0,
            "lowered": compiles.count - lowered0}


def traced_slice(session, workload, tag: str, seconds: float, annotate,
                 compiles):
    """A slice of whole iterations under the profiler, and its reduction
    (None where the trace holds no device op). Starting and writing the
    trace fall outside the slice's own clock."""
    import jax
    from lib import trace as trace_lib
    keep = os.environ.get("BENCH_TRACE_DIR")
    trace_dir = os.path.join(keep or os.path.join(ROOT, ".bench_traces"), tag)
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with annotate("bench.slice"):
        window = run_window(session, seconds, annotate, compiles)
    jax.profiler.stop_trace()
    path = trace_lib.find_xplane(trace_dir)
    planes = trace_lib.load(path) if path else {}
    note("trace_file", path)
    if keep:
        for line in trace_lib.describe(planes):
            note("trace_line", line)
    else:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return window, trace_lib.reduce(planes)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    workload = load_json("workloads", args.workload + ".json")
    config = load_json("configs", workload["config"] + ".json")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    units = {m["name"]: m["unit"]
             for m in manifest["end_to_end"] + manifest["per_layer"]}
    chips = int(workload["chips"])

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")
    import jax
    # Every program goes to the persistent cache, not only those that took
    # a second to compile: a warm run then loads the dozens of small ones.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and (platform != "tpu" or len(devices) < chips):
        print(f"benchmark: the cell needs {chips} TPU chip(s); jax gave "
              f"{len(devices)} x {platform}", file=sys.stderr)
        return NO_ACCELERATOR
    used = devices[:chips]
    device_kind = used[0].device_kind
    t_chip = time.perf_counter()

    compiles = CompileCounter()
    driver = load_module("drivers", workload["driver"])
    session = driver.open_session(config, workload, args.seed, chips,
                                  args.rehearse)
    try:
        t_built = time.perf_counter()
        session.warm_up()
        t_warm = time.perf_counter()

        readers = {name: load_module("layer_metrics", name.split(".")[0])
                   for name in workload["per_layer"]} if args.trace else {}
        # What a per-layer reader may look at.
        ctx = SimpleNamespace(
            session=session, workload=workload, config=config, chips=chips,
            device_kind=device_kind, trace=None, slice_steps=0, window_s=0.0)
        states = {name: r.begin(ctx) if hasattr(r, "begin") else None
                  for name, r in readers.items()}

        # The traced slice comes after the counters' window, so that
        # starting and writing the trace fall into neither.
        slice_s = float(workload.get("trace_slice_s", 3)) if args.trace else 0
        annotate = jax.profiler.TraceAnnotation
        setup_seconds = time.perf_counter() - T_START
        # A cell whose iteration's time follows the episodes it happened to
        # roll out may ask for a floor of whole iterations under its
        # end-to-end window (`window.min_iterations`); a traced run's
        # windows are as long as every cell's.
        floor = 1 if args.trace else int(
            (workload.get("window") or {}).get("min_iterations", 1))
        windows = [run_window(session, max(args.seconds - slice_s, 1e-3),
                              annotate, compiles, floor)]
        ctx.window_s = windows[0]["seconds"]
        values = {name: r.read(ctx, states[name])
                  for name, r in readers.items() if r.SOURCE != "device_trace"}
        if args.trace:
            slice_w, ctx.trace = traced_slice(
                session, workload, f"{args.workload}.{args.seed}", slice_s,
                annotate, compiles)
            windows.append(slice_w)
            ctx.slice_steps = slice_w["steps"]
            values.update({
                name: r.read(ctx, states[name])
                for name, r in readers.items() if r.SOURCE == "device_trace"})

        outputs = session.check_outputs(args.seed)
        report = session.device_report()
        peak = max(device_memory_peak(d) for d in used)
    finally:
        session.close()

    attempted = sum(w["attempted"] for w in windows)
    failed = sum(w["failed"] for w in windows)
    compiled_in_window = sum(w["lowered"] for w in windows)
    on_device = (report.get("platform") == platform
                 and report.get("count") == chips
                 and report.get("params_on") == chips
                 and report.get("batch_on") == chips)
    correct = bool(on_device and failed == 0 and compiled_in_window == 0
                   and outputs["ok"] and (platform == "tpu" or args.rehearse))

    quantities = {
        "steps_per_second_per_chip":
            windows[0]["steps"] / windows[0]["seconds"] / chips,
        "setup_seconds": setup_seconds,
    }
    if args.trace:
        metrics = {k: v for k, v in values.items() if v is not None}
    else:
        metrics = {name: quantities[q]
                   for name, q in workload["end_to_end"].items()}
    device = {"platform": platform, "kind": device_kind, "count": chips,
              "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace and ctx.trace:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}

    note("set_up_split", {"to_chip": t_chip - T_START,
                          "to_build": t_built - t_chip,
                          "warm_up": t_warm - t_built,
                          "total": setup_seconds})
    note("windows", windows)
    note("quantities", quantities)
    note("compiled_in_window", compiled_in_window)
    note("outputs_vs_reference", outputs)
    note("device_report", report)
    note("memory_stats", [d.memory_stats() for d in used])
    if ctx.trace:
        note("trace", {k: ctx.trace[k] for k in (
            "window_s", "busy_s", "busy_s_per_chip", "longest_gap_s")})
    if args.rehearse:
        # A CPU run gives no device number: names and units only.
        metrics = {name: None for name in metrics}
    result["metrics"] = {
        name: {"value": value, "unit": units[name]} if value is not None
        else {"unit": units[name]} for name, value in metrics.items()}
    result["device"] = device
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
