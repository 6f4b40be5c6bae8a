"""The `ray` CLI equivalent.

Parity: `python/ray/scripts/scripts.py` —

    python -m ray_tpu.scripts start --head [--num-cpus N] [--num-tpus N]
    python -m ray_tpu.scripts start --address tcp://h:p [--num-cpus N]
    python -m ray_tpu.scripts stop
    python -m ray_tpu.scripts stat --address tcp://h:p
    python -m ray_tpu.scripts memory --address tcp://h:p
    python -m ray_tpu.scripts timeline --address tcp://h:p [--out f.json]

`start --head` boots a standalone head (scheduler + GCS + node0 worker
pool) serving TCP and blocks; drivers attach with
`ray_tpu.init(address=...)` (reference: `ray start --head` +
`ray.init(redis_address=...)`, scripts.py:234). `start --address` joins
as an additional node (a NodeAgent; reference: `ray start
--redis-address`). `stop` kills every process this CLI started on this
machine (reference: `ray stop`, scripts.py:426).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import signal
import sys
import tempfile
import time

PID_DIR = os.path.join(tempfile.gettempdir(), "ray_tpu_cli")
ADDRESS_FILE = os.path.join(PID_DIR, "head_address")


def _record_pid(kind: str):
    os.makedirs(PID_DIR, exist_ok=True)
    with open(os.path.join(PID_DIR, f"{kind}-{os.getpid()}.pid"),
              "w") as f:
        f.write(str(os.getpid()))


def _connect(address: str):
    from ray_tpu._private import protocol
    return protocol.connect(address, f"cli-{os.getpid()}",
                            lambda c, m: None,
                            hello_extra={"role": "probe"})


def cmd_start(args):
    if args.head:
        from ray_tpu._private import node as node_mod
        # Merge explicit flags over detected defaults (a bare
        # --num-tpus must not zero out the CPU resource).
        resources = node_mod.default_resources()
        if args.num_cpus is not None:
            resources["CPU"] = float(args.num_cpus)
        if args.num_tpus is not None:
            resources["TPU"] = float(args.num_tpus)
        node = node_mod.Node(
            resources, num_initial_workers=0, enable_tcp=True)
        _record_pid("head")
        os.makedirs(PID_DIR, exist_ok=True)
        with open(ADDRESS_FILE, "w") as f:
            f.write(node.head.tcp_addr)
        print(f"head started at {node.head.tcp_addr}")
        print(f"attach drivers with: "
              f"ray_tpu.init(address={node.head.tcp_addr!r})")
        _block_until_signal()
        node.shutdown()
    else:
        if not args.address:
            sys.exit("start needs --head or --address tcp://host:port")
        from ray_tpu._private.node_agent import NodeAgent
        resources = {"CPU": float(args.num_cpus
                                  if args.num_cpus is not None
                                  else (os.cpu_count() or 1))}
        if args.num_tpus is not None:
            resources["TPU"] = float(args.num_tpus)
        node_id = args.node_id or f"node-{os.getpid()}"
        session_dir = os.path.join(
            tempfile.gettempdir(), "ray-tpu-sessions",
            f"agent-{node_id}")
        os.makedirs(session_dir, exist_ok=True)
        agent = NodeAgent(args.address, node_id, resources, session_dir,
                          session_name=_session_name(args.address))
        _record_pid("agent")
        print(f"node {node_id} joined {args.address} with {resources}")
        _block_until_signal()
        agent.shutdown()


def _load_cluster_config(path: str) -> dict:
    import yaml

    from ray_tpu.autoscaler.autoscaler import validate_cluster_config
    with open(path) as f:
        cfg = yaml.safe_load(f) or {}
    return validate_cluster_config(cfg)


def cmd_up(args):
    """Boot an autoscaling cluster from a yaml config (parity:
    `ray up cluster.yaml`, reference scripts.py:622 + autoscaler): a
    head plus an AutoscalerMonitor launching/retiring provider worker
    nodes against load. The yaml is schema-validated (unknown keys are
    an error, ref autoscaler.py:815); an `ssh:` block switches the
    provider to CommandNodeProvider (remote hosts over ssh/any command
    transport); `worker_types:` enables heterogeneous demand-shape
    scaling."""
    from ray_tpu._private import node as node_mod
    from ray_tpu.autoscaler import LocalNodeProvider
    from ray_tpu.autoscaler.monitor import AutoscalerMonitor
    from ray_tpu.autoscaler.node_provider import CommandNodeProvider

    cfg = _load_cluster_config(args.config_file)
    resources = node_mod.default_resources()
    resources.update(cfg.get("head_resources") or {})
    node = node_mod.Node(resources, num_initial_workers=0,
                         enable_tcp=True)
    _record_pid("head")
    os.makedirs(PID_DIR, exist_ok=True)
    with open(ADDRESS_FILE, "w") as f:
        f.write(node.head.tcp_addr)
    worker_types = cfg.get("worker_types") or {}
    ssh = cfg.get("ssh")
    if ssh:
        provider = CommandNodeProvider(
            node.head.tcp_addr,
            hosts=ssh.get("hosts") or [],
            start_command=ssh.get("start_command", ""),
            stop_command=ssh.get("stop_command", ""),
            setup_command=ssh.get("setup_command", ""),
            node_resources=cfg.get("worker_resources") or {"CPU": 1.0},
            worker_types=worker_types)
    else:
        provider = LocalNodeProvider(
            node.head.tcp_addr, node.session_dir, node.session_name,
            node_resources=cfg.get("worker_resources") or {"CPU": 1.0},
            worker_types=worker_types,
            name_prefix=cfg.get("cluster_name", "autoscaled"))
    auto_cfg = {k: cfg[k] for k in ("min_workers", "max_workers",
                                    "idle_timeout_s",
                                    "max_launch_batch")
                if k in cfg}
    if worker_types:
        auto_cfg["worker_types"] = worker_types
    monitor = AutoscalerMonitor(
        provider, auto_cfg, head=node.head,
        update_interval_s=float(cfg.get("update_interval_s", 1.0)),
    ).start()
    print(f"cluster {cfg.get('cluster_name', '?')!r} up at "
          f"{node.head.tcp_addr} "
          f"(workers {monitor.autoscaler.config['min_workers']}-"
          f"{monitor.autoscaler.config['max_workers']}"
          + (f", types {sorted(worker_types)}" if worker_types else "")
          + (", provider ssh" if ssh else "") + ")")
    print(f"attach drivers with: "
          f"ray_tpu.init(address={node.head.tcp_addr!r})")
    _block_until_signal()
    monitor.stop(terminate_nodes=True)
    node.shutdown()


def cmd_down(args):
    """Tear down a `up`-started cluster (parity: `ray down`). The node
    agents are children of the `up` process; stopping it reaps them."""
    cmd_stop(args)


def cmd_exec(args):
    """Run a shell command against the running cluster (parity:
    `ray exec`): RAY_TPU_ADDRESS is injected so `ray_tpu.init()`
    inside the command attaches to it. NOTE the command runs with this
    CLI's privileges against whatever head the address resolves to —
    only point it at clusters you trust (the head socket is
    unauthenticated, same trust model as the reference's redis)."""
    import subprocess
    env = dict(os.environ)
    env["RAY_TPU_ADDRESS"] = _resolve_address(args)
    rc = subprocess.call(args.command, shell=True, env=env)
    sys.exit(rc)


def cmd_attach(args):
    """Interactive Python session attached to the cluster (parity:
    `ray attach`, reference scripts.py:622 — there an ssh shell onto
    the head node; here a REPL with `ray_tpu` already connected, which
    is the equivalent surface for a local/ssh-command cluster)."""
    import code

    address = _resolve_address(args)
    os.environ["RAY_TPU_ADDRESS"] = address
    import ray_tpu
    ray_tpu.init(address=address)
    banner = (f"ray_tpu attached to {address}\n"
              "`ray_tpu` is imported and connected; Ctrl-D detaches.")
    try:
        code.interact(banner=banner, local={"ray_tpu": ray_tpu})
    finally:
        ray_tpu.shutdown()


def cmd_submit(args):
    """Run a local python script against the cluster (parity:
    `ray submit`, reference scripts.py:692): the script executes with
    RAY_TPU_ADDRESS set so its `ray_tpu.init()` attaches; extra args
    after the script pass through."""
    import subprocess
    env = dict(os.environ)
    env["RAY_TPU_ADDRESS"] = _resolve_address(args)
    rc = subprocess.call(
        [sys.executable, args.script] + (args.script_args or []),
        env=env)
    sys.exit(rc)


def _rsync_template(cfg: dict, direction: str) -> str:
    ssh = cfg.get("ssh") or {}
    if direction == "up":
        return ssh.get("rsync_up_command",
                       "rsync -az {src} {host}:{dst}")
    return ssh.get("rsync_down_command",
                   "rsync -az {host}:{src} {dst}")


def _cluster_hosts(cfg: dict) -> list:
    return (cfg.get("ssh") or {}).get("hosts") or []


def cmd_rsync(args, direction: str):
    """File sync with cluster hosts (parity: `ray rsync-up/-down`,
    reference scripts.py:636,650). Uses the yaml's ssh.hosts and the
    rsync command templates ({host}/{src}/{dst} placeholders;
    override `ssh.rsync_up_command`/`rsync_down_command` for
    non-rsync transports). `rsync-up` syncs to EVERY host; `rsync-down`
    pulls from the first. Without an ssh block (local provider) the
    \"hosts\" are this machine and a plain copy is performed."""
    import shutil
    import subprocess
    cfg = _load_cluster_config(args.config_file)
    hosts = _cluster_hosts(cfg)
    if not hosts:
        # Local cluster: all nodes share this filesystem.
        if os.path.isdir(args.src):
            shutil.copytree(args.src, args.dst, dirs_exist_ok=True)
        else:
            os.makedirs(os.path.dirname(args.dst) or ".",
                        exist_ok=True)
            shutil.copy2(args.src, args.dst)
        print(f"copied {args.src} -> {args.dst} (local cluster)")
        return
    template = _rsync_template(cfg, direction)
    targets = hosts if direction == "up" else hosts[:1]
    for host in targets:
        cmd = template.format(host=host, src=args.src, dst=args.dst)
        print(f"[{host}] {cmd}")
        rc = subprocess.call(cmd, shell=True)
        if rc != 0:
            sys.exit(rc)


def _session_name(address: str) -> str:
    conn = _connect(address)
    try:
        return conn.request({"kind": "session_info"},
                            timeout=30)["session_name"]
    finally:
        conn.close()


def _block_until_signal():
    stop = {"flag": False}

    def handler(sig, frame):
        stop["flag"] = True

    signal.signal(signal.SIGTERM, handler)
    signal.signal(signal.SIGINT, handler)
    while not stop["flag"]:
        time.sleep(0.2)


def cmd_stop(args):
    killed = 0
    for path in glob.glob(os.path.join(PID_DIR, "*.pid")):
        try:
            with open(path) as f:
                pid = int(f.read().strip())
            if pid != os.getpid():
                os.kill(pid, signal.SIGTERM)
                killed += 1
        except (OSError, ValueError):
            pass
        finally:
            try:
                os.unlink(path)
            except OSError:
                pass
    print(f"sent SIGTERM to {killed} process(es)")


def _resolve_address(args) -> str:
    if args.address:
        return args.address
    try:
        with open(ADDRESS_FILE) as f:
            return f.read().strip()
    except OSError:
        sys.exit("no --address given and no head address file found")


def cmd_stat(args):
    if getattr(args, "config", False):
        # Config registry dump (parity: ray_config_def.h enumerability).
        from ray_tpu._private import config as config_mod
        for row in config_mod.dump():
            mark = "*" if row["overridden"] else " "
            print(f"{mark} {row['name']:<40s} "
                  f"{row['type']:<6s} {row['value']!r:<12} "
                  f"(default {row['default']!r}) — {row['doc']}")
        return
    address = _resolve_address(args)
    conn = _connect(address)
    try:
        if getattr(args, "tasks", False):
            reply = conn.request({"kind": "get_tasks", "limit": 40},
                                 timeout=30)
            counts = reply.get("state_counts") or {}
            print("task states: " + (" ".join(
                f"{s}={counts[s]}" for s in sorted(counts)) or "(none)"))
            print("summary (func x state):")
            for nm, per in sorted((reply.get("summary") or {}).items()):
                row = " ".join(f"{s}={c}" for s, c in sorted(per.items()))
                print(f"  {nm:<28s} {row}")
            print("recent tasks:")
            print(f"  {'task':<14s} {'name':<24s} {'state':<10s} "
                  f"{'node':<8s} {'pid':<7s} {'dur':<9s} error")
            for t in reply.get("tasks") or []:
                dur = f"{t['end'] - t['start']:.3f}s" \
                    if t.get("end") and t.get("start") else "-"
                print(f"  {t['task_id'][:12]:<14s} "
                      f"{(t['name'] or '-')[:23]:<24s} "
                      f"{t['state']:<10s} {str(t['node'] or '-'):<8s} "
                      f"{str(t['worker_pid'] or '-'):<7s} {dur:<9s} "
                      f"{(t['error'] or '')[:40]}")
            return
        if getattr(args, "rates", False):
            agg = conn.request({"kind": "get_metrics"},
                               timeout=30)["metrics"]
            rates = agg.get("rates") or {}
            if not rates:
                print("rates: (no rate-ring window yet — the head "
                      "samples every RAY_TPU_RATE_RING_INTERVAL_S)")
                return
            print("rates (per second, trailing window):")
            for k, v in sorted(rates.items()):
                print(f"  {k:<40s} {v:g}/s")
            return
        if getattr(args, "metrics", False):
            agg = conn.request({"kind": "get_metrics"},
                               timeout=30)["metrics"]
            print("counters:")
            for k, v in sorted(agg.get("counters", {}).items()):
                print(f"  {k:<32s} {v:g}")
            print("gauges:")
            for k, v in sorted(agg.get("gauges", {}).items()):
                print(f"  {k:<32s} {v:g}")
            quantiles = agg.get("quantiles") or {}
            if quantiles:
                print("histograms (seconds):")
                print(f"  {'name':<28s} {'count':>7s} {'p50':>10s} "
                      f"{'p95':>10s} {'p99':>10s} {'max':>10s}")
                for k, q in sorted(quantiles.items()):
                    def _f(x):
                        return f"{x:.4g}" if x is not None else "-"
                    print(f"  {k:<28s} {q['count']:>7g} "
                          f"{_f(q['p50']):>10s} {_f(q['p95']):>10s} "
                          f"{_f(q['p99']):>10s} {_f(q['max']):>10s}")
            return
        info = conn.request({"kind": "cluster_info"}, timeout=30)["info"]
    finally:
        conn.close()
    print(f"session: {info['session_name']}")
    print(f"total resources:     {info['total_resources']}")
    print(f"available resources: {info['available_resources']}")
    print(f"workers: {info['num_workers']}  pending tasks: "
          f"{info['num_pending_tasks']}")
    for nid, n in info.get("nodes", {}).items():
        print(f"  node {nid}: alive={n['alive']} "
              f"avail={n['available_resources']}")
    actors = info.get("actors", {})
    alive = sum(1 for a in actors.values() if a["state"] == "ALIVE")
    print(f"actors: {len(actors)} total, {alive} alive")
    locs = info.get("object_locations") or {}
    if locs.get("objects"):
        print(f"object locations: {locs['objects']} objects replicated, "
              f"{locs['replicas']} replicas")
        for oid_hex, count in locs.get("top", []):
            print(f"  {oid_hex[:16]:<18s} x{count}")


def cmd_dump(args):
    """Pretty-print a flight-recorder postmortem (`ray_tpu.debug_dump()`
    or the driver-fatal excepthook wrote it)."""
    import json
    with open(args.path) as f:
        dump = json.load(f)
    print(f"flight recorder dump — session {dump.get('session_dir')}")
    print(f"written at: {dump.get('ts')}")
    print("nodes:")
    for n in dump.get("nodes") or []:
        hb = n.get("heartbeat_age_s")
        hb_s = f"hb_age={hb:.1f}s" if hb is not None else "hb=local"
        print(f"  {n['node_id']:<10s} alive={n['alive']} {hb_s} "
              f"avail={n.get('available')}")
    print(f"workers registered: {dump.get('workers_registered')}")
    counts = dump.get("task_state_counts") or {}
    print("task states: " + (" ".join(
        f"{s}={counts[s]}" for s in sorted(counts)) or "(none)"))
    metrics = dump.get("metrics") or {}
    quantiles = metrics.get("quantiles") or {}
    if quantiles:
        print("histograms (seconds):")
        for k, q in sorted(quantiles.items()):
            p50, p99 = q.get("p50"), q.get("p99")
            print(f"  {k:<28s} n={q.get('count'):g} "
                  f"p50={p50 if p50 is None else format(p50, '.4g')} "
                  f"p99={p99 if p99 is None else format(p99, '.4g')}")
    rates = metrics.get("rates") or {}
    if rates:
        print("rates (trailing window):")
        for k, v in sorted(rates.items()):
            print(f"  {k:<40s} {v:g}/s")
    errors = dump.get("recent_errors") or []
    if errors:
        print("recent errors:")
        for e in errors[-10:]:
            print(f"  {e}")
    tail = (dump.get("tasks") or [])[:15]
    if tail:
        print("task-ring tail (newest first):")
        for t in tail:
            mark = f" straggler={t['straggler']}" \
                if t.get("straggler") else ""
            print(f"  {t['task_id'][:12]:<14s} "
                  f"{(t.get('name') or '-')[:24]:<26s} "
                  f"{t['state']:<10s}"
                  f"{(' ' + (t.get('error') or ''))[:40]}{mark}")
    print(f"spans: {len(dump.get('spans') or [])} recent "
          f"profiling events in bundle")
    prof = dump.get("profiling") or {}
    if prof:
        print("profiling:")
        host = prof.get("host_mem_frac") or {}
        if host:
            print("  host mem_frac: " + " ".join(
                f"{n}={v:.0%}" for n, v in sorted(host.items())
                if isinstance(v, (int, float))))
        hbm = prof.get("hbm_gauges") or {}
        for k, v in sorted(hbm.items()):
            print(f"  {k:<32s} {v:g}")
        for key in ("head_stacks", "driver_stacks"):
            stacks = prof.get(key) or {}
            if stacks:
                print(f"  {key} ({len(stacks)} thread(s)):")
                for name in sorted(stacks):
                    leaf = stacks[name].rsplit(";", 1)[-1]
                    print(f"    {name:<24s} {leaf}")


def _print_profile_summary(bundle: dict, top: int = 8):
    """Top-N hottest frames per process — the bundle usable without
    flamegraph tooling — and, under a process that took a device trace,
    its account: the ten heaviest scopes, collective seconds, idle by
    family and phase; under a process whose threads keep phase clocks,
    their wall and CPU seconds by phase and the process's CPU."""
    from ray_tpu._private import device_account
    from ray_tpu._private.profiling import render_host_account, top_frames
    procs = bundle.get("processes") or []
    print(f"capture {bundle.get('capture_id')}: "
          f"{bundle.get('duration_s')}s @ {bundle.get('hz')}Hz, "
          f"{len(procs)} process(es), "
          f"{len(bundle.get('trace_events') or [])} trace event(s)"
          + (f"; MISSING results from {bundle['missing']}"
             if bundle.get("missing") else ""))
    for p in procs:
        label = f"{p.get('role', '?')}:{p.get('pid', '?')}" \
                f"@{p.get('node', '?')}"
        if p.get("skipped"):
            print(f"-- {label}: skipped ({p['skipped']})")
            continue
        total = sum((p.get("folded") or {}).values())
        drops = f", {p['dropped']} dropped" if p.get("dropped") else ""
        xla = f", xla trace: {p['xla_trace_dir']}" \
            if p.get("xla_trace_dir") else ""
        print(f"-- {label}: {total} samples over "
              f"{len(p.get('threads') or [])} thread(s){drops}{xla}")
        for frame, count, share in top_frames(p.get("folded") or {},
                                              n=top):
            print(f"   {share:6.1%} {count:>6d}  {frame}")
        if p.get("device_account"):
            for line in device_account.render(p["device_account"], top=10,
                                              indent="   "):
                print(line)
        if (p.get("host_account") or {}).get("threads"):
            for line in render_host_account(p["host_account"],
                                            indent="   "):
                print(line)
        for d in p.get("hbm") or []:
            print(f"   hbm {d['device']} ({d.get('kind') or d.get('platform')}): "
                  f"used={d.get('used')} peak={d.get('peak')} "
                  f"limit={d.get('limit')}")


def cmd_profile(args):
    """Coordinated cluster capture (the `ray_tpu.profile(duration_s)`
    plane from the CLI): ask the head to fan a bounded stack/XLA
    sampling window to every selected process, write the merged bundle
    (+ flamegraph-ready .folded sidecar), and summarize it."""
    if args.summarize:
        with open(args.summarize) as f:
            _print_profile_summary(json.load(f), top=args.top)
        return
    address = _resolve_address(args)
    conn = _connect(address)
    try:
        reply = conn.request(
            {"kind": "profile_capture", "duration_s": args.duration,
             "target": args.target, "hz": args.hz},
            timeout=args.duration + 60.0)
    finally:
        conn.close()
    bundle = reply["bundle"]
    out = args.out or f"ray-tpu-profile-{int(time.time())}.json"
    with open(out, "w") as f:
        json.dump(bundle, f, default=str)
    base = out[:-5] if out.endswith(".json") else out
    folded_path = base + ".folded"
    with open(folded_path, "w") as f:
        for p in bundle.get("processes") or []:
            prefix = f"{p.get('role', '?')}:{p.get('pid', '?')}"
            for stack, count in sorted((p.get("folded") or {}).items()):
                f.write(f"{prefix};{stack} {count}\n")
    print(f"wrote {out} (load trace_events in chrome://tracing / "
          f"Perfetto alongside `timeline`)")
    print(f"wrote {folded_path} (flamegraph.pl / speedscope input)")
    _print_profile_summary(bundle, top=args.top)


def cmd_memory(args):
    """Object-store usage per node (parity: `ray memory`)."""
    address = _resolve_address(args)
    conn = _connect(address)
    try:
        info = conn.request({"kind": "cluster_info"}, timeout=30)["info"]
    finally:
        conn.close()
    session = info["session_name"]
    from ray_tpu._private import config as config_mod
    shm_dir = config_mod.get("RAY_TPU_SHM_DIR")
    by_node = {}
    for path in glob.glob(os.path.join(
            shm_dir, f"raytpu_{session}_*")):
        name = os.path.basename(path)[len(f"raytpu_{session}_"):]
        node = name.rsplit("_", 1)[0] if "_" in name else "node0"
        try:
            by_node.setdefault(node, [0, 0])
            by_node[node][0] += 1
            by_node[node][1] += os.stat(path).st_size
        except OSError:
            pass
    if not by_node:
        print("no objects in the local shared store")
    for node, (count, size) in sorted(by_node.items()):
        print(f"node {node}: {count} objects, {size / 1e6:.1f} MB")


def cmd_timeline(args):
    address = _resolve_address(args)
    conn = _connect(address)
    try:
        reply = conn.request({"kind": "get_profile_events"}, timeout=30)
        events, dropped = reply["events"], reply.get("dropped", 0)
    finally:
        conn.close()
    from ray_tpu._private.profiling import dump_chrome_trace
    out = args.out or f"ray-tpu-timeline-{int(time.time())}.json"
    dump_chrome_trace(events, out, dropped=dropped)
    print(f"wrote {len(events)} span(s) to {out} "
          f"(open in chrome://tracing or Perfetto)"
          + (f"; {dropped} span(s) dropped to buffer bounds"
             if dropped else ""))


def cmd_chaos(args):
    """Chaos-plane tooling: print the injection-site catalog, validate
    a spec, pretty-print a RAY_TPU_CHAOS_TRACE file from a (failed)
    run, or verify that the trace replays byte-identical from its seed
    (`--replay --spec <spec>`), which is how a CI failure's fault
    sequence is confirmed reproducible before re-running it locally."""
    from ray_tpu._private import chaos as chaos_mod
    if args.catalog:
        for site in sorted(chaos_mod.SITES):
            print(site)
            for kind, doc in sorted(chaos_mod.SITES[site].items()):
                print(f"  {kind:<12s} {doc}")
        return
    if args.spec and not args.trace:
        seed, rules = chaos_mod.parse_spec(args.spec)
        print(f"seed: {seed}")
        for r in rules:
            if r.trigger == "window":
                trig = f"window:{r.value:g}:{r.period:g}"
            else:
                trig = f"{r.trigger}{r.value:g}"
            print(f"  {r.site:<16s} {r.kind:<12s} {trig}"
                  + (f" param={r.param}" if r.param else ""))
        return
    if not args.trace:
        sys.exit("chaos needs a trace file, --spec, or --catalog")
    entries = chaos_mod.load_trace(args.trace)
    if args.replay:
        if not args.spec:
            sys.exit("--replay needs --spec <the run's RAY_TPU_CHAOS>")
        replayed = chaos_mod.replay(args.spec, entries)
        if chaos_mod.trace_bytes(entries) \
                == chaos_mod.trace_bytes(replayed):
            print(f"trace replays byte-identical from its seed "
                  f"({len(entries)} injection(s))")
            return
        print("trace DIVERGES from its seed replay:")
        for a, b in zip(entries, replayed + [None] * len(entries)):
            if a != b:
                print(f"  recorded: {a}\n  replayed: {b}")
        sys.exit(1)
    print(f"{'pid':<8s} {'seq':<5s} {'site':<16s} {'kind':<12s} "
          f"{'occ':<5s} detail")
    for e in entries:
        print(f"{e['pid']:<8d} {e['seq']:<5d} {e['site']:<16s} "
              f"{e['kind']:<12s} {e['occ']:<5d} {e.get('detail', '')}")
    by_kind = {}
    for e in entries:
        k = f"{e['site']}:{e['kind']}"
        by_kind[k] = by_kind.get(k, 0) + 1
    print(f"{len(entries)} injection(s): " + ", ".join(
        f"{k} x{n}" for k, n in sorted(by_kind.items())))


def cmd_fleet(args):
    """Elastic-fleet view: live fleet size, join/evict counters,
    recovery-time quantiles (all off the head's aggregated metrics),
    and the per-actor membership event history the FleetController
    publishes into the head KV (`fleet:events`)."""
    from ray_tpu._private.fleet import FLEET_EVENTS_KV_KEY
    address = _resolve_address(args)
    conn = _connect(address)
    try:
        agg = conn.request({"kind": "get_metrics"},
                           timeout=30)["metrics"]
        raw = conn.request({"kind": "kv_get",
                            "key": "ikv:" + FLEET_EVENTS_KV_KEY},
                           timeout=30).get("value")
    finally:
        conn.close()
    gauges = agg.get("gauges") or {}
    counters = agg.get("counters") or {}
    size = gauges.get("fleet_size")
    if size is None and not raw:
        print("no fleet controller has published yet (fleets form "
              "when an async optimizer runs with remote workers)")
        return
    print(f"fleet size: {size:g}" if size is not None
          else "fleet size: (gauge not published)")
    print(f"joins: {counters.get('fleet_joins_total', 0):g}  "
          f"evictions: {counters.get('fleet_evictions_total', 0):g}")
    q = (agg.get("quantiles") or {}).get("actor_recovery_s")
    if q:
        def _f(x):
            return f"{x:.4g}s" if x is not None else "-"
        print(f"recovery (death -> first rejoined sample): "
              f"n={q['count']:g} p50={_f(q['p50'])} "
              f"p95={_f(q['p95'])} max={_f(q['max'])}")
    if raw:
        try:
            events = json.loads(raw)
        except (TypeError, ValueError):
            events = []
        if events:
            print(f"membership events (last {len(events)}):")
            print(f"  {'when':<20s} {'event':<10s} {'tag':<8s} detail")
            for e in events:
                when = time.strftime(
                    "%Y-%m-%d %H:%M:%S",
                    time.localtime(e.get("ts", 0)))
                detail = e.get("reason", "")
                if "recovery_s" in e:
                    detail = f"recovery_s={e['recovery_s']}"
                print(f"  {when:<20s} {e.get('event', '?'):<10s} "
                      f"{e.get('tag', '?'):<8s} {detail}")


def cmd_check(args):
    """Framework-aware static analysis (graftcheck): lint rules for
    distributed anti-patterns + static lock-order cycle detection.
    `--race` adds the GC300 lockset data-race plane (seeded
    interleaving stress against a live runtime); `--stress SEED` pins
    the seed and verifies byte-identical replay. Exits non-zero on
    findings not covered by the suppression baseline. See README
    "Correctness tooling"."""
    from ray_tpu._private.graftcheck import cli as graftcheck_cli
    sys.exit(graftcheck_cli.run(
        args.paths, baseline_path=args.baseline,
        write_baseline=args.write_baseline, as_json=args.json,
        lockgraph=not args.no_lockgraph, race=args.race,
        stress_seed=args.stress, head_stress_seed=args.head_stress))


def main(argv=None):
    parser = argparse.ArgumentParser(prog="ray_tpu.scripts")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser(
        "check", help="static analysis: lint + lock-order checks")
    p.add_argument("paths", nargs="*", default=["ray_tpu"])
    p.add_argument("--baseline", default=None)
    p.add_argument("--write-baseline", action="store_true")
    p.add_argument("--json", action="store_true")
    p.add_argument("--no-lockgraph", action="store_true")
    p.add_argument("--race", action="store_true",
                   help="also run the lockset race plane (GC301/GC302) "
                        "via the interleaving stress harness")
    p.add_argument("--stress", type=int, default=None, metavar="SEED",
                   help="race-stress seed (implies --race); verifies "
                        "byte-identical replay")
    p.add_argument("--head-stress", type=int, default=None,
                   metavar="SEED", dest="head_stress",
                   help="race the sharded head: cross-shard kv/"
                        "location/lease/task-event interleavings "
                        "with racecheck armed + replay gate")
    p.set_defaults(fn=cmd_check)

    p = sub.add_parser(
        "chaos", help="chaos plane: trace pretty-print / replay-verify")
    p.add_argument("trace", nargs="?", default=None,
                   help="RAY_TPU_CHAOS_TRACE JSONL file")
    p.add_argument("--spec", default=None,
                   help="chaos spec (validate, or replay against)")
    p.add_argument("--replay", action="store_true",
                   help="verify the trace replays from its seed")
    p.add_argument("--catalog", action="store_true",
                   help="print the injection-site catalog")
    p.set_defaults(fn=cmd_chaos)

    p = sub.add_parser("start", help="start a head or join as a node")
    p.add_argument("--head", action="store_true")
    p.add_argument("--address", default=None)
    p.add_argument("--num-cpus", type=float, default=None)
    p.add_argument("--num-tpus", type=float, default=None)
    p.add_argument("--node-id", default=None)
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser("stop", help="stop CLI-started processes")
    p.set_defaults(fn=cmd_stop)

    p = sub.add_parser("up", help="boot an autoscaling cluster")
    p.add_argument("config_file")
    p.set_defaults(fn=cmd_up)

    p = sub.add_parser("down", help="tear down an up-started cluster")
    p.set_defaults(fn=cmd_down)

    p = sub.add_parser("exec",
                       help="run a command against the cluster")
    p.add_argument("command")
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_exec)

    p = sub.add_parser("attach",
                       help="interactive session on the cluster")
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_attach)

    p = sub.add_parser("submit",
                       help="run a local script against the cluster")
    p.add_argument("script")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_submit)

    for direction in ("up", "down"):
        p = sub.add_parser(f"rsync-{direction}",
                           help=f"sync files {direction} cluster hosts")
        p.add_argument("config_file")
        p.add_argument("src")
        p.add_argument("dst")
        p.set_defaults(fn=lambda a, _d=direction: cmd_rsync(a, _d))

    for name, fn in (("stat", cmd_stat), ("memory", cmd_memory),
                     ("timeline", cmd_timeline)):
        p = sub.add_parser(name)
        p.add_argument("--address", default=None)
        if name == "timeline":
            p.add_argument("--out", default=None)
        if name == "stat":
            p.add_argument("--metrics", action="store_true",
                           help="print cluster-aggregated counters/"
                                "gauges/histogram quantiles instead of "
                                "resource state")
            p.add_argument("--rates", action="store_true",
                           help="print trailing-window per-second "
                                "counter rates from the head's rate "
                                "ring (tasks/s, wire bytes/s, ...)")
            p.add_argument("--tasks", action="store_true",
                           help="print the task-lifecycle state table "
                                "(per-state counts, func x state "
                                "summary, recent tasks)")
            p.add_argument("--config", action="store_true",
                           help="dump the tunable-config registry "
                                "(effective values; * = env override)")
        p.set_defaults(fn=fn)

    p = sub.add_parser(
        "fleet", help="elastic-fleet view: live size, join/evict "
                      "history, recovery-time quantiles")
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_fleet)

    p = sub.add_parser(
        "dump", help="pretty-print a flight-recorder postmortem JSON "
                     "(ray_tpu.debug_dump() / the driver-fatal "
                     "excepthook write it)")
    p.add_argument("path", help="flight-recorder JSON file")
    p.set_defaults(fn=cmd_dump)

    p = sub.add_parser(
        "profile", help="coordinated cluster capture: stack-sample "
                        "(+XLA-trace) every selected process for a "
                        "bounded window, merge into one bundle")
    p.add_argument("--address", default=None)
    p.add_argument("--duration", type=float, default=2.0,
                   help="capture window seconds (clamped to "
                        "RAY_TPU_PROFILE_MAX_S)")
    p.add_argument("--target", default="all",
                   help="all | head | workers | drivers | nodes | "
                        "learner (device-owning processes) | a "
                        "process addr")
    p.add_argument("--hz", type=float, default=None,
                   help="sampling frequency (default "
                        "RAY_TPU_PROFILE_HZ)")
    p.add_argument("--out", default=None,
                   help="bundle JSON path (a .folded flamegraph "
                        "sidecar is written next to it)")
    p.add_argument("--top", type=int, default=8,
                   help="frames per process in the summary")
    p.add_argument("--summarize", default=None, metavar="BUNDLE",
                   help="print an existing bundle JSON instead of "
                        "capturing: top frames a process and, where a "
                        "process traced a device, its seconds by "
                        "named_scope, collective seconds, and idle "
                        "seconds by the phase each loop thread had open")
    p.set_defaults(fn=cmd_profile)

    args = parser.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
