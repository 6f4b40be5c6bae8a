"""A builder's run of any cell with the host's account of each window
printed (PR 50): where PERF.md's host accounts of the inline cells come
from.

    python3 benchmark/tests/run_with_host_account.py --workload <cell> \\
        --seed <n> --seconds 20 --trace 1        (run.py's own arguments)

Loads `benchmark/run.py` of this checkout as a module, wraps its
`run_window` with two `profiling.host_snapshot()`s (every registered clock
of a live thread) inside a `profiling.phase_cpu_reads()` window, and prints
`profiling.host_account(before, after)` as one `# host_account.<window>:`
JSON line on stdout ahead of the result line, and as a table on stderr.
The phases read their CPU over BOTH windows here, which costs the
benchmark's hosts 6-10 % of an inline cell's rate: a diagnostic run, its
rate and its per-layer shares are not the cell's.
"""

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.argv[0] = os.path.join(ROOT, "benchmark", "run.py")
sys.path.insert(0, ROOT)
spec = importlib.util.spec_from_file_location("bench_run", sys.argv[0])
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)

from ray_tpu._private import profiling  # noqa: E402

inner_window, inner_note = run.run_window, run.note
accounts = []


def run_window(session, seconds, annotate, compiles):
    with profiling.phase_cpu_reads():
        before = profiling.host_snapshot()
        out = inner_window(session, seconds, annotate, compiles)
        acct = profiling.host_account(before, profiling.host_snapshot())
    acct["steps"] = out["steps"]
    accounts.append(acct)
    return out


def note(key, value):
    if key == "windows":
        for which, acct in zip(("window", "slice"), accounts):
            print("# host_account.%s: %s" % (which, json.dumps(acct)),
                  flush=True)
            print("\n".join(profiling.render_host_account(
                acct, indent="# %s " % which)), file=sys.stderr, flush=True)
    inner_note(key, value)


run.run_window, run.note = run_window, note
sys.exit(run.main())
