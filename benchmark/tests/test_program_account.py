"""The readers of the program's own account (`layer_metrics/
program_account.py` and the eight metrics PR 36 added): against a synthetic
account and synthetic clocks, and the helper's keep-and-remove of
`BENCH_TRACE_DIR` around a trace file the harness would have written."""

import importlib.util
import os
import shutil
from types import SimpleNamespace

import pytest

from layer_metrics import program_account

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(BENCH, "tests", "data", "small_tpu_trace.xplane.pb")


def reader(base):
    path = os.path.join(BENCH, "layer_metrics", base + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + base, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ACCOUNT = {
    "window_s": 3.0, "busy_s": 0.5, "idle_s": 2.5, "chips": 4,
    "unscoped_s": 0.01, "collective_s": 0.02,
    "scopes": {"train/loss": 0.2, "train/allreduce": 0.02,
               "train/update": 0.02, "sebulba/select|policy/action": 0.15,
               "sebulba/apply": 0.05, "sebulba/pack": 0.03,
               "policy/action": 0.02, "unscoped|copy-done": 0.01},
    "threads": [{"family": "learner"}, {"family": "sebulba"}],
    "idle": {"learner": {"threads": 1, "seconds": {
                 "learner.dequeue": 2.0, "learner.train": 0.3, "other": 0.2}},
             "sebulba": {"threads": 4, "seconds": {
                 "sebulba.lock_wait": 1.0, "other": 1.5}}},
    "idle_any": {"sebulba.lock_wait": 1.5, "learner.lock_wait": 0.25,
                 "*.lock_wait": 1.6},
}


def ctx_with(account):
    return SimpleNamespace(program_account=account, trace={"busy_s": 0.5})


@pytest.mark.parametrize("base, value", [
    ("sampler_busy_share_pct", 100 * 0.25 / 0.5),
    ("collective_busy_pct", 100 * 0.02 / 0.5),
    ("unscoped_busy_pct", 100 * 0.01 / 0.5),
    ("idle_learner_starved_pct", 100 * 2.0 / 2.5),
    ("idle_lock_contended_pct", 100 * 1.6 / 2.5),
])
def test_account_readers(base, value):
    r = reader(base)
    assert r.SOURCE == "device_trace" and callable(r.begin)
    assert r.read(ctx_with(ACCOUNT), None) == pytest.approx(value)
    # a program without the account (the parent), or a CPU run: left out
    assert r.read(ctx_with(None), None) is None


def test_idle_readers_say_nothing_where_there_is_nothing_to_read():
    no_threads = dict(ACCOUNT, threads=[], idle={}, idle_any={})
    assert reader("idle_learner_starved_pct").read(
        ctx_with(no_threads), None) is None
    assert reader("idle_lock_contended_pct").read(
        ctx_with(no_threads), None) is None
    # one family waited: its row, no union to take
    one = dict(ACCOUNT, idle_any={"sebulba.lock_wait": 1.5})
    assert reader("idle_lock_contended_pct").read(
        ctx_with(one), None) == pytest.approx(60.0)
    no_idle = dict(ACCOUNT, idle_s=0.0)
    assert reader("idle_learner_starved_pct").read(
        ctx_with(no_idle), None) is None


class Clock:
    def __init__(self, seconds):
        self.seconds = seconds

    def snapshot(self):
        return {"seconds": dict(self.seconds)}


@pytest.mark.parametrize("base, value", [
    ("learner_train_pct", 100 * (1.5 + 0.5) / 10),
    ("learner_h2d_pct", 100 * 0.25 / 10),
    ("learner_lock_wait_pct", 100 * 1.0 / 10),
])
def test_learner_phase_readers(base, value):
    r = reader(base)
    assert r.SOURCE == "program_counter"
    clock = Clock({"learner.train": 1.0, "learner.readback": 1.0,
                   "learner.h2d": 1.0, "learner.lock_wait": 1.0,
                   "learner.dequeue": 5.0})
    ctx = SimpleNamespace(window_s=10.0, session=SimpleNamespace(
        optimizer=SimpleNamespace(learner=SimpleNamespace(clock=clock))))
    state = r.begin(ctx)
    clock.seconds.update({"learner.train": 2.5, "learner.readback": 1.5,
                          "learner.h2d": 1.25, "learner.lock_wait": 2.0})
    assert r.read(ctx, state) == pytest.approx(value)
    # an optimizer without a learner thread, or a thread without a clock
    for optimizer in (SimpleNamespace(),
                      SimpleNamespace(learner=SimpleNamespace())):
        bare = SimpleNamespace(window_s=10.0,
                               session=SimpleNamespace(optimizer=optimizer))
        assert r.read(bare, r.begin(bare)) is None


def harness_writes(trace_root):
    """What `run.py` leaves under `BENCH_TRACE_DIR/<tag>` after a slice."""
    where = os.path.join(trace_root, "cell.1", "plugins", "profile", "t")
    os.makedirs(where)
    shutil.copy(RECORDED, os.path.join(where, "host.xplane.pb"))


def test_the_helper_keeps_the_harness_s_trace_and_removes_what_it_made(
        monkeypatch):
    monkeypatch.delenv("BENCH_TRACE_DIR", raising=False)
    ctx = SimpleNamespace(trace=None)
    assert program_account.account(ctx) is None  # nobody asked to keep one
    program_account.begin(ctx)
    made = os.environ["BENCH_TRACE_DIR"]
    program_account.begin(ctx)  # a second reader: the same directory
    assert os.environ["BENCH_TRACE_DIR"] == made and os.path.isdir(made)
    harness_writes(made)
    ctx.trace = {"busy_s": 1.0}
    acct = program_account.account(ctx)
    assert acct["chips"] == 1 and acct["window_s"] == pytest.approx(
        0.019159981)
    assert program_account.account(ctx) is acct  # reduced once
    assert not os.path.exists(made) and "BENCH_TRACE_DIR" not in os.environ


def test_the_helper_leaves_a_builder_s_directory_alone(monkeypatch, tmp_path):
    monkeypatch.setenv("BENCH_TRACE_DIR", str(tmp_path))
    ctx = SimpleNamespace(trace={"busy_s": 1.0})
    program_account.begin(ctx)
    assert os.environ["BENCH_TRACE_DIR"] == str(tmp_path)
    harness_writes(str(tmp_path))
    assert program_account.account(ctx)["busy_s"] > 0
    assert os.path.isdir(tmp_path / "cell.1")
    assert os.environ["BENCH_TRACE_DIR"] == str(tmp_path)


def test_a_run_without_device_ops_still_removes_the_directory(monkeypatch):
    monkeypatch.delenv("BENCH_TRACE_DIR", raising=False)
    ctx = SimpleNamespace(trace=None)  # every rehearsal
    program_account.begin(ctx)
    made = os.environ["BENCH_TRACE_DIR"]
    assert program_account.account(ctx) is None
    assert not os.path.exists(made) and "BENCH_TRACE_DIR" not in os.environ


def test_a_trace_that_cannot_be_read_is_no_account(monkeypatch, capsys):
    monkeypatch.delenv("BENCH_TRACE_DIR", raising=False)
    ctx = SimpleNamespace(trace={"busy_s": 1.0})
    program_account.begin(ctx)
    made = os.environ["BENCH_TRACE_DIR"]  # and the harness wrote nothing
    assert program_account.account(ctx) is None
    assert "FileNotFoundError" in capsys.readouterr().err
    assert not os.path.exists(made)
