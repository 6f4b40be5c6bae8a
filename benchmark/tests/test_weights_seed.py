"""A configuration's `weights_seed` (`drivers/rllib_token_trainer.py`): the
weights are the configuration's, the traffic is the run's. At the cells'
rehearsal sizes on the CPU. And a cell's `window.min_iterations` (`run.py`):
a floor of whole iterations under the end-to-end window.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import contextlib
import importlib.util
import os
from types import SimpleNamespace

import jax
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = ["smallthinker_token_anakin_8k", "kimi_linear_token_anakin_4k"]

# `run.py` itself: its window, and how it finds a cell's files and driver.
spec = importlib.util.spec_from_file_location(
    "bench_run", os.path.join(BENCH, "run.py"))
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)
load = run.load_json


def opened(cell, seed, weights_seed):
    """(the parameters a session of `cell` holds when it is opened, the
    tokens its env stands at after one call of the trainer's program)."""
    workload = load("workloads", cell + ".json")
    config = dict(load("configs", workload["config"] + ".json"))
    config.pop("weights_seed", None)
    if weights_seed is not None:
        config["weights_seed"] = weights_seed
    session = run.load_module("drivers", workload["driver"]).open_session(
        config, workload, seed, 1, True)
    try:
        params = jax.tree.map(np.asarray, session.policy.params)
        out = session.iterate()
        assert out["ok"], out
        return params, np.asarray(session.optimizer._obs)
    finally:
        session.close()


def same(a, b):
    return all(jax.tree.leaves(jax.tree.map(np.array_equal, a, b)))


@pytest.mark.parametrize("cell", CELLS)
def test_named_weights_do_not_follow_the_runs_seed(cell):
    first, tokens = opened(cell, 11, 5)
    second, other_tokens = opened(cell, 12, 5)
    assert same(first, second)
    assert not np.array_equal(tokens, other_tokens)
    # They are the weights of a run at `--seed 5`, bit for bit.
    assert same(first, opened(cell, 5, None)[0])
    assert not same(first, opened(cell, 12, 6)[0])


@pytest.mark.parametrize("cell", CELLS)
def test_without_the_key_the_weights_are_the_runs(cell):
    first, tokens = opened(cell, 11, None)
    again, same_tokens = opened(cell, 11, None)
    assert same(first, again) and np.array_equal(tokens, same_tokens)
    assert not same(first, opened(cell, 12, None)[0])


def test_a_configuration_that_names_its_draw_says_why():
    named = []
    for name in os.listdir(os.path.join(BENCH, "configs")):
        config = load("configs", name)
        if "weights_seed" in config:
            assert isinstance(config["weights_seed"], int)
            assert config["weights_seed_why"]
            named.append(config["name"])
    assert {"impala_smallthinker_21b_a3b",
            "impala_kimi_linear_48b_a3b"} <= set(named)


class Calls:
    """A session whose iteration takes no time and trains 10 steps."""

    def __init__(self):
        self.steps = 0

    def steps_trained(self):
        return self.steps

    def iterate(self):
        self.steps += 10
        return {"ok": True, "steps": 10, "why": None}


@pytest.mark.parametrize("floor, calls", [(1, 1), (3, 3)])
def test_a_windows_floor_counts_whole_iterations(floor, calls):
    window = run.run_window(
        Calls(), 1e-9, lambda name: contextlib.nullcontext(),
        SimpleNamespace(count=0), floor)
    assert (window["attempted"], window["steps"]) == (calls, 10 * calls)
    assert window["failed"] == 0 and window["lowered"] == 0


def test_only_the_cell_that_asks_has_a_floor():
    asked = {name[:-5]: load("workloads", name).get("window")
             for name in os.listdir(os.path.join(BENCH, "workloads"))}
    assert {cell for cell, window in asked.items() if window} == {
        "smallthinker_token_anakin_8k"}
    assert asked["smallthinker_token_anakin_8k"]["min_iterations"] == 3
