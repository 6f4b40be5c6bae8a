"""Tier-1's copy of the benchmark's own test of `weights_seed`
(`benchmark/tests/test_weights_seed.py`, which tier-1 does not collect): its
first two cases, run from that file and not copied, at the cells' rehearsal
sizes on the CPU, over the cells it names and the newest one whose
configuration names a draw. The weights are the configuration's, the traffic
is the run's: two sessions under two run seeds hold the same parameters, bit
for bit those of a run at `--seed <weights_seed>`, and decode different
tokens; without the key the weights follow `--seed`, as they always did.
"""

import importlib.util
import os

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")

spec = importlib.util.spec_from_file_location(
    "benchmark_test_weights_seed",
    os.path.join(BENCH, "tests", "test_weights_seed.py"))
theirs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(theirs)

CELLS = theirs.CELLS + ["laguna_token_anakin_8k"]


@pytest.mark.parametrize("cell", CELLS)
def test_named_weights_do_not_follow_the_runs_seed(cell):
    theirs.test_named_weights_do_not_follow_the_runs_seed(cell)


@pytest.mark.parametrize("cell", CELLS)
def test_without_the_key_the_weights_are_the_runs(cell):
    theirs.test_without_the_key_the_weights_are_the_runs(cell)


def test_the_cells_here_are_cells_whose_configuration_names_its_draw():
    for cell in CELLS:
        workload = theirs.load("workloads", cell + ".json")
        config = theirs.load("configs", workload["config"] + ".json")
        assert isinstance(config["weights_seed"], int), cell
        assert config["weights_seed_why"], cell
