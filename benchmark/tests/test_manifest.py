"""BENCHMARK.json against the files it names, and the contract's limits
that a typing slip would break."""

import importlib.util
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def reader(base):
    path = os.path.join(BENCH, "layer_metrics", base + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + base, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics(kind):
    return {m["name"]: m for m in MANIFEST[kind]}


def reported_in(metric, cell):
    return cell in metric.get("workloads", CELLS)


def test_top_level():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(
        1, len(CELLS) // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_names_and_units():
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[kind]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((kind in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names)), "a name is used twice"
    for kind in ("end_to_end", "per_layer"):
        for m in MANIFEST[kind]:
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
            extra = {"bound"} if kind == "end_to_end" else {"layer", "moves"}
            assert set(m) - {"workloads"} == {
                "name", "unit", "better", "source"} | extra, m
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_configs_have_their_files():
    used = {w["config"] for w in MANIFEST["workloads"]}
    for c in MANIFEST["configs"]:
        assert c["name"] in used, "a configuration with no cell"
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        body = load("configs", c["name"] + ".json")
        assert body["source"] == c["source"] and len(c["source"]) <= 200
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|hidden|conv_filters)$", key)
        assert "trainer_config" in body and "network" in body


@pytest.mark.parametrize("cell", CELLS)
def test_cell_has_its_files_and_metrics(cell):
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    body = load("workloads", cell + ".json")
    for key in ("config", "traffic", "chips"):
        assert body[key] == entry[key], key
    assert os.path.exists(os.path.join(
        BENCH, "configs", body["config"] + ".json"))
    assert os.path.exists(os.path.join(
        BENCH, "drivers", body["driver"] + ".py"))
    assert body["config"] in {c["name"] for c in MANIFEST["configs"]}

    e2e, layer = metrics("end_to_end"), metrics("per_layer")
    # What the cell's file says it reports is what the manifest says.
    assert set(body["end_to_end"]) == {
        n for n, m in e2e.items() if reported_in(m, cell)}
    assert set(body["per_layer"]) == {
        n for n, m in layer.items() if reported_in(m, cell)}
    assert "setup_s" in body["end_to_end"] and len(body["end_to_end"]) >= 2
    assert body["per_layer"]
    for name in body["per_layer"]:
        m = layer[name]
        assert m["moves"] in body["end_to_end"], (
            f"{name} moves {m['moves']}, which {cell} does not report")
        r = reader(name.split(".")[0])
        assert (r.UNIT, r.LAYER, r.SOURCE, r.BETTER) == (
            m["unit"], m["layer"], m["source"], m["better"]), name
        assert callable(r.read)


def test_run_py_names_nothing():
    """The command finds cells, configurations, drivers and metrics by
    name; none is written into it."""
    with open(os.path.join(BENCH, "run.py")) as f:
        text = f.read()
    listed = [e["name"] for kind in ("configs", "workloads", "end_to_end",
                                     "per_layer") for e in MANIFEST[kind]]
    listed += [n.split(".")[0] for n in listed if "." in n]
    listed += [f[:-3] for f in os.listdir(os.path.join(BENCH, "drivers"))
               if f.endswith(".py")]
    for name in set(listed):
        whole = r"(?<![A-Za-z0-9_])" + re.escape(name) + r"(?![A-Za-z0-9_])"
        assert not re.search(whole, text), name
