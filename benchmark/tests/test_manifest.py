"""BENCHMARK.json against the contract's mechanical rules and against the
files it names.  `python -m pytest benchmark/tests -q` (outside tier-1)."""

import importlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_KEY = re.compile(r"(_dim|_rank)$|(hidden|intermediate|latent|state|"
                       r"projection|head)_size|expansion|experts_per_tok")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_and_limits():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmark"]
    assert len(m["command"]) <= 32 and all(line(w) for w in m["command"])
    assert 1 <= m["run_seconds"] <= 51
    cells = len(m["workloads"])
    assert 2 <= cells <= 24
    # 2 + 14 runs a cell, run_seconds + 60 each, 180 s a cell to compile,
    # 1200 s spare, inside 43200 s — with the full 24 cells.
    assert (2 + 14 * 24) * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_configs():
    m = manifest()
    names = [c["name"] for c in m["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in m["workloads"]}
    files = set()
    for config in m["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(config["name"]) and config["name"] in used
        assert line(config["source"]) and line(config["why"])
        assert config["file"].startswith("benchmark/")
        assert config["file"] not in files
        files.add(config["file"])
        assert len(config["reduced"]) <= 16
        with open(os.path.join(ROOT, config["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == config["reduced"]
        assert body["source"].startswith(config["source"][:40])
        for key in config["reduced"]:
            assert NAME.match(key) and key in body
            assert not WIDTH_KEY.search(key), f"{key} is a width"
        importlib.import_module(f"benchmark.builders.{body['builder']}")


def test_workloads():
    m = manifest()
    configs = {c["name"] for c in m["configs"]}
    names = [w["name"] for w in m["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "traffic",
                                           w["traffic"] + ".json"))
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(names) // 4)


def test_metrics():
    m = manifest()
    cells = [w["name"] for w in m["workloads"]]
    every = m["end_to_end"] + m["per_layer"]
    names = [x["name"] for x in every]
    assert len(set(names)) == len(names)
    assert 1 <= len(m["end_to_end"]) <= 16 and 1 <= len(m["per_layer"]) <= 128
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for x in every:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher") and x["source"] in SOURCES
        assert set(x.get("workloads", cells)) <= set(cells)
    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
        importlib.import_module(
            "benchmark.end_to_end_metrics." + x["name"].split(".")[0])
    layers = set()
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert line(x["layer"]) and x["moves"] in e2e
        layers.add(x["layer"])
        reader = importlib.import_module(
            "benchmark.layer_metrics." + x["name"].split(".")[0])
        assert callable(reader.read)
    # Every layer named here is a layer of PERF.md's list, letter for letter.
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert f"| {layer} |" in perf, layer

    def in_cell(metric, cell):
        return cell in metric.get("workloads", cells)

    for cell in cells:
        here = {x["name"] for x in m["end_to_end"] if in_cell(x, cell)}
        assert "setup_s" in here and len(here) >= 2, cell
        layer_metrics = [x for x in m["per_layer"] if in_cell(x, cell)]
        assert layer_metrics, cell
        for x in layer_metrics:   # a per-layer metric moves one of its cell's
            assert x["moves"] in here, (cell, x["name"])
            assert x["moves"] != "setup_s"


def test_files_under_paths_are_named_from_a_names_characters():
    import subprocess

    listed = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard",
         "benchmark"], cwd=ROOT, stdout=subprocess.PIPE, text=True).stdout
    for path in listed.split():
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", path), path
