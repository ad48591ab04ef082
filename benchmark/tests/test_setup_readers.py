"""The seven readers of the program's own account of its set-up
(`layer_metrics/_setup.py`), on a small recorded `probes` record: the shape
`_setup.probe` returns, the numbers made up (no measurement)."""

import importlib
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# The process began at 100.0 on its clock and the window 50 s later: the
# reference pass's kernels (140.2) are set-up's, the scaling probe's twin
# (171.0) traced its kernels after the window.
RECORDED = {
    "step": {"trace_s": 3.2, "lower_s": 0.96, "load_s": 4.1,
             "programs": {"traced": 1, "lowered": 1, "loaded": 2},
             "cache_hits": 2, "cache_misses": 0, "cache_retrieval_s": 1.4,
             "code_bytes": 40110000, "first_call_s": 3.9, "recompiles": 0,
             "last_compile_call": None,
             "kernels": {"hvd_flash_fwd": {"calls": 16, "trace_s": 0.5},
                         "hvd_flash_bwd": {"calls": 16, "trace_s": 0.7}}},
    "entries": [[120.0, "kernel", "hvd_flash_fwd", 0.5],
                [121.0, "kernel", "hvd_flash_bwd", 0.7],
                [122.0, "trace", "shard_step", 3.2],
                [123.0, "lower", "jit(shard_step)", 0.96],
                [125.0, "cache_hit", "", 0.0],
                [125.1, "load", "jit(shard_step)", 2.0],
                [140.2, "kernel", "hvd_flash_fwd", 0.25],
                [171.0, "kernel", "hvd_flash_fwd", 0.5]],
    "process_start": 100.0}
WANT = {"step_trace_s": 3.2, "step_lower_s": 0.96, "step_load_s": 4.1,
        "step_programs_loaded": 2, "step_cache_misses": 0,
        "step_code_mb": 40.11, "kernel_trace_s": 1.45}


def reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


def run_with(probed):
    return {"setup_s": 50.0, "probes": {"step_trace_s": probed}}


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_reads_its_number(name):
    assert reader(name).read(run_with(RECORDED)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_program_without_the_account_reads_nothing(name):
    assert reader(name).read(run_with(None)) is None
    assert reader(name).read({"setup_s": 50.0, "probes": {}}) is None


def test_a_cell_without_kernels_reads_zero_and_a_number():
    record = dict(RECORDED, entries=[e for e in RECORDED["entries"]
                                     if e[1] != "kernel"])
    assert reader("kernel_trace_s").read(run_with(record)) == 0.0
    held_none = dict(RECORDED, step=dict(RECORDED["step"], code_bytes=None))
    assert reader("step_code_mb").read(run_with(held_none)) is None


def test_one_probe_serves_the_seven_and_a_parent_gives_none():
    owners = [name for name in WANT if hasattr(reader(name), "probe")]
    assert owners == ["step_trace_s"]

    class Built:
        step = object()             # a step that keeps no account

    assert reader("step_trace_s").probe(
        {"built": Built, "note": lambda **_: None}) is None


def test_the_manifest_lists_them_in_every_cell_under_setup_s():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    mine = [m for m in manifest["per_layer"] if m["name"] in WANT]
    assert [m["name"] for m in mine] == list(WANT)
    assert mine == manifest["per_layer"][-len(WANT):]
    for m in mine:
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["workloads"] == cells
        assert m["layer"] == ("kernels" if m["name"] == "kernel_trace_s"
                              else "compiled step")
