"""run.py walked end to end on the CPU at tiny sizes (`--rehearse 1`): the
control flow, the references (in float32 the system must match them to
rounding), the readers, and that a cell, a configuration, a traffic mix and
a per-layer metric are added by new files and new entries only.  No number
these runs print is a measurement."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run(root, *args, devices=1, rehearse=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"), *args]
    if rehearse:
        cmd += ["--rehearse", "1"]
    proc = subprocess.run(cmd, cwd=root, env=env, stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True, timeout=600)
    lines = proc.stdout.strip().splitlines()
    return proc, (json.loads(lines[-1]) if lines else None)


def manifest_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return manifest, [(w["name"], w["chips"]) for w in manifest["workloads"]]


@pytest.mark.parametrize("cell,chips", manifest_cells()[1])
def test_untraced_line(cell, chips):
    manifest = manifest_cells()[0]
    proc, result = run(ROOT, "--workload", cell, "--seed", "3", "--seconds",
                       "2", "--trace", "0", devices=chips)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["rehearsal"] and result["device"]["platform"] == "cpu"
    expected = {m["name"] for m in manifest["end_to_end"]
                if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) == expected
    assert all(v["value"] > 0 for v in result["metrics"].values())


@pytest.mark.parametrize("cell,chips", manifest_cells()[1])
def test_traced_line(cell, chips):
    manifest = manifest_cells()[0]
    proc, result = run(ROOT, "--workload", cell, "--seed", "4", "--seconds",
                       "2", "--trace", "1", devices=chips)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is True
    wanted = {m["name"] for m in manifest["per_layer"]
              if cell in m.get("workloads", [cell])}
    # A CPU has no entry in the table of peaks: readers that need one return
    # nothing and the harness leaves them out of the line.
    needs_peak = {n for n in wanted
                  if n.split(".")[0] in ("mfu_pct", "flash_roofline")}
    assert set(result["metrics"]) == wanted - needs_peak
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] >= result["device"]["busy_s"]
    assert len(result["breakdown"]["device_ops"]) <= 10
    assert len(result["breakdown"]["idle_gaps"]) <= 10


def test_no_accelerator_is_an_error():
    cell = manifest_cells()[1][0][0]
    proc, _ = run(ROOT, "--workload", cell, "--seed", "1", "--seconds", "1",
                  "--trace", "0", rehearse=False)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def _copy(tmp_path, with_program: bool) -> str:
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("_out", "__pycache__"))
    if with_program:
        os.symlink(os.path.join(ROOT, "horovod_tpu"),
                   os.path.join(root, "horovod_tpu"))
    return root


def test_benchmark_alone_is_an_error(tmp_path):
    root = _copy(tmp_path, with_program=False)
    cell = manifest_cells()[1][0][0]
    proc, _ = run(root, "--workload", cell, "--seed", "1", "--seconds", "1",
                  "--trace", "0")
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_additions_are_files_and_entries_only(tmp_path):
    """A later PR's cell, configuration, traffic mix and per-layer metric:
    four new files, four new entries, no existing file edited."""
    root = _copy(tmp_path, with_program=True)
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "pythia410m.json")) as f:
        config = json.load(f)
    config.update(name="throwaway", num_hidden_layers=3,
                  reduced=["num_hidden_layers"])
    config["rehearsal"]["num_hidden_layers"] = 3
    with open(os.path.join(bench, "configs", "throwaway.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", "1chip_4x2k.json")) as f:
        traffic = json.load(f)
    traffic["rehearsal"].update(batch_per_chip=3, sequence_length=256)
    with open(os.path.join(bench, "traffic", "throwaway_mix.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "layer_metrics", "steps_done.py"),
              "w") as f:
        f.write("def read(run):\n    return run['steps']\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "throwaway", "source": "test",
         "file": "benchmark/configs/throwaway.json",
         "reduced": ["num_hidden_layers"], "why": "test"})
    manifest["workloads"].append(
        {"name": "throwaway_cell", "config": "throwaway",
         "traffic": "throwaway_mix", "chips": 1, "why": "test"})
    for metric in manifest["end_to_end"]:
        if metric["name"] == "tokens_per_s_chip":
            metric["workloads"].append("throwaway_cell")
    manifest["per_layer"].append(
        {"name": "steps_done", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "compiled step",
         "moves": "tokens_per_s_chip", "workloads": ["throwaway_cell"]})
    with open(path, "w") as f:
        json.dump(manifest, f)
    proc, result = run(root, "--workload", "throwaway_cell", "--seed", "5",
                       "--seconds", "1", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is True
    assert set(result["metrics"]) == {"steps_done"}
    assert result["metrics"]["steps_done"]["value"] == result["attempted"]
    first = json.loads(proc.stdout.splitlines()[0])
    assert first["samples_per_step"] == 3 * 256
    assert first["parameters"] != 131392      # three layers, not two
