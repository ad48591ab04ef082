"""benchmark/program_trace.py and the nine readers over it: on a hand-made
program whose answers can be worked out on paper; on a small recorded one
(`recorded_program_trace.json.gz`: the wait gap of a traced
`pythia410m_1chip_4x2k` run on the v5e, PR 24 — the device step before it and
the one after, the eight `hvd.train_step` spans the host wrote meanwhile, the
same cut as `trace_reduce.load()` gave it, and the op_name of each instruction
from the step compiled for a described v5e, whose instruction names are the
chip's); and through a rehearsal of run.py."""

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import ops_count, program_trace as pt  # noqa: E402
from benchmark.layer_metrics import _program  # noqa: E402
from benchmark.run import reader  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = "/device:TPU:0"
READERS = ("step_dispatch_ms", "optimizer_time_share_pct",
           "backward_time_share_pct", "phase_unattributed_pct",
           "flash_fwd_time_share_pct", "flash_bwd_time_share_pct",
           "flash_fwd_roofline", "flash_bwd_roofline",
           "device_idle_in_step_call_pct")

STEP = "jit(shard_step)/"
HAND_NAMES = {
    "fusion.1": STEP + "jvp(hvd_loss)/Model/dot_general",
    "hvd_flash_fwd.2": STEP + "jvp(hvd_loss)/Model/attn/hvd_flash_fwd/"
    "pallas_call",
    "hvd_flash_bwd_dq.3": STEP + "transpose(jvp(hvd_loss))/Model/attn/"
    "hvd_flash_bwd_dq/pallas_call",
    "all-reduce.4": STEP + "transpose(jvp(hvd_loss))/Model/dot_general",
    "fusion.5": STEP + "hvd_optimizer/hvd_grad_exchange/div",
    "fusion.6": STEP + "hvd_loss_report/pmean",
}


def hand_made():
    return {"devices": {CHIP: [
        ["fusion.1|fusion|kOutput|f32[8]", 0, 100],
        ["hvd_flash_fwd.2|custom-call||f32[8]", 100, 50],
        ["hvd_flash_bwd_dq.3|custom-call||f32[8]", 150, 150],
        ["all-reduce.4|all-reduce||f32[8]", 300, 40],
        ["fusion.5|fusion|kLoop|f32[8]", 340, 40],
        ["fusion.6|fusion|kLoop|f32[]", 380, 10],
        ["copy.7|copy||f32[8]", 390, 10],
        # idle from 400 to 500, then the next step
        ["fusion.1|fusion|kOutput|f32[8]", 500, 100]]},
        "program_spans": [["hvd.train_step", 0, 20, 0],
                          ["hvd.train_step", 460, 60, 1],
                          ["hvd.other", 400, 10, None]]}


@pytest.mark.parametrize("path,phase", [
    (None, "unattributed"),
    ("", "unattributed"),
    (STEP + "convert_element_type", "unattributed"),
    (STEP + "hvd_loss_report/pmean", "unattributed"),
    (STEP + "jvp(hvd_loss)/Model/dot_general", "forward"),
    (STEP + "transpose(jvp(hvd_loss))/Model/dot_general", "backward"),
    (STEP + "hvd_optimizer/add", "optimizer"),
    (STEP + "hvd_optimizer/hvd_grad_exchange/psum", "optimizer"),
    (STEP + "hvd_optimizer/transpose(x)", "optimizer"),
])
def test_one_rule_sorts_every_operation(path, phase):
    assert pt.phase(path) == phase


def test_hand_made_program():
    program = hand_made()
    assert pt.phase_time(program, HAND_NAMES) == {
        "forward": 250.0, "backward": 190.0, "optimizer": 40.0,
        "unattributed": 20.0}
    assert pt.phase_time(program, {}) is None     # a program with no scopes
    assert pt.kernel_time(program, "hvd_flash_fwd") == (50.0, 500.0)
    assert pt.kernel_time(program, "hvd_flash_bwd") == (150.0, 500.0)
    assert pt.kernel_time(program, "hvd_ring_flash_fwd") is None
    assert [s[3] for s in pt.step_spans(program)] == [0, 1]
    # idle is [400, 500]; the second call is open over [460, 520]
    assert pt.idle_in_step_call(program) == (40, 600)
    assert pt.idle_in_step_call(dict(program, program_spans=[])) is None


def test_op_names_of_a_compiled_text():
    text = """HloModule jit_shard_step
%fused_computation.1 (p: f32[8]) -> f32[8] {
  ROOT %add.3 = f32[8]{0} add(f32[8]{0} %p, f32[8]{0} %p), metadata={op_name="jit(shard_step)/hvd_optimizer/add" source_file="x.py"}
}
ENTRY %main (a: f32[8]) -> f32[8] {
  %hvd_flash_fwd.2 = f32[8]{0} custom-call(f32[8]{0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(shard_step)/jvp(hvd_loss)/attn/hvd_flash_fwd/pallas_call" source_line=7}
  %copy.7 = f32[8]{0} copy(f32[8]{0} %hvd_flash_fwd.2)
  ROOT %fusion.5 = f32[8]{0} fusion(f32[8]{0} %copy.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(shard_step)/hvd_optimizer/add"}
}"""
    assert pt.op_names(text) == {
        "add.3": "jit(shard_step)/hvd_optimizer/add",
        "hvd_flash_fwd.2": "jit(shard_step)/jvp(hvd_loss)/attn/hvd_flash_fwd"
        "/pallas_call",
        "fusion.5": "jit(shard_step)/hvd_optimizer/add"}


# ---------------------------------------------------------------------------
# The recorded cut, through the readers.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "recorded_program_trace.json.gz"),
                   "rt") as f:
        return json.load(f)


def a_run(recorded, program, op_names):
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    # Two profiled steps of 4 x 2,048 tokens at the published widths.
    return {"cell": {"name": "recorded"}, "trace": recorded["trace"],
            "probes": {_program.OP_NAMES_PROBE: {"op_names": op_names}},
            "kernels": {"flash": {
                "ops": ops_count.flash_kernel_ops_per_token(2048, 1024, 16),
                "bytes": ops_count.flash_kernel_bytes_per_token(1024, 16)}},
            "peak": peak, "profiled_steps": 2, "samples": 2 * 8192,
            "steps": 2, "chips": 1}


def read_all(run):
    return {name: reader("layer_metrics", name).read(run)
            for name in READERS}


def test_recorded_program_trace(recorded, monkeypatch):
    monkeypatch.setattr(pt, "of_run", lambda run: recorded["program"])
    got = read_all(a_run(recorded, recorded["program"],
                         recorded["op_names"]))
    with open(os.path.join(HERE,
                           "recorded_program_trace.expected.json")) as f:
        expected = json.load(f)
    assert set(got) == set(expected) == set(READERS)
    assert got == pytest.approx(expected)
    # The two directions are the custom calls, and nothing else is.
    whole = reader("layer_metrics", "flash_time_share_pct").read(
        {"trace": recorded["trace"]})
    assert got["flash_fwd_time_share_pct"] + got[
        "flash_bwd_time_share_pct"] == pytest.approx(whole, abs=0.1)
    # What the figures have to look like on this chip at 2,048 tokens.
    assert got["flash_bwd_time_share_pct"] > got["flash_fwd_time_share_pct"]
    assert 0 < got["flash_fwd_roofline"] < got["flash_bwd_roofline"] < 100
    assert got["phase_unattributed_pct"] < 5
    assert got["backward_time_share_pct"] > 50


def test_a_program_that_names_nothing_reads_as_nothing(recorded,
                                                       monkeypatch):
    """The parent of the PR that added the names: instructions `attn.<n>`,
    op_names without a scope of the program, no `hvd.` span.  Every reader
    leaves its metric out and none raises."""
    import re

    bare = {"devices": {CHIP: [
        [re.sub(r"^hvd_flash_\w+?(\.\d+)?\|", r"attn\1|", e[0]), e[1], e[2]]
        for e in recorded["program"]["devices"][CHIP]]},
        "program_spans": []}
    names = {k: re.sub(r"hvd_\w+", "x", v)
             for k, v in recorded["op_names"].items()}
    monkeypatch.setattr(pt, "of_run", lambda run: bare)
    assert read_all(a_run(recorded, bare, names)) == dict.fromkeys(READERS)
    monkeypatch.setattr(pt, "of_run", lambda run: None)     # no trace at all
    assert read_all(a_run(recorded, bare, names)) == dict.fromkeys(READERS)


def test_rehearsal_prints_the_programs_own_span():
    """A traced rehearsal on the CPU client: no `XLA Ops` line and no Pallas
    custom call, but the library's `hvd.train_step` span is on the
    profiler's clock whatever the backend, and the CPU client's thunks are
    named after instructions, so the phases are sorted too (most of that
    trace is the executor's own events: unattributed)."""
    from benchmark.tests.test_rehearsal import run

    proc, result = run(ROOT, "--workload", "pythia410m_1chip_4x2k", "--seed",
                       "3000000019", "--seconds", "1", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is True
    metrics = result["metrics"]
    assert 0 < metrics["step_dispatch_ms"]["value"]
    # The probe's own compile of the step carries the phase scopes.
    shares = [metrics[name]["value"] for name in (
        "optimizer_time_share_pct", "backward_time_share_pct",
        "phase_unattributed_pct")]
    assert all(0 < share < 100 for share in shares) and sum(shares) < 100
    for name in ("flash_fwd_time_share_pct", "flash_bwd_time_share_pct",
                 "flash_fwd_roofline", "flash_bwd_roofline"):
        assert name not in metrics      # interpreted kernels are no calls
