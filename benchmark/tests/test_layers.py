"""benchmark/layer_metrics/_layers.py and the nine readers over it, on a
hand-made program whose answers can be worked out on paper: a step of one
attention layer, a dense MLP inside a sparse-expert layer, a delta rule of
four stages, the head with its loss, an optimizer pass and a copy."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import program_trace as pt  # noqa: E402
from benchmark.layer_metrics import _hybrid, _layers, _program  # noqa: E402
from benchmark.run import reader  # noqa: E402

CHIP = "/device:TPU:0"
FWD = "jit(shard_step)/jvp(hvd_loss)/TransformerLM/"
BWD = "jit(shard_step)/transpose(jvp(hvd_loss))/TransformerLM/"
READERS = ("head_time_share_pct", "embed_time_share_pct",
           "mlp_time_share_pct", "attn_proj_time_share_pct",
           "model_unscoped_pct", "kda_scan_decays_time_share_pct",
           "kda_scan_chunk_time_share_pct", "kda_scan_solve_time_share_pct",
           "kda_scan_carry_time_share_pct")

# instruction: (op_name, nanoseconds, the column it must be filed under)
PROGRAM = {
    "gather.1": (FWD + "hvd_embed/embed/gather", 10, "embed"),
    "fusion.2": (BWD + "hvd_embed/embed/scatter-add", 30, "embed"),
    "fusion.3": (FWD + "layer_0/attn_norm/mul", 5, "unscoped"),
    "fusion.4": (FWD + "layer_0/attn/hvd_attn_qkv/dot_general", 40,
                 "attn_proj"),
    "fusion.5": (FWD + "layer_0/attn/hvd_attn_attend/mul", 10, "attn_proj"),
    # the kernels beneath hvd_attn_attend are flash's, by instruction name
    "hvd_flash_fwd.6": (FWD + "layer_0/attn/hvd_attn_attend/hvd_flash_fwd/"
                        "pallas_call", 50, "flash"),
    "hvd_flash_bwd.7": (BWD + "layer_0/attn/hvd_attn_attend/hvd_flash_bwd/"
                        "pallas_call", 100, "flash"),
    "fusion.8": (BWD + "layer_0/attn/hvd_attn_out/dot_general", 20,
                 "attn_proj"),
    # a dense MLP inside the shared expert: the innermost scope wins
    "fusion.9": (FWD + "layer_1/moe/hvd_moe_shared/hvd_mlp/up/dot_general",
                 60, "mlp"),
    "fusion.10": (FWD + "layer_1/moe/hvd_moe_router/dot_general", 15, "moe"),
    # libtpu drops its kernels' scope path
    "ragged-dot-none.11": ("ragged-dot-none", 25, "moe"),
    "fusion.12": (FWD + "layer_2/mixer/hvd_kda_scan/hvd_kda_scan_decays/exp",
                  8, "kda"),
    "fusion.13": (BWD + "layer_2/mixer/hvd_kda_scan/hvd_kda_scan_chunk/"
                  "dot_general", 12, "kda"),
    "fusion.14": (FWD + "layer_2/mixer/hvd_kda_scan/hvd_kda_scan_solve/"
                  "dot_general", 16, "kda"),
    "while.15": (BWD + "layer_2/mixer/hvd_kda_scan/hvd_kda_scan_carry/while",
                 24, "kda"),
    "fusion.16": (FWD + "layer_2/mixer/hvd_kda_in_proj/dot_general", 20,
                  "kda"),
    "fusion.17": (FWD + "layer_3/mixer/hvd_ssm_scan/dot_general", 7, "ssm"),
    "fusion.18": (FWD + "layer_4/mixer/hvd_mla_q_proj/dot_general", 9,
                  "mla"),
    "fusion.19": (FWD + "final_norm/mul", 5, "unscoped"),
    "fusion.20": (FWD + "hvd_lm_head/dot_general", 70, "head"),
    "fusion.21": ("jit(shard_step)/transpose(jvp(hvd_loss))/hvd_token_xent/"
                  "exp", 30, "head"),
    "fusion.22": ("jit(shard_step)/hvd_optimizer/add", 40, "optimizer"),
    "fusion.23": ("jit(shard_step)/hvd_loss_report/pmean", 4,
                  "unattributed"),
    "copy.24": (None, 6, "unattributed"),
}
TOTAL = sum(ns for _, ns, _ in PROGRAM.values())


def a_run(program=PROGRAM):
    events, clock = [], 0
    for name, (_, ns, _) in program.items():
        opcode = "custom-call" if name.startswith(("hvd_flash", "ragged")) \
            else name.split(".")[0]
        events.append([f"{name}|{opcode}||f32[8]", clock, ns])
        clock += ns
    names = {name: path for name, (path, _, _) in program.items() if path}
    return ({"devices": {CHIP: events}, "program_spans": []},
            {"cell": {"name": "hand_made"},
             "probes": {_program.OP_NAMES_PROBE: {"op_names": names}}})


@pytest.fixture
def run(monkeypatch):
    program, run = a_run()
    monkeypatch.setattr(pt, "of_run", lambda run: program)
    return run


@pytest.mark.parametrize("path,family", [
    (None, None), ("", None), (FWD + "layer_0/attn_norm/mul", None),
    (FWD + "hvd_lm_head/dot_general", "head"),
    (BWD + "hvd_embed/embed/scatter-add", "embed"),
    (FWD + "layer_0/hvd_mlp/up/dot_general", "mlp"),
    (FWD + "layer_0/mlp_norm/mul", None),        # a module's name, no scope
    (FWD + "layer_0/attn/hvd_attn_out/dot_general", "attn_proj"),
    (FWD + "layer_1/moe/hvd_moe_shared/hvd_mlp/up/dot_general", "mlp"),
    (FWD + "layer_1/hvd_mlp/moe/hvd_moe_shared/dot_general", "moe"),
    (FWD + "layer_4/mixer/hvd_mla_attend/hvd_flash_fwd/pallas_call", "mla"),
    (FWD + "layer_2/mixer/hvd_kda_scan/hvd_kda_scan_carry/while", "kda"),
])
def test_the_innermost_layer_scope_wins(path, family):
    assert _layers.layer_of(path) == family


def test_no_new_scope_holds_an_older_readers_needle():
    """`_hybrid.scope_time` matches substrings: a name of this PR inside
    another family's needle (or the reverse) would be counted twice."""
    older = ("hvd_moe_", "hvd_ssm_", "hvd_kda_", "hvd_mla_", "hvd_optimizer",
             "hvd_loss", "hvd_flash")
    for scope in ("hvd_embed", "hvd_lm_head", "hvd_mlp", "hvd_attn_qkv",
                  "hvd_attn_attend", "hvd_attn_out"):
        assert not any(n in scope or scope in n for n in older), scope
    for stage in _layers.STAGES:
        assert "hvd_kda_scan" in f"hvd_kda_scan_{stage}"


def test_every_event_is_filed_once(run):
    for name, (path, _, column) in PROGRAM.items():
        assert _layers.column_of(f"{name}|x||f32[8]", path) == column, name
    columns, stages, everything = _layers.sorted_time(run)
    assert set(columns) == set(_layers.COLUMNS)
    assert everything == TOTAL == pytest.approx(sum(columns.values()))
    want = dict.fromkeys(_layers.COLUMNS, 0.0)
    for _, ns, column in PROGRAM.values():
        want[column] += ns
    assert columns == want
    assert columns["attn_proj"] == 70 and columns["flash"] == 150
    assert columns["unscoped"] == 10 and columns["unattributed"] == 10
    assert stages == {"decays": 8, "chunk": 12, "solve": 16, "carry": 24}


def test_the_readers(run):
    got = {name: reader("layer_metrics", name).read(run) for name in READERS}
    assert got == pytest.approx({
        "head_time_share_pct": 100 * 100 / TOTAL,
        "embed_time_share_pct": 100 * 40 / TOTAL,
        "mlp_time_share_pct": 100 * 60 / TOTAL,
        "attn_proj_time_share_pct": 100 * 70 / TOTAL,
        "model_unscoped_pct": 100 * 10 / TOTAL,
        "kda_scan_decays_time_share_pct": 100 * 8 / TOTAL,
        "kda_scan_chunk_time_share_pct": 100 * 12 / TOTAL,
        "kda_scan_solve_time_share_pct": 100 * 16 / TOTAL,
        "kda_scan_carry_time_share_pct": 100 * 24 / TOTAL})
    # The stages partition hvd_kda_scan, which the older reader reads whole.
    assert sum(got[f"kda_scan_{s}_time_share_pct"] for s in _layers.STAGES) \
        == pytest.approx(_hybrid.share_pct(run, ["hvd_kda_scan"]))
    # With the optimizer's and the unattributed share, everything, once.
    columns, _, everything = _layers.sorted_time(run)
    assert sum(_layers.share_pct(run, c) or 0.0 for c in _layers.COLUMNS) \
        == pytest.approx(100.0)
    assert _layers.share_pct(run, "optimizer") == pytest.approx(
        _program.phase_share_pct(run, "optimizer"))


def test_a_model_that_leaves_nothing_out_reads_zero_not_nothing(monkeypatch):
    named = {k: v for k, v in PROGRAM.items() if v[2] != "unscoped"}
    program, run = a_run(named)
    monkeypatch.setattr(pt, "of_run", lambda run: program)
    assert reader("layer_metrics", "model_unscoped_pct").read(run) == 0.0
    assert reader("layer_metrics", "head_time_share_pct").read(run) > 0


def test_a_parents_program_reads_as_nothing(monkeypatch):
    """The parent of the PR that added the names has `hvd_token_xent`, the
    sparse-expert and mixer scopes, and none of the dense layers': every
    reader leaves its metric out, the gauge too, and none raises."""
    import re

    parent = {
        name: (path and re.sub(
            r"hvd_(embed|lm_head|mlp|attn_\w+|kda_scan_\w+)/", "", path),
            ns, column)
        for name, (path, ns, column) in PROGRAM.items()}
    assert "hvd_token_xent" in parent["fusion.21"][0]
    program, run = a_run(parent)
    monkeypatch.setattr(pt, "of_run", lambda run: program)
    assert {name: reader("layer_metrics", name).read(run)
            for name in READERS} == dict.fromkeys(READERS)
    assert _hybrid.share_pct(run, ["hvd_kda_scan"]) > 0    # the older reader
    monkeypatch.setattr(pt, "of_run", lambda run: None)    # no trace at all
    assert {name: reader("layer_metrics", name).read(run)
            for name in READERS} == dict.fromkeys(READERS)
    run["probes"] = {}                                     # no compiled text
    monkeypatch.setattr(pt, "of_run", lambda run: program)
    assert {name: reader("layer_metrics", name).read(run)
            for name in READERS} == dict.fromkeys(READERS)
