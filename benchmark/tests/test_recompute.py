"""`recompute_time_share_pct` and the older readers on a hand-made step of one
recomputing attention layer and one recomputing expert layer, whose answers
can be worked out on paper: the forward pass, what of it is computed again
inside the backward phase under JAX's marker (the flash kernel is not: a
recomputing layer keeps its outputs), the backward pass proper."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import program_trace as pt  # noqa: E402
from benchmark.layer_metrics import _layers, _program  # noqa: E402
from benchmark.run import reader  # noqa: E402
from benchmark.tests.test_layers import a_run  # noqa: E402

FWD = "jit(shard_step)/jvp(hvd_loss)/TransformerLM/layer_0/checkpoint/"
AGAIN = ("jit(shard_step)/transpose(jvp(hvd_loss))/TransformerLM/layer_0/"
         "checkpoint/rematted_computation/")
BWD = ("jit(shard_step)/transpose(jvp(hvd_loss))/TransformerLM/layer_0/"
       "checkpoint/")

# instruction: (op_name, nanoseconds, the column `_layers` files it under)
PROGRAM = {
    "fusion.1": (FWD + "mixer/hvd_attn_qkv/dot_general", 40, "attn_proj"),
    "hvd_flash_fwd_window.2": (FWD + "mixer/hvd_attn_attend/"
                               "hvd_flash_fwd_window/pallas_call", 50,
                               "flash"),
    "fusion.3": (AGAIN + "mixer/hvd_attn_qkv/dot_general", 40, "attn_proj"),
    "fusion.4": (AGAIN + "mixer/hvd_attn_qkv/hvd_attn_rotate/mul", 6,
                 "attn_proj"),
    "hvd_flash_bwd_dkdv_window.6": (BWD + "mixer/hvd_attn_attend/"
                                    "hvd_flash_bwd_dkdv_window/pallas_call",
                                    90, "flash"),
    "hvd_flash_bwd_dq_window.7": (BWD + "mixer/hvd_attn_attend/"
                                  "hvd_flash_bwd_dq_window/pallas_call", 60,
                                  "flash"),
    "fusion.8": (BWD + "mixer/hvd_attn_qkv/dot_general", 80, "attn_proj"),
    "fusion.9": (AGAIN.replace("layer_0", "layer_1")
                 + "mixer/hvd_moe_dispatch/gather", 14, "moe"),
    # libtpu drops its kernels' scope path, the marker with it: a grouped
    # product computed again reads as no recomputation
    "ragged-dot-none.10": ("ragged-dot-none", 30, "moe"),
    "fusion.11": ("jit(shard_step)/jvp(hvd_loss)/TransformerLM/hvd_lm_head/"
                  "dot_general", 70, "head"),
    "fusion.12": ("jit(shard_step)/hvd_optimizer/add", 40, "optimizer"),
}
TOTAL = sum(ns for _, ns, _ in PROGRAM.values())
MARKED = 40 + 6 + 14


@pytest.fixture
def run(monkeypatch):
    program, run = a_run(PROGRAM)
    monkeypatch.setattr(pt, "of_run", lambda run: program)
    return run


def test_the_recomputed_share(run):
    read = reader("layer_metrics", "recompute_time_share_pct").read
    assert read(run) == pytest.approx(100 * MARKED / TOTAL)
    # It stands inside the backward phase, and its layers keep their columns.
    assert _program.phase_share_pct(run, "backward") == pytest.approx(
        100 * (MARKED + 90 + 60 + 80) / TOTAL)
    for name, (path, _, column) in PROGRAM.items():
        assert _layers.column_of(f"{name}|x||f32[8]", path) == column, name
    assert _layers.share_pct(run, "unscoped") == 0.0


def test_a_program_that_recomputes_nothing_reads_as_nothing(monkeypatch):
    kept = {name: (path and path.replace("rematted_computation/", ""), ns,
                   column) for name, (path, ns, column) in PROGRAM.items()}
    program, run = a_run(kept)
    monkeypatch.setattr(pt, "of_run", lambda run: program)
    read = reader("layer_metrics", "recompute_time_share_pct").read
    assert read(run) is None
    monkeypatch.setattr(pt, "of_run", lambda run: None)    # no trace at all
    assert read(run) is None
    run["probes"] = {}                                     # no compiled text
    monkeypatch.setattr(pt, "of_run", lambda run: program)
    assert read(run) is None
