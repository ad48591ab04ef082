"""`exit_time_share_pct`, `exit_expected_passes` and the older readers on a
hand-made step of a looped model — one layer inside the rolled loop, a pass's
head computed again in the backward loop, the exit gate and the exit loss —
whose answers can be worked out on paper."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import program_trace as pt  # noqa: E402
from benchmark.layer_metrics import _layers  # noqa: E402
from benchmark.run import reader  # noqa: E402
from benchmark.tests.test_layers import a_run  # noqa: E402

BODY = "TransformerLM/while/body/closed_call/TransformerLM.one_pass/"
FWD = "jit(shard_step)/jvp(hvd_loss)/" + BODY
BWD = "jit(shard_step)/transpose(jvp(hvd_loss))/" + BODY
AGAIN = BWD + "TransformerLM.one_pass/checkpoint/rematted_computation/"

# instruction: (op_name, nanoseconds, the column `_layers` files it under)
PROGRAM = {
    "fusion.1": (FWD + "layer_1/layer_1._forward/mixer/hvd_mlp/up/"
                 "dot_general", 60, "mlp"),
    "hvd_flash_fwd.2": (FWD + "layer_0/layer_0._forward/mixer/"
                        "hvd_attn_attend/hvd_flash_fwd/pallas_call", 50,
                        "flash"),
    "fusion.3": (FWD + "hvd_exit_gate/reduce_sum", 4, "unscoped"),
    "fusion.4": (FWD + "hvd_lm_head/dot_general", 70, "head"),
    "fusion.5": (AGAIN + "hvd_lm_head/dot_general", 70, "head"),
    "fusion.6": (AGAIN + "hvd_token_xent/exp", 20, "head"),
    "fusion.7": (BWD + "hvd_exit_gate/mul", 6, "unscoped"),
    "fusion.8": ("jit(shard_step)/jvp(hvd_loss)/hvd_exit_loss/exp", 3,
                 "unscoped"),
    "fusion.9": ("jit(shard_step)/transpose(jvp(hvd_loss))/hvd_exit_loss/"
                 "mul", 2, "unscoped"),
    "fusion.10": ("jit(shard_step)/hvd_optimizer/add", 40, "optimizer"),
    # The loops' own events, each as long as its body: the chip's trace has
    # one a direction, and the backward one's path holds `hvd_loss`.
    "while.11": (FWD[:FWD.index("/body")], 60 + 50 + 4 + 70, "unscoped"),
    "while.12": (BWD[:BWD.index("/body")], 70 + 20 + 6, "unscoped"),
}
# Every event, as `_hybrid.scope_time` sums them, and the operations alone.
TOTAL = sum(ns for _, ns, _ in PROGRAM.values())
LOOPS = sum(ns for name, (_, ns, _) in PROGRAM.items()
            if name.startswith("while"))


@pytest.fixture
def run(monkeypatch):
    program, run = a_run(PROGRAM)
    monkeypatch.setattr(pt, "of_run", lambda run: program)
    return run


def test_the_exits_share(run):
    read = reader("layer_metrics", "exit_time_share_pct").read
    # Over the operations alone: a loop's event spans its body's.
    assert read(run) == pytest.approx(100 * (4 + 6 + 3 + 2) / (TOTAL - LOOPS))
    # The heads are the head's, computed again or not, and the loop's layers
    # keep their columns: `_layers.SCOPES` has no row for the exits.  The
    # older readers count the loops' events among all operations.
    assert reader("layer_metrics", "head_time_share_pct").read(run) \
        == pytest.approx(100 * (70 + 70 + 20) / TOTAL)
    assert reader("layer_metrics", "recompute_time_share_pct").read(run) \
        == pytest.approx(100 * (70 + 20) / TOTAL)
    for name, (path, _, column) in PROGRAM.items():
        assert _layers.column_of(f"{name}|x||f32[8]", path) == column, name


def test_a_program_without_exits_reads_as_nothing(monkeypatch):
    kept = {name: (path.replace("hvd_exit_", "exit_"), ns, column)
            for name, (path, ns, column) in PROGRAM.items()}
    program, run = a_run(kept)
    monkeypatch.setattr(pt, "of_run", lambda run: program)
    read = reader("layer_metrics", "exit_time_share_pct").read
    assert read(run) is None
    monkeypatch.setattr(pt, "of_run", lambda run: None)    # no trace at all
    assert read(run) is None


def test_the_expected_passes_are_the_probes():
    metric = reader("layer_metrics", "exit_expected_passes")
    record = {"mean_p": [0.5, 0.25, 0.125, 0.125], "entropy": 1.2,
              "expected_passes": 1.875}
    assert metric.read({"probes": {metric.PROBE: record}}) == 1.875
    assert metric.read({"probes": {}}) is None
    assert metric.read({"probes": {metric.PROBE: None}}) is None

    class Built:                       # a builder of another architecture
        pass

    noted = []
    context = {"built": Built(), "state": ({},), "pool": [()],
               "note": lambda **fields: noted.append(fields)}
    assert metric.probe(context) is None and not noted
    Built.exit_distribution = staticmethod(lambda params, batch: record)
    assert metric.probe(context) == record
    assert noted == [{"exit_distribution_probe": record}]
