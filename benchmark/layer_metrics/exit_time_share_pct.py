"""Device time of a looped model's exits — `hvd_exit_gate` (the gate's
float32 product over every pass's state) and `hvd_exit_loss` (the exit
distribution, the weighing of the passes' cross-entropies and the entropy
term: `models.looped_exit_loss`), forward and backward — over the time of all
operations.  The heads' matmuls and their cross-entropies are not in it
(`head_time_share_pct`).

An operation that holds others — the rolled loop's `while`, one event a
direction spanning every event of its body — is left out of both sums: with
it the body's time would be counted twice.  The older `*_time_share_pct`
readers go through `_hybrid.scope_time`, which keeps such events in the
denominator: in a cell whose step is a loop they read about half of this
reader's share of the same time (PERF.md section 5).

A program with no such scope (any other cell, a parent without the looped
model) gives None.  Source: device trace, sorted by the compiled step's
op_name."""

from benchmark import program_trace
from benchmark.layer_metrics._program import OP_NAMES_PROBE

SCOPES = ("hvd_exit_gate", "hvd_exit_loss")
HOLDS_OTHERS = ("while", "conditional", "call")        # opcodes


def scope_time(run: dict):
    """(nanoseconds under `SCOPES`, nanoseconds of all operations), mean over
    chips, both without the events of `HOLDS_OTHERS`; None where no operation
    runs under one of `SCOPES`."""
    program = program_trace.of_run(run)
    names = run["probes"].get(OP_NAMES_PROBE)
    if not program or not names:
        return None
    inside = everything = 0.0
    for events in program["devices"].values():
        for short, _, duration in events:
            name, opcode = short.split("|")[:2]
            if opcode in HOLDS_OTHERS:
                continue
            everything += duration
            path = names["op_names"].get(name) or ""
            if any(scope in path for scope in SCOPES):
                inside += duration
    chips = max(len(program["devices"]), 1)
    return (inside / chips, everything / chips) if inside else None


def read(run: dict):
    timed = scope_time(run)
    return timed and 100.0 * timed[0] / timed[1]
