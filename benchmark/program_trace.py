"""What the program says of itself in a traced run: its own host spans and
the scope path of every operation of the device trace.

`trace_reduce.load()` keeps the benchmark's three spans and cuts an event to
`name|opcode|kind|type`; this module reads the same `.xplane.pb` again for
what horovod_tpu writes (PERF.md section 3 has the table of names):

* `hvd.` host spans: `hvd.train_step` around every call of a
  `build_train_step` step (`jax/train.py` `_TimedStep`), with its `step_num`;
* kernel names: a Pallas kernel's custom call is the instruction
  `%<name>.<n>` (`hvd_flash_fwd`, `hvd_flash_bwd`, ...: `name=` on each
  `pallas_call` of `ops/`);
* phase scopes in each operation's `op_name` (`hvd_loss`, `hvd_optimizer`,
  `hvd_grad_exchange`, `hvd_loss_report`: `jax.named_scope` in
  `build_train_step`).  On this machine no event of the `XLA Ops` line carries
  that path (its stats are `device_offset_ps`, `device_duration_ps`, `Time
  Scale Multiplier`), so it is looked up by instruction name in the compiled
  step's text (`metadata={op_name="..."}`), which `op_names()` indexes.

The structure, plain data like `trace_reduce`'s:

    {"devices": {"/device:TPU:0": [[short name, start_ns, duration_ns], ...]},
     "program_spans": [[name, start_ns, duration_ns, step_num or None], ...]}

A program that writes none of these (the parent of the PR that added them)
gives empty lists and `None` from every reduction: nothing here raises for
lack of a name.
"""

from __future__ import annotations

import functools
import os
import re

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
SPAN_PREFIX = "hvd."
STEP_SPAN = "hvd.train_step"
PHASES = ("forward", "backward", "optimizer", "unattributed")
# A rehearsal has no device plane: run.py reads the CPU client's threads.
_REHEARSAL_PLANE = re.compile(r"^/host:CPU$")
_REHEARSAL_LINE = re.compile(r"^tf_XLA")


def load(profile) -> dict:
    devices, spans = {}, []
    planes = list(profile.planes)
    on_device = any(trace_reduce.DEVICE_PLANE.match(p.name) for p in planes)
    plane_re, line_re = ((trace_reduce.DEVICE_PLANE, trace_reduce.OPS_LINE)
                         if on_device else
                         (_REHEARSAL_PLANE, _REHEARSAL_LINE))
    for plane in planes:
        if plane_re.match(plane.name):
            for line in plane.lines:
                if line_re.match(line.name):
                    devices.setdefault(plane.name, []).extend(
                        [trace_reduce.short_name(e.name), e.start_ns,
                         e.duration_ns] for e in line.events)
        if plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        step = dict(e.stats).get("step_num")
                        spans.append([e.name, e.start_ns, e.duration_ns,
                                      None if step is None else int(step)])
    for events in devices.values():
        events.sort(key=lambda e: e[1])
    spans.sort(key=lambda e: e[1])
    return {"devices": devices, "program_spans": spans}


@functools.lru_cache(maxsize=2)
def _read(path: str) -> dict:
    return load(trace_reduce.read_xplane(path))


def of_run(run: dict):
    """The traced run's own trace, parsed once a process; None where the run
    left none."""
    try:
        path = trace_reduce.find_xplane(
            os.path.join(HERE, "_out", run["cell"]["name"]))
    except FileNotFoundError:
        return None
    return _read(path)


# ---------------------------------------------------------------------------
# Scope paths and phases.
# ---------------------------------------------------------------------------

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_names(compiled_text: str) -> dict:
    """{instruction name: op_name path} of every instruction of a compiled
    program's text that has one."""
    out = {}
    for line in compiled_text.splitlines():
        head = _INSTRUCTION.match(line)
        if head:
            path = _OP_NAME.search(line, head.end())
            if path:
                out[head.group(1)] = path.group(1)
    return out


def phase(path) -> str:
    """One rule sorts every operation by its scope path.  The optimizer's
    scope wins (the gradient exchange is beneath it); inside `hvd_loss` JAX's
    own name stack marks what autodiff transposed."""
    if not path:
        return "unattributed"
    if "hvd_optimizer" in path:
        return "optimizer"
    if "hvd_loss" in path.replace("hvd_loss_report", ""):
        return "backward" if "transpose(" in path else "forward"
    return "unattributed"


def instruction(short: str) -> str:
    return short.split("|", 1)[0]


def phase_time(program: dict, names: dict) -> dict:
    """{phase: nanoseconds on the operations line}, mean over chips; None
    where no operation's path holds a scope of the program."""
    totals = dict.fromkeys(PHASES, 0.0)
    chips = max(len(program["devices"]), 1)
    for events in program["devices"].values():
        for short, _, duration in events:
            totals[phase(names.get(instruction(short)))] += duration / chips
    if not totals["forward"] + totals["backward"] + totals["optimizer"]:
        return None
    return totals


def kernel_time(program: dict, prefix: str):
    """(nanoseconds in custom calls whose instruction is named
    `<prefix>[_...].<n>`, nanoseconds of all operations), mean over chips;
    None where the trace holds no such kernel."""
    named = re.compile(rf"^{re.escape(prefix)}(?:_\w+)?(?:\.\d+)?$")
    kernel = total = 0.0
    chips = max(len(program["devices"]), 1)
    for events in program["devices"].values():
        for short, _, duration in events:
            total += duration / chips
            parts = short.split("|")
            if parts[1] == "custom-call" and named.match(parts[0]):
                kernel += duration / chips
    if not kernel:
        return None
    return kernel, total


# ---------------------------------------------------------------------------
# The program's spans.
# ---------------------------------------------------------------------------

def step_spans(program: dict) -> list:
    return [s for s in program["program_spans"] if s[0] == STEP_SPAN]


def idle_in_step_call(program: dict):
    """(nanoseconds in which the first chip ran nothing while an
    `hvd.train_step` span was open on the host, nanoseconds of the traced
    window as `trace_reduce.window` has it); None where the program wrote
    no such span."""
    spans = step_spans(program)
    planes = sorted(program["devices"])
    if not spans or not planes or not program["devices"][planes[0]]:
        return None
    start, end = trace_reduce.window(program)
    busy = trace_reduce.merge(
        (s, s + d) for _, s, d in program["devices"][planes[0]])
    idle = trace_reduce.subtract([[start, end]], busy)
    calls = trace_reduce.merge((s, s + d) for _, s, d, _ in spans)
    outside = trace_reduce.subtract(idle, calls)
    return (trace_reduce.length(idle) - trace_reduce.length(outside),
            end - start)
