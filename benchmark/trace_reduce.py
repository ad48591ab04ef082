"""From a profiler trace to numbers: the one reduction every PR is measured by.

`read_xplane()` parses the `.xplane.pb` the JAX profiler wrote, with
`jax.profiler.ProfileData` and nothing else (no TensorFlow); `load()` turns it
into a plain structure that `json` can keep:

    {"devices": {"/device:TPU:0": [[short name, start_ns, duration_ns], ...]},
     "async":   {"/device:TPU:0": [[short name, start_ns, duration_ns], ...]},
     "spans":   [[name, start_ns, duration_ns], ...]}     # the benchmark's own

Everything else works on that structure, so it is checked on a small recorded
one (benchmark/tests/).  What a real trace of this machine looks like — which
planes are chips, which line holds the operations, how the flash kernels are
named — is written down in PERF.md section 3; the constants below follow it.

Times are nanoseconds as the profiler gives them; device planes and the host
plane share one clock.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
# On a device plane: the line whose events are the program's operations, one
# after another as the core ran them, and the line of operations that run
# beside them (asynchronous collectives and copies), where the runtime
# records one.
OPS_LINE = re.compile(r"^XLA Ops$")
ASYNC_LINE = re.compile(r"^Async XLA Ops")
# The benchmark's own host spans (run.py writes them with TraceAnnotation).
SPAN_NAMES = ("dispatch", "fetch_loss", "stage_batch")

# On this machine an operation's event is named by the whole text of its HLO
# instruction ("%fusion.23 = (f32[1024,50304]{...}, ...) fusion(...),
# kind=kOutput, calls=%fused_computation.26"): operands' names are in it, so
# a pattern over the raw text matches the wrong things.  `short_name` cuts it
# to "instruction|opcode|kind|result type" and the categories read the
# opcode and the fusion kind only.
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all", "collective-broadcast")
_MOVES = ("copy", "copy-start", "copy-done", "async-start", "async-done",
          "async-update", "transpose", "reshape", "bitcast", "slice",
          "dynamic-slice", "dynamic-update-slice", "pad", "concatenate",
          "broadcast", "convert", "gather", "scatter", "iota")


def short_name(text: str) -> str:
    """"%name = type opcode(operands), attributes" ->
    "name|opcode|kind|type without layouts".  A name that is not an HLO
    instruction (the CPU client's thunks in a rehearsal) is its own
    instruction, with its stem as opcode."""
    head = re.match(r"^%([\w.\-]+) = ", text)
    if not head:
        stem = re.sub(r"^wrapped_|[.\d]+$", "", text)
        return f"{text}|{stem}||"
    rest = text[head.end():]
    if rest.startswith("("):          # a tuple type: skip to its closing
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        result, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        result, _, rest = rest.partition(" ")
    opcode = re.match(r"([\w\-]+)\(", rest)
    kind = re.search(r"\bkind=(\w+)", rest)
    result = re.sub(r"\{[^{}]*\}|/\*.*?\*/", "", result)
    return "|".join([head.group(1), opcode.group(1) if opcode else "",
                     kind.group(1) if kind else "", result[:80]])


def categorize(short: str) -> str:
    """The category of an operation from `short_name`'s opcode and kind.
    On a TPU XLA turns every dot into a convolution and fuses elementwise
    consumers into its output: a `kOutput` (or `kConvolution`) fusion is a
    matmul or a convolution with its epilogue, `kLoop`/`kInput` fusions are
    elementwise passes and reductions over HBM."""
    parts = short.split("|")
    opcode, kind = (parts + ["", ""])[1:3]
    base = re.sub(r"-(start|done|update)$", "", opcode)
    if base in _COLLECTIVES:
        return "collective"
    if opcode == "custom-call":
        return "custom_call"
    if opcode in ("convolution", "dot") or kind in ("kOutput",
                                                    "kConvolution"):
        return "matmul_conv"
    if opcode == "fusion":
        return "fusion_other"
    if opcode in _MOVES:
        return "data_movement"
    return "other"


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def read_xplane(path: str):
    """The profile, parsed once; `summarize` and `load` both take it."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def summarize(profile, samples: int = 12) -> dict:
    """Planes, their lines, how many events each holds and a few names: what
    to look at by hand before trusting the constants above."""
    out = {}
    for plane in profile.planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            names = {}
            for event in events:
                names[event.name] = names.get(event.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:samples]
            lines[line.name] = {"events": len(events), "names": top}
        out[plane.name] = lines
    return out


def load(profile, device_plane=DEVICE_PLANE, ops_line=OPS_LINE) -> dict:
    devices, background, spans = {}, {}, []
    for plane in profile.planes:
        if device_plane.match(plane.name):
            for line in plane.lines:
                if ops_line.match(line.name):
                    target = devices
                elif ASYNC_LINE.match(line.name):
                    target = background
                else:
                    continue
                target.setdefault(plane.name, []).extend(
                    [short_name(e.name), e.start_ns, e.duration_ns]
                    for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events if e.name in SPAN_NAMES)
    for events in list(devices.values()) + list(background.values()):
        events.sort(key=lambda e: e[1])
    spans.sort(key=lambda e: e[1])
    return {"devices": devices, "async": background, "spans": spans}


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load_saved(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# Interval arithmetic.
# ---------------------------------------------------------------------------

def merge(intervals):
    """Sorted, disjoint union of (start, end) pairs."""
    out = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def length(merged) -> float:
    return sum(end - start for start, end in merged)


def subtract(merged_a, merged_b):
    """The part of union A that no interval of union B covers."""
    out, j = [], 0
    for start, end in merged_a:
        cursor = start
        while j < len(merged_b) and merged_b[j][1] <= cursor:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < end:
            if merged_b[k][0] > cursor:
                out.append([cursor, merged_b[k][0]])
            cursor = max(cursor, merged_b[k][1])
            k += 1
        if cursor < end:
            out.append([cursor, end])
    return out


def _intervals(events):
    return [(start, start + duration) for _, start, duration in events]


# ---------------------------------------------------------------------------
# Reductions.  All take the structure `load()` returns.
# ---------------------------------------------------------------------------

def window(trace: dict):
    """(start, end) of the traced steady window: first operation's start to
    last operation's end over all chips."""
    starts = [events[0][1] for events in trace["devices"].values() if events]
    ends = [max(s + d for _, s, d in events)
            for events in trace["devices"].values() if events]
    if not starts:
        return None
    return min(starts), max(ends)


def busy(trace: dict) -> dict:
    """Per chip: nanoseconds in which an operation ran on the core (union of
    the operations line).  The background line is left out: its events span
    from a copy's or a collective's start to its done, whether or not
    anything moved in between, and would paper over every gap."""
    return {plane: length(merge(_intervals(events)))
            for plane, events in trace["devices"].items()}


def category_time(trace: dict) -> dict:
    """{category: nanoseconds on the operations line}, mean over chips."""
    totals = {}
    chips = max(len(trace["devices"]), 1)
    for events in trace["devices"].values():
        for name, _, duration in events:
            key = categorize(name)
            totals[key] = totals.get(key, 0.0) + duration / chips
    return totals


def exposed_collective(trace: dict) -> float:
    """Nanoseconds, mean over chips, in which a collective operation ran on
    a chip while no other operation ran there.  On the operations line a
    collective's event is the time the core itself spends in it (issuing it,
    or waiting for it to finish): the core runs one operation at a time, so
    that time is not hidden behind compute.  A collective that proceeds in
    the background shows on the background line, and only the part of it
    that no compute operation covers counts."""
    total = 0.0
    for plane, events in trace["devices"].items():
        compute = merge(_intervals(
            e for e in events if categorize(e[0]) != "collective"))
        collective = merge(_intervals(
            [e for e in events if categorize(e[0]) == "collective"]
            + [e for e in trace["async"].get(plane, [])
               if categorize(e[0]) == "collective"]))
        total += length(subtract(collective, compute))
    return total / max(len(trace["devices"]), 1)


def top_ops(trace: dict, n: int = 10):
    """[[name, seconds]]: the operations with most time, mean over chips."""
    totals = {}
    chips = max(len(trace["devices"]), 1)
    for events in trace["devices"].values():
        for name, _, duration in events:
            totals[name] = totals.get(name, 0.0) + duration / chips
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(trace: dict, n: int = 5):
    """[[what the host was doing, seconds]]: the longest stretches in which
    the first chip ran nothing, each named after the benchmark's own span
    that covers most of it (or "no_span")."""
    planes = sorted(trace["devices"])
    if not planes:
        return []
    plane = planes[0]
    merged = merge(_intervals(trace["devices"][plane]))
    gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])]
    gaps.sort(reverse=True)
    out = []
    for duration, start, end in gaps[:n]:
        best, best_cover = "no_span", 0.0
        for name, span_start, span_duration in trace["spans"]:
            cover = min(end, span_start + span_duration) - max(start,
                                                               span_start)
            if cover > best_cover:
                best, best_cover = name, cover
        out.append([best, duration / 1e9])
    return out
