"""What the nine readers of the compiled step's table of collectives share.

`horovod_tpu/jax/train.py` `_TimedStep.collectives()` is the table of the
executable that ran: one entry a collective of the step — a start and a done
(libtpu's `async-collective-start[.N]` / `-done[.N]` fusions, XLA's plain
pairs) or one synchronous instruction, the fusions between a pair that carry
its state, the bytes a chip hands in, the op_name that says whose value
travelled, and a role (`gradient`, `model`, `report`).  Its names are the
compiled text's instruction names, which are the names of the events on a
device plane's `XLA Ops` line (`benchmark/program_trace.py`): on that line a
start's, a done's and a synchronous collective's event is the time the CORE
spends in it, and what runs between a start and its done runs beside the
exchange.

One probe (`exchange_wait_pct` owns it) copies the table after the window —
the text read the step no longer makes in its first call — and notes, for
whoever reads the run's output by hand, where a step's exchange time goes
(`breakdown`).  The readers join the table to `program_trace.of_run(run)`'s
events by instruction name, mean over chips, over the profiled steps.

A program without the table (a parent of PR 68), a step that holds no
executable of its own (one device: an empty table; a rehearsal's CPU mesh)
and a run without a trace give None from every function here: nothing raises.
"""

import os
import time

from benchmark import program_trace, trace_reduce

PROBE = "exchange_wait_pct"


def probe(context: dict):
    collectives = getattr(context["built"].step, "collectives", None)
    if collectives is None:
        return None
    began = time.perf_counter()
    try:
        table = [dict(entry) for entry in collectives()]
    except ValueError:          # the jit's own call: no executable is held
        return None
    out = {"table": table, "read_s": time.perf_counter() - began}
    try:
        # The newest trace under _out/ is this run's: run.py wrote it
        # before the probes (the readers find the same file by the cell).
        program = program_trace._read(trace_reduce.find_xplane(
            os.path.join(program_trace.HERE, "_out")))
    except FileNotFoundError:
        program = None
    context["note"](exchange_probe=dict(
        breakdown(table, program) or {}, entries=len(table),
        read_s=round(out["read_s"], 3)))
    return out


def _table(run: dict):
    probed = run["probes"].get(PROBE)
    return (probed and probed["table"]) or None


def _joined(run: dict):
    """(the table, the run's device events) where both exist."""
    table, program = _table(run), program_trace.of_run(run)
    if not table or not program or not any(program["devices"].values()):
        return None
    return table, program


def _by_instruction(events) -> dict:
    """{instruction: [(start_ns, duration_ns), ...]} in the line's order."""
    out = {}
    for short, start, duration in events:
        out.setdefault(program_trace.instruction(short), []).append(
            (start, duration))
    return out


def _core_names(entry: dict) -> list:
    """The instructions of an entry in which the core itself spends time."""
    return [name for name in (entry["start"], entry["done"],
                              entry["instruction"]) if name]


def _flights(entry: dict, seen: dict) -> list:
    """[(begin_ns, end_ns)] an entry's collective was in flight on one chip:
    a start's beginning to the end of the first done that begins after it; a
    synchronous one's own event."""
    if not entry["asynchronous"]:
        return [(s, s + d) for s, d in seen.get(entry["instruction"], ())]
    out, dones = [], iter(seen.get(entry["done"], ()))
    for begin, _ in seen.get(entry["start"], ()):
        for done, lasted in dones:
            if done >= begin:
                out.append((begin, done + lasted))
                break
    return out


def wait_pct(run: dict, role=None):
    """Core time in the table's starts, dones and synchronous collectives
    (those of `role` alone, where given) / the traced window, mean over
    chips."""
    joined = _joined(run)
    if joined is None:
        return None
    table, program = joined
    names = {name for entry in table if role in (None, entry["role"])
             for name in _core_names(entry)}
    start, end = trace_reduce.window(program)
    waited = sum(duration for events in program["devices"].values()
                 for short, _, duration in events
                 if program_trace.instruction(short) in names)
    return 100.0 * waited / len(program["devices"]) / (end - start)


def ici_pct(run: dict):
    """The share of a chip's interconnect the all-reduces reach while they
    are in flight: 2 (n - 1) / n times their bytes (a ring's traffic a chip)
    over `peaks.json`'s `ici_bits_per_s`, over the union of their in-flight
    intervals; mean over chips."""
    joined = _joined(run)
    peak = (run.get("peak") or {}).get("ici_bits_per_s")
    if joined is None or not peak or run["chips"] < 2:
        return None
    table, program = joined
    reduces = [entry for entry in table if entry["op"] == "all-reduce"]
    ring = 2.0 * (run["chips"] - 1) / run["chips"]
    shares = []
    for events in program["devices"].values():
        seen = _by_instruction(events)
        flights, sent = [], 0
        for entry in reduces:
            spans = _flights(entry, seen)
            flights += spans
            sent += entry["bytes"] * len(spans)
        in_flight_ns = trace_reduce.length(trace_reduce.merge(flights))
        if in_flight_ns:
            shares.append(ring * sent / (peak / 8.0) / (in_flight_ns / 1e9))
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)


def megabytes(run: dict):
    """Bytes a chip hands to collectives a step, the table's sum, in MB."""
    table = _table(run)
    return table and sum(entry["bytes"] for entry in table) / 1e6


def async_pct(run: dict):
    """The share of those bytes that travel asynchronously."""
    table = _table(run)
    total = table and sum(entry["bytes"] for entry in table)
    if not total:
        return None
    return 100.0 * sum(entry["bytes"] for entry in table
                       if entry["asynchronous"]) / total


def breakdown(table: list, program):
    """Where a step's exchange time goes on the first chip, in ms a step:
    the core's time in starts, dones and synchronous collectives, the same
    by role, the carriers by fusion kind and by the phase their name sorts
    under (`program_trace.phase`), the dones the core waits longest
    in by the op_name that says whose value they bring, and the exchange's
    place in the step (from the step's first operation, which is the first
    event of the trace: the loop has waited for the device before it).  None
    without a table or a device line."""
    planes = program and sorted(p for p, e in program["devices"].items() if e)
    if not table or not planes:
        return None
    events = program["devices"][planes[0]]
    seen = _by_instruction(events)
    first = program_trace.instruction(events[0][0])
    begins = [s for s, _ in seen[first]]
    steps = len(begins)

    def ms(names):
        return sum(d for name in names for _, d in seen.get(name, ())) \
            / steps / 1e6

    def field(name):
        return [e[name] for e in table if e[name]]

    roles = {}
    for entry in table:
        role = roles.setdefault(entry["role"], {"entries": 0, "ms": 0.0,
                                                "mb": 0.0})
        role["entries"] += 1
        role["ms"] += ms(_core_names(entry))
        role["mb"] += entry["bytes"] / 1e6
    kinds = {program_trace.instruction(short): short.split("|")[2]
             for short, _, _ in events}
    carriers = {}       # fusion kind x phase of the name -> fusions, ms
    for name, path in {(n, p) for e in table for n, p in zip(
            e["carriers"], e["carrier_op_names"])}:
        key = f"{kinds.get(name, 'not in the trace')} {program_trace.phase(path)}"
        group = carriers.setdefault(key, {"fusions": 0, "ms": 0.0})
        group["fusions"] += 1
        group["ms"] += ms([name])
    dones = sorted(((ms([e["done"]]), e["op_name"], e["bytes"])
                    for e in table if e["done"]), reverse=True)[:5]
    flights = sorted(span for e in table for span in _flights(e, seen))
    bounds = list(zip(begins, begins[1:] + [float("inf")]))

    def place(moments, pick):
        """Mean ms from a step's first operation to `pick` (min, max) of
        the moments inside that step; None where no step holds one."""
        gaps = [pick(inside) - begin for begin, end in bounds
                if (inside := [m for m in moments if begin <= m < end])]
        return sum(gaps) / len(gaps) / 1e6 if gaps else None

    return {
        "steps": steps,
        "step_ms": (begins[-1] - begins[0]) / max(steps - 1, 1) / 1e6,
        "ms_in_starts": ms(field("start")), "ms_in_dones": ms(field("done")),
        "ms_in_synchronous": ms(field("instruction")),
        "ms_in_flight": trace_reduce.length(trace_reduce.merge(flights))
        / steps / 1e6,
        "roles": roles, "carriers_by_kind_and_phase": carriers,
        "longest_dones_ms_opname_bytes": dones,
        "first_start_ms_into_step": place(
            [s for name in field("start") for s, _ in seen.get(name, ())],
            min),
        "last_flash_bwd_end_ms_into_step": place(
            [s + d for name, spans in seen.items()
             if name.startswith("hvd_flash_bwd") for s, d in spans], max),
        "last_done_end_ms_into_step": place([e for _, e in flights], max)}
