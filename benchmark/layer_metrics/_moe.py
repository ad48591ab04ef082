"""What the four readers of the sparse-expert layer share.

The layer (`models.SparseExperts`) runs under four `jax.named_scope`s —
`hvd_moe_router`, `hvd_moe_dispatch`, `hvd_moe_experts`, `hvd_moe_combine` —
which reach an operation's `op_name` in the compiled step's text, forward
(`jvp(hvd_loss)/.../hvd_moe_experts/...`) and backward alike; the device
trace's events are looked up there by instruction name, as the phase readers
do (`_program.op_names_probe`).  libtpu names its grouped-matmul kernels
itself — instruction `%ragged-dot-none.<n>`, op_name `ragged-dot-none`, and
`%ragged-dot-metadata.<n>` for the tile schedule — and drops the scope path,
so those count under `hvd_moe_experts` by instruction name.

A program with no such scope (any other cell, the parent of the PR that
added them) gives None from every function here: nothing raises.
"""

import re

from benchmark import ops_count_moe, program_trace
from benchmark.layer_metrics._program import OP_NAMES_PROBE

SCOPES = ("hvd_moe_router", "hvd_moe_dispatch", "hvd_moe_experts",
          "hvd_moe_combine")
_GROUPED_MATMUL = re.compile(r"^ragged-dot-")
LOAD_PROBE = "moe_load_max_over_mean"


def scope_of(instruction: str, names: dict):
    """The layer's scope an operation ran under, or None."""
    if _GROUPED_MATMUL.match(instruction):
        return "hvd_moe_experts"
    path = names.get(instruction) or ""
    for scope in SCOPES:
        if scope in path:
            return scope
    return None


def scope_time(run: dict):
    """({scope: nanoseconds}, nanoseconds of all operations), mean over
    chips; None where no operation ran under a scope of the layer."""
    program = program_trace.of_run(run)
    names = run["probes"].get(OP_NAMES_PROBE)
    if not program or not names:
        return None
    totals, everything = dict.fromkeys(SCOPES, 0.0), 0.0
    chips = max(len(program["devices"]), 1)
    for events in program["devices"].values():
        for short, _, duration in events:
            everything += duration / chips
            scope = scope_of(program_trace.instruction(short),
                             names["op_names"])
            if scope:
                totals[scope] += duration / chips
    if not sum(totals.values()):
        return None
    return totals, everything


def share_pct(run: dict, scopes):
    timed = scope_time(run)
    if not timed:
        return None
    return 100.0 * sum(timed[0][s] for s in scopes) / timed[1]


def load_probe(context: dict):
    """Rows per local expert of every layer, for the first batch of the pool
    and the weights as the window left them: one forward pass outside the
    window.  None where the builder has no sparse-expert model."""
    rows_of = getattr(context["built"], "expert_rows", None)
    if rows_of is None:
        return None
    from benchmark.reference import compare

    seen = rows_of(compare.first_device_copy(context["state"][0]),
                   compare.first_device_copy(context["pool"][0]))
    rows = [[int(n) for n in layer]
            for layer in seen["rows_per_local_expert"]]
    out = {"rows_per_local_expert": rows,
           "rows_over_bound": int(seen["rows_over_bound"].sum())}
    context["note"](moe_load_probe=out)
    return out


def experts_roofline_pct(run: dict):
    """The least time the chip could take for the nine grouped matmuls of
    every layer on the rows really routed (the load probe's count, scaled
    from its one batch to a step) — the larger of operations over peak FLOP/s
    and bytes over peak bytes/s, benchmark/ops_count_moe.py — over the time
    under `hvd_moe_experts`."""
    timed, load = scope_time(run), run["probes"].get(LOAD_PROBE)
    shape = run["kernels"].get("moe_experts")
    if not timed or not load or not shape or not run["peak"] \
            or not run.get("profiled_steps"):
        return None
    seconds = timed[0]["hvd_moe_experts"] / 1e9
    if not seconds:
        return None
    least = 0.0
    for layer in load["rows_per_local_expert"]:
        rows = sum(layer)
        least += max(
            ops_count_moe.expert_kernel_ops(
                rows, shape["hidden"], shape["expert_width"])
            / run["peak"]["bf16_flops_per_s"],
            ops_count_moe.expert_kernel_bytes(
                rows, shape["local_experts"], shape["hidden"],
                shape["expert_width"], shape["itemsize"])
            / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least * run["profiled_steps"] / seconds
