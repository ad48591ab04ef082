"""What the seven readers of Trinity-Mini's attention share.

`ops/attention.py` names a banded call's kernels after the causal ones with
`_window` behind — `hvd_flash_fwd_window`, `hvd_flash_bwd_window`,
`hvd_flash_bwd_dkdv_window`, `hvd_flash_bwd_dq_window` — so the prefix readers
(`flash_fwd_time_share_pct`, `flash_bwd_time_share_pct`) count both and these
tell them apart by the whole name.  A windowed `models.Attention` sows
`attn_blocks_visited` and `attn_blocks_causal` into `intermediates`; its output
gate runs under the scope `hvd_attn_gate`.

A program with no such kernel, counter or scope (any other cell, a parent
without the window) gives None from every function here: nothing raises.
"""

import re

from benchmark import program_trace
from benchmark.layer_metrics import _hybrid

# direction -> the instruction names of that direction's kernels
WINDOW = {"fwd": re.compile(r"^hvd_flash_fwd_window(?:\.\d+)?$"),
          "bwd": re.compile(r"^hvd_flash_bwd(?:_dkdv|_dq)?_window(?:\.\d+)?$")}
FULL = {"fwd": re.compile(r"^hvd_flash_fwd(?:\.\d+)?$"),
        "bwd": re.compile(r"^hvd_flash_bwd(?:_dkdv|_dq)?(?:\.\d+)?$")}
BLOCKS_PROBE = "attn_window_blocks_visited_pct"


def kernel_time(run: dict, patterns):
    """(nanoseconds in the custom calls whose instruction name matches one of
    `patterns`, nanoseconds of all operations), mean over chips; None where
    the trace holds no such kernel."""
    program = program_trace.of_run(run)
    if not program:
        return None
    kernel = total = 0.0
    chips = max(len(program["devices"]), 1)
    for events in program["devices"].values():
        for short, _, duration in events:
            total += duration / chips
            parts = short.split("|")
            if parts[1] == "custom-call" and any(
                    p.match(parts[0]) for p in patterns):
                kernel += duration / chips
    return (kernel, total) if kernel else None


def roofline_pct(run: dict, kernel: str, names: dict, direction: str):
    """The least time the chip could take for one direction of the flash
    kernels `run["kernels"][kernel]` counts (benchmark/ops_count_trinity.py:
    the band's exact pairs, or the causal half's) — the larger of operations
    over peak FLOP/s and bytes over peak bytes/s — over the time in the custom
    calls named `names[direction]`."""
    timed, work = kernel_time(run, [names[direction]]), \
        run["kernels"].get(kernel)
    if not timed or not work or not run["peak"] \
            or not run.get("profiled_steps"):
        return None
    tokens, work = _hybrid._tokens_profiled(run), work[direction]
    least = max(work["ops"] * tokens / run["peak"]["bf16_flops_per_s"],
                work["bytes"] * tokens / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / (timed[0] / 1e9)


def blocks_probe(context: dict):
    """[[visited, causal] per windowed layer] from one forward pass outside
    the window (the builder's counter pass); None where the builder's model
    sows no such counter."""
    rows_of = getattr(context["built"], "expert_rows", None)
    if rows_of is None:
        return None
    from benchmark.reference import compare

    seen = rows_of(compare.first_device_copy(context["state"][0]),
                   compare.first_device_copy(context["pool"][0]))
    if "attn_blocks" not in seen:
        return None
    out = {"blocks": [[int(n) for n in layer]
                      for layer in seen["attn_blocks"]]}
    context["note"](attn_blocks_probe=out)
    return out
