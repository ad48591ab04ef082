"""What the six readers of Ling-3.0-flash's mixers share.

The Kimi-delta mixer (`models.delta.DeltaMixer`) runs under six
`jax.named_scope`s — `hvd_kda_in_proj`, `hvd_kda_conv`, `hvd_kda_gate`,
`hvd_kda_scan`, `hvd_kda_gate_norm`, `hvd_kda_out_proj` — and latent attention
(`models.transformer.LatentAttention`) under four — `hvd_mla_q_proj`,
`hvd_mla_kv_latent`, `hvd_mla_attend`, `hvd_mla_out_proj`.  They reach an
operation's `op_name` in the compiled step's text, forward and backward alike
(`_hybrid.scope_time`); the flash kernels beneath `hvd_mla_attend` are custom
calls named `hvd_flash_fwd`, `hvd_flash_bwd_dkdv`, `hvd_flash_bwd_dq`, counted
by instruction name whether or not their `op_name` keeps the scope.

A program with no such scope or kernel shape (any other cell, a parent without
the layers) gives None from every function here: nothing raises.
"""

import re

from benchmark import ops_count_ling, program_trace
from benchmark.layer_metrics import _hybrid
from benchmark.layer_metrics._program import OP_NAMES_PROBE

_FLASH = re.compile(r"^hvd_flash_(fwd|bwd)")


def latent_attention_share_pct(run: dict):
    """Device time of every operation under an `hvd_mla_` scope and of every
    flash kernel, over the time of all operations; None where no operation
    ran under such a scope (then the flash kernels are another layer's)."""
    program = program_trace.of_run(run)
    names = run["probes"].get(OP_NAMES_PROBE)
    if not program or not names or not _hybrid.scope_time(run, ["hvd_mla_"]):
        return None
    inside = everything = 0.0
    for events in program["devices"].values():
        for short, _, duration in events:
            everything += duration
            instruction = program_trace.instruction(short)
            if _FLASH.match(instruction) or "hvd_mla_" in (
                    names["op_names"].get(instruction) or ""):
                inside += duration
    return 100.0 * inside / everything


def flash_roofline_pct(run: dict, prefix: str, direction: str):
    """The least time the chip could take for the two-width flash kernels of
    one direction — the larger of operations over peak FLOP/s and bytes over
    peak bytes/s, benchmark/ops_count_ling.py's exact counts — over the time
    in the custom calls named `prefix`."""
    program, kernel = program_trace.of_run(run), \
        run["kernels"].get("mla_flash")
    timed = program and program_trace.kernel_time(program, prefix)
    if not timed or not kernel or not run["peak"] \
            or not run.get("profiled_steps"):
        return None
    tokens, work = _hybrid._tokens_profiled(run), kernel[direction]
    least = max(work["ops"] * tokens / run["peak"]["bf16_flops_per_s"],
                work["bytes"] * tokens / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / (timed[0] / 1e9)


def scan_roofline_pct(run: dict):
    """The least time the chip could take for every Kimi-delta layer's delta
    rule, forward and backward, over the time under `hvd_kda_scan`."""
    timed, shape = _hybrid.scope_time(run, ["hvd_kda_scan"]), \
        run["kernels"].get("kda_scan")
    if not timed or not shape or not run["peak"] \
            or not run.get("profiled_steps"):
        return None
    tokens = _hybrid._tokens_profiled(run)
    sizes = (shape["heads"], shape["head_dim"], shape["head_dim"])
    least = shape["layers"] * max(
        ops_count_ling.delta_rule_kernel_ops(tokens, *sizes, shape["chunk"])
        / run["peak"]["bf16_flops_per_s"],
        ops_count_ling.delta_rule_kernel_bytes(tokens, *sizes,
                                               shape["itemsize"])
        / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / (timed[0] / 1e9)
