"""What the five readers of Qwen3-Next's mixers share.

The Gated DeltaNet mixer (`models.delta.DeltaMixer(gate="head")`) runs under
six `jax.named_scope`s — `hvd_gdn_in_proj`, `hvd_gdn_conv`, `hvd_gdn_gate`,
`hvd_gdn_scan` (the delta rule's four stages beneath it), `hvd_gdn_gate_norm`,
`hvd_gdn_out_proj` — which reach an operation's `op_name` in the compiled
step's text, forward and backward alike (`_hybrid.scope_time`).  The gated
attention layer's flash kernels at a head of 256 are custom calls named
`hvd_flash_fwd` and, under the split backward plan, `hvd_flash_bwd_dkdv` and
`hvd_flash_bwd_dq`, counted by instruction name.

A program with no such scope or kernel shape (any other cell, a parent without
the layers) gives None from every function here: nothing raises.
"""

from benchmark import ops_count_qwen3next, program_trace
from benchmark.layer_metrics import _hybrid


def flash_roofline_pct(run: dict, prefix: str, direction: str):
    """The least time the chip could take for the causal flash kernels of one
    direction at a head of 256 — the larger of operations over peak FLOP/s
    and bytes over peak bytes/s, benchmark/ops_count_qwen3next.py's counts —
    over the time in the custom calls named `prefix`."""
    program, kernel = program_trace.of_run(run), \
        run["kernels"].get("flash_h256")
    timed = program and program_trace.kernel_time(program, prefix)
    if not timed or not kernel or not run["peak"] \
            or not run.get("profiled_steps"):
        return None
    tokens, work = _hybrid._tokens_profiled(run), kernel[direction]
    least = max(work["ops"] * tokens / run["peak"]["bf16_flops_per_s"],
                work["bytes"] * tokens / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / (timed[0] / 1e9)


def scan_roofline_pct(run: dict):
    """The least time the chip could take for every Gated DeltaNet layer's
    delta rule, forward and backward, over the time under `hvd_gdn_scan`."""
    timed, shape = _hybrid.scope_time(run, ["hvd_gdn_scan"]), \
        run["kernels"].get("gdn_scan")
    if not timed or not shape or not run["peak"] \
            or not run.get("profiled_steps"):
        return None
    tokens = _hybrid._tokens_profiled(run)
    sizes = (shape["key_heads"], shape["value_heads"], shape["head_dim"],
             shape["head_dim"])
    least = shape["layers"] * max(
        ops_count_qwen3next.delta_rule_kernel_ops(tokens, *sizes,
                                                  shape["chunk"])
        / run["peak"]["bf16_flops_per_s"],
        ops_count_qwen3next.delta_rule_kernel_bytes(tokens, *sizes,
                                                    shape["itemsize"])
        / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / (timed[0] / 1e9)
