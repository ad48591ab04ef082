"""What the five readers of the hybrid decoder's layers share.

The Mamba-2 mixer (`models.ssm.Mamba2Mixer`) runs under five
`jax.named_scope`s — `hvd_ssm_in_proj`, `hvd_ssm_conv`, `hvd_ssm_scan`,
`hvd_ssm_gate_norm`, `hvd_ssm_out_proj` — and the latent sparse-expert layer
adds `hvd_moe_latent` and `hvd_moe_shared` to `_moe.SCOPES`.  They reach an
operation's `op_name` in the compiled step's text, forward and backward alike;
the device trace's events are looked up there by instruction name, as
`_moe.scope_time` does.

A program with no such scope (any other cell, a parent without the layers)
gives None from every function here: nothing raises.
"""

from benchmark import ops_count_hybrid, program_trace
from benchmark.layer_metrics import _moe
from benchmark.layer_metrics._program import OP_NAMES_PROBE


def scope_time(run: dict, needles):
    """(nanoseconds of the operations whose op_name holds one of `needles`,
    nanoseconds of all operations), mean over chips; None where none does."""
    program = program_trace.of_run(run)
    names = run["probes"].get(OP_NAMES_PROBE)
    if not program or not names:
        return None
    inside = everything = 0.0
    chips = max(len(program["devices"]), 1)
    for events in program["devices"].values():
        for short, _, duration in events:
            everything += duration / chips
            path = names["op_names"].get(
                program_trace.instruction(short)) or ""
            if any(needle in path for needle in needles):
                inside += duration / chips
    return (inside, everything) if inside else None


def share_pct(run: dict, needles):
    timed = scope_time(run, needles)
    return timed and 100.0 * timed[0] / timed[1]


def _tokens_profiled(run: dict):
    return run["profiled_steps"] * run["samples"] / run["steps"] \
        / run["chips"]


def scan_roofline_pct(run: dict):
    """The least time the chip could take for every layer's scan, forward and
    backward — the larger of its operations over peak FLOP/s and the bytes it
    cannot avoid over peak bytes/s, benchmark/ops_count_hybrid.py — over the
    time under `hvd_ssm_scan`."""
    timed, shape = scope_time(run, ["hvd_ssm_scan"]), \
        run["kernels"].get("ssm_scan")
    if not timed or not shape or not run["peak"] \
            or not run.get("profiled_steps"):
        return None
    tokens = _tokens_profiled(run)
    sizes = [shape[k] for k in ("heads", "head_dim", "groups", "state")]
    least = shape["layers"] * max(
        ops_count_hybrid.scan_kernel_ops(tokens, *sizes, shape["chunk"])
        / run["peak"]["bf16_flops_per_s"],
        ops_count_hybrid.scan_kernel_bytes(tokens, *sizes, shape["itemsize"])
        / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / (timed[0] / 1e9)


def experts_roofline_pct(run: dict):
    """`_moe.experts_roofline_pct` for non-gated experts in a latent width:
    six grouped matmuls a layer on the rows really routed (the load probe's
    count, scaled from its one batch to a step), over the time under
    `hvd_moe_experts`."""
    timed, load = _moe.scope_time(run), run["probes"].get(_moe.LOAD_PROBE)
    shape = run["kernels"].get("latent_moe_experts")
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
            ops_count_hybrid.expert_kernel_ops(
                rows, shape["latent"], shape["expert_width"])
            / run["peak"]["bf16_flops_per_s"],
            ops_count_hybrid.expert_kernel_bytes(
                rows, shape["local_experts"], shape["latent"],
                shape["expert_width"], shape["itemsize"])
            / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least * run["profiled_steps"] / seconds
