"""The flash kernels' share of their roofline: the least time the chip
could take for the work they are given — the larger of operations over peak
FLOP/s and bytes over peak bytes/s, both from shapes (benchmark/ops_count.py,
the backward pass's recompute counted because the kernel does it) — over the
time the trace shows in custom calls.  At head size 64 and these lengths the
operations bound it (PERF.md section 3).  Source: device trace."""

from benchmark import trace_reduce


def read(run: dict):
    trace, kernel = run["trace"], run["kernels"].get("flash")
    if not trace or not kernel or not run["peak"]:
        return None
    seconds = trace_reduce.category_time(trace).get("custom_call", 0.0) / 1e9
    if not seconds or not run.get("profiled_steps"):
        return None
    samples = run["profiled_steps"] * run["samples"] / run["steps"] \
        / run["chips"]
    least = max(kernel["ops"] * samples / run["peak"]["bf16_flops_per_s"],
                kernel["bytes"] * samples / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
