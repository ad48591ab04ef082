"""What the nine readers of the program's own names share: the traced run's
`benchmark.program_trace` structure, and the arithmetic over it."""

import statistics

from benchmark import ops_count, program_trace

# The reader whose probe keeps the compiled step's {instruction: op_name}.
OP_NAMES_PROBE = "optimizer_time_share_pct"


def op_names_probe(context: dict) -> dict:
    """{instruction: op_name} of the step, from a compile of this program's
    own text.  Not from the executable that ran: JAX's persistent cache keys
    a program without its metadata, so that one may have been compiled from
    another checkout's text (the parent's, whose op_names hold no scope) and
    an identical `lower()` returns it from memory.  The same step is built
    again (a new jit, the same HLO and so the same instruction names) and
    compiled past the cache: metadata in the key, nothing written back.  A
    cold compile of the step, after the window, in a traced run only."""
    jax = context["jax"]
    from jax.sharding import NamedSharding, PartitionSpec

    built, pool = context["build"](context["devices"])
    replicated = NamedSharding(built.mesh, PartitionSpec())
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=replicated),
        jax.eval_shape(built.init_state))
    past_the_cache = {"jax_compilation_cache_include_metadata_in_key": True,
                      "jax_persistent_cache_min_compile_time_secs":
                      float("inf")}
    saved = {name: getattr(jax.config, name) for name in past_the_cache}
    for name, value in past_the_cache.items():
        jax.config.update(name, value)
    try:
        text = built.step.lower(
            state[0], state[1],
            pool[0] + tuple(state[2:])).compile().as_text()
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)
    names = program_trace.op_names(text)
    context["note"](op_names_probe={
        "instructions_with_op_name": len(names),
        "in_a_scope_of_the_program": sum(
            program_trace.phase(path) != "unattributed"
            for path in names.values())})
    return {"op_names": names}


def phase_share_pct(run: dict, phase: str):
    program = program_trace.of_run(run)
    names = run["probes"].get(OP_NAMES_PROBE)
    if not program or not names:
        return None
    totals = program_trace.phase_time(program, names["op_names"])
    if totals is None:
        return None
    return 100.0 * totals[phase] / sum(totals.values())


def kernel_share_pct(run: dict, prefix: str):
    program = program_trace.of_run(run)
    timed = program and program_trace.kernel_time(program, prefix)
    if not timed:
        return None
    return 100.0 * timed[0] / timed[1]


def flash_roofline_pct(run: dict, prefix: str, backward: bool):
    """`flash_roofline`'s arithmetic for one direction: the kernels' work
    (`run["kernels"]["flash"]`) splits 1 : FLASH_BACKWARD_OVER_FORWARD in
    operations and 4 : 8 in bytes (benchmark/ops_count.py)."""
    program, kernel = program_trace.of_run(run), run["kernels"].get("flash")
    timed = program and program_trace.kernel_time(program, prefix)
    if not timed or not kernel or not run["peak"] \
            or not run.get("profiled_steps"):
        return None
    ratio = ops_count.FLASH_BACKWARD_OVER_FORWARD
    ops_share = (ratio if backward else 1.0) / (1.0 + ratio)
    bytes_share = (8.0 if backward else 4.0) / 12.0
    samples = run["profiled_steps"] * run["samples"] / run["steps"] \
        / run["chips"]
    least = max(
        kernel["ops"] * ops_share * samples / run["peak"]["bf16_flops_per_s"],
        kernel["bytes"] * bytes_share * samples
        / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / (timed[0] / 1e9)


def step_dispatch_ms(run: dict):
    program = program_trace.of_run(run)
    spans = program and program_trace.step_spans(program)
    if not spans:
        return None
    return statistics.median(d for _, _, d, _ in spans) / 1e6


def idle_in_step_call_pct(run: dict):
    program = program_trace.of_run(run)
    covered = program and program_trace.idle_in_step_call(program)
    if not covered:
        return None
    return 100.0 * covered[0] / covered[1]
