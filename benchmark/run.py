#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics with `--trace 0`, its
per-layer metrics with `--trace 1`), `device`, and `breakdown` when traced.
Everything else worth reading is printed on earlier lines, one JSON object
each.  No TPU, or another number of chips than the cell asks for, is an error:
nothing here runs on a CPU (`--rehearse 1`, which the tests use to walk the
control flow at a tiny size, stamps its line and is no measurement).

This file knows no cell, configuration, traffic mix or metric by name.  It
finds, by the names in BENCHMARK.json: the configuration's file and in it the
name of a builder (benchmark/builders/), the traffic file
(benchmark/traffic/<traffic>.json), and one reader per metric
(benchmark/end_to_end_metrics/<name>.py, benchmark/layer_metrics/<name>.py; a
name `reader.variant` uses `reader.py`, so that one reader can move another
end-to-end metric in another cell).
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
PROFILED_STEPS = 16
PROBE_STEPS = 20
# The compiler's count of the step's operations over the count from shapes
# (attention left out: Pallas calls are invisible to the compiler).  Under
# 0.9 the program does less arithmetic than the model requires; over 1.6 it
# recomputes more than a third of it, and the count no longer describes it.
OPS_AGREEMENT = (0.9, 1.6)


def note(**fields) -> None:
    print(json.dumps(fields), flush=True)


# ---------------------------------------------------------------------------
# The manifest and the cell's files.
# ---------------------------------------------------------------------------

def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, rehearse: bool) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in "
                         f"BENCHMARK.json; it has {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if rehearse:
        config = dict(config, **config.get("rehearsal", {}))
        traffic = dict(traffic, **traffic.get("rehearsal", {}))
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in manifest["end_to_end"]
                           if applies(m, workload)],
            "per_layer": [m for m in manifest["per_layer"]
                          if applies(m, workload)]}


def reader(package: str, metric_name: str):
    return importlib.import_module(
        f"benchmark.{package}.{metric_name.split('.')[0]}")


def read_metrics(package: str, metrics: list, run: dict) -> dict:
    out = {}
    for metric in metrics:
        value = reader(package, metric["name"]).read(run)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


# ---------------------------------------------------------------------------
# JAX, the device and the compile cache.
# ---------------------------------------------------------------------------

def place_compile_cache() -> str:
    """The rule of horovod_tpu/common/compile_cache.py: where the variable is
    set JAX's own handling of it is all there is; where it is not, one fixed
    directory inside the checkout.  Before JAX is imported."""
    if not os.environ.get(CACHE_ENV):
        os.environ[CACHE_ENV] = os.path.join(ROOT, ".jax_compilation_cache")
    return os.environ[CACHE_ENV]


def claim_devices(chips: int, rehearse: bool):
    import jax

    devices = jax.devices()
    if rehearse:
        if len(devices) < chips:
            raise SystemExit(f"run.py: rehearsal wants {chips} devices")
        return jax, devices[:chips]
    if devices[0].platform != "tpu":
        raise SystemExit(f"run.py: no TPU: jax.devices() is {devices}")
    if len(devices) != chips:
        raise SystemExit(f"run.py: the cell wants {chips} TPU device(s), "
                         f"JAX reports {len(devices)}: {devices}")
    return jax, devices


class CompileWatch:
    """Counts compilations (cache hits included: a hit still loads a
    program) and what the persistent cache said of each."""

    def __init__(self, jax):
        self.compiles = self.hits = self.misses = 0
        self.compile_seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        self.hits += name == CACHE_HIT_EVENT
        self.misses += name == CACHE_MISS_EVENT

    def _duration(self, name, seconds, **_):
        if name == COMPILE_EVENT:
            self.compiles += 1
            self.compile_seconds += seconds

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.hits,
                "cache_misses": self.misses,
                "compile_seconds": round(self.compile_seconds, 2)}


def program_counts(text: str) -> dict:
    """What the compiled program holds, counted from its text."""
    def count(op):
        return len(re.findall(rf"\b{op}(?:-start)?\(", text))

    return {"tpu_custom_call": text.count('"tpu_custom_call"'),
            "convolution": count("convolution"),
            "all_reduce": count("all-reduce"),
            "collective_permute": count("collective-permute"),
            "while": count("while")}


def step_memory_bytes(compiled) -> int:
    """The compiler's own account of the step that ran, per device:
    arguments + outputs + temporaries - what the outputs alias."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


# ---------------------------------------------------------------------------
# Stepping.
# ---------------------------------------------------------------------------

def advance(step, state, batch):
    """One step the way the examples call it: an aux (batch statistics)
    rides the batch in and comes back as a fourth output."""
    out = step(state[0], state[1], batch + tuple(state[2:]))
    return (out[0], out[1]) + tuple(out[3:]), out[2]


def blocked_steps(jax, step, state, pool, n: int, offset: int = 0):
    """`n` steps, each waited for: (state, losses, seconds of each)."""
    losses, seconds = [], []
    for i in range(n):
        t0 = time.perf_counter()
        state, loss = advance(step, state, pool[(offset + i) % len(pool)])
        jax.block_until_ready(loss)
        seconds.append(time.perf_counter() - t0)
        losses.append(loss)
    return state, losses, seconds


class Window:
    """How many steps fit `seconds`.  The host runs ahead of the device
    between two waits, so a clock read at dispatch says little; after each
    wait everything issued is complete, the rate is known, and the steps
    still to issue follow from it.  The window then ends within half a step
    of `seconds`, whatever the step time."""

    def __init__(self, seconds: float, step_estimate: float):
        self.start = time.perf_counter()
        self.seconds = seconds
        self.target = max(1, math.ceil(seconds / step_estimate))

    def stop(self, issued: int) -> bool:
        return issued >= self.target

    def waited(self, issued: int) -> None:
        elapsed = time.perf_counter() - self.start
        self.target = issued + max(0, round(
            (self.seconds - elapsed) * issued / elapsed))


def steady_loop(jax, step, state, pool, fetch_every: int, first: int,
                stop, annotate=None, waited=None):
    """The loop a training script runs: dispatch step after step, wait for
    the loss every `fetch_every` steps (a logging loop) and at the end.
    `stop(steps_issued)` ends it; `waited(steps_issued)` is told after each
    wait.  Returns the state, the losses (still on the device), the host
    seconds each call took to return, and the steps that raised (the loop
    ends at the first: the state is donated)."""
    from contextlib import nullcontext

    span = annotate or (lambda name: nullcontext())
    losses, dispatch, failed = [], [], 0
    issued, loss = 0, None
    while not stop(issued):
        with span("stage_batch"):
            batch = pool[(first + issued) % len(pool)]
        t0 = time.perf_counter()
        try:
            with span("dispatch"):
                state, loss = advance(step, state, batch)
        except Exception as error:  # noqa: BLE001 - counted, reported, ends
            note(step_raised=repr(error), at_step=issued)
            failed += 1
            break
        dispatch.append(time.perf_counter() - t0)
        losses.append(loss)
        issued += 1
        if issued % fetch_every == 0:
            with span("fetch_loss"):
                jax.block_until_ready(loss)
            if waited is not None:
                waited(issued)
    if loss is not None:
        with span("fetch_loss"):
            jax.block_until_ready(loss)
    return state, losses, dispatch, failed


def replicas_equal(jax, mesh, tree) -> bool:
    """Every device of the mesh holds the same tree: a 32-bit sum of each
    leaf's bits is the same on all of them."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]

    def local(tree):
        words = [lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
                 for x in jax.tree.leaves(tree)]
        sums = jnp.stack([w.sum(dtype=jnp.uint32) for w in words])
        return (lax.pmax(sums, axis) == lax.pmin(sums, axis)).all()

    # check_vma=False: shard_map must not assume what is being checked.
    check = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P(),),
                                  out_specs=P(), check_vma=False))
    return bool(check(tree))


def plain_step(jax, loss_fn, optimizer, has_aux: bool):
    """The benchmark's own step with no framework in it: jax.jit over
    value_and_grad and the optax update, the signature of the library's."""
    import optax

    def step(params, opt_state, batch):
        out, grads = jax.value_and_grad(loss_fn, has_aux=has_aux)(params,
                                                                  batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state) + (tuple(out) if has_aux else (out,))

    return jax.jit(step, donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# One run.
# ---------------------------------------------------------------------------

def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--rehearse", type=int, choices=(0, 1), default=0,
                        help="tests only: tiny sizes, any platform; the "
                        "result line is stamped and is no measurement")
    args = parser.parse_args()
    rehearse, traced = bool(args.rehearse), bool(args.trace)

    spec = load_cell(args.workload, rehearse)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    if not os.path.isdir(os.path.join(ROOT, "horovod_tpu")):
        raise SystemExit("run.py: no horovod_tpu package beside benchmark/: "
                         "there is no system to measure")
    cache_dir = place_compile_cache()
    phases = {}          # seconds since process start at the end of each

    def phase(name):
        phases[name] = round(time.perf_counter() - _PROCESS_START, 2)

    jax, devices = claim_devices(cell["chips"], rehearse)
    phase("runtime_up")
    on_tpu = devices[0].platform == "tpu"
    watch = CompileWatch(jax)
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if on_tpu and devices[0].device_kind not in peaks:
        raise SystemExit(f"run.py: no peaks for {devices[0].device_kind!r} "
                         "in benchmark/peaks.json")
    peak = peaks.get(devices[0].device_kind)

    from benchmark import trace_reduce, traffic_gen

    builder = importlib.import_module(
        f"benchmark.builders.{config['builder']}")

    def build(on_devices):
        built = builder.build(config, traffic, on_devices, args.seed)
        fields = traffic_gen.make_pool(
            built.fields, traffic["batch_per_chip"] * len(on_devices),
            traffic["pool_batches"], args.seed, built.mesh,
            built.mesh.axis_names[0])
        return built, [built.make_batch(f) for f in fields]

    # ---- set-up: weights, pool, the compiled step, warm-up, reference ----
    built, pool = build(devices)
    state = built.init_state()
    jax.block_until_ready((state, pool))
    phase("weights_and_pool")
    compiled = built.step.lower(state[0], state[1],
                                pool[0] + tuple(state[2:])).compile()
    phase("step_compiled_or_loaded")
    counts = program_counts(compiled.as_text())
    cost = compiled.cost_analysis() or {}
    step_bytes = step_memory_bytes(compiled)
    parameters = sum(x.size for x in jax.tree.leaves(state[0]))
    samples_per_chip = built.samples_per_step / len(devices)
    ops_agreement = (cost.get("flops", float("nan")) / (
        built.ops_per_sample["visible_to_compiler"] * samples_per_chip))
    program_ok = (not on_tpu) or (
        all(counts[k] == v for k, v in built.program_exactly.items())
        and all(counts[k] > 0 for k in built.program_at_least_one)
        and OPS_AGREEMENT[0] <= ops_agreement <= OPS_AGREEMENT[1])
    note(cell=cell["name"], seed=args.seed, cache_dir=cache_dir,
         parameters=parameters, samples_per_step=built.samples_per_step,
         ops_per_sample=built.ops_per_sample, program=counts,
         program_expected={"exactly": built.program_exactly,
                           "at_least_one": built.program_at_least_one},
         compiler_flops_over_counted=ops_agreement, program_ok=program_ok,
         step_memory_bytes=step_bytes, notes=built.notes)

    warmup = traffic["warmup_steps"]
    phase("program_read")
    state, _, warm_seconds = blocked_steps(jax, built.step, state, pool,
                                           warmup)
    phase("warmed_up")
    checks = built.reference_checks(state, pool)
    phase("reference_checked")
    for row in checks:
        row["ok"] = bool(math.isfinite(row["value"])
                         and row["value"] <= row["limit"])
    note(reference_checks=checks, setup_phases_s=phases,
         warmup_step_seconds=[round(s, 4) for s in warm_seconds])
    setup_compiles = watch.snapshot()

    # ---- the measured window ----
    setup_s = time.perf_counter() - _PROCESS_START
    window = Window(args.seconds, min(warm_seconds))
    state, losses, dispatch, failed = steady_loop(
        jax, built.step, state, pool, traffic["fetch_every"], warmup,
        window.stop, waited=window.waited)
    window_s = time.perf_counter() - window.start
    compiles_in_window = watch.compiles - setup_compiles["compiles"]

    losses = [float(x) for x in jax.device_get(losses)]
    attempted = len(losses) + failed          # issued, and the one that raised
    failed += sum(not math.isfinite(x) for x in losses)
    cycle = len(pool)
    first_cycle = statistics.fmean(losses[:cycle])
    last_cycle = statistics.fmean(losses[-cycle:])
    falling = len(losses) >= 2 * cycle and last_cycle < first_cycle
    equal = len(devices) == 1 or replicas_equal(jax, built.mesh, state[0])
    correct = bool(program_ok and all(r["ok"] for r in checks)
                   and failed == 0 and falling and equal
                   and compiles_in_window == 0)
    note(setup=setup_compiles, compiles_in_window=compiles_in_window,
         steps=len(losses), window_s=window_s, setup_s=setup_s,
         loss_first_cycle=first_cycle, loss_last_cycle=last_cycle,
         loss_falling=falling, replicas_equal=equal,
         step_ms_mean=1e3 * window_s / max(len(losses), 1))

    runtime_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devices)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              # The runtime's peak counts live buffers and leaves a
              # program's temporaries out; the compiler's account of the
              # step has them.  The larger is the peak.
              "memory_peak_bytes": max(runtime_peak, step_bytes),
              "runtime_peak_bytes_in_use": runtime_peak}
    run = {"cell": cell, "config": config, "traffic": traffic,
           "chips": len(devices), "peak": peak, "setup_s": setup_s,
           "window_s": window_s, "steps": len(losses),
           "samples": len(losses) * built.samples_per_step,
           "sample_unit": built.sample_unit,
           "ops_per_sample": built.ops_per_sample, "kernels": built.kernels,
           "dispatch_s": dispatch, "step_memory_bytes": step_bytes,
           "probes": {}, "trace": None, "profiled_steps": PROFILED_STEPS}

    breakdown = None
    if traced:
        # ---- a short profiled window of the same loop, then the probes ----
        trace_dir = os.path.join(HERE, "_out", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            state, _, _, _ = steady_loop(
                jax, built.step, state, pool, traffic["fetch_every"],
                warmup + len(losses),
                lambda issued: issued >= PROFILED_STEPS,
                annotate=jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        profile = trace_reduce.read_xplane(trace_reduce.find_xplane(trace_dir))
        with open(os.path.join(trace_dir, "summary.json"), "w") as f:
            json.dump(trace_reduce.summarize(profile), f, indent=1)
        # A rehearsal has no device plane: it reads the CPU client's threads.
        trace = trace_reduce.load(profile) if on_tpu else trace_reduce.load(
            profile, re.compile(r"^/host:CPU$"), re.compile(r"^tf_XLA"))
        trace_reduce.save(trace, os.path.join(trace_dir, "events.json.gz"))
        run["trace"] = trace
        span = trace_reduce.window(trace)
        if span is None:
            raise SystemExit("run.py: the trace holds no device operation")
        busy = trace_reduce.busy(trace)
        device["busy_s"] = statistics.fmean(busy.values()) / 1e9
        device["window_s"] = (span[1] - span[0]) / 1e9
        breakdown = {"device_ops": trace_reduce.top_ops(trace, 10),
                     "idle_gaps": trace_reduce.idle_gaps(trace, 5)}

        context = {"jax": jax, "devices": devices, "built": built,
                   "pool": pool, "state": state, "build": build,
                   "advance": advance, "blocked_steps": blocked_steps,
                   "plain_step": plain_step, "steps": PROBE_STEPS,
                   "note": note}
        for metric in spec["per_layer"]:
            probe = getattr(reader("layer_metrics", metric["name"]),
                            "probe", None)
            name = metric["name"].split(".")[0]
            if probe is not None and name not in run["probes"]:
                run["probes"][name] = probe(context)
        metrics = read_metrics("layer_metrics", spec["per_layer"], run)
    else:
        metrics = read_metrics("end_to_end_metrics", spec["end_to_end"], run)

    result = {"correct": correct, "attempted": attempted,
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if rehearse:
        result["rehearsal"] = "tiny sizes, no measurement"
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
