#!/usr/bin/env python3
"""Compile each cell's step at full size for a described, unattached v5e —
no chip time — and print what the chip's compiler says of it: memory per
device (`memory_analysis()`), kernels and collectives in the text, and its own
operation count beside benchmark/ops_count.py's.  The evidence for the depth
and the batch each cell runs at (PERF.md section 4).

    JAX_PLATFORMS=cpu python benchmark/rehearse_compile.py [cell ...]
    JAX_PLATFORMS=cpu python benchmark/rehearse_compile.py --set num_hidden_layers=24 pythia410m_1chip_1x8k

A compile that passes is not a chip run: nothing here is a speed.  Run one
such process at a time (libtpu takes a lock file).  Not a test file: the
topology is described inside main() and nowhere else.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def fusion_kinds(text: str) -> dict:
    """{fusion kind: [fusions, of which the fused computation holds a
    convolution]} — the evidence for trace_reduce.categorize reading a
    `kOutput` fusion as a matmul or convolution with its epilogue."""
    import re

    bodies, name = {}, None
    for line in text.splitlines():
        opened = re.match(r"^%?([\w.\-]+) \(.*\{$", line)
        if opened:
            name = opened.group(1)
            bodies[name] = False
        elif name and " convolution(" in line:
            bodies[name] = True
    kinds = {}
    for kind, called in re.findall(
            r"\bfusion\(.*kind=(\w+), calls=%([\w.\-]+)", text):
        pair = kinds.setdefault(kind, [0, 0])
        pair[0] += 1
        pair[1] += bool(bodies.get(called))
    return kinds


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("cells", nargs="*")
    parser.add_argument("--set", action="append", default=[],
                        metavar="KEY=JSON", help="override a configuration "
                        "key, to try another depth")
    parser.add_argument("--traffic-set", action="append", default=[],
                        metavar="KEY=JSON", help="override a traffic key")
    args = parser.parse_args()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import run as harness

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # The program asks jax.default_backend() whether to compile its Pallas
    # kernels or interpret them; here it is compiling for the described chip.
    jax.default_backend = lambda: "tpu"

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    for name in args.cells or names:
        spec = harness.load_cell(name, rehearse=False)
        config, traffic = spec["config"], spec["traffic"]
        for target, pairs in ((config, args.set), (traffic, args.traffic_set)):
            for pair in pairs:
                key, value = pair.split("=", 1)
                target[key] = json.loads(value)
        devices = topo.devices[:spec["cell"]["chips"]]
        builder = importlib.import_module(
            f"benchmark.builders.{config['builder']}")
        built = builder.build(config, traffic, devices, seed=0)
        axis = built.mesh.axis_names[0]

        def shaped(tree, spec):
            sharding = NamedSharding(built.mesh, spec)
            return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=sharding), tree)

        state = shaped(jax.eval_shape(built.init_state), P())
        global_batch = traffic["batch_per_chip"] * len(devices)
        fields = {f["name"]: jax.ShapeDtypeStruct(
            (global_batch, *f["shape"]), f["dtype"]) for f in built.fields}
        batch = shaped(jax.eval_shape(built.make_batch, fields), P(axis))
        t0 = time.perf_counter()
        compiled = built.step.lower(state[0], state[1],
                                    tuple(batch) + tuple(state[2:])).compile()
        seconds = time.perf_counter() - t0
        memory = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        per_chip = built.samples_per_step / len(devices)
        counted = built.ops_per_sample["visible_to_compiler"] * per_chip
        print(json.dumps({
            "cell": name, "overrides": args.set + args.traffic_set,
            "compiled_for": f"{devices[0].device_kind} x{len(devices)} "
            "(described, not attached)",
            "compile_seconds_on_this_cpu": round(seconds, 1),
            "parameters": sum(x.size for x in jax.tree.leaves(state[0])),
            "GiB_per_device": {
                "arguments": round(memory.argument_size_in_bytes / 2**30, 2),
                "outputs": round(memory.output_size_in_bytes / 2**30, 2),
                "aliased": round(memory.alias_size_in_bytes / 2**30, 2),
                "temporaries": round(memory.temp_size_in_bytes / 2**30, 2),
                "step": round(harness.step_memory_bytes(compiled) / 2**30,
                              2)},
            "program": harness.program_counts(compiled.as_text()),
            "fusions_[all,with_convolution]": fusion_kinds(
                compiled.as_text()),
            "program_expected": {"exactly": built.program_exactly,
                                 "at_least_one": built.program_at_least_one},
            "compiler_flops_per_device": cost.get("flops"),
            "counted_ops_without_attention": counted,
            "compiler_over_counted": (cost.get("flops", float("nan"))
                                      / counted),
            "notes": built.notes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
