#!/usr/bin/env python
"""Headline benchmark: ResNet-101 synthetic-data training throughput per chip.

Reproduces the reference's benchmark protocol
(/root/reference/docs/benchmarks.md:22-38: tf_cnn_benchmarks ResNet-101,
batch 64 per accelerator, synthetic ImageNet data) on one TPU chip.  The
reference's published number is 1656.82 images/sec on 16 Pascal GPUs =
103.55 images/sec/GPU; `vs_baseline` is our per-chip throughput over that.

Prints the headline JSON line FIRST:
  {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N}
then (default resnet101 invocation) re-prints it enriched with the
transformer LM and long-context (seq 8192) tokens/sec folded into
"extra_metrics" — a second line, so an extra that fails (or floods stderr
with a compiler error) can never erase the already-printed headline.
Extra errors are clipped to one short line.  BENCH_EXTRA=0 disables,
BENCH_EXTRA_CONFIGS="seq:batch,..." overrides the sweep.

Env knobs: BENCH_MODEL (resnet101|resnet50|resnet18|vgg16|inception_v3|
mnist|transformer|allreduce|small_allreduce|big_allreduce|hier_allreduce|
negotiation_scale|serve_decode|checkpoint|scaling|pipeline), BENCH_BATCH,
BENCH_STEPS, BENCH_WARMUP, BENCH_IMAGE (side
length); transformer adds BENCH_SEQ/BENCH_VOCAB/BENCH_D_MODEL/BENCH_LAYERS/
BENCH_HEADS; allreduce adds BENCH_NP/BENCH_BYTES/BENCH_ITERS;
small_allreduce (the negotiation-bound cache microbench) adds
BENCH_NP/BENCH_TENSORS/BENCH_STEPS; big_allreduce (the bandwidth-bound
wire-compression sweep, docs/performance.md#wire-compression) adds
BENCH_NP/BENCH_BYTES/BENCH_ITERS; negotiation_scale (the simulated-scale
control-plane bench, docs/performance.md#control-plane-scaling) adds
BENCH_SCALE_RANKS/BENCH_OPS/BENCH_WARM_CYCLES/BENCH_STEADY_CYCLES;
serve_decode (the serving-plane continuous-batching bench,
docs/inference.md) adds BENCH_NP/BENCH_REQUESTS; pipeline (the 1F1B
pipeline-parallel sweep, docs/pipeline.md) adds BENCH_NP/BENCH_STAGES/
BENCH_CHUNKS/BENCH_MICROBATCHES plus the transformer size knobs.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

REFERENCE_IMG_PER_SEC_PER_DEVICE = 1656.82 / 16  # docs/benchmarks.md:22-38


def bench_transformer(seq: int = None, batch: int = None,
                      steps: int = None, report: bool = True) -> float:
    """LM training throughput (tokens/sec/chip), flash attention + bf16."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu.models import TransformerLM, next_token_loss

    # Batch 16 is the measured single-chip sweet spot on v5e (batch 8
    # under-fills the MXU; batch 32 pressures HBM with the f32 logits).
    if batch is None:
        batch = int(os.environ.get("BENCH_BATCH", "16"))
    if seq is None:
        seq = int(os.environ.get("BENCH_SEQ", "1024"))
    if steps is None:
        steps = int(os.environ.get("BENCH_STEPS", "20"))
    # Multi-step dispatch, as the resnet headline (r5: 305k -> 320k
    # tok/s at seq 1024 going 1 -> 8); default 4 balances the gain
    # against the ~unroll-fold compile time across the extras sweep.
    unroll = max(1, int(os.environ.get("BENCH_UNROLL", "4")))
    warmup = int(os.environ.get("BENCH_WARMUP", "3"))
    vocab = int(os.environ.get("BENCH_VOCAB", "32768"))
    # bf16 logits STORAGE (f32 accumulation and f32 loss internals): the
    # logits tensor dominates the step's HBM traffic; see TransformerLM.
    logits_dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
        os.environ.get("BENCH_LOGITS_DTYPE", "bfloat16")]
    model = TransformerLM(
        vocab_size=vocab,
        d_model=int(os.environ.get("BENCH_D_MODEL", "512")),
        n_layers=int(os.environ.get("BENCH_LAYERS", "8")),
        n_heads=int(os.environ.get("BENCH_HEADS", "8")),
        logits_dtype=logits_dtype)

    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, vocab, (batch, seq + 1)))
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    params = model.init(jax.random.PRNGKey(0), inputs[:, :128])["params"]
    tx = optax.adamw(1e-3)
    opt_state = tx.init(params)

    def one_step(params, opt_state, inputs, targets):
        def loss_fn(p):
            return next_token_loss(
                model.apply({"params": p}, inputs), targets)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    # Donation lets XLA update params/opt state in place (no fresh HBM
    # buffers per step), same as the image-model step below.  inputs/
    # targets MUST thread through as traced jit arguments — closed-over
    # arrays would bake into the executable as constants, letting XLA
    # specialize the program in ways impossible in real training.
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, inputs, targets):
        for _ in range(unroll):
            params, opt_state, loss = one_step(params, opt_state,
                                               inputs, targets)
        return params, opt_state, loss

    for _ in range(max(warmup, 1)):
        params, opt_state, loss = step(params, opt_state, inputs, targets)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, inputs, targets)
    final_loss = float(loss)
    dt = time.perf_counter() - t0
    assert np.isfinite(final_loss), final_loss
    value = batch * seq * steps * unroll / dt
    if report:
        print(json.dumps({
            "metric": "transformer_train_tokens_per_sec_per_chip",
            "value": round(value, 2),
            "unit": "tokens/sec/chip",
            "vs_baseline": None,  # the reference has no LM benchmark
        }))
    return value


def bench_scaling() -> None:
    """DP scaling efficiency: ResNet-50 shard_map step at 1 vs N devices.

    The BASELINE.md tracked metric (scaling efficiency 8->256 chips on a
    v5e pod) measured with the same methodology on whatever mesh is
    available: efficiency = throughput(N) / (throughput(1) * N) with the
    per-device batch held constant.  On a single-chip or CPU environment
    this exercises the harness on a virtual device mesh.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu import models
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.parallel import (data_parallel_mesh, replicate,
                                      shard_batch)

    per_dev_batch = int(os.environ.get("BENCH_BATCH", "16"))
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    warmup = int(os.environ.get("BENCH_WARMUP", "2"))
    side = int(os.environ.get("BENCH_IMAGE", "96"))
    n_dev = len(jax.devices())

    def throughput(devices):
        n = len(devices)
        mesh = data_parallel_mesh(devices, axis_name="hvd")
        model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                                axis_name="hvd")
        batch = per_dev_batch * n
        images = np.random.RandomState(0).rand(
            batch, side, side, 3).astype(np.float32)
        labels = np.random.RandomState(1).randint(0, 1000, batch)
        variables = model.init(jax.random.PRNGKey(0), images[:2],
                               train=False)
        params, stats = variables["params"], variables["batch_stats"]

        def loss_fn(params, b):
            imgs, labs, stats = b
            logits, upd = model.apply(
                {"params": params, "batch_stats": stats}, imgs,
                train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labs).mean()
            return loss, upd["batch_stats"]

        tx = optax.sgd(0.1, momentum=0.9)
        step = build_train_step(loss_fn, tx, mesh, axis_name="hvd",
                                has_aux=True,
                                batch_spec=(P("hvd"), P("hvd"), P()))
        params = replicate(mesh, params)
        opt_state = replicate(mesh, tx.init(params))
        b = (shard_batch(mesh, images),
             shard_batch(mesh, jnp.asarray(labels, jnp.int32)),
             replicate(mesh, stats))
        for _ in range(max(warmup, 1)):
            params, opt_state, loss, stats2 = step(params, opt_state, b)
            b = (b[0], b[1], stats2)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss, stats2 = step(params, opt_state, b)
            b = (b[0], b[1], stats2)
        float(loss)
        return batch * steps / (time.perf_counter() - t0)

    # Baseline at the smallest addressable granularity: one device in
    # single-process jobs, this process's devices on a multi-host slice
    # (a 1-global-device mesh would be non-addressable from other hosts).
    local = jax.local_devices()
    base_devices = local[:1] if jax.process_count() == 1 else local
    base = throughput(base_devices)
    full = throughput(jax.devices())
    n_base = len(base_devices)
    efficiency = full / (base * n_dev / n_base)
    # Regression guard (BENCH_SCALING_FLOOR): on the virtual CPU mesh all
    # N devices share the host cores, so the meaningful floor is against
    # the core-normalized ceiling 1/N (e.g. 0.10 at N=8 = 83% of the
    # 1-core ceiling); on a real pod slice compare against 0.88.
    floor = os.environ.get("BENCH_SCALING_FLOOR")
    if floor is not None:
        assert efficiency >= float(floor), (
            f"scaling efficiency {efficiency:.4f} fell below the floor "
            f"{float(floor):.4f}")
    # Core-normalized floor, portable across virtual-mesh hosts: with C
    # cores shared by N virtual devices the compute-bound ceiling is C/N,
    # so efficiency * N / min(C, N) isolates sharding+collective overhead
    # from host core count (docs/benchmarks.md, scaling harness).
    norm_floor = os.environ.get("BENCH_SCALING_FLOOR_NORM")
    if norm_floor is not None:
        try:  # respects taskset/cgroup pinning, unlike os.cpu_count()
            cores = len(os.sched_getaffinity(0))
        except AttributeError:  # pragma: no cover - non-Linux
            cores = os.cpu_count() or 1
        normalized = efficiency * n_dev / min(cores, n_dev)
        assert normalized >= float(norm_floor), (
            f"core-normalized scaling efficiency {normalized:.4f} "
            f"(raw {efficiency:.4f} x {n_dev}/{min(cores, n_dev)}) fell "
            f"below the floor {float(norm_floor):.4f}")
    if jax.process_index() == 0:  # one JSON line per job, not per host
        print(json.dumps({
            "metric": f"resnet50_dp_scaling_efficiency_{n_base}_to_{n_dev}",
            "value": round(efficiency, 4),
            "unit": "fraction",
            "vs_baseline": round(efficiency / 0.88, 3),  # target >= 0.88
        }))


def bench_allreduce() -> None:
    """Engine eager ring-allreduce bandwidth over NP local ranks."""
    import subprocess
    import sys

    np_ = int(os.environ.get("BENCH_NP", "2"))
    nbytes = int(os.environ.get("BENCH_BYTES", str(64 * 1024 * 1024)))
    iters = int(os.environ.get("BENCH_ITERS", "10"))
    code = f"""
import json, time, numpy as np, horovod_tpu as hvd
hvd.init()
x = np.ones({nbytes} // 4, np.float32)
hvd.allreduce(x, average=False, name="warmup")
t0 = time.perf_counter()
for i in range({iters}):
    hvd.allreduce(x, average=False, name=f"bench.{{i}}")
dt = time.perf_counter() - t0
if hvd.rank() == 0:
    # Ring allreduce moves 2*(N-1)/N * nbytes per rank per iteration.
    n = hvd.size()
    algo_bytes = 2 * (n - 1) / n * {nbytes} * {iters}
    print("BW_GBPS", algo_bytes / dt / 1e9, flush=True)
    # Collective-layer health alongside throughput (docs/metrics.md):
    # the launcher env enables the registry, so the snapshot carries the
    # op/byte/stall counters for this rank's run.
    snap = hvd.metrics_snapshot()
    at = snap["autotune"]
    print("METRICS_JSON " + json.dumps({{
        "collective_ops": sum(sum(v.values()) for v in snap["ops"].values()),
        "collective_bytes_in": sum(v["in"] for v in snap["bytes"].values()),
        "collective_bytes_out": sum(v["out"] for v in snap["bytes"].values()),
        "stall_events": snap["stalls"]["count"],
        "autotune": {{k: at[k] for k in ("enabled", "frozen", "windows",
                                         "fusion_threshold",
                                         "cycle_time_ms")}},
    }}), flush=True)
"""
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ,
               PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
    # Metrics ride along in extra_metrics (docs/metrics.md); an explicit
    # HVD_TPU_METRICS=0 in the caller's env still wins.
    env.setdefault("HVD_TPU_METRICS", "1")
    if os.environ.get("BENCH_AUTOTUNE", "0") != "0":
        # Autotune ride-along (docs/performance.md#autotuning): tune while
        # the bandwidth bench runs and fold the applied params into
        # extra_metrics.  Small windows — the bench only runs
        # BENCH_ITERS collectives.
        env["HVD_TPU_AUTOTUNE"] = "1"
        env.setdefault("HVD_TPU_AUTOTUNE_WINDOW", "4")
        env.setdefault("HVD_TPU_AUTOTUNE_WARMUP", "1")
    out = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_), "--",
         sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    bw = next(float(line.split()[1]) for line in out.stdout.splitlines()
              if line.startswith("BW_GBPS"))
    floor = os.environ.get("BENCH_ALLREDUCE_FLOOR_GBPS")
    if floor is not None:
        assert bw >= float(floor), (
            f"engine ring-allreduce bandwidth {bw:.3f} GB/s at np={np_} "
            f"fell below the floor {float(floor):.3f} GB/s")
    record = {
        "metric": f"engine_ring_allreduce_bandwidth_np{np_}",
        "value": round(bw, 3),
        "unit": "GB/s",
        "vs_baseline": None,  # the reference published no allreduce number
    }
    # Fold rank 0's metrics snapshot in so BENCH rounds track collective-
    # layer health (ops, bytes, stalls) alongside the bandwidth headline.
    for line in out.stdout.splitlines():
        if line.startswith("METRICS_JSON "):
            record["extra_metrics"] = json.loads(
                line[len("METRICS_JSON "):])
    print(json.dumps(record))


def bench_small_allreduce() -> None:
    """Negotiation-bound microbench (docs/performance.md): BENCH_TENSORS
    tiny named allreduces repeated steady-state for BENCH_STEPS steps over
    BENCH_NP local ranks.  The payload is 32 bytes, so throughput here is
    pure control plane: coordinator roundtrips, string (de)serialization,
    and the engine tick — exactly what the response cache and adaptive
    tick attack.  Runs twice (cache on, then HVD_TPU_RESPONSE_CACHE=0) and
    folds the comparison, rank 0's cache hit/miss counters, and the
    negotiation_sec p50 into extra_metrics.

    BENCH_AUTOTUNE=1 adds a third run: online autotuning from
    deliberately bad initial params (fusion threshold 1024 B, cycle 50 ms
    — the docs/performance.md#autotuning acceptance shape), training
    until the search freezes, then measuring steady-state throughput.
    extra_metrics gains the tuned ops/sec, tuned-vs-default ratio,
    windows-to-convergence, and the frozen params."""
    import subprocess
    import sys

    # 256 tensors/step puts the run squarely in the regime the cache
    # targets: with a handful of tensors the frame round trip dominates
    # and cache on/off measure within noise of each other.
    np_ = int(os.environ.get("BENCH_NP", "4"))
    tensors = int(os.environ.get("BENCH_TENSORS", "256"))
    steps = int(os.environ.get("BENCH_STEPS", "50"))
    repo = os.path.dirname(os.path.abspath(__file__))
    code = f"""
import json, os, sys, time, numpy as np, horovod_tpu as hvd
sys.path.insert(0, {repo!r})
from tools.metrics_dump import quantile
hvd.init()
K, S = {tensors}, {steps}
# Realistic gradient-style names: string volume on the wire and at the
# coordinator is what the cache removes, and production tensor names are
# long ("model/layer_42/attention/query/kernel_grad"), not "t3".
names = [f"model.layer_{{k:04d}}.attention.query.kernel.grad"
         for k in range(K)]
xs = [np.ones(8, np.float32) for _ in range(K)]
def step():
    hs = [hvd.allreduce_async(xs[k], average=False, name=names[k])
          for k in range(K)]
    for h in hs:
        h.wait()
step()  # warm: full negotiation populates the cache
if os.environ.get("HVD_TPU_AUTOTUNE"):
    # Autotune mode: train through the search (bad initial params) until
    # it freezes, so the timed window below measures the TUNED steady
    # state, not the climb.  The break is decided COLLECTIVELY: ranks
    # observe the freeze broadcast at different wall times, and a
    # rank-local break would leave the others' last step unmatched.
    for s in range(4000):
        step()
        f = np.asarray([int(hvd.autotune_report()["frozen"])], np.int32)
        if int(hvd.allreduce(f, average=False,
                             name="at.poll")[0]) == hvd.size():
            break
t0 = time.perf_counter()
for s in range(S - 1):
    step()
dt = time.perf_counter() - t0
if hvd.rank() == 0:
    snap = hvd.metrics_snapshot()
    p50 = quantile(snap["histograms"]["negotiation_sec"], 0.5)
    print("SMALL_JSON " + json.dumps({{
        "ops_per_sec": K * (S - 1) / dt,
        "cache": snap["cache"]["engine"],
        "negotiation_p50_us": round((p50 or 0.0) * 1e6, 1),
        "autotune": snap["autotune"],
    }}), flush=True)
"""

    def run(cache_on: bool, autotune: bool = False) -> dict:
        env = dict(os.environ,
                   PYTHONPATH=repo + os.pathsep +
                   os.environ.get("PYTHONPATH", ""),
                   HVD_TPU_RESPONSE_CACHE="1" if cache_on else "0")
        env.setdefault("HVD_TPU_METRICS", "1")
        if autotune:
            # The acceptance shape (docs/performance.md#autotuning):
            # deliberately bad initial params the search must climb out
            # of before the timed window runs.
            env["HVD_TPU_AUTOTUNE"] = "1"
            env["HVD_TPU_FUSION_THRESHOLD"] = "1024"
            env["HVD_TPU_CYCLE_TIME_MS"] = "50"
            env.setdefault("HVD_TPU_AUTOTUNE_WINDOW", "256")
        else:
            env.pop("HVD_TPU_AUTOTUNE", None)
            # A tight idle cycle keeps the (cache-independent) co-arrival
            # alignment window from drowning the negotiation-work delta
            # this bench exists to measure; override to probe other
            # regimes.
            env.setdefault("HVD_TPU_CYCLE_TIME_MS", "1")
        out = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_),
             "--", sys.executable, "-c", code],
            capture_output=True, text=True, env=env, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        return next(json.loads(line[len("SMALL_JSON "):])
                    for line in out.stdout.splitlines()
                    if line.startswith("SMALL_JSON "))

    on = run(True)
    off = run(False)
    hits, misses = on["cache"]["hits"], on["cache"]["misses"]
    record = {
        "metric": f"small_allreduce_ops_per_sec_np{np_}",
        "value": round(on["ops_per_sec"], 1),
        "unit": "ops/sec",
        "vs_baseline": None,  # the reference published no such number
        "extra_metrics": {
            "cache_off_ops_per_sec": round(off["ops_per_sec"], 1),
            "cache_speedup": round(on["ops_per_sec"]
                                   / max(off["ops_per_sec"], 1e-9), 3),
            "cache_hits": hits,
            "cache_misses": misses,
            "cache_hit_rate": round(hits / max(hits + misses, 1), 4),
            "negotiation_p50_us_cached": on["negotiation_p50_us"],
            "negotiation_p50_us_uncached": off["negotiation_p50_us"],
        },
    }
    if os.environ.get("BENCH_AUTOTUNE", "0") != "0":
        tuned = run(True, autotune=True)
        at = tuned.get("autotune", {})
        record["extra_metrics"].update({
            "autotune_ops_per_sec": round(tuned["ops_per_sec"], 1),
            # >= 0.9 is the acceptance bar: starting from deliberately
            # bad params the tuner must recover (nearly) the hand-tuned
            # default throughput.
            "autotune_vs_default": round(
                tuned["ops_per_sec"] / max(on["ops_per_sec"], 1e-9), 3),
            "autotune_windows_to_convergence": at.get("windows"),
            "autotune_frozen": at.get("frozen"),
            "autotune_fusion_threshold": at.get("fusion_threshold"),
            "autotune_cycle_time_ms": at.get("cycle_time_ms"),
        })
    print(json.dumps(record))


def bench_big_allreduce() -> None:
    """Bandwidth-bound large-tensor allreduce with the wire-compression
    sweep (docs/performance.md#wire-compression): BENCH_BYTES of fp32
    repeated steady-state over BENCH_NP local ranks, once per
    HVD_TPU_COMPRESSION mode (off, bf16, fp8).  Headline is the bf16-mode
    ops/sec; extra_metrics carries each mode's ops/sec and wire bytes
    (`_bytes` extras gate lower-is-better in tools/bench_compare.py), the
    off/compressed byte ratios (>= 1.8x for bf16 is the acceptance bar),
    each mode's max relative error vs the fp32 result, and the bf16
    -payload wire inflation (1.0 = native width; 2.0 was the old f32
    staging)."""
    import subprocess
    import sys

    np_ = int(os.environ.get("BENCH_NP", "4"))
    nbytes = int(os.environ.get("BENCH_BYTES", str(32 * 1024 * 1024)))
    iters = int(os.environ.get("BENCH_ITERS", "8"))
    repo = os.path.dirname(os.path.abspath(__file__))
    code = f"""
import json, time, numpy as np, ml_dtypes, horovod_tpu as hvd
hvd.init()
n = {nbytes} // 4
x = np.random.RandomState(hvd.rank()).rand(n).astype(np.float32) - 0.5
want = np.zeros(n, np.float32)
for i in range(hvd.size()):
    want += np.random.RandomState(i).rand(n).astype(np.float32) - 0.5
want /= hvd.size()
out = hvd.allreduce(x, average=True, name="big.steady")  # warm: negotiate
mark = hvd.compression_report()["engine"]
t0 = time.perf_counter()
for i in range({iters}):
    out = hvd.allreduce(x, average=True, name="big.steady")
dt = time.perf_counter() - t0
rep = hvd.compression_report()["engine"]
err = float(np.max(np.abs(out - want)) / max(float(np.max(np.abs(want))),
                                             1e-9))
# bf16-payload inflation probe: native-width wire means delta wire ==
# delta payload (the old f32 staging paid 2x).
xb = (np.random.RandomState(7).rand(1 << 18).astype(np.float32)
      / 4).astype(ml_dtypes.bfloat16)
b0 = hvd.compression_report()["engine"]
hvd.allreduce(xb, average=False, name="big.half")
b1 = hvd.compression_report()["engine"]
if hvd.rank() == 0:
    print("BIG_JSON " + json.dumps({{
        "ops_per_sec": {iters} / dt,
        "gbps": 2 * (hvd.size() - 1) / hvd.size() * {nbytes} * {iters}
                / dt / 1e9,
        "wire_bytes": rep["wire_bytes"] - mark["wire_bytes"],
        "payload_bytes": rep["payload_bytes"] - mark["payload_bytes"],
        "max_rel_err": err,
        "half_wire_inflation": (b1["wire_bytes"] - b0["wire_bytes"])
                               / max(b1["payload_bytes"]
                                     - b0["payload_bytes"], 1),
    }}), flush=True)
"""

    def run(mode: str) -> dict:
        env = dict(os.environ,
                   PYTHONPATH=repo + os.pathsep
                   + os.environ.get("PYTHONPATH", ""),
                   HVD_TPU_COMPRESSION=mode)
        out = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_),
             "--", sys.executable, "-c", code],
            capture_output=True, text=True, env=env, timeout=600)
        assert out.returncode == 0, (mode, out.stderr[-2000:])
        return next(json.loads(line[len("BIG_JSON "):])
                    for line in out.stdout.splitlines()
                    if line.startswith("BIG_JSON "))

    off = run("off")
    b16 = run("bf16")
    f8 = run("fp8")
    ratio16 = off["wire_bytes"] / max(b16["wire_bytes"], 1)
    ratio8 = off["wire_bytes"] / max(f8["wire_bytes"], 1)
    floor = float(os.environ.get("BENCH_BIG_ALLREDUCE_MIN_RATIO", "1.8"))
    assert ratio16 >= floor, (
        f"bf16 wire mode moved only {ratio16:.2f}x fewer bytes than the "
        f"fp32 baseline (want >= {floor:.1f}x): "
        f"{b16['wire_bytes']} vs {off['wire_bytes']}")
    print(json.dumps({
        "metric": f"big_allreduce_ops_per_sec_np{np_}",
        "value": round(b16["ops_per_sec"], 2),
        "unit": "ops/sec",
        "vs_baseline": None,  # the reference published no such number
        "extra_metrics": {
            "off_ops_per_sec": round(off["ops_per_sec"], 2),
            "fp8_ops_per_sec": round(f8["ops_per_sec"], 2),
            "bf16_gbps_effective": round(b16["gbps"], 3),
            "off_wire_bytes": off["wire_bytes"],
            "bf16_wire_bytes": b16["wire_bytes"],
            "fp8_wire_bytes": f8["wire_bytes"],
            "bf16_compression_ratio": round(ratio16, 3),
            "fp8_compression_ratio": round(ratio8, 3),
            "bf16_max_rel_err": round(b16["max_rel_err"], 6),
            "fp8_max_rel_err": round(f8["max_rel_err"], 6),
            "half_wire_inflation": round(off["half_wire_inflation"], 3),
        },
    }))


def bench_hier_allreduce() -> None:
    """Two-level topology bench (docs/performance.md#two-level-topology):
    flat-ring vs two-level allreduce at BENCH_NP ranks as
    local_size-2 nodes, BENCH_BYTES fp32 steady-state.  Headline is the
    two-level ops/sec; extra_metrics carries the flat baseline, the
    per-phase mean times (``_ms`` extras gate lower-is-better in
    tools/bench_compare.py), the per-hop wire bytes (``_bytes`` extras,
    same convention), the bf16 cross-hop run and its DCN byte reduction
    (asserted >= 1.8x in-bench), the flat-vs-two-level bit identity
    with compression off (exact integer payloads; the kill-switch
    identity bar PR 9 set), and the shared-memory transport cells
    (docs/performance.md#transport): the two-level run repeated with
    HVD_TPU_SHM=force vs the HVD_TPU_SHM=0 kill switch — asserted
    bit-identical and reported as shm_transport_speedup with both
    transports' local-hop phase times."""
    import subprocess
    import sys

    np_ = int(os.environ.get("BENCH_NP", "4"))
    nbytes = int(os.environ.get("BENCH_BYTES", str(8 * 1024 * 1024)))
    iters = int(os.environ.get("BENCH_ITERS", "16"))
    repo = os.path.dirname(os.path.abspath(__file__))
    code = f"""
import hashlib, json, os, time, numpy as np
rank = int(os.environ["HVD_TPU_RANK"])
if os.environ.get("BENCH_HIER") == "1":
    os.environ["HVD_TPU_LOCAL_SIZE"] = "2"
    os.environ["HVD_TPU_LOCAL_RANK"] = str(rank % 2)
    os.environ["HOROVOD_HIERARCHICAL_ALLREDUCE"] = "1"
import horovod_tpu as hvd
hvd.init()
n = {nbytes} // 4
# Integer-valued fp32: sums are exact, so flat and two-level results can
# bit-compare (association order cannot change bits).
x = (np.arange(n) % 251 + hvd.rank()).astype(np.float32)
out = hvd.allreduce(x, average=False, name="hier.steady")  # warm
snap0 = hvd.metrics_snapshot()
t0 = time.perf_counter()
for i in range({iters}):
    out = hvd.allreduce(x, average=False, name="hier.steady")
dt = time.perf_counter() - t0
snap1 = hvd.metrics_snapshot()
topo0, topo1 = snap0["topology"], snap1["topology"]

def phase_ms(name):
    h0 = snap0["histograms"].get(name, {{"sum": 0.0, "count": 0}})
    h1 = snap1["histograms"].get(name, {{"sum": 0.0, "count": 0}})
    cnt = h1["count"] - h0["count"]
    return 1e3 * (h1["sum"] - h0["sum"]) / cnt if cnt else 0.0

if hvd.rank() == 0:
    print("HIER_JSON " + json.dumps({{
        "ops_per_sec": {iters} / dt,
        "digest": hashlib.sha256(out.tobytes()).hexdigest(),
        "local_transport": topo1.get("local_transport", "tcp"),
        "local_bytes": topo1["bytes"]["local"] - topo0["bytes"]["local"],
        "cross_bytes": topo1["bytes"]["cross"] - topo0["bytes"]["cross"],
        "local_rs_ms": round(phase_ms("topology_local_rs_sec"), 3),
        "cross_ms": round(phase_ms("topology_cross_sec"), 3),
        "local_ag_ms": round(phase_ms("topology_local_ag_sec"), 3),
    }}), flush=True)
hvd.shutdown()
"""

    def run(hier: bool, mode: str, shm: str = "0") -> dict:
        env = dict(os.environ,
                   PYTHONPATH=repo + os.pathsep
                   + os.environ.get("PYTHONPATH", ""),
                   BENCH_HIER="1" if hier else "0",
                   HVD_TPU_COMPRESSION=mode,
                   HVD_TPU_SHM=shm)
        env.pop("HOROVOD_HIERARCHICAL_ALLREDUCE", None)
        out = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_),
             "--", sys.executable, "-c", code],
            capture_output=True, text=True, env=env, timeout=600)
        assert out.returncode == 0, (hier, mode, out.stderr[-2000:])
        return next(json.loads(line[len("HIER_JSON "):])
                    for line in out.stdout.splitlines()
                    if line.startswith("HIER_JSON "))

    flat = run(False, "off")
    hier = run(True, "off")
    hier16 = run(True, "bf16")
    shm = run(True, "off", shm="force")
    # Kill-switch identity: flat and two-level agree BITWISE with
    # compression off (exact payloads).
    assert flat["digest"] == hier["digest"], (
        "flat vs two-level results diverged bitwise with compression off")
    # Transport identity: the shm rings carry the same bits the sockets
    # did (force, so a silent TCP demotion cannot fake the pass).
    assert shm["local_transport"] == "shm", shm
    assert hier["local_transport"] == "tcp", hier
    assert shm["digest"] == hier["digest"], (
        "shm vs TCP two-level results diverged bitwise with compression "
        "off")
    ratio16 = hier["cross_bytes"] / max(hier16["cross_bytes"], 1)
    floor = float(os.environ.get("BENCH_HIER_MIN_CROSS_RATIO", "1.8"))
    assert ratio16 >= floor, (
        f"bf16 cross hop moved only {ratio16:.2f}x fewer DCN bytes than "
        f"full width (want >= {floor:.1f}x): {hier16['cross_bytes']} vs "
        f"{hier['cross_bytes']}")
    speedup = hier["ops_per_sec"] / max(flat["ops_per_sec"], 1e-9)
    speed_floor = float(os.environ.get("BENCH_HIER_MIN_SPEEDUP", "0.9"))
    assert speedup >= speed_floor, (
        f"two-level ran {speedup:.2f}x the flat ring at "
        f"{nbytes >> 20} MiB (want >= {speed_floor:.2f}x)")
    print(json.dumps({
        "metric": f"hier_allreduce_ops_per_sec_np{np_}",
        "value": round(hier["ops_per_sec"], 2),
        "unit": "ops/sec",
        "vs_baseline": None,  # the reference published no such number
        "extra_metrics": {
            "flat_ops_per_sec": round(flat["ops_per_sec"], 2),
            "bf16_ops_per_sec": round(hier16["ops_per_sec"], 2),
            "two_level_speedup": round(speedup, 3),
            "local_wire_bytes": hier["local_bytes"],
            "cross_wire_bytes": hier["cross_bytes"],
            "cross_wire_bytes_bf16": hier16["cross_bytes"],
            "cross_compression_ratio": round(ratio16, 3),
            "local_rs_ms": hier["local_rs_ms"],
            "cross_ms": hier["cross_ms"],
            "local_ag_ms": hier["local_ag_ms"],
            "shm_ops_per_sec": round(shm["ops_per_sec"], 2),
            "shm_transport_speedup": round(
                shm["ops_per_sec"] / max(hier["ops_per_sec"], 1e-9), 3),
            "shm_local_rs_ms": shm["local_rs_ms"],
            "shm_local_ag_ms": shm["local_ag_ms"],
        },
    }))


def bench_negotiation_scale() -> None:
    """Simulated-scale control-plane bench (docs/performance.md
    #control-plane-scaling): hundreds of engine-plane ranks IN ONE
    PROCESS over loopback (the C++ simscale harness — every rank a full
    Engine with its own sockets and background thread), driving OP_NOOP
    negotiation cycles so the measured latency is pure control plane.

    Six measured cells: {small, large} ranks x {star baseline,
    tree+steady} plus the large tree cell rerun twice — once with the
    heartbeat detector disabled, once with the perf-introspection plane
    (link accounting + anomaly detector) disabled.  The headline is steady-state cycles/sec at the
    LARGE size; extras carry the per-cell p50s, the steady-vs-small
    flatness ratio (the acceptance bar: within 1.5x of the small size,
    where the star grows superlinearly), the steady-window control-frame
    delta (the zero-frames-per-cycle contract, asserted via the same
    counters metrics_snapshot()["control"] exposes), the heartbeat
    on-vs-off steady p50 inflation (asserted <
    BENCH_HB_MAX_OVERHEAD_PCT, default 5% — the detector must be
    unmeasurable in the steady state,
    docs/fault-tolerance.md#failure-detection), and rank 0's init
    clock-sync fan-in (asserted O(hosts) on the tree — the sub-
    coordinator relay, not the O(ranks) star probe).

    BENCH_SCALE_RANKS="16,256" overrides the sizes; BENCH_OPS /
    BENCH_WARM_CYCLES / BENCH_STEADY_CYCLES the per-cycle shape."""
    import ctypes
    import resource

    from horovod_tpu.common import _load_lib

    lib = _load_lib()
    sizes = [int(s) for s in os.environ.get(
        "BENCH_SCALE_RANKS", "16,256").split(",") if s]
    small, large = sizes[0], sizes[-1]
    ops = int(os.environ.get("BENCH_OPS", "2"))
    warm = int(os.environ.get("BENCH_WARM_CYCLES", "40"))
    steady = int(os.environ.get("BENCH_STEADY_CYCLES", "30"))
    threshold = 8
    # The harness opens ~5 fds per simulated rank (listener, ring pair,
    # control, transient rendezvous); lift the soft NOFILE limit so the
    # large cell fits.
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    want = max(soft, 8 * large + 512)
    if soft < want:
        resource.setrlimit(resource.RLIMIT_NOFILE,
                           (min(hard, want), hard))

    def local_size(n: int) -> int:
        # ~n/16 ranks per simulated host, floored at 2 so the tree has
        # real fan-in at the small size too.
        for cand in (max(2, n // 16), 4, 2):
            if n % cand == 0 and cand >= 2:
                return cand
        return 1

    def run(size: int, use_tree: bool, use_steady: bool, port: int,
            hb_ms: int = 100, introspection: bool = True) -> dict:
        # The simulated engines read the heartbeat / introspection knobs
        # from the real environment at Init (same contract as launched
        # ranks), so the on/off cells toggle them via os.environ —
        # putenv makes the change visible to the in-process C++ getenv.
        # introspection=False turns off the whole perf-introspection
        # plane: link accounting (HVD_TPU_LINK_STATS=0) and the anomaly
        # detector thread (HVD_TPU_ANOMALY_SIGMA=0).
        saved = {k: os.environ.get(k)
                 for k in ("HVD_TPU_HEARTBEAT_MS", "HVD_TPU_LINK_STATS",
                           "HVD_TPU_ANOMALY_SIGMA")}
        os.environ["HVD_TPU_HEARTBEAT_MS"] = str(hb_ms)
        if not introspection:
            os.environ["HVD_TPU_LINK_STATS"] = "0"
            os.environ["HVD_TPU_ANOMALY_SIGMA"] = "0"
        buf = ctypes.create_string_buffer(2048)
        try:
            for attempt in range(3):  # port collisions retry on a new base
                rc = lib.hvd_tpu_simscale_run(
                    size, local_size(size), ops, warm, steady,
                    threshold if use_steady else 0, int(use_tree),
                    port + attempt * (size + 16), 60.0, buf, 2048)
                rep = json.loads(buf.value.decode() or "{}")
                if rc == 0 and rep.get("ok"):
                    return rep
            raise RuntimeError(f"simscale run failed: {rep}")
        finally:
            for key, value in saved.items():
                if value is None:
                    os.environ.pop(key, None)
                else:
                    os.environ[key] = value

    base_port = 45000 + (os.getpid() % 400) * 16
    cells = {}
    for size in (small, large):
        cells[(size, "star")] = run(size, False, False, base_port)
        base_port += size + 64
        cells[(size, "tree")] = run(size, True, True, base_port)
        base_port += size + 64
    hb_off = run(large, True, True, base_port, hb_ms=0)
    base_port += large + 64
    intro_off = run(large, True, True, base_port, introspection=False)
    base_port += large + 64

    t_small, t_large = cells[(small, "tree")], cells[(large, "tree")]
    s_small, s_large = cells[(small, "star")], cells[(large, "star")]
    steady_p50 = t_large["steady_p50_us"]
    value = 1e6 / steady_p50 if steady_p50 > 0 else 0.0
    # Heartbeat overhead must be unmeasurable: the beat threads wake at
    # 10 Hz off the engine tick and never touch the steady-state replay
    # path, so steady p50 with the detector on stays within
    # BENCH_HB_MAX_OVERHEAD_PCT of the detector-off run.  The same 300µs
    # floor as the flatness ratio absorbs the co-located simulator's
    # thread-wake quantum; the frame counters prove each cell really ran
    # in its regime.
    assert t_large["hb_frames_sent"] > 0, t_large
    assert hb_off["hb_frames_sent"] == 0, hb_off
    hb_max_pct = float(os.environ.get("BENCH_HB_MAX_OVERHEAD_PCT", "5"))
    hb_inflation = (t_large["steady_p50_us"]
                    / max(hb_off["steady_p50_us"], 300.0))
    assert hb_inflation <= 1.0 + hb_max_pct / 100.0, (
        f"heartbeat detector inflated steady p50 at {large} ranks by "
        f"{100.0 * (hb_inflation - 1.0):.1f}% (want <= {hb_max_pct:g}%): "
        f"{hb_off['steady_p50_us']:.1f}us off -> "
        f"{t_large['steady_p50_us']:.1f}us on")
    # Perf-introspection overhead must be unmeasurable too: link
    # accounting is one short mutex hold per transport call and the
    # anomaly detector wakes off the tick, so steady p50 with the plane
    # on stays within BENCH_LINK_MAX_OVERHEAD_PCT (default 5%, the same
    # bar as the heartbeat detector) of the plane-off run.  link_sends
    # is process-cumulative across cells, so the off cell is proven by
    # ZERO GROWTH over the cell that ran just before it, and the on
    # cells by a nonzero total.
    assert t_large["link_sends"] > 0, t_large
    assert intro_off["link_sends"] == hb_off["link_sends"], (
        f"link accounting grew while HVD_TPU_LINK_STATS=0: "
        f"{hb_off['link_sends']} -> {intro_off['link_sends']}")
    link_max_pct = float(os.environ.get(
        "BENCH_LINK_MAX_OVERHEAD_PCT", "5"))
    link_inflation = (t_large["steady_p50_us"]
                      / max(intro_off["steady_p50_us"], 300.0))
    assert link_inflation <= 1.0 + link_max_pct / 100.0, (
        f"perf-introspection plane inflated steady p50 at {large} ranks "
        f"by {100.0 * (link_inflation - 1.0):.1f}% (want <= "
        f"{link_max_pct:g}%): {intro_off['steady_p50_us']:.1f}us off -> "
        f"{t_large['steady_p50_us']:.1f}us on")
    # Init clock-sync fan-in at rank 0 is O(hosts) on the tree: the
    # sub-coordinator relay probes only direct children (own-host ranks
    # + one sub-coordinator per other host), never the O(ranks) star.
    hosts_large = large // local_size(large)
    fanin = t_large["clock_fanin"]
    assert 0 < fanin <= hosts_large + local_size(large), (
        f"rank-0 clock-sync fan-in {fanin} at {large} ranks exceeds "
        f"O(hosts): want <= {hosts_large} hosts + {local_size(large)} "
        f"local ranks")
    assert s_large["clock_fanin"] == large - 1, s_large  # the star probe
    extras = {
        "ranks_small": small,
        "ranks_large": large,
        f"star_p50_us_{small}": s_small["steady_p50_us"],
        f"star_p50_us_{large}": s_large["steady_p50_us"],
        f"steady_p50_us_{small}": t_small["steady_p50_us"],
        f"steady_p50_us_{large}": t_large["steady_p50_us"],
        f"warm_tree_p50_us_{large}": t_large["warm_p50_us"],
        # The acceptance bar: steady-state cost flat in ranks, against
        # the star's growth in the same run.  The 300µs floor absorbs
        # the co-located simulator's thread-wake quantum (the real
        # signal is µs-scale local replay — docs/performance.md
        # #control-plane-scaling).  "inflation" keys gate
        # lower-is-better in tools/bench_compare.py.
        "steady_scale_inflation": (
            t_large["steady_p50_us"] / max(t_small["steady_p50_us"], 300.0)),
        "star_scale_inflation": (
            s_large["steady_p50_us"] / s_small["steady_p50_us"]
            if s_small["steady_p50_us"] > 0 else 0.0),
        "steady_entered": int(t_small["steady_entered"]
                              and t_large["steady_entered"]),
        # Control frames sent during the steady window (max over ranks):
        # the decentralized steady state's contract is ZERO.
        "steady_frames_delta": max(t_small["steady_frames_delta"],
                                   t_large["steady_frames_delta"]),
        f"coord_children_{large}": t_large["coord_children"],
        # "inflation" keys gate lower-is-better in tools/bench_compare.py.
        f"hb_off_steady_p50_us_{large}": hb_off["steady_p50_us"],
        "hb_overhead_inflation": round(hb_inflation, 4),
        f"hb_frames_sent_{large}": t_large["hb_frames_sent"],
        f"intro_off_steady_p50_us_{large}": intro_off["steady_p50_us"],
        "link_overhead_inflation": round(link_inflation, 4),
        f"link_sends_{large}": t_large["link_sends"],
        f"clock_fanin_tree_{large}": fanin,
        f"clock_fanin_star_{large}": s_large["clock_fanin"],
    }
    print(json.dumps({
        "metric": "negotiation_scale_steady_cycles_per_sec",
        "value": round(value, 1),
        "unit": "cycles/sec",
        "vs_baseline": round(value / (1e6 / s_large["steady_p50_us"]), 2)
        if s_large["steady_p50_us"] > 0 else 0.0,
        "extra_metrics": extras,
    }), flush=True)


def bench_serve_decode() -> None:
    """Serving-plane bench (docs/inference.md): a synthetic multi-tenant
    request stream against the continuous-batching engine over BENCH_NP
    ranks.  Headline is generated tokens/sec; extra_metrics carries p50/
    p99 time-to-first-token and per-token latency (lower-is-better: the
    ``_ms`` suffix tells tools/bench_compare.py to gate them in that
    direction), mean batch occupancy, and the steady-state negotiation-
    cache hit rate measured over the serve window only (init-time param
    broadcasts are legitimate misses) — asserted >= 0.9, the number that
    proves decode steps pay zero coordinator roundtrips."""
    import subprocess
    import sys

    np_ = int(os.environ.get("BENCH_NP", "2"))
    n_requests = int(os.environ.get("BENCH_REQUESTS", "24"))
    repo = os.path.dirname(os.path.abspath(__file__))
    code = f"""
import json, threading, time, numpy as np, horovod_tpu as hvd
from tools.metrics_dump import quantile
from horovod_tpu.serving.engine import (ModelSpec, ServingEngine,
                                        broadcast_params, init_params)
from horovod_tpu.serving.scheduler import Scheduler, ServeConfig
hvd.init()
spec = ModelSpec(vocab=211, d_model=64, n_layers=2, n_heads=2)
cfg = ServeConfig(max_batch=8, prefill_chunk=8, block_tokens=8,
                  num_blocks=192, max_blocks_per_seq=12)
params = broadcast_params(init_params(spec))
rank0 = hvd.rank() == 0
sch = Scheduler(cfg) if rank0 else None
engine = ServingEngine(spec, cfg, params, sch)
if not rank0:
    engine.run()
    hvd.shutdown()
    raise SystemExit(0)
loop = threading.Thread(target=engine.run, daemon=True)
loop.start()
base = hvd.metrics_snapshot()["cache"]["engine"]
rng = np.random.RandomState(0)
reqs = []
t0 = time.perf_counter()
# Mixed tenants/lengths arriving while earlier requests decode: the
# continuous-batching shape (joins and retirements at step boundaries).
for i in range({n_requests}):
    tenant = ("acme", "beta", "gamma")[i % 3]
    prompt = rng.randint(0, 211, int(rng.randint(4, 40))).tolist()
    reqs.append(sch.submit(tenant, prompt, int(rng.randint(8, 32))))
    time.sleep(0.002)
for r in reqs:
    assert r.event.wait(300), f"request {{r.id}} hung"
dt = time.perf_counter() - t0
engine.request_stop()
loop.join(60)
snap = hvd.metrics_snapshot()
cache = snap["cache"]["engine"]
hits = cache["hits"] - base["hits"]
misses = cache["misses"] - base["misses"]
hit_rate = hits / max(hits + misses, 1)
assert hit_rate >= 0.9, (
    f"steady-state negotiation cache hit rate {{hit_rate:.3f}} < 0.9 "
    f"({{hits}} hits / {{misses}} misses over the serve window)")
serving = snap["serving"]
hists = snap["histograms"]
tokens = sum(len(r.generated) for r in reqs)
print("SERVE_JSON " + json.dumps({{
    "tokens_per_sec": tokens / dt,
    "requests": len(reqs),
    "ttft_p50_ms": round((quantile(hists["serving_ttft_sec"], 0.5)
                          or 0.0) * 1e3, 2),
    "ttft_p99_ms": round((quantile(hists["serving_ttft_sec"], 0.99)
                          or 0.0) * 1e3, 2),
    "token_p50_ms": round((quantile(hists["serving_token_sec"], 0.5)
                           or 0.0) * 1e3, 2),
    "token_p99_ms": round((quantile(hists["serving_token_sec"], 0.99)
                           or 0.0) * 1e3, 2),
    "occupancy": round(serving["occupancy"], 4),
    "steps": serving["steps"],
    "cache_hit_rate": round(hit_rate, 4),
}}), flush=True)
hvd.shutdown()
"""
    env = dict(os.environ,
               PYTHONPATH=repo + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    env.setdefault("HVD_TPU_METRICS", "1")
    out = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_), "--",
         sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    stats = next(json.loads(line[len("SERVE_JSON "):])
                 for line in out.stdout.splitlines()
                 if line.startswith("SERVE_JSON "))
    print(json.dumps({
        "metric": f"serve_decode_tokens_per_sec_np{np_}",
        "value": round(stats.pop("tokens_per_sec"), 2),
        "unit": "tokens/sec",
        "vs_baseline": None,  # the reference serves nothing
        "extra_metrics": stats,
    }))


def bench_checkpoint() -> None:
    """State-plane bench (docs/fault-tolerance.md#state-plane): three
    questions, one record.  (1) Async snapshot overhead: steps/sec over
    BENCH_NP ranks, snapshots on vs off measured as interleaved windows
    of ONE job (two launches would compare different transient host
    load) — the overlap must keep overhead under
    BENCH_CKPT_MAX_OVERHEAD_PCT (default 5%).
    (2) Durable save wall time: sharded ``ckpt-<step>/rank-N.pkl`` vs the
    legacy rank-0 pickle for the same BENCH_BYTES state (``_ms`` extras
    gate lower-is-better in tools/bench_compare.py).  (3) Elastic resync:
    peer-copy restore vs PR-6 root broadcast after an injected crash,
    measured by a custom reshape driver (``_ms`` extras again).  Headline
    is the sharded save throughput in MB/s."""
    import subprocess
    import sys
    import tempfile

    np_ = int(os.environ.get("BENCH_NP", "2"))
    nbytes = int(os.environ.get("BENCH_BYTES", str(8 * 1024 * 1024)))
    steps = int(os.environ.get("BENCH_STEPS", "30"))
    repo = os.path.dirname(os.path.abspath(__file__))
    snap_code = f"""
import json, os, time, numpy as np, horovod_tpu as hvd
from horovod_tpu.jax.train import save_checkpoint
hvd.init()
n = {nbytes} // 4 // 4
state = hvd.ElasticState(
    weights=np.random.RandomState(0).rand(n).astype(np.float32),
    mu=np.zeros(n, np.float32), nu=np.zeros(n, np.float32),
    extra=np.zeros(n, np.float32), step=0)
plane = hvd.state.arm()
plane.exchange_peers()  # ring-neighbor mirroring without run_elastic
# The step's gradient allreduce moves the FULL state size — the real
# data-parallel proportion (gradient bytes == model bytes per step) the
# snapshot's O(model/size) capture must hide behind.  Snapshot cadence
# (BENCH_SNAP_EVERY, default 4) is the CheckFreq knob: on a CPU bench
# host the mirror's copy competes with the CPU-summed ring for CORES —
# not just for the step path — so per-step snapshots would measure
# resource contention, not fence overhead; recovery loss stays bounded
# at cadence steps (the plane retains the last two commits either way).
every = max(1, int(os.environ.get("BENCH_SNAP_EVERY", "4")))
g = np.ones({nbytes} // 4, np.float32)
snapping = False
def step():
    state.weights += hvd.allreduce(g, average=True,
                                   name="grad")[: state.weights.size]
    state.step += 1
    if snapping and state.step % every == 0:
        plane.snapshot(state)
step()  # warm: negotiate
# Snapshots-on vs snapshots-off measured as INTERLEAVED windows of one
# job (off, on, off, on, ...), best-of-3 each: two separate launches
# would compare different engine warmup and transient host load (the
# run-to-run spread exceeds the overhead being measured); alternating
# windows in one process pair cancels it.
best = {{False: 0.0, True: 0.0}}
for trial in range(6):
    snapping = trial % 2 == 1
    if snapping:
        plane.snapshot(state)  # warm the snapshot path before its window
        plane.wait()
    t0 = time.perf_counter()
    for _ in range({steps}):
        step()
    best[snapping] = max(best[snapping],
                         {steps} / (time.perf_counter() - t0))
    plane.wait()
# Durable-save timing rides the snapshot-on run (state already built).
tree = {{"weights": state.weights, "mu": state.mu, "nu": state.nu,
         "extra": state.extra}}
with tempfile_dir() as d:
    hvd.allreduce(np.ones(1, np.int32), average=False, name="save.align")
    t1 = time.perf_counter()
    save_checkpoint(os.path.join(d, "sharded"), 1, tree, sharded=True)
    sharded_sec = time.perf_counter() - t1
    legacy_sec = 0.0
    if hvd.rank() == 0:
        t2 = time.perf_counter()
        save_checkpoint(os.path.join(d, "legacy"), 1, tree, sharded=False)
        legacy_sec = time.perf_counter() - t2
    hvd.allreduce(np.ones(1, np.int32), average=False, name="save.done")
if hvd.rank() == 0:
    st = hvd.metrics_snapshot()["state"]
    print("SNAP_JSON " + json.dumps({{
        "on_steps_per_sec": best[True],
        "off_steps_per_sec": best[False],
        "overlap_ratio": st["overlap_ratio"],
        "snapshots": st["snapshots"],
        "sharded_save_sec": sharded_sec,
        "legacy_save_sec": legacy_sec,
    }}), flush=True)
"""
    # tempfile_dir: inlined helper so the rank script has no repo import
    # beyond horovod_tpu itself.
    snap_code = ("import contextlib, tempfile\n"
                 "@contextlib.contextmanager\n"
                 "def tempfile_dir():\n"
                 "    import shutil\n"
                 "    d = tempfile.mkdtemp()\n"
                 "    try:\n"
                 "        yield d\n"
                 "    finally:\n"
                 "        shutil.rmtree(d, ignore_errors=True)\n"
                 + snap_code)

    def run_snap() -> dict:
        env = dict(os.environ,
                   PYTHONPATH=repo + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        out = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_),
             "--", sys.executable, "-c", snap_code],
            capture_output=True, text=True, env=env, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        return next(json.loads(line[len("SNAP_JSON "):])
                    for line in out.stdout.splitlines()
                    if line.startswith("SNAP_JSON "))

    resync_code = f"""
import json, os, time, numpy as np, horovod_tpu as hvd
from horovod_tpu import common as _common
hvd.init()
lib = _common._load_lib()
n = {nbytes} // 4
state = hvd.ElasticState(weights=np.zeros(n, np.float32), step=0)
plane = hvd.state.arm() if os.environ.get("BENCH_PEER") == "1" else None
synced, resync_ms = -1, None
while True:
    try:
        epoch = int(lib.hvd_tpu_membership_epoch())
        if epoch != synced:
            lib.hvd_tpu_membership_ack()
            t0 = time.perf_counter()
            if plane is None or not plane.restore(state, epoch):
                state.sync(root=0, key=epoch)
            if epoch:
                resync_ms = (time.perf_counter() - t0) * 1e3
            synced = epoch
        while state.step < 12:
            s = state.step
            state.weights = state.weights + hvd.allreduce(
                np.ones(n, np.float32), average=True, name=f"g.{{s}}")
            state.step = s + 1
            if plane is not None:
                plane.snapshot(state)
        break
    except hvd.MembershipChangedError:
        deadline = time.monotonic() + 60.0
        while int(lib.hvd_tpu_membership_epoch()) == synced:
            assert time.monotonic() < deadline
            time.sleep(0.02)
if hvd.rank() == 0:
    print("RESYNC_JSON " + json.dumps({{
        "resync_ms": resync_ms,
        "peer_restores": hvd.metrics_snapshot()["state"]["peer_restores"],
    }}), flush=True)
"""

    def run_resync(peer: bool) -> dict:
        env = dict(os.environ,
                   PYTHONPATH=repo + os.pathsep
                   + os.environ.get("PYTHONPATH", ""),
                   BENCH_PEER="1" if peer else "0",
                   HVD_TPU_KILL_GRACE_SEC="3",
                   HVD_TPU_COLLECTIVE_TIMEOUT_SEC="30",
                   HVD_TPU_FAULT_SPEC="rank=1:crash@op=8")
        out = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_),
             "--min-np", "1", "--", sys.executable, "-c", resync_code],
            capture_output=True, text=True, env=env, timeout=600)
        assert out.returncode == 0, (peer, out.stderr[-2000:])
        return next(json.loads(line[len("RESYNC_JSON "):])
                    for line in out.stdout.splitlines()
                    if line.startswith("RESYNC_JSON "))

    snap = run_snap()
    overhead_pct = 100.0 * (snap["off_steps_per_sec"]
                            / snap["on_steps_per_sec"] - 1.0)
    max_overhead = float(os.environ.get(
        "BENCH_CKPT_MAX_OVERHEAD_PCT", "5"))
    assert overhead_pct <= max_overhead, (
        f"async snapshots cost {overhead_pct:.1f}% of step throughput "
        f"(want <= {max_overhead:g}%): {snap['off_steps_per_sec']:.2f} "
        f"-> {snap['on_steps_per_sec']:.2f} steps/sec")
    peer = run_resync(True)
    root = run_resync(False)
    assert peer["peer_restores"] >= 1, peer
    mb = nbytes / 1e6
    print(json.dumps({
        "metric": f"checkpoint_sharded_save_mb_per_sec_np{np_}",
        "value": round(mb / max(snap["sharded_save_sec"], 1e-9), 2),
        "unit": "MB/s",
        "vs_baseline": None,  # the reference has no checkpoint story
        "extra_metrics": {
            "snap_on_steps_per_sec": round(snap["on_steps_per_sec"], 2),
            "snap_off_steps_per_sec": round(snap["off_steps_per_sec"], 2),
            "snapshot_overhead_pct": round(overhead_pct, 2),
            "snapshot_overlap_ratio": round(snap["overlap_ratio"], 4),
            "sharded_save_ms": round(snap["sharded_save_sec"] * 1e3, 2),
            "legacy_save_ms": round(snap["legacy_save_sec"] * 1e3, 2),
            "peer_restore_ms": round(peer["resync_ms"], 2),
            "root_broadcast_restore_ms": round(root["resync_ms"], 2),
        },
    }))


def bench_pipeline() -> None:
    """Pipeline-parallel 1F1B training throughput over the engine's p2p
    plane (docs/pipeline.md): a BENCH_STAGES x DP grid (world BENCH_NP)
    trains the stage-partitioned transformer LM with BENCH_MICROBATCHES
    micro-batches per step, activations crossing stage boundaries as
    send/recv buckets and gradients DP-averaging inside each stage group.

    Headline is end-to-end tokens/sec across the whole grid.  Extras
    carry the schedule's bubble fraction (config-determined:
    (S-1)/(S-1+M*V), informational), the per-stage p2p wire bytes for
    the timed window (``_bytes`` extras gate lower-is-better in
    tools/bench_compare.py), and the steady-state response-cache hit
    rate measured AFTER the warmup steps (the >= 0.9 acceptance bar of
    docs/pipeline.md#steady-state; a rate extra gates higher-is-better).
    BENCH_CHUNKS > 1 switches to the interleaved schedule."""
    import subprocess
    import sys

    np_ = int(os.environ.get("BENCH_NP", "4"))
    stages = int(os.environ.get("BENCH_STAGES", "2"))
    chunks = int(os.environ.get("BENCH_CHUNKS", "1"))
    micro = int(os.environ.get("BENCH_MICROBATCHES", "4"))
    steps = int(os.environ.get("BENCH_STEPS", "6"))
    warmup = int(os.environ.get("BENCH_WARMUP", "1"))
    seq = int(os.environ.get("BENCH_SEQ", "32"))
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    d_model = int(os.environ.get("BENCH_D_MODEL", "64"))
    n_layers = int(os.environ.get("BENCH_LAYERS", "4"))
    n_heads = int(os.environ.get("BENCH_HEADS", "4"))
    vocab = int(os.environ.get("BENCH_VOCAB", "256"))
    repo = os.path.dirname(os.path.abspath(__file__))
    code = f"""
import json, time, numpy as np
import jax, jax.numpy as jnp, optax
import horovod_tpu as hvd
from horovod_tpu.jax.train import run_pipeline
from horovod_tpu.models import TransformerLM, next_token_loss
from horovod_tpu.parallel import (PipelineGrid, partition_params,
                                  partition_transformer)
hvd.init()
S, V, M, B, SEQ = {stages}, {chunks}, {micro}, {batch}, {seq}
grid = PipelineGrid(S, hvd.size(), hvd.rank())
full = TransformerLM(
    vocab_size={vocab}, d_model={d_model}, n_layers={n_layers},
    n_heads={n_heads}, dtype=jnp.float32, use_flash=False).init(
    jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32))["params"]
modules = partition_transformer(
    {vocab}, {d_model}, {n_layers}, {n_heads}, n_stages=S, n_chunks=V,
    dtype=jnp.float32, use_flash=False)[grid.stage]
params = partition_params(full, {n_layers}, S, n_chunks=V)[grid.stage]
tokens = np.random.RandomState(grid.dp_index).randint(
    0, {vocab}, (B, SEQ + 1)).astype(np.int32)
inputs, targets = tokens[:, :-1], tokens[:, 1:]
tx = optax.adamw(1e-3)
batches = [(inputs, targets)]
params, _, _ = run_pipeline(modules, params, tx, batches * {warmup},
                            n_stages=S, n_microbatches=M,
                            loss_fn=next_token_loss)
snap0 = hvd.metrics_snapshot()
t0 = time.perf_counter()
params, _, losses = run_pipeline(modules, params, tx, batches * {steps},
                                 n_stages=S, n_microbatches=M,
                                 loss_fn=next_token_loss)
dt = time.perf_counter() - t0
snap1 = hvd.metrics_snapshot()
p0, p1 = snap0["p2p"], snap1["p2p"]
print("PIPE_RANK_JSON " + json.dumps({{
    "rank": hvd.rank(), "stage": grid.stage,
    "p2p_bytes_out": p1["bytes"]["out"] - p0["bytes"]["out"],
    "p2p_bytes_in": p1["bytes"]["in"] - p0["bytes"]["in"],
    "sends": p1["sends"] - p0["sends"],
    "recvs": p1["recvs"] - p0["recvs"]}}), flush=True)
if hvd.rank() == 0:
    c0 = snap0["cache"]["engine"]
    c1 = snap1["cache"]["engine"]
    dh = c1["hits"] - c0["hits"]
    dm = c1["misses"] - c0["misses"]
    print("PIPE_JSON " + json.dumps({{
        "tokens_per_sec": B * grid.dp * SEQ * {steps} / dt,
        "steady_cache_hit_rate": round(dh / max(dh + dm, 1), 4),
        "steady_cache_hits": dh, "steady_cache_misses": dm}}), flush=True)
hvd.shutdown()
"""
    env = dict(os.environ,
               PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.setdefault("HVD_TPU_METRICS", "1")
    out = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_), "--",
         sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]

    def _scan(marker):
        # Rank stdout merges without line discipline: two ranks' prints
        # can land on one line, so find every marker and raw_decode from
        # it rather than trusting startswith + whole-line json.loads.
        dec = json.JSONDecoder()
        for line in out.stdout.splitlines():
            start = 0
            while True:
                idx = line.find(marker, start)
                if idx < 0:
                    break
                obj, start = dec.raw_decode(line, idx + len(marker))
                yield obj

    head = next(_scan("PIPE_JSON "))
    from horovod_tpu.parallel import bubble_fraction
    extras = {
        "bubble_fraction": round(bubble_fraction(stages, micro, chunks), 4),
        "steady_cache_hit_rate": head["steady_cache_hit_rate"],
        "steady_cache_hits": head["steady_cache_hits"],
        "steady_cache_misses": head["steady_cache_misses"],
    }
    # Per-stage wire volume for the timed window: sum the stage's DP
    # ranks so the extra is stable under BENCH_NP changes at fixed S.
    per_stage = {}
    for r in _scan("PIPE_RANK_JSON "):
        agg = per_stage.setdefault(r["stage"], {"out": 0, "in": 0})
        agg["out"] += r["p2p_bytes_out"]
        agg["in"] += r["p2p_bytes_in"]
    for stage, agg in sorted(per_stage.items()):
        extras[f"stage{stage}_p2p_bytes_out"] = agg["out"]
        extras[f"stage{stage}_p2p_bytes_in"] = agg["in"]
    print(json.dumps({
        "metric": (f"pipeline_train_tokens_per_sec_s{stages}"
                   f"x{np_ // stages}dp"),
        "value": round(head["tokens_per_sec"], 2),
        "unit": "tokens/sec",
        "vs_baseline": None,  # the reference has no pipeline benchmark
        "extra_metrics": extras,
    }))


def main() -> None:
    from horovod_tpu.common.compile_cache import place_compile_cache

    place_compile_cache()
    import jax

    # BENCH_PLATFORM=cpu pins the CPU backend for the tests that drive
    # this script (tests/test_models.py); JAX_PLATFORMS does the same from
    # outside.  A number printed under it is not a chip measurement.
    platform = os.environ.get("BENCH_PLATFORM")
    if platform:
        jax.config.update("jax_platforms", platform)

    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu import models

    model_name = os.environ.get("BENCH_MODEL", "resnet101")
    if model_name == "transformer":
        return bench_transformer()
    if model_name == "allreduce":
        return bench_allreduce()
    if model_name == "small_allreduce":
        return bench_small_allreduce()
    if model_name == "big_allreduce":
        return bench_big_allreduce()
    if model_name == "hier_allreduce":
        return bench_hier_allreduce()
    if model_name == "negotiation_scale":
        return bench_negotiation_scale()
    if model_name == "serve_decode":
        return bench_serve_decode()
    if model_name == "checkpoint":
        return bench_checkpoint()
    if model_name == "pipeline":
        return bench_pipeline()
    if model_name == "scaling":
        return bench_scaling()
    batch = int(os.environ.get("BENCH_BATCH", "64"))
    steps = int(os.environ.get("BENCH_STEPS", "30"))
    warmup = int(os.environ.get("BENCH_WARMUP", "3"))
    side = int(os.environ.get("BENCH_IMAGE", "224"))

    kwargs = {}
    if model_name == "mnist":
        model = models.MnistCNN()
        side, classes = 28, 10
        shape = (batch, side, side, 1)
    else:
        cls = {"resnet50": models.ResNet50, "resnet101": models.ResNet101,
               "resnet18": models.ResNet18, "vgg16": models.VGG16,
               "inception_v3": models.InceptionV3}[model_name]
        if model_name.startswith("resnet"):
            # Step-level fused BN running-stats EMA (models/norm.py): same
            # math as per-layer flax BN, ~1.4 ms/step less tiny-op
            # overhead; the train step applies models.ema_batch_stats.
            kwargs["fused_ema"] = True
        model = cls(num_classes=1000, dtype=jnp.bfloat16, **kwargs)
        if model_name == "inception_v3" and "BENCH_IMAGE" not in os.environ:
            side = 299
        classes = 1000
        shape = (batch, side, side, 3)

    rng = jax.random.PRNGKey(0)
    images = jnp.asarray(np.random.RandomState(0).rand(*shape), jnp.float32)
    labels = jnp.asarray(np.random.RandomState(1).randint(0, classes, batch),
                         jnp.int32)
    variables = model.init(rng, images, train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})

    # Tiny-leaf packing (models/packing.py): the ~420 1-D tensors of a
    # BN model's train state (scale/bias/mean/var + momentum mirrors)
    # each pay a ~40 us memory-space-assignment copy per step — 11% of
    # the r3 ResNet-101 step.  Carrying them as one flat vector removes
    # all but two of those buffers; numerics pinned float32-tight by
    # tests/test_models.py::test_packed_train_step_bit_identical.
    packed = os.environ.get("BENCH_PACKED", "1") != "0"
    if packed:
        from horovod_tpu.models.packing import TreePacker
        p_packer = TreePacker(params)
        params = p_packer.pack(params)
        if has_bn := bool(batch_stats):
            s_packer = TreePacker(batch_stats)
            batch_stats = s_packer.pack(batch_stats)
    else:
        has_bn = bool(batch_stats)
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    dropout_rng = jax.random.PRNGKey(2)

    def loss_fn(params, batch_stats, images, labels):
        if packed:
            params = p_packer.unpack(params)
            if has_bn:
                batch_stats = s_packer.unpack(batch_stats)
        variables = {"params": params}
        # Unused rngs are fine in flax; models mixing BN and dropout
        # (inception_v3) need both the rng and the mutable stats.
        kwargs = {"rngs": {"dropout": dropout_rng}}
        if has_bn:
            variables["batch_stats"] = batch_stats
            kwargs["mutable"] = ["batch_stats"]
        out = model.apply(variables, images, train=True, **kwargs)
        logits, new_stats = out if has_bn else (out, batch_stats)
        new_stats = new_stats["batch_stats"] if has_bn else new_stats
        if packed and has_bn:
            new_stats = s_packer.pack(new_stats)  # one concatenate
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, new_stats

    fused_ema = bool(kwargs.get("fused_ema"))

    def one_step(params, batch_stats, opt_state, images, labels):
        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats, images, labels)
        if fused_ema and has_bn:
            new_stats = models.ema_batch_stats(batch_stats, new_stats, 0.9)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, loss

    # BENCH_UNROLL=K dispatches K optimizer steps per executable (python
    # -level unroll, NOT lax.scan — the scan body loses ~2 ms/step of
    # memory-space-assignment quality, r3 tuning log): the per-execute
    # dispatch overhead amortizes K-fold while the per-step HLO stays
    # identical.  Default 8 for the resnet101 headline was chosen on a
    # set-up that no longer exists; its gain on today's machine is not
    # measured.
    # Compile time grows ~K-fold, so other image models keep 1; the
    # transformer bench has its own default of 4, and an explicit
    # BENCH_UNROLL overrides BOTH (the extras sweep inherits it).
    # Donating params/stats/opt_state lets XLA update
    # in place instead of allocating fresh HBM buffers every step (~1.5%
    # on resnet101).
    unroll = max(1, int(os.environ.get(
        "BENCH_UNROLL", "8" if model_name == "resnet101" else "1")))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, batch_stats, opt_state, images, labels):
        for _ in range(unroll):
            params, batch_stats, opt_state, loss = one_step(
                params, batch_stats, opt_state, images, labels)
        return params, batch_stats, opt_state, loss

    for _ in range(warmup):
        params, batch_stats, opt_state, loss = train_step(
            params, batch_stats, opt_state, images, labels)
    float(loss)  # fetching a value drains the warm-up steps

    t0 = time.perf_counter()
    for _ in range(steps):
        params, batch_stats, opt_state, loss = train_step(
            params, batch_stats, opt_state, images, labels)
    # The final loss depends on every step's params, so one scalar fetch
    # drains the whole chain.
    final_loss = float(loss)
    dt = time.perf_counter() - t0
    assert np.isfinite(final_loss), final_loss

    value = batch * steps * unroll / dt
    # The reference published an absolute throughput only for ResNet-101
    # (1656.82 img/s on 16 GPUs); other models have no comparable number.
    vs = (round(value / REFERENCE_IMG_PER_SEC_PER_DEVICE, 3)
          if model_name == "resnet101" else None)
    record = {
        "metric": f"{model_name}_train_images_per_sec_per_chip",
        "value": round(value, 2),
        "unit": "images/sec/chip",
        "vs_baseline": vs,
    }
    # Print the headline NOW, before any extra runs: round 4 lost its whole
    # recorded result because an extra's compile failure bloated the final
    # (only) JSON line past the driver's capture window.  The headline must
    # be on stdout before anything else can go wrong.
    print(json.dumps(record), flush=True)
    if model_name == "resnet101" and os.environ.get("BENCH_EXTRA", "1") != "0":
        # Fold the LM and long-context headline numbers into a second,
        # enriched JSON line so the driver's default invocation records
        # them too (VERDICT r2 #8: these were builder-attested only).
        # Failures of the extras must not cost the headline metric — and
        # error strings are clipped to one short line so the enriched
        # record can never outgrow the driver's output tail (the r4
        # failure mode: a 20 KB Mosaic error inside the JSON).
        extras = {}
        # Round records track which wire-compression mode the run was
        # configured with (a config row, not a measurement: the
        # single-chip transformer sweep moves no collective bytes).
        extras["wire_compression"] = os.environ.get(
            "HVD_TPU_COMPRESSION", "off")
        # seq:batch pairs, token-constant (16k tokens/step — the
        # long-context protocol of docs/benchmarks.md); the full
        # documented sweep so each round's driver record carries it.
        cfgs = os.environ.get("BENCH_EXTRA_CONFIGS",
                              "1024:16,4096:4,8192:2,16384:1")
        for cfg in cfgs.split(","):
            try:  # a malformed config must not cost the headline metric
                s, b = (int(v) for v in cfg.split(":"))
            except ValueError:
                s = None
                extras[f"bad_config:{cfg.strip()}"] = "error: want seq:batch"
            if s is not None:
                key = ("transformer_train_tokens_per_sec_per_chip"
                       if s == 1024 else
                       f"transformer_seq{s}_tokens_per_sec_per_chip")
                try:
                    if os.environ.get("BENCH_EXTRA_INJECT_FAIL"):
                        # Test hook: the headline-survives-a-failing-extra
                        # property is load-bearing (see r4 post-mortem
                        # above) and must stay verifiable end-to-end.
                        raise RuntimeError(
                            "injected failure (BENCH_EXTRA_INJECT_FAIL)")
                    # Full default step count: steps cost ~1s while
                    # compile dominates the extras' runtime, and short
                    # windows under-report by several percent.
                    extras[key] = round(
                        bench_transformer(seq=s, batch=b, report=False), 2)
                except Exception as exc:  # record, don't fail the headline
                    first = (str(exc).splitlines()[0] if str(exc)
                             else repr(exc))
                    extras[key] = f"error: {first[:160]}"
            # Cumulative re-print after EVERY config (incl. malformed):
            # if the driver kills the process mid-sweep, the last
            # parseable line still carries the headline plus every extra
            # completed so far.
            record["extra_metrics"] = dict(extras)
            print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
