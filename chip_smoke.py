#!/usr/bin/env python3
"""The quickest proof that the compiled training path still starts on the chip.

    python chip_smoke.py             # one TPU chip: device, engine, resnet50,
                                     # transformer
    python chip_smoke.py --chips 4   # one four-chip host: dp4, ring4 (the
                                     # one-device reference and each ring_impl
                                     # in a process of its own), plane4

Every phase runs as a child process (``--phase NAME``), one after another,
each under a time limit of its own: a chip belongs to one process at a time,
so this parent never imports JAX, and a kernel that hangs or a runtime that
aborts its process is reported instead of taking the report with it.  Each
phase prints one JSON object on standard output; the last line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as JAX reports it.  Any failed phase makes that
``{"ok": false, ...}`` and the exit code 1.  A host with no TPU fails at the
first phase: nothing here runs on a CPU.  Every number printed is set-up
information (what a cold call costs, whether losses fall), not a benchmark.

The phases go through the entry points a user calls — ``hvd.init``,
``build_train_step``, ``data_parallel_mesh``, the launcher — at the full
width of the models the examples train, with seeded random
weights and one repeated seeded batch, and they read the compiled HLO for
the kernels instead of trusting the call that asked for them.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# (phase, its time limit in seconds).  The parent also holds the whole run
# to TOTAL_LIMIT, inside the 1200 s the chip check allows.
# The limits are a few times what a cold run took on the chip (PERF.md): a
# hung kernel on four chips is paid for four times over.
ONE_CHIP = [("device", 120), ("engine", 300), ("resnet50", 420),
            ("transformer", 420)]
FOUR_CHIP = [("dp4", 480), ("ring4_ref", 240), ("ring4_ppermute", 240),
             ("ring4_fused", 240), ("ring4_rdma", 240), ("plane4", 300)]
TOTAL_LIMIT = 1150
RING_IMPL_PHASES = ("ring4_ppermute", "ring4_fused", "ring4_rdma")

# ResNet-50 as examples/jax_imagenet_resnet50.py trains it: a global batch
# of 64 (the reference benchmark's 64 a device) at 224 x 224.
RESNET_BATCH, RESNET_IMAGE = 64, 224
# A small LM (the benchmark's cells run the published widths): 512 wide,
# 8 layers, 8 heads, bf16.
LM = dict(vocab_size=32768, d_model=512, n_layers=8, n_heads=8)
LM_SHAPES = [(1024, 16), (8192, 2)]  # (seq, batch): 16k tokens a step
RING_SEQ, RING_BATCH = 8192, 2       # 2048 rows a chip over four chips
PLANE_ENV = ("HVD_TPU_XLA_DATA_PLANE", "HOROVOD_XLA_DATA_PLANE")

# Tolerances, stated once.  flash_attention in bf16 against the float32
# reference on the same (bf16-rounded) inputs: one bf16 rounding of the
# probabilities and of the result, 2^-8 relative each.
FLASH_FWD_ATOL = 2e-2
FLASH_GRAD_RTOL = 4e-2     # max error over the reference gradient's max
# Four chips against one on the same batch: the same mathematics in another
# order of bf16 operations.
DP4_LOSS_RTOL = 1e-2
DP4_UPDATE_RTOL = 1e-1     # L2 error of the parameter update over its norm
RING_LOSS0_RTOL = 1e-2     # same weights, same tokens, before any update
RING_LOSS_RTOL = 5e-2      # after each of the following updates


# --------------------------------------------------------------------------
# Parent: no JAX here.
# --------------------------------------------------------------------------

def _run_phase(name: str, limit: float, extra: list) -> dict:
    """Run one phase as a child under ``limit`` seconds; print and return
    its record.  The child's own JSON line is its result; everything else
    it wrote to standard output goes to our standard error."""
    env = dict(os.environ)
    if name == "plane4":
        for key in PLANE_ENV:  # the plane must form without being asked for
            env.pop(key, None)
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name] + extra
    started = time.monotonic()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, cwd=ROOT,
                            env=env, start_new_session=True)
    timed_out = False
    try:
        out, _ = proc.communicate(timeout=limit)
    except subprocess.TimeoutExpired:
        timed_out = True
        out = ""
    finally:
        # The child leads a session of its own: whatever it started (the
        # launcher's ranks) goes with it, on a time-out and on a clean exit.
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    if timed_out:
        out, _ = proc.communicate()
    result = None
    for line in out.splitlines():
        try:
            parsed = json.loads(line)
        except ValueError:
            parsed = None
        if isinstance(parsed, dict) and "phase" in parsed:
            result = parsed
        else:
            print(line, file=sys.stderr)
    record = dict(result or {})
    record["phase"] = name  # plane4's line comes from its rank 0
    record["ok"] = bool(result and result.get("ok") is True
                        and proc.returncode == 0 and not timed_out)
    record["exit_code"] = proc.returncode
    record["timed_out"] = timed_out
    record["seconds"] = round(time.monotonic() - started, 1)
    return record


def _parent(chips: int, seed: int) -> int:
    if not os.path.isdir(os.path.join(ROOT, "horovod_tpu")):
        print(json.dumps({"ok": False, "error": "no horovod_tpu package "
                          "beside chip_smoke.py"}))
        return 1
    phases = ONE_CHIP if chips == 1 else FOUR_CHIP
    deadline = time.monotonic() + TOTAL_LIMIT
    records, ring_ref = [], None
    for name, limit in phases:
        extra = ["--seed", str(seed)]
        left = deadline - time.monotonic()
        if name in RING_IMPL_PHASES and ring_ref is None:
            record = {"phase": name, "ok": False,
                      "error": "no reference losses"}
        elif left < 30:
            record = {"phase": name, "ok": False,
                      "error": "run out of the whole run's time"}
        else:
            if name in RING_IMPL_PHASES:
                extra += ["--ref-losses", json.dumps(ring_ref)]
            record = _run_phase(name, min(limit, left), extra)
        print(json.dumps(record), flush=True)
        records.append(record)
        if name == "ring4_ref" and record["ok"]:
            ring_ref = record["losses"]
        if name == "device" and not record["ok"]:
            break  # no chip: nothing else may run, least of all on a CPU
    failed = [r["phase"] for r in records if not r["ok"]]
    devices = [r["device"] for r in records if "device" in r]
    if not failed and not devices:
        failed = ["device"]
    if not failed:
        device = devices[0]
        if (any(d != device for d in devices) or device["platform"] != "tpu"
                or device["count"] != chips):
            print(json.dumps({"ok": False, "error": "phases disagree on the "
                              "device, or it is not the one asked for",
                              "devices": devices}))
            return 1
        print(json.dumps({"ok": True, "device": device}))
        return 0
    print(json.dumps({"ok": False, "failed": failed}))
    return 1


# --------------------------------------------------------------------------
# Children.  Each returns the dict that becomes its JSON line; an assertion
# or any other exception fails the phase (no try/except turns a failure
# into a note).
# --------------------------------------------------------------------------

def _tpu(count: int):
    """JAX with the compile cache placed, on exactly ``count`` TPU devices
    — or no phase at all."""
    from horovod_tpu.common.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: jax.devices() is {devices}")
    if len(devices) != count:
        raise SystemExit(f"chip_smoke: wants {count} TPU device(s), JAX "
                         f"reports {len(devices)}: {devices}")
    return jax, devices, cache_dir


def _device(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


class _CacheWatch:
    """Says of each compile whether JAX's persistent cache answered it."""

    def __init__(self, jax):
        self._events = []
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, **_):
        self._events.append(name)

    def compile(self, lowered):
        self._events.clear()
        t0 = time.perf_counter()
        compiled = lowered.compile()
        seconds = time.perf_counter() - t0
        if "/jax/compilation_cache/cache_hits" in self._events:
            cache = "hit"
        elif "/jax/compilation_cache/cache_misses" in self._events:
            cache = "miss"  # compiled, and written for the next process
        else:
            cache = "not used"
        return compiled, {"seconds": round(seconds, 2), "cache": cache}


def _hlo(text: str) -> dict:
    """What the compiled program holds, counted from its text."""
    def count(op):
        return len(re.findall(rf"\b{op}(?:-start)?\(", text))

    return {"tpu_custom_call": text.count('"tpu_custom_call"'),
            "convolution": count("convolution"),
            "all_reduce": count("all-reduce"),
            "collective_permute": count("collective-permute"),
            "while": count("while")}


def _run_steps(jax, step, state, batch, warmup: int, timed: int):
    """``warmup + timed`` steps on one repeated batch.  ``state`` is
    (params, opt_state[, aux]); an aux (batch statistics) rides the batch,
    as in the ResNet example.  Returns the state, every loss, and the
    seconds of the timed steps, each taken around block_until_ready."""
    losses, seconds = [], []
    for i in range(warmup + timed):
        t0 = time.perf_counter()
        out = step(state[0], state[1], batch + tuple(state[2:]))
        jax.block_until_ready(out)
        if i >= warmup:
            seconds.append(round(time.perf_counter() - t0, 4))
        state = (out[0], out[1]) + tuple(out[3:])
        losses.append(float(out[2]))
    return state, losses, seconds


def _assert_falling(losses):
    import math

    assert all(math.isfinite(x) for x in losses), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_device(args) -> dict:
    import importlib.metadata as metadata

    jax, devices, cache_dir = _tpu(1)
    import jaxlib

    return {"device": _device(devices), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": metadata.version("libtpu"),
            "compile_cache_dir": cache_dir,
            "hbm_bytes": (devices[0].memory_stats() or {}).get("bytes_limit")}


def phase_engine(args) -> dict:
    """The host engine builds from the committed sources and a one-process
    hvd.init() serves device arrays."""
    from horovod_tpu.engine import build

    t0 = time.perf_counter()
    lib = build(force=True)
    build_seconds = round(time.perf_counter() - t0, 1)
    jax, devices, _ = _tpu(1)
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu.jax as hvd

    hvd.init()
    assert (hvd.rank(), hvd.size()) == (0, 1), (hvd.rank(), hvd.size())
    key = jax.random.PRNGKey(args.seed)
    params = {"w": jax.random.normal(key, (256, 128)),
              "b": jnp.arange(128, dtype=jnp.bfloat16), "step": 7}
    got = hvd.broadcast_parameters(params, root_rank=0)
    assert got["step"] == 7
    for name in ("w", "b"):
        assert got[name].dtype == params[name].dtype
        assert list(got[name].devices()) == [devices[0]]
        np.testing.assert_array_equal(np.asarray(got[name], np.float32),
                                      np.asarray(params[name], np.float32))
    for average in (True, False):
        out = hvd.allreduce(params["w"], average=average, name=f"w.{average}")
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(params["w"]))
    hvd.shutdown()
    return {"device": _device(devices), "engine_build_seconds": build_seconds,
            "engine_lib": os.path.relpath(lib, ROOT), "rank": 0, "size": 1}


def _resnet50(jax, mesh, seed: int):
    """The step of examples/jax_imagenet_resnet50.py as it is, at a global
    batch of RESNET_BATCH on whatever mesh is given."""
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import ResNet50
    from horovod_tpu.parallel import replicate, shard_batch

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16, axis_name="hvd")
    host_images = np.random.RandomState(seed).rand(
        RESNET_BATCH, RESNET_IMAGE, RESNET_IMAGE, 3).astype(np.float32)
    host_labels = np.random.RandomState(seed + 1).randint(
        0, 1000, RESNET_BATCH).astype(np.int32)
    variables = model.init(jax.random.PRNGKey(seed), host_images[:2],
                           train=False)

    def loss_fn(params, batch):
        images, labels, batch_stats = batch
        logits, updated = model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, updated["batch_stats"]

    # The example's rate, 0.0125 a device at 64 images a device, is a rate
    # per 64 images of global batch: the same for one chip and for four at
    # the same global batch, so the two stay the same mathematics.
    tx = optax.sgd(0.0125 * RESNET_BATCH / 64, momentum=0.9)
    step = build_train_step(loss_fn, tx, mesh, axis_name="hvd", has_aux=True,
                            batch_spec=(P("hvd"), P("hvd"), P()))
    params = replicate(mesh, variables["params"])
    state = (params, replicate(mesh, tx.init(params)),
             replicate(mesh, variables["batch_stats"]))
    batch = (shard_batch(mesh, host_images), shard_batch(mesh, host_labels))
    return step, state, batch


def phase_resnet50(args) -> dict:
    jax, devices, cache_dir = _tpu(1)
    from horovod_tpu.parallel import data_parallel_mesh

    watch = _CacheWatch(jax)
    step, state, batch = _resnet50(jax, data_parallel_mesh(), args.seed)
    step_args = (state[0], state[1], batch + (state[2],))
    # The same program compiled twice: first as this checkout's cache stands
    # (cold in a fresh one), then again with JAX's in-memory caches dropped,
    # which only the persistent cache can make quick.
    compiled, first = watch.compile(step.lower(*step_args))
    text = compiled.as_text()
    memory = compiled.memory_analysis()
    jax.clear_caches()
    _, again = watch.compile(step.lower(*step_args))
    assert again["cache"] == "hit", (first, again)
    state, losses, seconds = _run_steps(jax, step, state, batch, 3, 5)
    _assert_falling(losses)
    return {"device": _device(devices), "model": "ResNet50 bf16 sync-BN",
            "batch": RESNET_BATCH, "image": RESNET_IMAGE,
            "compile_first": first,
            "compile_again": again, "compile_cache_dir": cache_dir,
            "hlo": _hlo(text), "temp_bytes": memory.temp_size_in_bytes,
            "losses": [round(x, 4) for x in losses],
            "step_seconds": seconds, "peak_bytes": _peak_bytes(devices[0])}


def _lm(jax, mesh, seq: int, batch: int, seed: int, axis_name, spec,
        **model_kwargs):
    """A TransformerLM train step at ``LM``'s width through
    build_train_step; ``model_kwargs`` choose single-shard flash or a ring."""
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding

    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import TransformerLM, next_token_loss
    from horovod_tpu.parallel import replicate

    kwargs = dict(LM, dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16)
    model = TransformerLM(**kwargs, **model_kwargs)
    tokens = np.random.RandomState(seed).randint(
        0, LM["vocab_size"], (batch, seq + 1)).astype(np.int32)
    # Weights do not depend on how attention is computed: initialise on the
    # plain model at a short length.
    params = TransformerLM(**kwargs).init(
        jax.random.PRNGKey(seed), jnp.asarray(tokens[:1, :128]))["params"]

    def loss_fn(params, batch):
        inputs, targets = batch
        return next_token_loss(model.apply({"params": params}, inputs),
                               targets)

    tx = optax.adamw(1e-3)
    step = build_train_step(loss_fn, tx, mesh, axis_name=axis_name,
                            batch_spec=(spec, spec))
    params = replicate(mesh, params)
    state = (params, replicate(mesh, tx.init(params)))
    sharding = NamedSharding(mesh, spec)
    data = (jax.device_put(tokens[:, :-1], sharding),
            jax.device_put(tokens[:, 1:], sharding))
    return step, state, data


def _flash_against_reference(jax, seed: int) -> dict:
    """flash_attention forward and gradients against mha_reference on the
    chip, bf16 at the LM's head shape."""
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops import flash_attention, mha_reference

    shape = (2, LM["n_heads"], 1024, LM["d_model"] // LM["n_heads"])
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(key, shape, jnp.bfloat16)
               for key in keys[:3])
    weight = jax.random.normal(keys[3], shape, jnp.float32)

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=True)
        return (out.astype(jnp.float32) * weight).sum()

    def reference_loss(q, k, v):
        return (mha_reference(q, k, v, causal=True) * weight).sum()

    flash_grad = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))
    text = flash_grad.lower(q, k, v).compile().as_text()
    assert _hlo(text)["tpu_custom_call"] == 2, _hlo(text)  # fwd + combined
    wide = [t.astype(jnp.float32) for t in (q, k, v)]
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
        q, k, v)
    forward_error = float(jnp.abs(out.astype(jnp.float32)
                                  - mha_reference(*wide, causal=True)).max())
    assert forward_error <= FLASH_FWD_ATOL, forward_error
    grad_errors = []
    for got, want in zip(flash_grad(q, k, v),
                         jax.grad(reference_loss, argnums=(0, 1, 2))(*wide)):
        error = float(jnp.abs(got.astype(jnp.float32) - want).max()
                      / jnp.abs(want).max())
        assert np.isfinite(error) and error <= FLASH_GRAD_RTOL, error
        grad_errors.append(round(error, 5))
    return {"shape": list(shape), "dtype": "bfloat16",
            "forward_max_abs_error": round(forward_error, 5),
            "forward_atol": FLASH_FWD_ATOL,
            "grad_max_error_over_max": grad_errors,
            "grad_rtol": FLASH_GRAD_RTOL}


def phase_transformer(args) -> dict:
    jax, devices, _ = _tpu(1)
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.ops.attention import _bwd_plan
    from horovod_tpu.parallel import data_parallel_mesh

    watch = _CacheWatch(jax)
    mesh = data_parallel_mesh()
    head_dim = LM["d_model"] // LM["n_heads"]
    shapes = []
    for seq, batch in LM_SHAPES:
        step, state, data = _lm(jax, mesh, seq, batch, args.seed, "hvd",
                                P("hvd"), use_flash=True)
        compiled, compile_info = watch.compile(
            step.lower(state[0], state[1], data))
        hlo = _hlo(compiled.as_text())
        # Flash forward and backward must be Pallas kernels in the compiled
        # step, as many as the plan says, and the blockwise_attention scan
        # (a while loop) must not be there.
        mode = _bwd_plan(seq, head_dim, 1024, 1024, batch * LM["n_heads"])[0]
        per_layer = {"combined": 2, "split": 3}[mode]
        assert hlo["tpu_custom_call"] == per_layer * LM["n_layers"], hlo
        assert hlo["while"] == 0, hlo
        state, losses, seconds = _run_steps(jax, step, state, data, 2, 3)
        _assert_falling(losses)
        shapes.append({"seq": seq, "batch": batch, "backward": mode,
                       "compile": compile_info, "hlo": hlo,
                       "losses": [round(x, 4) for x in losses],
                       "step_seconds": seconds,
                       "peak_bytes": _peak_bytes(devices[0])})
        del step, state, data
    return {"device": _device(devices), "model": dict(LM, dtype="bfloat16"),
            "shapes": shapes,
            "flash_vs_reference": _flash_against_reference(jax, args.seed)}


def _chips_of_2x2(devices) -> list:
    """(id, coords) of the mesh's devices in mesh order.  parallel/mesh.py
    takes jax.devices() in the order it comes: for a one-axis all-reduce
    any order of the four chips of a 2x2 host is right, as long as they are
    the four."""
    order = [(d.id, list(d.coords)) for d in devices]
    assert sorted(c for _, c in order) == [[0, 0, 0], [0, 1, 0], [1, 0, 0],
                                           [1, 1, 0]], order
    return order


def phase_dp4(args) -> dict:
    """ResNet-50 over four chips at 16 images a chip against one chip at 64:
    with sync BN the same mathematics, from the same seed."""
    jax, devices, _ = _tpu(4)
    import numpy as np

    from horovod_tpu.parallel import data_parallel_mesh

    def placement(array):
        return [(s.device, s.data.shape, s.index)
                for s in array.addressable_shards]

    def run(mesh):
        step, state, batch = _resnet50(jax, mesh, args.seed)
        before = jax.device_get(state[0])
        text = step.lower(state[0], state[1],
                          batch + (state[2],)).compile().as_text()
        shards = {"images": placement(batch[0]),  # before the step donates
                  "a_parameter": placement(jax.tree.leaves(state[0])[0])}
        state, losses, _ = _run_steps(jax, step, state, batch, 0, 2)
        after = jax.device_get(state[0])
        update = jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                              - np.asarray(b, np.float64), after, before)
        # Steady steps, for the record only: many small gradient leaves and
        # 99 batch-norm all-reduces, the exchange that is all latency.
        _, _, seconds = _run_steps(jax, step, state, batch, 2, 10)
        return (_hlo(text), shards, losses, jax.tree.leaves(update), seconds,
                step.exchange_overlap)

    mesh4 = data_parallel_mesh()
    order = _chips_of_2x2(mesh4.devices.flat)
    hlo4, shards, losses4, update4, seconds4, overlap4 = run(mesh4)
    assert hlo4["all_reduce"] > 0, hlo4
    # The batch really is spread, 16 images a chip, and a replicated leaf
    # has its four whole copies.
    per_chip = (RESNET_BATCH // 4, RESNET_IMAGE, RESNET_IMAGE, 3)
    assert len({device for device, _, _ in shards["images"]}) == 4
    assert all(shape == per_chip for _, shape, _ in shards["images"])
    assert len({index for _, _, index in shards["images"]}) == 4
    assert len({device for device, _, _ in shards["a_parameter"]}) == 4
    assert len({(shape, index)
                for _, shape, index in shards["a_parameter"]}) == 1
    hlo1, _, losses1, update1, seconds1, _ = run(
        data_parallel_mesh(devices[:1]))
    assert hlo1["all_reduce"] == 0, hlo1
    for got, want in zip(losses4, losses1):
        assert abs(got - want) <= DP4_LOSS_RTOL * abs(want), (losses4,
                                                              losses1)
    error = np.sqrt(sum(((a - b) ** 2).sum()
                        for a, b in zip(update4, update1)))
    norm = np.sqrt(sum((b ** 2).sum() for b in update1))
    assert np.isfinite(error) and error <= DP4_UPDATE_RTOL * norm, (error,
                                                                    norm)
    return {"device": _device(devices), "device_order": order,
            "per_chip_batch": RESNET_BATCH // 4, "hlo_four_chips": hlo4,
            "hlo_one_chip": hlo1, "exchange_overlap": overlap4,
            "step_seconds_four_chips": seconds4,
            "step_seconds_one_chip": seconds1, "losses_four_chips": losses4,
            "losses_one_chip": losses1, "loss_rtol": DP4_LOSS_RTOL,
            "update_error_over_norm": float(error / norm),
            "update_rtol": DP4_UPDATE_RTOL}


def phase_ring4_ref(args) -> dict:
    """The one-device flash losses every ring variant is held to."""
    jax, devices, _ = _tpu(4)
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel import data_parallel_mesh

    step, state, data = _lm(jax, data_parallel_mesh(devices[:1]), RING_SEQ,
                            RING_BATCH, args.seed, "hvd", P("hvd"),
                            use_flash=True)
    _, losses, seconds = _run_steps(jax, step, state, data, 0, 3)
    _assert_falling(losses)
    return {"device": _device(devices), "seq": RING_SEQ, "batch": RING_BATCH,
            "losses": losses, "step_seconds": seconds}


def phase_ring4(args, impl: str) -> dict:
    """TransformerLM(seq_axis="sp", ring_impl=impl) on a (dp, sp) = (1, 4)
    mesh, 2048 rows a chip, against the one-device flash losses."""
    jax, devices, _ = _tpu(4)
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(devices).reshape(1, 4), ("dp", "sp"))
    step, state, data = _lm(jax, mesh, RING_SEQ, RING_BATCH, args.seed,
                            ("dp", "sp"), P("dp", "sp"), seq_axis="sp",
                            ring_impl=impl)
    text = step.lower(state[0], state[1], data).compile().as_text()
    hlo = _hlo(text)
    layers = LM["n_layers"]
    # Which rotation the program really holds (the call alone proves
    # nothing: off a TPU backend two of the three reroute).
    if impl == "ppermute":
        assert hlo["tpu_custom_call"] == 0 and hlo["collective_permute"], hlo
    elif impl == "rdma":
        # K and V, three rotations each, forward and transposed.
        assert hlo["tpu_custom_call"] == 12 * layers, hlo
    else:
        # Four step kernels and a barrier-only closer per pass; the barrier
        # namespaces alternate through the whole program, closers included.
        assert hlo["tpu_custom_call"] == 10 * layers, hlo
        ids = re.findall(r'collective_id\W+(\d+)', text)
        assert ids == ["15", "16"] * (4 * layers), ids
    _, losses, seconds = _run_steps(jax, step, state, data, 0, 3)
    _assert_falling(losses)
    reference = json.loads(args.ref_losses)
    for i, (got, want) in enumerate(zip(losses, reference)):
        rtol = RING_LOSS0_RTOL if i == 0 else RING_LOSS_RTOL
        assert abs(got - want) <= rtol * abs(want), (impl, losses, reference)
    return {"device": _device(devices), "ring_impl": impl, "hlo": hlo,
            "rows_per_chip": RING_SEQ // 4, "losses": losses,
            "reference_losses": reference, "step_seconds": seconds,
            "loss0_rtol": RING_LOSS0_RTOL, "loss_rtol": RING_LOSS_RTOL}


def phase_plane4(args) -> dict:
    """Four pinned ranks under the launcher; stays off JAX itself, as the
    launcher does.  The ranks' report (rank 0's JSON line) is the result."""
    from horovod_tpu.engine import build

    build()  # once here, not four times in a race between the ranks
    assert not any(key in os.environ for key in PLANE_ENV)
    assert "jax" not in sys.modules
    env = dict(os.environ, HVD_TPU_METRICS="1")
    cmd = [sys.executable, "-m", "horovod_tpu.runner", "--tpu-pin", "-np", "4",
           "--timeout", "240", "--", sys.executable,
           os.path.abspath(__file__), "--phase", "plane4_rank",
           "--seed", str(args.seed)]
    subprocess.run(cmd, cwd=ROOT, env=env, check=True)
    return None  # rank 0 printed the phase's line


def phase_plane4_rank(args) -> dict:
    assert not any(key in os.environ for key in PLANE_ENV)
    assert os.environ.get("TPU_VISIBLE_CHIPS"), "the launcher did not pin"
    from horovod_tpu.common.compile_cache import place_compile_cache

    place_compile_cache()
    import jax.numpy as jnp
    import numpy as np
    from jax._src import xla_bridge

    import horovod_tpu.common as common
    import horovod_tpu.jax as hvd

    assert not xla_bridge.backends_are_initialized()
    hvd.init()
    import jax

    local, everyone = jax.local_devices(), jax.devices()
    assert len(local) == 1 and local[0].platform == "tpu", local
    assert len(everyone) == 4, everyone
    plane = common._xla_plane
    assert plane is not None, "pinned ranks must form the XLA data plane"
    rank, size = hvd.rank(), hvd.size()
    assert size == 4
    base = np.arange(1024, dtype=np.float32)
    out = hvd.allreduce(jnp.asarray(base * (rank + 1)), average=False,
                        name="smoke.allreduce")
    np.testing.assert_allclose(np.asarray(out), base * 10.0, rtol=1e-6)
    out = hvd.allgather(jnp.full((rank + 1, 3), float(rank)),
                        name="smoke.allgather")  # ragged: rank+1 rows each
    want = np.concatenate([np.full((r + 1, 3), float(r)) for r in range(4)])
    np.testing.assert_array_equal(np.asarray(out), want)
    out = hvd.broadcast(jnp.arange(16, dtype=jnp.int32) * (rank + 1),
                        root_rank=2, name="smoke.broadcast")
    np.testing.assert_array_equal(np.asarray(out), np.arange(16) * 3)
    chips = np.asarray(hvd.allgather(
        jnp.asarray([[rank, local[0].id, jax.process_index()]], jnp.int32),
        name="smoke.chips"))
    assert len(set(chips[:, 1].tolist())) == 4, chips  # a chip each
    # The ops rode the plane, not the TCP engine.
    ops = common.metrics_snapshot()["ops"]
    assert ops["xla"]["allreduce"] >= 1 and ops["xla"]["broadcast"] >= 1
    assert ops["xla"]["allgather"] >= 2, ops
    assert not ops.get("engine", {}).get("allgather"), ops
    assert not ops.get("engine", {}).get("broadcast"), ops
    assert plane.stats["dispatches"] >= 4, plane.stats
    hvd.shutdown()
    if rank != 0:
        return None
    return {"device": _device(everyone), "rank_chip_process": chips.tolist(),
            "ops": ops, "plane_dispatches": plane.stats["dispatches"]}


def _child(args) -> int:
    if not __debug__:
        raise SystemExit("chip_smoke checks with assert: run it without -O")
    name = args.phase
    if name in RING_IMPL_PHASES:
        result = phase_ring4(args, name[len("ring4_"):])
    else:
        result = globals()[f"phase_{name}"](args)
    if result is not None:
        print(json.dumps({"phase": name, "ok": True, **result}), flush=True)
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="1: the one-chip phases (default); 4: the "
                             "phases that exist only across chips")
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the random weights and batches")
    parser.add_argument("--phase", help=argparse.SUPPRESS)
    parser.add_argument("--ref-losses", help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.phase:
        return _child(args)
    return _parent(args.chips, args.seed)


if __name__ == "__main__":
    sys.exit(main())
