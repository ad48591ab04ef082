#!/usr/bin/env python
"""The delta rule's solve alone in a program on the attached TPU, at the two
shapes the benchmark's Gated DeltaNet cells run: `ops.delta_rule._solved`
(XLA's forward substitution and full-precision einsums, which the channel
form still runs) fed what the head form fed it before its kernels — a key
head's k once a VALUE head in float32, `beta * k * decay` and `beta * v` —
against `_head_solve` (the pair of Pallas kernels `<scope>_solve_fwd`,
`<scope>_solve_bwd`): the measurements in the comment over the kernels.

A shape is (tokens, key heads, value heads, d_k, d_v) at a chunk of 64, bf16
q, k, v.  `A` is what the rule builds (unit keys, `beta` up to 2, a decay
matrix of a head's summed log-decays).  A timed program holds the stage and
nothing else: forward (`W`, `U0`), or forward with backward (the cotangents
of `A`, k, v, beta and the decay from random cotangents of `W` and `U0`).  A
time is the median of `--calls` calls, each on the host's clock to
`block_until_ready`, after two warm-up calls.  `apart`: the largest absolute
difference of the kernels' results from `_solved`'s over the largest absolute
value there.

A stage alone pays for layouts at its boundary that a step's neighbours
share with it (the kernels' operands arrive from XLA fusions in a step, from
HBM as given here), so a time here ranks the two forms and does not predict a
step's share.

Times the chip and nothing else: without a TPU it refuses, as
`tools/flash_bwd_sweep.py` does.

Usage: python tools/solve_sweep.py [--calls 20] [--shapes qwen3next olmohybrid]
Writes one JSON line a measurement, also to chiprun_out/solve_sweep.jsonl.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from horovod_tpu.ops import delta_rule

CHUNK = 64
# (tokens, key heads, value heads, d_k, d_v): a layer of each cell.
SHAPES = {"qwen3next": (4096, 16, 32, 128, 128),
          "olmohybrid": (8192, 15, 15, 96, 192)}
SCOPE = "hvd_gdn_scan"


def operands(key, tokens, key_heads, heads, d_k, d_v):
    """`A`, k, v, beta and the decay from the chunk's start as
    `_head_decay_rule` hands them to the solve, by chunk."""
    chunks, per_key = tokens // CHUNK, heads // key_heads
    keys = jax.random.split(key, 4)
    k = jax.random.normal(keys[0], (1, chunks, key_heads, CHUNK, d_k))
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).astype(jnp.bfloat16)
    v = jax.random.normal(keys[1], (1, chunks, heads, CHUNK, d_v),
                          jnp.bfloat16)
    beta = 2.0 * jax.nn.sigmoid(
        jax.random.normal(keys[2], (1, chunks, heads, CHUNK)))
    steps = -jnp.exp(jax.random.normal(keys[3], (1, chunks, heads, CHUNK)))
    within = jnp.cumsum(steps, axis=-1)
    at = jnp.arange(CHUNK)
    earlier = at[:, None] > at[None, :]
    decay = jnp.exp(jnp.where(
        earlier, within[..., :, None] - within[..., None, :], 0.0))
    kk = jnp.repeat(jnp.einsum("bngtc,bngsc->bngts", k, k,
                               preferred_element_type=jnp.float32),
                    per_key, axis=2)
    a = jnp.where(earlier, beta[..., None] * decay * kk, 0.0)
    return a, k, v, beta, jnp.exp(within)


def through_xla(a, k, v, beta, from_start):
    """The stage as the head form ran it before its kernels."""
    per_key = v.shape[2] // k.shape[2]
    wide_k = jnp.repeat(k.astype(jnp.float32), per_key, axis=2)
    return delta_rule._solved(
        a, beta[..., None], wide_k, from_start[..., None],
        v.astype(jnp.float32), k.dtype, SCOPE)


def through_kernels(a, k, v, beta, from_start):
    return delta_rule._head_solve(a, k, v, beta, from_start,
                                  f"{SCOPE}_solve", False)


def with_backward(stage):
    def both(operands, cotangents):
        results, pull = jax.vjp(stage, *operands)
        return results, pull(cotangents)
    return both


def timed(fn, args, calls):
    """(median, least, most) ms over ``calls`` calls, and the last result."""
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(calls):
        start = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - start) * 1e3)
    return (statistics.median(times), min(times), max(times)), out


def apart(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES),
                    choices=list(SHAPES))
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit("solve_sweep.py times the chip: no TPU here "
                         f"({device.platform})")
    os.makedirs("chiprun_out", exist_ok=True)
    lines = []
    for name in args.shapes:
        given = jax.jit(operands, static_argnums=(1, 2, 3, 4, 5))(
            jax.random.PRNGKey(61), *SHAPES[name])
        a, k, v = given[:3]
        cotangents = (
            jax.random.normal(jax.random.PRNGKey(1),
                              v.shape[:-1] + k.shape[-1:], k.dtype),
            jax.random.normal(jax.random.PRNGKey(2), v.shape, jnp.float32))
        want = {}
        for form, stage in (("xla", through_xla),
                            ("kernels", through_kernels)):
            for direction, fn, fed in (
                    ("forward", jax.jit(stage), given),
                    ("forward_backward", jax.jit(with_backward(stage)),
                     (given, cotangents))):
                (median, least, most), out = timed(fn, fed, args.calls)
                flat = jax.tree.leaves(out)
                line = {"shape": name, "sizes": SHAPES[name], "form": form,
                        "direction": direction, "ms_median": median,
                        "ms_least": least, "ms_most": most,
                        "calls": args.calls, "device": device.device_kind}
                if form == "xla":
                    want[direction] = flat
                else:
                    line["apart"] = [apart(g, w) for g, w in
                                     zip(flat, want[direction])]
                print(json.dumps(line), flush=True)
                lines.append(line)
    with open("chiprun_out/solve_sweep.jsonl", "w") as f:
        f.writelines(json.dumps(line) + "\n" for line in lines)


if __name__ == "__main__":
    main()
