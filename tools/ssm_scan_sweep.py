#!/usr/bin/env python
"""The chunked scan alone in a program on the attached TPU, at the two shapes
the benchmark's Mamba-2 cells run: `ops.ssm.chunked_scan` on XLA's products
(every stage a `dot` or a fusion of XLA's) against the same call on the pair
of Pallas kernels `hvd_ssm_scan_intra_fwd`, `hvd_ssm_scan_intra_bwd` (which
hold a chunk's masked decay matrix, `mixed` and the states between chunks on
the chip) at several numbers of heads a grid step: the table
`ops.ssm.lowered_plan`'s threshold and `_HEADS_A_STEP` are set from (PERF.md
section 6, PR 65).  The form is set here, whatever the rule says of a shape.

A shape is (tokens, heads, groups, head_dim, state, chunk), bf16 x, B, C, with
seeded `dt`, `A` and `D` (Mamba-2's ranges).  A timed program holds the scan
and nothing else: forward (y), or forward with backward (the cotangents of x,
dt, A, B, C, D from a random cotangent of y).  A time is the median of
`--calls` calls, each on the host's clock to `block_until_ready`, after two
warm-up calls.  `apart`: the largest absolute difference of the kernels'
results from the products' over the largest absolute value there.

A scan alone pays for layouts at its boundary that a step's neighbours share
with it (x arrives and y leaves (batch, seq, heads, head_dim) here; in a step
the mixer's activations hold the tokens last, as the kernels read them), so a
time here ranks the two forms and does not predict a step's share.

Times the chip and nothing else: without a TPU it refuses, as
`tools/flash_bwd_sweep.py` does.

Usage: python tools/ssm_scan_sweep.py [--calls 20] [--shapes granite nemotron]
           [--heads-a-step 8 16 32]
Writes one JSON line a measurement, also to chiprun_out/ssm_scan_sweep.jsonl.
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

from horovod_tpu.ops import ssm

RULE = ssm.lowered_plan
# (tokens, heads, groups, head_dim, state, chunk): a layer of each cell.
SHAPES = {"granite": (8192, 64, 1, 64, 128, 256),
          "nemotron": (4096, 16, 1, 64, 128, 128)}


def operands(key, tokens, heads, groups, head_dim, state, chunk):
    """x, dt, A, B, C, D as a mixer hands them to `chunked_scan`."""
    keys = jax.random.split(key, 6)
    x = jax.random.normal(keys[0], (1, tokens, heads, head_dim), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (1, tokens, heads)) - 4.0)
    A = -jnp.exp(jax.random.uniform(keys[2], (heads,), minval=0.0,
                                    maxval=2.77))                # [-16, -1]
    B, C = (jax.random.normal(k, (1, tokens, groups, state), jnp.bfloat16)
            for k in keys[3:5])
    return x, dt, A, B, C, jax.random.normal(keys[5], (heads,))


def scan(form, heads_a_step, chunk):
    """`chunked_scan`'s y on ``form``, whatever `lowered_plan` says."""
    def y(*given):
        ssm.lowered_plan = lambda *shape: {"scan": form,
                                           "heads_a_step": heads_a_step}
        try:
            return ssm.chunked_scan(*given, chunk)[0]
        finally:
            ssm.lowered_plan = RULE
    return y


def with_backward(stage):
    def both(given, dy):
        y, pull = jax.vjp(stage, *given)
        return y, pull(dy)
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
    ap.add_argument("--heads-a-step", nargs="+", type=int, default=[8, 16])
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit("ssm_scan_sweep.py times the chip: no TPU here "
                         f"({device.platform})")
    os.makedirs("chiprun_out", exist_ok=True)
    lines = []
    for name in args.shapes:
        sizes = SHAPES[name]
        given = jax.jit(operands, static_argnums=tuple(range(1, 7)))(
            jax.random.PRNGKey(65), *sizes)
        dy = jax.random.normal(jax.random.PRNGKey(1), given[0].shape,
                               jnp.float32)
        forms = [("products", None, scan("products", None, sizes[5]))]
        forms += [("kernels", heads, scan("kernels", heads, sizes[5]))
                  for heads in args.heads_a_step
                  if (sizes[1] // sizes[2]) % heads == 0]
        want = {}
        for form, heads, stage in forms:
            for direction, fn, fed in (
                    ("forward", jax.jit(stage), given),
                    ("forward_backward", jax.jit(with_backward(stage)),
                     (given, dy))):
                (median, least, most), out = timed(fn, fed, args.calls)
                flat = jax.tree.leaves(out)
                line = {"shape": name, "sizes": sizes, "form": form,
                        "heads_a_step": heads, "direction": direction,
                        "ms_median": median, "ms_least": least,
                        "ms_most": most, "calls": args.calls,
                        "plan": RULE(*sizes[1:], jnp.bfloat16),
                        "device": device.device_kind}
                if form == "products":
                    want[direction] = flat
                else:
                    line["apart"] = [apart(g, w) for g, w in
                                     zip(flat, want[direction])]
                print(json.dumps(line), flush=True)
                lines.append(line)
    with open("chiprun_out/ssm_scan_sweep.jsonl", "w") as f:
        f.writelines(json.dumps(line) + "\n" for line in lines)


if __name__ == "__main__":
    main()
