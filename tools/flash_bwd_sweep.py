#!/usr/bin/env python
"""The flash backward alone in a program on the attached TPU, each plan at ONE
shape: the combined kernel (`ops.attention._combined_bwd_call`, asking Mosaic
for the scoped VMEM `_combined_vmem_limit` computes where the default is too
little) against the split pair (`_split_bwd_call`), in the blocks named, causal
and under a band — the measurements behind the 16,384-row band of
`ops.attention._bwd_plan` and, with `--d-v`, behind its wide-head band (PERF.md
section 7 has the tables).

A shape is (batch * heads, rows, head width), bf16; `--d-v` gives v (and the
output's cotangent) a width of its own, as latent attention has (192 / 128).
The forward runs once,
outside the timed program, for the residuals (out, the rows' log-sum-exp); a
timed program holds `_flash_backward` under the forced plan and nothing else
(delta's row sums and the three casts ride along, as they do in a step).  A
time is the median of `--calls` calls, each on the host's clock to
`block_until_ready`, after two warm-up calls.  `dq_apart` / `dk_apart` /
`dv_apart`: the largest absolute difference from the first plan of the same
mask, over the largest absolute value there (the plans compute the same
gradients in another order of sums).

Times the chip and nothing else: without a TPU it refuses, as
`tools/pair_rows_sweep.py` and `tools/grouped_sweep.py` do.

Usage: python tools/flash_bwd_sweep.py [--rows 16384] [--bh 32] [--d 128]
                                       [--d-v 128] [--window 1024] [--calls 10]
(`--window 0`: causal alone.)
Writes one JSON line a measurement, also to chiprun_out/flash_bwd_sweep.jsonl.
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

import horovod_tpu.ops.attention as attn

# (mode, block_q, block_k): the pair as the plan ran it at 16,384 rows, then
# the combined kernel in the blocks the band could take.
PLANS = [("split", 1024, 1024), ("split", 512, 512), ("combined", 512, 512),
         ("combined", 512, 1024), ("combined", 1024, 1024)]


def backward_under(plan, mask):
    """A jitted `_flash_backward` whose plan is ``plan`` whatever the shape."""

    def backward(q, k, v, out, lse, g):
        planned = attn._bwd_plan
        attn._bwd_plan = lambda *a, **kw: plan
        try:
            return attn._flash_backward(
                q, k, v, out, lse, g, mask, q.shape[-1] ** -0.5, 1024, 1024,
                False)
        finally:
            attn._bwd_plan = planned

    return jax.jit(backward)


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
    ap.add_argument("--rows", type=int, default=16384)
    ap.add_argument("--bh", type=int, default=32)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--d-v", type=int, default=None,
                    help="v's width where it is not --d")
    ap.add_argument("--window", type=int, default=1024)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=57)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("flash_bwd_sweep.py times the chip: no TPU here")
    d_v = args.d_v or args.d
    shape = (1, args.bh, args.rows, args.d)
    q, k, v, g = (jax.random.normal(key, shape[:3] + (width,), jnp.float32
                                    ).astype(jnp.bfloat16)
                  for key, width in zip(
                      jax.random.split(jax.random.key(args.seed), 4),
                      (args.d, args.d, d_v, d_v)))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash_bwd_sweep.jsonl", "a") as log:
        for window in (None, args.window) if args.window else (None,):
            mask = attn.Mask.of(args.rows, args.rows, True, window)
            out, lse = jax.jit(
                lambda q, k, v: attn._flash_forward(
                    q, k, v, mask, args.d ** -0.5, 1024, 1024, False)
            )(q, k, v)
            first = None
            for plan in PLANS:
                fn = backward_under(plan, mask)
                operands = (q, k, v, out, lse, g)
                try:
                    (median, least, most), grads = timed(fn, operands,
                                                         args.calls)
                except Exception as e:   # a plan the compiler refuses
                    line = {"error": str(e).splitlines()[0][:200]}
                else:
                    first = first or grads
                    line = {"median_ms": median, "least_ms": least,
                            "most_ms": most,
                            **{name + "_apart": apart(a, b) for name, a, b
                               in zip(("dq", "dk", "dv"), grads, first)}}
                line = {"device": jax.devices()[0].device_kind,
                        "shape": list(shape), "d_v": d_v, "window": window,
                        "mode": plan[0], "blocks": list(plan[1:]),
                        "vmem_limit_bytes":
                            attn._combined_vmem_limit(args.rows, args.d,
                                                      *plan[1:], d_v)
                            if plan[0] == "combined" else None,
                        "live_tiles": attn._live_tiles(args.rows, plan[1:],
                                                       mask),
                        "calls": args.calls, **line}
                print(json.dumps(line), flush=True)
                log.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
