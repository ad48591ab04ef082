#!/usr/bin/env python
"""Scoped-VMEM calibration sweep for the flash-attention backward.

Recompiles `jax.grad(flash_attention)` over (seq, head_dim, block_q,
block_k) on the attached TPU and reports which configs fit the chip's
scoped-VMEM ceiling — the ground truth behind
`horovod_tpu.ops.attention._bwd_plan` (r5 calibration; the r4 regression
was a tuned block choice that stopped compiling at seq 8192).  Compile-
only: safe to run anywhere a TPU is visible, ~1-2 s per config.

Usage: python tools/vmem_sweep.py [--full]
  default: the documented sweep {1k, 4k, 8k, 16k} x {64, 128} with the
  plan's chosen blocks (should print all OK);
  --full: every block candidate per shape, to re-derive the plan table
  after a Mosaic/compiler update.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from horovod_tpu.ops.attention import _bwd_plan, flash_attention


def try_compile(sl, d, bq, bk, bh=16):
    q = jnp.zeros((bh // 8, 8, sl, d), jnp.bfloat16)

    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=bq,
                               block_k=bk).astype(jnp.float32).sum()

    t0 = time.time()
    try:
        jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(q, q, q).compile()
        return "OK", time.time() - t0, ""
    except Exception as e:  # report the Mosaic scoped-vmem line if present
        lines = str(e).splitlines() or [repr(e)]
        key = next((ln.strip() for ln in lines
                    if "Scoped allocation" in ln), lines[0])
        return "FAIL", time.time() - t0, key[:110]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="sweep every block candidate, not just the plan's")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        # Without the chip flash_attention would run the interpreter and
        # every config would "compile": that is no calibration.
        sys.exit("no TPU backend: this sweep calibrates the chip's compiler")
    cands = [(1024, 1024), (512, 1024), (1024, 512), (512, 512),
             (256, 512), (256, 256)]
    # bench-protocol bh (token-constant seq:batch sweep) plus a high-bh
    # probe per seq: the scoped size varies non-monotonically with the
    # batch*heads grid dim (see attention._bwd_plan).
    bench_bh = {1024: 128, 4096: 32, 8192: 16, 16384: 8}
    failures = 0
    for d in (64, 128):
        for sl in (1024, 4096, 8192, 16384):
            for bh in dict.fromkeys((bench_bh[sl], 128)):
                if args.full:
                    todo = [c for c in cands
                            if sl % c[0] == 0 and sl % c[1] == 0]
                else:
                    mode, bq, bk = _bwd_plan(sl, d, 1024, 1024, bh)
                    todo = [(bq, bk)]
                for bq, bk in todo:
                    st, dt, key = try_compile(sl, d, bq, bk, bh)
                    plan = _bwd_plan(sl, d, bq, bk, bh)
                    print(f"d={d} sl={sl} bh={bh} bq={bq} bk={bk} "
                          f"plan={plan}: {st} ({dt:.1f}s) {key}", flush=True)
                    failures += st != "OK" and not args.full
    if failures:
        sys.exit(f"{failures} plan-chosen config(s) failed to compile")
    print("all plan-chosen configs compile")


if __name__ == "__main__":
    main()
