#!/usr/bin/env python
"""Microbenchmark each distinct ResNet-101 conv (fwd, bwd-data, bwd-filter).

Times XLA's lowering of every conv shape in the headline model at the
benchmark batch size and reports achieved TFLOP/s vs the chip's published
bf16 peak — the shape-by-shape evidence behind conv-optimisation
decisions (docs/benchmarks.md round-4 log).

Each measurement runs K convolutions inside ONE jitted lax.scan (over K
distinct weight buffers, so XLA cannot CSE them) and fetches one scalar,
so the per-dispatch cost is paid once; per-conv time is the scan time
over K with the empty-scan overhead subtracted.

Usage: python tools/conv_microbench.py [--batch 64] [--k 24]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# (name, H, Cin, Cout, k, stride, count) — ResNet-101 v1.5 @224, after the
# space-to-depth stem.  count = occurrences in the network.
SHAPES = [
    ("stem 4x4x12->64 /1@112", 112, 12, 64, 4, 1, 1),
    ("s1 1x1 64->64", 56, 64, 64, 1, 1, 2),
    ("s1 1x1 256->64", 56, 256, 64, 1, 1, 2),
    ("s1 3x3 64->64", 56, 64, 64, 3, 1, 3),
    ("s1 1x1 64->256", 56, 64, 256, 1, 1, 4),
    ("s2 1x1 256->128", 56, 256, 128, 1, 1, 1),
    ("s2 3x3 128->128 /2", 56, 128, 128, 3, 2, 1),
    ("s2 1x1 512->128", 28, 512, 128, 1, 1, 3),
    ("s2 3x3 128->128", 28, 128, 128, 3, 1, 3),
    ("s2 1x1 128->512", 28, 128, 512, 1, 1, 4),
    ("s2 proj 1x1 256->512 /2", 56, 256, 512, 1, 2, 1),
    ("s3 1x1 512->256", 28, 512, 256, 1, 1, 1),
    ("s3 3x3 256->256 /2", 28, 256, 256, 3, 2, 1),
    ("s3 1x1 1024->256", 14, 1024, 256, 1, 1, 22),
    ("s3 3x3 256->256", 14, 256, 256, 3, 1, 22),
    ("s3 1x1 256->1024", 14, 256, 1024, 1, 1, 23),
    ("s3 proj 1x1 512->1024 /2", 28, 512, 1024, 1, 2, 1),
    ("s4 1x1 1024->512", 14, 1024, 512, 1, 1, 1),
    ("s4 3x3 512->512 /2", 14, 512, 512, 3, 2, 1),
    ("s4 1x1 2048->512", 7, 2048, 512, 1, 1, 2),
    ("s4 3x3 512->512", 7, 512, 512, 3, 1, 2),
    ("s4 1x1 512->2048", 7, 512, 2048, 1, 1, 3),
    ("s4 proj 1x1 1024->2048 /2", 14, 1024, 2048, 1, 2, 1),
]

DN = ("NHWC", "HWIO", "NHWC")


def scan_time(make_scalar, pool, iters, reps=3):
    """Median wall time of one jitted scan running `make_scalar` `iters`
    times (one dispatch, one scalar fetch).  Weights cycle through a
    small pool by dynamic index — distinct enough that XLA cannot hoist
    the conv out of the loop, small enough to bound HBM."""

    @jax.jit
    def run(pool):
        def body(acc, idx):
            return acc + make_scalar(pool[idx]), None

        acc, _ = lax.scan(body, jnp.float32(0),
                          jnp.arange(iters) % pool.shape[0])
        return acc

    float(run(pool))  # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(run(pool))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def per_iter_time(make_scalar, pool, iters):
    """Two-point measurement: (T(3N) - T(N)) / 2N cancels the constant
    dispatch + fetch overhead exactly,
    instead of subtracting a separately measured (noisy) baseline."""
    t1 = scan_time(make_scalar, pool, iters)
    t3 = scan_time(make_scalar, pool, 3 * iters)
    return max(t3 - t1, 1e-12) / (2 * iters)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--k", type=int, default=24, help="convs per dispatch")
    ap.add_argument("--peak", type=float, default=197.0,
                    help="bf16 TFLOP/s peak of the chip (197 = published "
                         "v5e)")
    ap.add_argument("--only", default="",
                    help="substring filter on shape names (comma-separated)")
    args = ap.parse_args()
    B, K = args.batch, args.k
    shapes = SHAPES
    if args.only:
        keys = [s.strip() for s in args.only.split(",") if s.strip()]
        shapes = [s for s in SHAPES if any(k in s[0] for k in keys)]

    # Overhead of an empty scan + dispatch + fetch, subtracted from
    # every sample; iteration counts below are
    # sized so the conv signal is several times this noise floor.
    base = scan_time(lambda wi: jnp.sum(wi),
                     jnp.zeros((4, 8), jnp.float32), 16)

    total = {"fwd": 0.0, "dx": 0.0, "dw": 0.0}
    ideal = {"fwd": 0.0, "dx": 0.0, "dw": 0.0}
    print(f"dispatch+empty-scan overhead: {base * 1e3:.2f} ms")
    print(f"{'shape':<27}{'dir':>5}{'iters':>6}{'us':>9}{'TF/s':>8}"
          f"{'%peak':>7}")
    for name, H, cin, cout, k, stride, count in shapes:
        Ho = (H + stride - 1) // stride
        x = jnp.asarray(np.random.RandomState(0).randn(B, H, H, cin),
                        jnp.bfloat16)
        flops_one = 2 * B * Ho * Ho * k * k * cin * cout
        # Enough iterations that at ~200 TF/s the N-vs-3N delta is
        # several times the run-to-run RTT noise; pool bounded to ~64 MB.
        iters = int(min(2048, max(
            32, 2 * base / (flops_one / 200e12))))
        pool_n = max(1, min(iters, (64 << 20) // (2 * k * k * cin * cout)))
        ws = jnp.asarray(
            np.random.RandomState(1).randn(pool_n, k, k, cin, cout),
            jnp.bfloat16)

        def conv(x, w):
            return lax.conv_general_dilated(x, w, (stride, stride), "SAME",
                                            dimension_numbers=DN)

        # sum(y*y), NOT sum(y): a linear consumer lets XLA's algebraic
        # simplifier collapse reduce(conv) into a tiny matmul (and makes
        # d/dw independent of w, so the whole grad hoists out of the
        # timing loop) — both were observed, reporting >nominal-peak
        # numbers.  The square also gives the backward a realistic
        # activation-dependent cotangent.
        def fwd_scalar(wi):
            y = conv(x, wi).astype(jnp.float32)
            return jnp.sum(y * y)

        def dx_scalar(wi):
            g = jax.grad(lambda xx: fwd_scalar_x(xx, wi))(x)
            return jnp.sum(g.astype(jnp.float32) ** 2)

        def fwd_scalar_x(xx, wi):
            y = conv(xx, wi).astype(jnp.float32)
            return jnp.sum(y * y)

        def dw_scalar(wi):
            g = jax.grad(lambda w_: fwd_scalar_x(x, w_))(wi)
            return jnp.sum(g.astype(jnp.float32) ** 2)

        flops = flops_one
        # grad-of-sum-of-squares times include the forward conv recompute;
        # subtract the measured forward to isolate the backward conv.
        fwd_dt = None
        for tag, fn in (("fwd", fwd_scalar), ("dx", dx_scalar),
                        ("dw", dw_scalar)):
            dt = per_iter_time(fn, ws, iters)
            if tag == "fwd":
                fwd_dt = dt
            else:
                dt = max(dt - fwd_dt, 1e-9)
            tf = flops / dt / 1e12
            total[tag] += dt * count * 1e3
            ideal[tag] += flops * count / (args.peak * 1e12) * 1e3
            print(f"{name:<27}{tag:>5}{iters:>6}{dt * 1e6:>9.1f}{tf:>8.1f}"
                  f"{100 * tf / args.peak:>6.1f}%")
    print("\nnetwork totals (shape x count), ms and vs practical peak:")
    for tag in ("fwd", "dx", "dw"):
        print(f"  {tag}: {total[tag]:8.2f} ms   ideal {ideal[tag]:6.2f} ms "
              f" -> {100 * ideal[tag] / max(total[tag], 1e-9):.0f}% eff")


if __name__ == "__main__":
    main()
