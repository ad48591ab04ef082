#!/usr/bin/env python
"""Scoped-VMEM calibration sweep for the flash-attention backward.

Recompiles `jax.grad(flash_attention)` over (seq, head_dim, block_q,
block_k) with the chip's compiler and reports which configs fit the chip's
scoped-VMEM ceiling — the ground truth behind
`horovod_tpu.ops.attention._bwd_plan` (r5 calibration; the r4 regression
was a tuned block choice that stopped compiling at seq 8192; re-run at PR 44,
when the kernels' grids became `(bh, live tiles)` tables, and at PR 57, when
the combined kernel began to ask Mosaic for the scoped VMEM its plan computes:
a probe past the default budget compiles with the `vmem_limit_bytes` its call
names, `ops.attention._combined_vmem_limit`, printed as `asks=`; and at PR 67
over heads past 128 lanes, `--wide`, before the plan's wide-head band was
written).  Compile-only,
~1-2 s per config: on the attached TPU, or, where there is none, for a
DESCRIBED v5e (libtpu compiles for an unattached chip and refuses what the
chip's compiler refuses; one such process at a time).

Usage: python tools/vmem_sweep.py [--full] [--cells] [--wide]
  default: the documented sweep {1k, 4k, 8k, 16k} x {64, 128} with the
  plan's chosen blocks (should print all OK);
  --full: every block candidate per shape, forced onto the COMBINED kernel
  (the split pair compiles everywhere), to re-derive the plan table after a
  Mosaic/compiler update or a change of the kernels' grids;
  --cells: the benchmark's cells' shapes under their masks (causal, Trinity's
  and Mellum's windows, SDAR's block diffusion) at the plan's blocks;
  --wide: heads past 128 lanes — latent attention's 192 (q, k) / 128 (v) and
  256 — at {2k, 4k, 8k, 16k} rows x bh {4, 16, 32, 64, 128} x every block
  candidate, forced onto the COMBINED kernel: the region behind the plan's
  wide-head band.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp

import horovod_tpu.ops.attention as attn
from horovod_tpu.ops.attention import _bwd_plan, flash_attention


def chip():
    """The sharding that puts an operand on the chip compiled for: the
    attached TPU's first device, else a described v5e's."""
    from jax.sharding import SingleDeviceSharding

    if jax.default_backend() == "tpu":
        return SingleDeviceSharding(jax.devices()[0])
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def try_compile(on_chip, sl, d, bq, bk, bh=16, force=None, d_v=None, **mask):
    """Compile forward and backward at (bh, sl, d) in the blocks (bq, bk);
    ``force``: that backward mode at exactly these blocks, whatever the plan
    says; ``d_v``: v's width where it is not q's and k's."""
    q, v = (jax.ShapeDtypeStruct((max(bh // 8, 1), min(bh, 8), sl, width),
                                 jnp.bfloat16, sharding=on_chip)
            for width in (d, d_v or d))
    mask = mask or dict(causal=True)

    def f(q, k, v):
        return flash_attention(q, k, v, block_q=bq, block_k=bk,
                               interpret=False, **mask
                               ).astype(jnp.float32).sum()

    planned = attn._bwd_plan
    if force is not None:
        attn._bwd_plan = lambda *a, **kw: (force, bq, bk)
    t0 = time.time()
    try:
        jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(q, q, v).compile()
        return "OK", time.time() - t0, ""
    except Exception as e:  # report the Mosaic scoped-vmem line if present
        lines = str(e).splitlines() or [repr(e)]
        key = next((ln.strip() for ln in lines
                    if "Scoped allocation" in ln or "scoped" in ln.lower()),
                   lines[0])
        return "FAIL", time.time() - t0, key[:160]
    finally:
        attn._bwd_plan = planned


# The benchmark's cells: rows, head width (q and k's, v's), batch * heads,
# mask.
CELLS = [
    (2048, (64, 64), 64, dict(causal=True)),                 # _4x2k, dp4
    (8192, (64, 64), 16, dict(causal=True)),                 # _1x8k
    (4096, (128, 128), 32, dict(causal=True)),               # OLMoE
    (4096, (128, 128), 4, dict(causal=True)),                # Nemotron
    (8192, (128, 128), 32, dict(causal=True)),               # Trinity, full
    (8192, (128, 128), 32, dict(causal=True, window=2048)),  # Trinity, band
    (8192, (128, 128), 32, dict(block_diffusion=4)),         # SDAR
    (16384, (128, 128), 32, dict(causal=True)),              # Mellum, full
    (16384, (128, 128), 32, dict(causal=True, window=1024)),  # Mellum, band
    (8192, (192, 128), 4, dict(causal=True)),                # Ling, latent
    (8192, (192, 128), 32, dict(causal=True)),               # JoyAI, latent
    (4096, (256, 256), 16, dict(causal=True)),               # Qwen3-Next
]


def asks(mode, sl, d, bq, bk, d_v=None):
    """What a combined call at these blocks asks Mosaic for, in MiB ("-": the
    default; "past": more than any call may, which the call refuses)."""
    try:
        limit = attn._combined_vmem_limit(sl, d, bq, bk, d_v) \
            if mode == "combined" else None
    except AssertionError:
        return "past"
    return "-" if limit is None else f"{limit / (1 << 20):.1f}MiB"


def sweep_cells(on_chip) -> int:
    """The benchmark's cells at the plan's blocks, as both backward modes;
    the failures among the modes the plan picks."""
    failures = 0
    for sl, (d, d_v), bh, mask in CELLS:
        plan = _bwd_plan(sl, d, 1024, 1024, bh, d_v)
        for force in ("combined", "split"):
            st, dt, key = try_compile(on_chip, sl, d, *plan[1:], bh,
                                      force=force, d_v=d_v, **mask)
            print(f"d={d}/{d_v} sl={sl} bh={bh} {mask} plan={plan} "
                  f"as {force} asks={asks(force, sl, d, *plan[1:], d_v)}: "
                  f"{st} ({dt:.1f}s) {key}", flush=True)
            failures += st != "OK" and force == plan[0]
    return failures


# The block pairs a --full or --wide sweep forces onto the combined kernel.
CANDS = [(1024, 1024), (512, 1024), (1024, 512), (512, 512), (256, 512),
         (256, 256)]


def sweep_bands(on_chip, full: bool) -> int:
    """The documented sweep at the plan's blocks, or (``full``) every block
    candidate on the combined kernel; the plan's own failures."""
    # bench-protocol bh (token-constant seq:batch sweep) plus the band
    # edges' bh per seq: the scoped size varies non-monotonically with the
    # batch*heads grid dim (see attention._bwd_plan).
    # 12,288 and 11,520 rows (384-blocks): inside the 16,384-row band.
    bench_bh = {1024: (128, 1024), 2048: (64, 1024), 4096: (32, 128, 512),
                8192: (16, 32, 64, 128), 11520: (8,), 12288: (32,),
                16384: (8, 16, 32, 64, 128)}
    failures = 0
    for d in (64, 128):
        for sl, bhs in bench_bh.items():
            for bh in bhs:
                todo = [_bwd_plan(sl, d, 1024, 1024, bh)[1:]]
                if full:
                    todo = [c for c in CANDS
                            if sl % c[0] == 0 and sl % c[1] == 0
                            ] + [c for c in todo if c not in CANDS]
                for bq, bk in todo:
                    st, dt, key = try_compile(
                        on_chip, sl, d, bq, bk, bh,
                        force="combined" if full else None)
                    plan = _bwd_plan(sl, d, bq, bk, bh)
                    ran = "combined" if full else plan[0]
                    print(f"d={d} sl={sl} bh={bh} bq={bq} bk={bk} "
                          f"plan={plan} asks={asks(ran, sl, d, bq, bk)}: "
                          f"{st} ({dt:.1f}s) {key}", flush=True)
                    failures += st != "OK" and not full
    return failures


# Heads past 128 lanes, (d, d_v): latent attention's, and Qwen3-Next's.
WIDE = [(192, 128), (256, 256)]


def sweep_wide(on_chip) -> int:
    """Every block candidate on the combined kernel at heads past 128 lanes;
    the failures among the probes the plan sends to that kernel."""
    failures = 0
    for d, d_v in WIDE:
        for sl in (2048, 4096, 8192, 16384):
            for bh in (4, 16, 32, 64, 128):
                plan = _bwd_plan(sl, d, 1024, 1024, bh, d_v)
                for bq, bk in CANDS:
                    st, dt, key = try_compile(on_chip, sl, d, bq, bk, bh,
                                              force="combined", d_v=d_v)
                    print(f"d={d}/{d_v} sl={sl} bh={bh} bq={bq} bk={bk} "
                          f"plan={plan} "
                          f"asks={asks('combined', sl, d, bq, bk, d_v)}: "
                          f"{st} ({dt:.1f}s) {key}", flush=True)
                    failures += st != "OK" and plan == ("combined", bq, bk)
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="sweep every block candidate on the combined "
                         "kernel, not just the plan's")
    ap.add_argument("--cells", action="store_true",
                    help="the benchmark's cells' shapes under their masks")
    ap.add_argument("--wide", action="store_true",
                    help="heads past 128 lanes (192 / 128 and 256) on the "
                         "combined kernel")
    args = ap.parse_args()
    on_chip = chip()
    print(f"compiling for {on_chip._device.device_kind} "
          f"(backend {jax.default_backend()})", flush=True)
    failures = sweep_cells(on_chip) if args.cells \
        else sweep_wide(on_chip) if args.wide \
        else sweep_bands(on_chip, args.full)
    if failures:
        sys.exit(f"{failures} plan-chosen config(s) failed to compile")
    print("all plan-chosen configs compile")


if __name__ == "__main__":
    main()
