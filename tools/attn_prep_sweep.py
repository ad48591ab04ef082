#!/usr/bin/env python
"""A grouped layer's per-head norm and rotation alone in a program on the
attached TPU, at the shapes the benchmark's cells with such layers run:
`ops.attn_prep.normed_and_turned` (the pair of Pallas kernels
`hvd_attn_prep_fwd`, `hvd_attn_prep_bwd`) against the composition it replaces
on q in `models.Attention` — the per-head RMSNorm in float32 behind
`_cotangent_written_out`, rounded, then `rope` (a product with the signed
pair permutation at full precision), rounded again.

A shape is (batch, heads, rows, head_dim[, rotary_dim]) of bfloat16.  The four
q shapes the layers hand the kernels are two: Mellum's, and the one SDAR's,
Keye's and Trinity's banded layers share.  The key heads' and Qwen3-Next's
are here for the record: a layer keeps the composition there
(`attn_prep.prep_rows`), the kernels take any number of heads of 128 lanes all
the same, and no form of them takes 256 lanes with a partial turn
(composition only).

A timed program holds the stage and nothing else: forward, or forward with
backward (the cotangents of the input and the scale from a random cotangent
of the result).  A time is `--calls` calls enqueued back to back and waited
for once, a call's share of it, the best of three rounds (`timed`); `gb_s` is
the bytes that have to move (one read and one write forward, two reads and a
write more backward) over it.  The key heads' shapes are under the host's
dispatch: their times say that and no more.  `apart`: the largest absolute
difference of the kernels' results from the composition's over the largest
absolute value there (the composition rounds twice, the kernels once).
`trace_s`: the seconds Python spent tracing the form's kernel bodies
(`common.metrics.kernel_trace`), forward and both ways.

A stage alone pays for layouts at its boundary that a step's neighbours share
with it, so a time here ranks the forms and does not predict a step's share.
`--tile-rows` and `--chunk-rows` time the kernels at other tiles than
`ops.attn_prep` takes (rows of a grid step and rows the body holds at a
time); `--written-out` times, beside the library's body (a `lax.fori_loop`
over a tile's chunks, traced once and unrolled where it is lowered), the same
chunks written out in Python, which is what the loop has to match.

Times the chip and nothing else: without a TPU it refuses, as
`tools/flash_bwd_sweep.py` does.

Usage: python tools/attn_prep_sweep.py [--calls 30] [--shapes mellum_q ...]
Writes one JSON line a measurement, also to chiprun_out/attn_prep_sweep.jsonl.
"""
import argparse
import functools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.common.metrics import setup_table
from horovod_tpu.models import transformer
from horovod_tpu.ops import attn_prep

# (batch, heads, rows, head_dim, rotary_dim): q and k of a layer.
SHAPES = {
    "mellum_q": (1, 32, 16384, 128, None),
    "sdar_q": (1, 32, 8192, 128, None),     # Keye's and Trinity's banded too
    "mellum_k": (1, 4, 16384, 128, None),
    "sdar_k": (1, 4, 8192, 128, None),
    "qwen3next_q": (1, 16, 4096, 256, 64),
    "qwen3next_k": (1, 2, 4096, 256, 64),
}
EPS = 1e-6
THETA = 1e6
_LIBRARYS = attn_prep._staged


def composition(x, scale, rotary_dim):
    """`Attention._head_norm` then `rope`, as a grouped layer runs them."""
    wide = transformer._cotangent_written_out(x).astype(jnp.float32)
    mean_sq = jnp.mean(jnp.square(wide), axis=-1, keepdims=True)
    y = (wide * lax.rsqrt(mean_sq + EPS) * scale).astype(x.dtype)
    return transformer.rope(y, jnp.arange(x.shape[2]), THETA, -2, rotary_dim)


def kernels(x, scale, rotary_dim):
    del rotary_dim
    return attn_prep.normed_and_turned(x, scale, *transformer.rotary_tables(
        jnp.arange(x.shape[2]), x.shape[3], THETA), EPS)


def written_out(rows, first, second, carry=None):
    """`attn_prep._staged` with every chunk written out in Python (PR 62's
    form of the body)."""
    chunk = math.gcd(rows, attn_prep._CHUNK_ROWS)
    at = [slice(start, start + chunk) for start in range(0, rows, chunk)]
    ahead = first(at[0])
    for here, after in zip(at, at[1:] + [None]):
        now, ahead = ahead, None if after is None else first(after)
        carry = second(here, now, carry)
    return carry


def with_backward(stage):
    def both(x, scale, d_out):
        out, pull = jax.vjp(stage, x, scale)
        return out, pull(d_out)
    return both


def timed(fn, args, calls):
    """(ms a call, the last result): ``calls`` calls enqueued back to back
    and waited for once, the best of three such rounds after three warm-up
    calls — the device's time a call wherever it is longer than the host's
    dispatch (about 0.2 ms here), which a call timed alone to
    `block_until_ready` carries whole (about 0.55 ms)."""
    for _ in range(3):
        out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(3):
        start = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - start) / calls * 1e3)
    return best, out


def apart(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def traced_s():
    return sum(entry["trace_s"]
               for name, entry in setup_table.process()["kernels"].items()
               if name.startswith("hvd_attn_prep"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=30)
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES),
                    choices=list(SHAPES))
    ap.add_argument("--tile-rows", type=int, nargs="+", default=[None])
    ap.add_argument("--chunk-rows", type=int, nargs="+", default=[None])
    ap.add_argument("--written-out", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit("attn_prep_sweep.py times the chip: no TPU here "
                         f"({device.platform})")
    os.makedirs("chiprun_out", exist_ok=True)
    bodies = [("loop", _LIBRARYS)] + (
        [("written_out", written_out)] if args.written_out else [])
    forms = [("composition", composition, None, None, None)] + [
        ("kernels", kernels, tile, chunk, body)
        for tile in args.tile_rows for chunk in args.chunk_rows
        for body in bodies]
    tile_rows, chunk_rows = attn_prep._TILE_ROWS, attn_prep._CHUNK_ROWS
    lines = []
    for name in args.shapes:
        *shape, rotary_dim = SHAPES[name]
        taken = shape[3] == attn_prep.LANES and rotary_dim is None
        keys = jax.random.split(jax.random.PRNGKey(63), 3)
        x = jax.random.normal(keys[0], shape, jnp.bfloat16)
        scale = 1.0 + 0.1 * jax.random.normal(keys[1], shape[-1:])
        d_out = jax.random.normal(keys[2], shape, jnp.bfloat16)
        want = {}
        for form, stage, tile, chunk, body in forms:
            if form == "kernels" and not taken:
                continue
            attn_prep._TILE_ROWS = tile or tile_rows
            attn_prep._CHUNK_ROWS = chunk or chunk_rows
            if body is not None:
                attn_prep._staged = body[1]
            jax.clear_caches()
            stage = functools.partial(stage, rotary_dim=rotary_dim)
            for direction, fn, fed, passes in (
                    ("forward", jax.jit(stage), (x, scale), 2),
                    ("forward_backward", jax.jit(with_backward(stage)),
                     (x, scale, d_out), 5)):
                before = traced_s()
                ms, out = timed(fn, fed, args.calls)
                flat = jax.tree.leaves(out)
                line = {"shape": name, "sizes": shape,
                        "rotary_dim": rotary_dim, "form": form,
                        "body": body and body[0], "tile_rows": tile,
                        "chunk_rows": chunk, "direction": direction, "ms": ms,
                        "gb_s": passes * x.nbytes / ms / 1e6,
                        "trace_s": traced_s() - before,
                        "calls": args.calls, "device": device.device_kind}
                if form == "composition":
                    want[direction] = flat
                else:
                    line["apart"] = [apart(g, w) for g, w in
                                     zip(flat, want[direction])]
                print(json.dumps(line), flush=True)
                lines.append(line)
    with open("chiprun_out/attn_prep_sweep.jsonl", "w") as f:
        f.writelines(json.dumps(line) + "\n" for line in lines)


if __name__ == "__main__":
    main()
