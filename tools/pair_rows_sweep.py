#!/usr/bin/env python
"""The way back from the experts to the tokens at the sparse-expert cells'
shapes, each form alone in a program on the attached TPU: the `pairs` form's
k-wide gather (`ops.moe._pairs_summed`: gather, mask, weighted float32 sum)
against the `held_pairs` kernel (`ops.moe.pair_rows`), ms a pass — the
measurements behind `ops.moe.HELD_PAIRS_BUFFER_BYTES` (the table in the
comment over it).

A shape is (tokens, choices a token, experts, shards, row bound, width): a
seeded softmax router's top-k through `dispatch_rows` for shard 0, so that a
shard-th of the pairs is valid, and a seeded bf16 buffer.  The two passes a
layer makes: `combine` (the forward of `buffer_rows_to_tokens`: the router's
weights) and `dispatch_bwd` (the backward of `token_rows_to_buffer`: the plain
sum).  A time is the host's clock over `--calls` back-to-back calls of one
jitted program that holds the pass and nothing else, after two warm-up calls;
`least_ms` is what the valid rows' bytes, read once, and the tokens', written
once, would take at the chip's 819 GB/s.

Usage: python tools/pair_rows_sweep.py [--shapes mellum,olmoe,sdar]
Writes one JSON line a measurement, also to chiprun_out/pair_rows_sweep.jsonl.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops import moe
from tools.grouped_sweep import timed

# (tokens, k, experts, shards, row bound, width): the benchmark cells' expert
# layers on one chip (benchmark/configs/*.json, PERF.md section 4).
SHAPES = {
    "mellum": (16384, 8, 64, 4, 1.5, 2304),
    "olmoe": (8192, 8, 64, 4, 1.5, 2048),
    "sdar": (8192, 8, 128, 8, 1.5, 2048),       # and Trinity's
    # No cell's: Mellum's layer at 8,192 and 12,288 tokens, a buffer either
    # side of the chip's 128 MiB of VMEM.
    "mellum_8k": (8192, 8, 64, 4, 1.5, 2304),
    "mellum_12k": (12288, 8, 64, 4, 1.5, 2304),
}


def pairs_pass(buffer, sent, weight=None):
    return moe._pairs_summed(buffer, sent, weight).astype(buffer.dtype)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=52)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("pair_rows_sweep.py times the chip: no TPU here")
    os.makedirs("chiprun_out", exist_ok=True)
    log = open("chiprun_out/pair_rows_sweep.jsonl", "a")

    def say(**line):
        text = json.dumps(line)
        print(text, flush=True)
        log.write(text + "\n")
        log.flush()

    for shape in args.shapes.split(","):
        tokens, k, experts, shards, row_bound, width = SHAPES[shape]
        local = experts // shards
        bound = int(tokens * k / shards * row_bound)
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 2)
        probs = jax.nn.softmax(jax.random.normal(keys[0], (tokens, experts)))
        weight, expert = lax.top_k(probs, k)
        sent = jax.jit(lambda e: moe.dispatch_rows(e, 0, local, bound))(
            expert)
        buffer = jax.random.normal(keys[1], (bound, width), jnp.bfloat16)
        valid = int(sent.valid.sum())
        least = (valid + tokens) * width * 2 / 819e9 * 1e3
        for name, operands in (("combine", (buffer, sent, weight)),
                               ("dispatch_bwd", (buffer, sent))):
            want = jax.jit(pairs_pass)(*operands).astype(jnp.float32)
            got = jax.jit(moe.pair_rows)(*operands).astype(jnp.float32)
            say(shape=shape, tokens=tokens, bound=bound, width=width,
                valid_pairs=valid, buffer_mb=bound * width * 2 / 1e6,
                pass_=name, least_ms=least,
                pairs_ms=timed(pairs_pass, operands, args.calls),
                held_pairs_ms=timed(moe.pair_rows, operands, args.calls),
                apart=float(jnp.abs(got - want).max() / jnp.abs(want).max()),
                differ=float((got != want).mean()))


if __name__ == "__main__":
    main()
