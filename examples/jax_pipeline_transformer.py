"""Pipeline-parallel LM training: 1F1B over the engine's p2p plane.

The world is a ``stages x data-parallel`` grid (docs/pipeline.md): each
stage holds a contiguous layer range of the transformer, activations and
activation-gradients cross stage boundaries as ``hvd.send``/``hvd.recv``
micro-batch buckets, and gradients DP-average inside each stage's
``hvd.stage_group``.  After the first step the fixed-shape bucket cycle
replays through the response cache (steady-state hit rate >= 0.9).

Run 2 stages x 2 DP on one host:

    hvdrun -np 4 python examples/jax_pipeline_transformer.py \
        --stages 2 --microbatches 4 --steps 20
"""

import argparse
import time

from horovod_tpu.common.compile_cache import place_compile_cache

place_compile_cache()  # before jax is imported: it reads the variable then

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.jax.train import run_pipeline
from horovod_tpu.models import TransformerLM, next_token_loss
from horovod_tpu.parallel import (PipelineGrid, bubble_fraction,
                                  partition_params, partition_transformer)

parser = argparse.ArgumentParser(description="Pipeline-parallel LM example")
parser.add_argument("--stages", type=int, default=2,
                    help="pipeline stages (world must divide evenly)")
parser.add_argument("--chunks", type=int, default=1,
                    help="model chunks per rank (interleaved 1F1B)")
parser.add_argument("--microbatches", type=int, default=4)
parser.add_argument("--batch", type=int, default=8,
                    help="per-DP-rank batch (micro-batch = batch/microbatches)")
parser.add_argument("--seq-len", type=int, default=64)
parser.add_argument("--vocab", type=int, default=256)
parser.add_argument("--d-model", type=int, default=64)
parser.add_argument("--n-layers", type=int, default=4)
parser.add_argument("--n-heads", type=int, default=4)
parser.add_argument("--steps", type=int, default=20)
parser.add_argument("--lr", type=float, default=1e-3)
args = parser.parse_args()


def main():
    hvd.init()
    grid = PipelineGrid(args.stages, hvd.size(), hvd.rank())
    if hvd.rank() == 0:
        print(f"grid: {args.stages} stages x {grid.dp} DP "
              f"(x{args.chunks} chunks), micro-batches "
              f"{args.microbatches}, bubble "
              f"{bubble_fraction(args.stages, args.microbatches, args.chunks):.0%}")

    # Deterministic init on every rank (same seed) — each rank keeps only
    # its stage's slice, so no broadcast is needed.
    full = TransformerLM(
        vocab_size=args.vocab, d_model=args.d_model,
        n_layers=args.n_layers, n_heads=args.n_heads,
        dtype=jnp.float32, use_flash=False).init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, args.seq_len), jnp.int32))["params"]
    modules = partition_transformer(
        args.vocab, args.d_model, args.n_layers, args.n_heads,
        n_stages=args.stages, n_chunks=args.chunks,
        dtype=jnp.float32, use_flash=False)[grid.stage]
    params = partition_params(full, args.n_layers, args.stages,
                              n_chunks=args.chunks)[grid.stage]

    # Synthetic corpus with learnable structure (token t+1 = P[token t]),
    # DP-sharded by this rank's dp_index.
    rng = np.random.RandomState(1234 + grid.dp_index)
    mat = np.random.RandomState(0).permutation(args.vocab)
    tokens = np.zeros((args.batch, args.seq_len + 1), np.int32)
    tokens[:, 0] = rng.randint(0, args.vocab, args.batch)
    for t in range(args.seq_len):
        tokens[:, t + 1] = mat[tokens[:, t]]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    t0 = time.perf_counter()
    params, _, losses = run_pipeline(
        modules, params, optax.adamw(args.lr),
        [(inputs, targets)] * args.steps,
        n_stages=args.stages, n_microbatches=args.microbatches,
        loss_fn=next_token_loss)
    dt = time.perf_counter() - t0

    if losses[-1] is not None:  # last-stage ranks see the loss
        toks = args.batch * grid.dp * args.seq_len * args.steps / dt
        print(f"rank {hvd.rank()} (stage {grid.stage}): "
              f"loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
              f"{toks:.0f} tokens/sec")
        snap = hvd.metrics_snapshot()["p2p"]
        print(f"p2p: {snap['sends']} sends / {snap['recvs']} recvs, "
              f"{snap['bytes']['out']} B out")
    hvd.shutdown()


if __name__ == "__main__":
    main()
