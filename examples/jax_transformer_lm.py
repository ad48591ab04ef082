"""Long-context LM training with sequence parallelism (dp x sp mesh).

The long-context flagship (no reference counterpart — the reference is
DP-only, SURVEY §5.7): token batches shard over the `dp` axis and the
sequence dimension over `sp`, where ring attention rotates K/V shards over
ICI.  Per-device activation memory is O(seq/sp): context scales linearly
with the ring size.

Run on a pod (or simulate 8 devices on CPU):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/jax_transformer_lm.py --dp 2 --sp 4 \
        --seq-len 512 --d-model 64 --n-layers 2 --steps 10

The same lines train a sparse-expert model: `--olmoe-config` takes a
Hugging Face OLMoE `config.json` (or the benchmark's
benchmark/configs/olmoe1b7b.json, whose `expert_shard` and `row_bound` it
honours) and builds the same TransformerLM with `moe=`, `qk_norm=True` and
the config's widths; the loss gains the router's two terms.
"""

import argparse
import json
import time

from horovod_tpu.common.compile_cache import place_compile_cache

place_compile_cache()  # before jax is imported: it reads the variable then

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.jax.train import build_train_step
from horovod_tpu.models import (MoEConfig, TransformerLM,
                                moe_next_token_loss, next_token_loss)
from horovod_tpu.parallel import replicate

parser = argparse.ArgumentParser(description="Sequence-parallel LM example")
parser.add_argument("--dp", type=int, default=0,
                    help="data-parallel mesh axis size (0 = devices/sp)")
parser.add_argument("--sp", type=int, default=4,
                    help="sequence-parallel (ring) axis size")
parser.add_argument("--batch", type=int, default=4, help="global batch")
parser.add_argument("--seq-len", type=int, default=2048)
parser.add_argument("--vocab", type=int, default=1024)
parser.add_argument("--d-model", type=int, default=256)
parser.add_argument("--n-layers", type=int, default=4)
parser.add_argument("--n-heads", type=int, default=8)
parser.add_argument("--ring-impl", default="ppermute",
                    choices=["ppermute", "rdma", "fused"],
                    help="K/V rotation: XLA collective permute, raw "
                         "Pallas remote DMA, or the fused ring-flash "
                         "kernel (DMA overlapped inside the attention "
                         "program)")
parser.add_argument("--olmoe-config", default=None,
                    help="an OLMoE config.json: its widths, depth, vocabulary "
                         "and experts replace --vocab/--d-model/--n-layers/"
                         "--n-heads")
parser.add_argument("--steps", type=int, default=30)
parser.add_argument("--lr", type=float, default=3e-4)
args = parser.parse_args()


def main():
    n_dev = len(jax.devices())
    sp = args.sp
    dp = args.dp or max(n_dev // sp, 1)
    assert dp * sp <= n_dev, f"need {dp * sp} devices, have {n_dev}"
    mesh = Mesh(np.array(jax.devices()[:dp * sp]).reshape(dp, sp),
                ("dp", "sp"))
    print(f"mesh: dp={dp} x sp={sp}, seq/device = {args.seq_len // sp}")

    shape = dict(vocab_size=args.vocab, d_model=args.d_model,
                 n_layers=args.n_layers, n_heads=args.n_heads)
    if args.olmoe_config:
        with open(args.olmoe_config) as f:
            c = json.load(f)
        shape = dict(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
            qk_norm=True, norm_eps=c["rms_norm_eps"],
            moe=MoEConfig(c["num_experts"], c["num_experts_per_tok"],
                          c["intermediate_size"],
                          tuple(c.get("expert_shard", (0, 1))),
                          c.get("row_bound")))
    args.vocab = shape["vocab_size"]
    model = TransformerLM(**shape, seq_axis="sp", ring_impl=args.ring_impl)

    # A tiny synthetic corpus with learnable structure (token t+1 depends
    # on token t), deterministic across hosts.
    rng = np.random.RandomState(0)
    mat = rng.permutation(args.vocab)
    tokens = np.zeros((args.batch, args.seq_len + 1), np.int32)
    tokens[:, 0] = rng.randint(0, args.vocab, args.batch)
    for t in range(args.seq_len):
        tokens[:, t + 1] = mat[tokens[:, t]]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    pad = (-inputs.shape[1]) % sp
    inputs = np.pad(inputs, ((0, 0), (0, pad)))
    targets = np.pad(targets, ((0, 0), (0, pad)))
    mask = np.pad(np.ones((args.batch, args.seq_len)), ((0, 0), (0, pad)))

    params = TransformerLM(**shape).init(
        jax.random.PRNGKey(0), jnp.asarray(inputs[:1, :64]))["params"]

    def loss_fn(params, batch):
        inp, tgt, msk = batch
        if model.moe is None:
            logits = model.apply({"params": params}, inp)
            return next_token_loss(logits, tgt, msk, axis_name=("dp", "sp"))
        # The sparse-expert layers write their router statistics to the
        # `router` collection; the loss adds the load-balancing and z terms.
        logits, wrote = model.apply({"params": params}, inp,
                                    mutable=["router"])
        return moe_next_token_loss(logits, tgt, wrote["router"], mask=msk,
                                   axis_name=("dp", "sp"))

    tx = optax.adamw(args.lr)
    spec = P("dp", "sp")
    # Interpret-mode Pallas collectives (rdma/fused rotation on CPU test
    # meshes) need check_vma=False; compiled TPU kernels don't.
    check_vma = (args.ring_impl == "ppermute"
                 or jax.default_backend() == "tpu")
    step = build_train_step(loss_fn, tx, mesh, axis_name=("dp", "sp"),
                            batch_spec=(spec, spec, spec),
                            check_vma=check_vma)
    params = replicate(mesh, params)
    opt_state = replicate(mesh, tx.init(params))
    batch = tuple(jax.device_put(np.asarray(x), NamedSharding(mesh, spec))
                  for x in (inputs, targets, mask))

    t0 = None
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, batch)
        if i == 0:
            jax.block_until_ready(loss)
            t0 = time.perf_counter()  # exclude compile
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")
    if args.steps > 1:
        dt = time.perf_counter() - t0
        toks = args.batch * args.seq_len * (args.steps - 1) / dt
        print(f"{toks:.0f} tokens/sec on {dp * sp} devices")


if __name__ == "__main__":
    main()
