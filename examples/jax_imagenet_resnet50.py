"""JAX-native distributed ResNet-50 — the flagship compiled-path workload.

The TPU-first expression of the reference's headline benchmark
(/root/reference/docs/benchmarks.md: ResNet, batch 64/accelerator, synthetic
ImageNet data): bfloat16 compute on the MXU, a data-parallel `shard_map`
step whose gradient psums XLA overlaps with the backward pass over ICI, and
cross-replica (sync) batch norm.

Run:
    python examples/jax_imagenet_resnet50.py --steps 20
Multi-host pod slice (one process per host, same flags everywhere):
    python examples/jax_imagenet_resnet50.py --multihost ...
On CPU, simulate 8 devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/jax_imagenet_resnet50.py \
        --steps 4 --batch-size 2 --image-size 32
"""

import argparse
import time

from horovod_tpu.common.compile_cache import place_compile_cache

place_compile_cache()  # before jax is imported: it reads the variable then

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu.jax.train import build_train_step
from horovod_tpu.models import ResNet50
from horovod_tpu.parallel import data_parallel_mesh, replicate, shard_batch

parser = argparse.ArgumentParser(description="JAX ImageNet ResNet-50")
parser.add_argument("--batch-size", type=int, default=64,
                    help="per-device batch size (the reference benchmark's 64)")
parser.add_argument("--steps", type=int, default=100)
parser.add_argument("--warmup-steps", type=int, default=3)
parser.add_argument("--base-lr", type=float, default=0.0125)
parser.add_argument("--momentum", type=float, default=0.9)
parser.add_argument("--image-size", type=int, default=224)
parser.add_argument("--multihost", action="store_true",
                    help="initialize jax.distributed (pod-slice metadata)")
args = parser.parse_args()

if args.multihost:
    jax.distributed.initialize()


def main():
    mesh = data_parallel_mesh(axis_name="hvd")
    n_dev = mesh.devices.size
    global_batch = args.batch_size * n_dev

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16, axis_name="hvd")
    rng = jax.random.PRNGKey(0)
    host_batch = np.random.RandomState(0).rand(
        global_batch, args.image_size, args.image_size, 3).astype(np.float32)
    host_labels = np.random.RandomState(1).randint(
        0, 1000, global_batch).astype(np.int32)

    variables = model.init(rng, host_batch[:2], train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]

    def loss_fn(params, batch):
        images, labels, batch_stats = batch
        logits, updated = model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, updated["batch_stats"]

    # LR scaled by device count (arXiv:1706.02677), as in every reference
    # example.
    tx = optax.sgd(args.base_lr * n_dev, momentum=args.momentum)
    step = build_train_step(loss_fn, tx, mesh, axis_name="hvd", has_aux=True,
                            batch_spec=(P("hvd"), P("hvd"), P()))

    params = replicate(mesh, params)
    opt_state = replicate(mesh, tx.init(params))
    batch_stats = replicate(mesh, batch_stats)
    images = shard_batch(mesh, host_batch)
    labels = shard_batch(mesh, host_labels)

    # Warmup (compile) steps, excluded from timing.
    for _ in range(args.warmup_steps):
        params, opt_state, loss, batch_stats = step(
            params, opt_state, (images, labels, batch_stats))
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for i in range(args.steps):
        params, opt_state, loss, batch_stats = step(
            params, opt_state, (images, labels, batch_stats))
    final_loss = float(loss)  # drains the step chain
    dt = time.perf_counter() - t0

    if jax.process_index() == 0:
        total = global_batch * args.steps / dt
        print(f"loss {final_loss:.4f}")
        print(f"{total:.1f} images/sec total, "
              f"{total / n_dev:.1f} images/sec/device on {n_dev} devices")


if __name__ == "__main__":
    main()
