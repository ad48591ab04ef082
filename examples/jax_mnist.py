"""JAX-native distributed MNIST — the compiled TPU path.

No reference counterpart (the reference predates JAX); this is the idiomatic
TPU expression of the same five-step recipe: the mesh replaces the MPI
communicator, `shard_batch` replaces DistributedSampler, and
`DistributedOptimizer`'s per-leaf psum — compiled and overlapped by XLA over
ICI — replaces the background engine's fused allreduce.

Run (single host, all local devices form the mesh):
    python examples/jax_mnist.py
On CPU, simulate 8 devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/jax_mnist.py
"""

import argparse

from horovod_tpu.common.compile_cache import place_compile_cache

place_compile_cache()  # before jax is imported: it reads the variable then

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu.jax as hvd
from horovod_tpu.jax.train import build_train_step
from horovod_tpu.models import MnistCNN
from horovod_tpu.parallel import data_parallel_mesh, replicate, shard_batch

parser = argparse.ArgumentParser(description="JAX MNIST Example")
parser.add_argument("--batch-size", type=int, default=64,
                    help="per-device batch size")
parser.add_argument("--steps", type=int, default=100)
parser.add_argument("--lr", type=float, default=0.01)
parser.add_argument("--momentum", type=float, default=0.5)
parser.add_argument("--train-samples", type=int, default=4096)
args = parser.parse_args()


def synthetic_mnist(n, seed):
    rng = np.random.RandomState(seed)
    labels = rng.randint(0, 10, n)
    images = rng.rand(n, 28, 28, 1).astype(np.float32) * 0.25
    for i, y in enumerate(labels):
        r, c = divmod(int(y), 5)
        images[i, r * 14:(r + 1) * 14, c * 5:(c + 1) * 5, 0] += 0.75
    return images, labels.astype(np.int32)


def main():
    mesh = data_parallel_mesh(axis_name="hvd")
    n_dev = mesh.devices.size
    global_batch = args.batch_size * n_dev

    model = MnistCNN()
    rng = jax.random.PRNGKey(42)
    images, labels = synthetic_mnist(args.train_samples, seed=1234)
    variables = model.init(rng, jnp.zeros((1, 28, 28, 1)), train=False)
    params = variables["params"]

    def loss_fn(params, batch):
        imgs, labs = batch
        logits = model.apply({"params": params}, imgs, train=True,
                             rngs={"dropout": jax.random.PRNGKey(0)})
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labs).mean()

    # LR scaled by the number of devices (the size() of this job).
    tx = optax.sgd(args.lr * n_dev, momentum=args.momentum)
    step = build_train_step(loss_fn, tx, mesh, axis_name="hvd")

    # Params/opt state replicated on the mesh; rank-0 "broadcast" is the
    # device_put replication itself — one host initializes, all devices get
    # the same bytes.
    params = replicate(mesh, params)
    opt_state = replicate(mesh, tx.init(params))

    rng_np = np.random.RandomState(0)
    for i in range(args.steps):
        idx = rng_np.randint(0, len(images), global_batch)
        batch = (shard_batch(mesh, images[idx]),
                 shard_batch(mesh, labels[idx]))
        params, opt_state, loss = step(params, opt_state, batch)
        if i % 10 == 0:
            print(f"step {i:4d}  loss {float(loss):.4f}")

    # Eval: argmax accuracy on a held-out synthetic set.
    test_images, test_labels = synthetic_mnist(1024, seed=4321)
    logits = jax.jit(lambda p, x: model.apply({"params": p}, x, train=False))(
        params, jnp.asarray(test_images))
    acc = float(jnp.mean(jnp.argmax(logits, -1) == jnp.asarray(test_labels)))
    print(f"test accuracy: {acc:.4f}")


if __name__ == "__main__":
    main()
