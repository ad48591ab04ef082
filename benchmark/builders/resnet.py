"""A bottleneck ResNet trained through the program's normal path:
`models.ResNet` with cross-replica batch norm, `hvd.jax.build_train_step` on
`data_parallel_mesh(devices)`, SGD with momentum — the step of
`examples/jax_imagenet_resnet50.py` at the sizes the configuration file gives.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import ops_count
from benchmark.builders import Built, collectives_expected, dtype_of
from benchmark.reference import compare, resnet as reference

AXIS = "hvd"


def build(config: dict, traffic: dict, devices, seed: int) -> Built:
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models.resnet import BottleneckBlock, ResNet
    from horovod_tpu.parallel import data_parallel_mesh

    image, channels = config["image_size"], config["image_channels"]
    classes, stages = config["num_classes"], tuple(config["stage_sizes"])
    global_batch = traffic["batch_per_chip"] * len(devices)
    if config["bottleneck_expansion"] != 4:
        raise ValueError("models.BottleneckBlock widens by 4, nothing else")
    make_model = functools.partial(
        ResNet, stage_sizes=stages, block_cls=BottleneckBlock,
        num_classes=classes, num_filters=config["num_filters"],
        dtype=dtype_of(config["compute_dtype"]))
    model = make_model(axis_name=AXIS if config["sync_batch_norm"] else None)
    mesh = data_parallel_mesh(devices, axis_name=AXIS)
    replicated = NamedSharding(mesh, P())
    opt = config["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError(f"resnet builds SGD with momentum, not {opt}")
    # The example's rate is per 64 images of global batch (Goyal et al.).
    tx = optax.sgd(opt["learning_rate_per_64_images"] * global_batch / 64,
                   momentum=opt["momentum"])

    def loss_with(model):
        def loss_fn(params, batch):
            images, labels, batch_stats = batch
            logits, updated = model.apply(
                {"params": params, "batch_stats": batch_stats}, images,
                train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
            return loss, updated["batch_stats"]
        return loss_fn

    loss_fn = loss_with(model)
    batch_spec = (P(AXIS), P(AXIS), P())
    step = build_train_step(loss_fn, tx, mesh, axis_name=AXIS, has_aux=True,
                            batch_spec=batch_spec)

    def init_state():
        def init(key):
            variables = model.init(
                key, jnp.zeros((2, image, image, channels), jnp.float32),
                train=False)
            params = variables["params"]
            return params, tx.init(params), variables["batch_stats"]

        return jax.jit(init, out_shardings=replicated)(
            jax.random.PRNGKey(seed))

    def reference_checks(state, pool):
        n = traffic["reference_check"]["grad_batch"]
        params, batch_stats = (compare.first_device_copy(t)
                               for t in (state[0], state[2]))
        images, labels = (x[:n] for x in compare.first_device_copy(pool[0]))
        system = compare.system_on_one_device(loss_fn, batch_spec, True,
                                              devices[0], AXIS)
        return compare.loss_and_gradients(
            system, functools.partial(reference.loss, stage_sizes=stages),
            params, (images, labels, batch_stats), (images, labels),
            reference.LOSS_RTOL, reference.GRAD_RTOL,
            reference.GRAD_NORM_RTOL)

    no_more, at_least_one = collectives_expected(devices)
    shape = dict(stage_sizes=stages, num_filters=config["num_filters"],
                 num_classes=classes, image_size=image, channels=channels,
                 expansion=config["bottleneck_expansion"])
    return Built(
        mesh=mesh, step=step, init_state=init_state,
        fields=[{"name": "images", "shape": [image, image, channels],
                 "dtype": "float32"},
                {"name": "labels", "shape": [], "dtype": "int32",
                 "high": classes}],
        make_batch=lambda fields: (fields["images"], fields["labels"]),
        samples_per_step=global_batch, sample_unit="image",
        ops_per_sample=ops_count.resnet_train_ops_per_image(**shape),
        kernels={},
        # XLA's convolutions do the work: no Pallas kernel in this program,
        # and on one chip no collective either.
        program_exactly={"tpu_custom_call": 0, **no_more},
        program_at_least_one=at_least_one,
        # One chip's batch norm needs no axis: the plain step drops it.
        plain_loss_fn=loss_with(make_model(axis_name=None)),
        optimizer=tx, has_aux=True, reference_checks=reference_checks)
