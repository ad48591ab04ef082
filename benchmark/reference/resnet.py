"""Plain float32 reference of the bottleneck ResNet (v1.5) the repository
runs (`models.ResNet50`), written out with `lax.conv_general_dilated`: 7x7/2
stem (the published form, not the space-to-depth rewrite), batch norm in
training mode (the batch's own mean and biased variance), ReLU, 3x3/2 max
pool, bottleneck blocks with the stride on the 3x3 and a projection shortcut
where the shape changes, global mean, dense head, mean cross-entropy.

It reads the system's own parameter tree (flax names).  The caller traces it
under `jax.default_matmul_precision("highest")`.

Tolerances (used by benchmark/builders/resnet.py), each with its reason: the
system convolves in bfloat16 with float32 accumulation and normalises each
layer by statistics of a small batch, which amplifies rounding noise more
than a transformer's residual stream does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# Measured on the chip over the seeds of PR 22 (PERF.md section 6), after the
# three warm-up steps (at step 0 every block's last batch-norm scale is zero
# and most of the gradient with it).
#
# |loss_system - loss_reference| / loss_reference: the head and the
# cross-entropy are float32 in the system too; what differs is 53 layers of
# bfloat16 activations feeding the pooled features.  Measured 1e-5 to
# 2.4e-4.
LOSS_RTOL = 1.5e-3
# ||g_system - g_reference|| / ||g_reference||: bfloat16 activations through
# 53 convolutions and as many batch norms over 8 images, forward and back.
# Measured 0.049 to 0.057, seven times the LM's: each batch norm divides by
# a standard deviation of rounded values.  An 8-bit float's roundings are 16
# times bfloat16's and would not pass.
GRAD_RTOL = 1e-1
# | ||g_system|| / ||g_reference|| - 1 |.  Measured 0.0005 to 0.0014.
GRAD_NORM_RTOL = 1e-2

BN_EPS = 1e-5
_DIMS = ("NHWC", "HWIO", "NHWC")


def conv(x, kernel, stride: int = 1):
    return lax.conv_general_dilated(x, jnp.asarray(kernel, jnp.float32),
                                    (stride, stride), "SAME",
                                    dimension_numbers=_DIMS)


def batch_norm(x, p):
    mean = x.mean(axis=(0, 1, 2))
    var = ((x - mean) ** 2).mean(axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def max_pool_3x3_s2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1), "SAME")


def bottleneck(x, p, stride: int):
    y = jax.nn.relu(batch_norm(conv(x, p["Conv_0"]["kernel"]),
                               p["BatchNorm_0"]))
    y = jax.nn.relu(batch_norm(conv(y, p["Conv_1"]["kernel"], stride),
                               p["BatchNorm_1"]))
    y = batch_norm(conv(y, p["Conv_2"]["kernel"]), p["BatchNorm_2"])
    if "conv_proj" in p:
        x = batch_norm(conv(x, p["conv_proj"]["kernel"], stride),
                       p["norm_proj"])
    return jax.nn.relu(x + y)


def logits(params, images, stage_sizes):
    x = jnp.asarray(images, jnp.float32)
    x = jax.nn.relu(batch_norm(conv(x, params["conv_init"]["kernel"], 2),
                               params["bn_init"]))
    x = max_pool_3x3_s2(x)
    index = 0
    for stage, blocks in enumerate(stage_sizes):
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            x = bottleneck(x, params[f"BottleneckBlock_{index}"], stride)
            index += 1
    x = x.mean(axis=(1, 2))
    return x @ params["head"]["kernel"] + params["head"]["bias"]


def loss(params, batch, stage_sizes):
    """Mean cross-entropy; `batch` is (images, labels)."""
    images, labels = batch
    logp = jax.nn.log_softmax(logits(params, images, stage_sizes), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
