"""VGG (configurations A/D/E = VGG-11/16/19), TPU-tuned flax implementation.

VGG-16 is one of the reference's three published scaling benchmarks
(68% efficiency at 512 GPUs, /root/reference/README.md:50,
docs/benchmarks.md:6) — the hard case, being parameter-heavy: its ~138M
parameters stress gradient-exchange bandwidth.

NHWC, bfloat16 compute, float32 params; classifier matches the original
(4096-4096-classes with dropout).
"""

from __future__ import annotations

import functools
from typing import Any, Sequence

import flax.linen as nn
import jax.numpy as jnp

# Per-stage conv counts; all convs are 3x3, channels 64,128,256,512,512.
_CFG = {
    "vgg11": (1, 1, 2, 2, 2),
    "vgg16": (2, 2, 3, 3, 3),
    "vgg19": (2, 2, 4, 4, 4),
}
_CHANNELS = (64, 128, 256, 512, 512)


class VGG(nn.Module):
    stage_convs: Sequence[int]
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = x.astype(self.dtype)
        for stage, n_convs in enumerate(self.stage_convs):
            for i in range(n_convs):
                x = nn.Conv(_CHANNELS[stage], (3, 3), padding="SAME",
                            dtype=self.dtype,
                            name=f"conv{stage}_{i}")(x)
                x = nn.relu(x)
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape(x.shape[0], -1)
        x = nn.Dense(4096, dtype=self.dtype, name="fc1")(x)
        x = nn.relu(x)
        x = nn.Dropout(0.5, deterministic=not train)(x)
        x = nn.Dense(4096, dtype=self.dtype, name="fc2")(x)
        x = nn.relu(x)
        x = nn.Dropout(0.5, deterministic=not train)(x)
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(
            x.astype(jnp.float32))


VGG11 = functools.partial(VGG, stage_convs=_CFG["vgg11"])
VGG16 = functools.partial(VGG, stage_convs=_CFG["vgg16"])
VGG19 = functools.partial(VGG, stage_convs=_CFG["vgg19"])
