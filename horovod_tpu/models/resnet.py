"""ResNet family (v1.5), TPU-tuned flax implementation.

The workload of the reference's flagship examples and benchmarks
(`/root/reference/examples/keras_imagenet_resnet50.py`,
`/root/reference/examples/pytorch_imagenet_resnet50.py`,
`/root/reference/docs/benchmarks.md:22-38` — ResNet-101 images/sec).

TPU-first choices:
* NHWC layout and bfloat16 compute (`dtype=jnp.bfloat16`) — the MXU's native
  convolution layout and precision; parameters stay float32.
* BatchNorm with optional ``axis_name`` for cross-replica (sync) statistics
  inside `shard_map` — the role the reference delegates to per-worker BN plus
  gradient allreduce.
* Static shapes and `nn.Conv` everywhere: XLA tiles the convs onto the MXU
  and fuses the elementwise tail (BN + ReLU + residual add) into them.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp
from jax import lax

ModuleDef = Any


class BottleneckBlock(nn.Module):
    """1x1 → 3x3 → 1x1 bottleneck with projection shortcut (ResNet v1.5:
    stride on the 3x3, matching the torchvision/keras models the reference
    examples use)."""

    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1),
                                 self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class BasicBlock(nn.Module):
    """3x3 → 3x3 block (ResNet-18/34)."""

    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1),
                                 self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class SpaceToDepthStem(nn.Module):
    """The 7x7/stride-2 stem conv computed on space-to-depth-transformed
    input — mathematically *identical* to ``nn.Conv(64, (7,7), (2,2),
    SAME)`` (same (7,7,3,F) parameter, same function), but the MXU sees a
    4x4/stride-1 conv over 12 input channels instead of a 7x7/stride-2
    conv over 3, which tiles far better (3 channels fill 3 of 128 MXU
    lanes).  The MLPerf-era TPU ResNet trick, done as an in-graph weight
    reshape so checkpoints and initialization stay conv-compatible.
    """

    features: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        # Same init/param shape as the plain conv stem.
        w = self.param("kernel", nn.initializers.lecun_normal(),
                       (7, 7, x.shape[-1], self.features), jnp.float32)
        b, h, wd, c = x.shape
        if h % 2 or wd % 2:  # odd sizes: plain conv (correctness path)
            return lax.conv_general_dilated(
                x.astype(self.dtype), w.astype(self.dtype), (2, 2), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        # Input space-to-depth(2): (h, w, c) -> (h/2, w/2, 4c).
        x2 = x.reshape(b, h // 2, 2, wd // 2, 2, c)
        x2 = x2.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, wd // 2,
                                                    4 * c)
        # Kernel: zero-pad 7x7 -> 8x8, regroup as 4x4 over (dy, dx, c).
        # Output pixel o covers input rows 2o-2..2o+4 (SAME, k=7, s=2) =
        # s2d rows o-1..o+2, so ki = 2*di + dy with di in 0..3.
        wp = jnp.pad(w.astype(self.dtype), ((0, 1), (0, 1), (0, 0), (0, 0)))
        w4 = wp.reshape(4, 2, 4, 2, c, self.features)
        w4 = w4.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * c,
                                                    self.features)
        return lax.conv_general_dilated(
            x2.astype(self.dtype), w4, (1, 1), ((1, 2), (1, 2)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))


class ResNet(nn.Module):
    """ResNet v1.5 over NHWC inputs.

    ``axis_name`` enables cross-replica BatchNorm inside mapped computations;
    leave None for per-worker statistics (the reference's behavior).
    """

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    axis_name: Optional[str] = None
    small_inputs: bool = False  # CIFAR-style stem: 3x3/1, no maxpool
    # Step-level fused running-stats EMA (models/norm.py): the ~104 BN
    # layers' EMAs collapse into one op — the train step must then apply
    # models.ema_batch_stats to the mutable update.  Same math, two
    # kernels where there were two hundred.
    fused_ema: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        from horovod_tpu.models import norm as norm_mod

        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype,
                                 padding="SAME")
        # norm_mod.BatchNorm = BatchStatsNorm aliased so flax auto-names
        # (BatchNorm_0 ...) keep the two paths' trees path-identical.
        norm_cls = norm_mod.BatchNorm if self.fused_ema else nn.BatchNorm
        norm = functools.partial(
            norm_cls, use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=self.dtype, axis_name=self.axis_name)

        x = x.astype(self.dtype)
        if self.small_inputs:
            x = conv(self.num_filters, (3, 3), name="conv_init")(x)
        else:
            x = SpaceToDepthStem(self.num_filters, dtype=self.dtype,
                                 name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        if not self.small_inputs:
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(self.num_filters * 2 ** i, strides,
                                   conv=conv, norm=norm, act=nn.relu)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32,
                     name="head")(x.astype(jnp.float32))
        return x


ResNet18 = functools.partial(ResNet, stage_sizes=[2, 2, 2, 2],
                             block_cls=BasicBlock)
ResNet34 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3],
                             block_cls=BasicBlock)
ResNet50 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3],
                             block_cls=BottleneckBlock)
ResNet101 = functools.partial(ResNet, stage_sizes=[3, 4, 23, 3],
                              block_cls=BottleneckBlock)
ResNet152 = functools.partial(ResNet, stage_sizes=[3, 8, 36, 3],
                              block_cls=BottleneckBlock)
