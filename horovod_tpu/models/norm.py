"""BatchStatsNorm: BatchNorm with the running-stats EMA hoisted out of the
model into ONE fused step-level op.

Why: a ResNet-101 has 104 BatchNorm layers; flax's ``nn.BatchNorm`` updates
each layer's running mean/var inside the module, which XLA compiles to
~208 tiny elementwise kernels plus memory-space copies (what they cost a
step is not measured on this machine; PERF.md).  ``BatchStatsNorm``
instead *writes the raw batch statistics* into the ``batch_stats``
collection, and the training step
applies the EMA once over the whole flattened tree
(:func:`ema_batch_stats`) — numerically identical to per-layer flax BN
(same formula, same f32 stats), but 2 kernels instead of ~200.

Drop-in: parameter and variable names match ``nn.BatchNorm`` ("scale",
"bias" / "mean", "var"), so checkpoints interchange.  The contract is that
the TRAINING STEP calls ``ema_batch_stats(old, new, momentum)`` on the
returned mutable update; forgetting it stores raw batch stats (still
usable, just not smoothed).  Eval mode reads the running stats as usual.

No reference counterpart (the reference delegates BN to the frameworks);
this is TPU-first step-level fusion of framework bookkeeping.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax.numpy as jnp
from jax import lax
from jax.flatten_util import ravel_pytree


class BatchStatsNorm(nn.Module):
    """``nn.BatchNorm``-compatible normalization with step-level EMA.

    In train mode (``use_running_average=False``) normalizes with the
    current batch statistics (exactly as flax BN does) and stores those
    RAW statistics in the ``batch_stats`` collection; apply
    :func:`ema_batch_stats` to the mutable update in the train step.
    """

    use_running_average: bool = False
    # NOT applied here: the step-level ema_batch_stats call must be passed
    # the same momentum (both default 0.9).  Kept as a field so module
    # configs stay interchangeable with nn.BatchNorm.
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16
    axis_name: Optional[str] = None
    scale_init: Callable = nn.initializers.ones_init()

    @nn.compact
    def __call__(self, x):
        features = x.shape[-1]
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros(features, jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones(features, jnp.float32))
        scale = self.param("scale", self.scale_init, (features,),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(), (features,),
                          jnp.float32)
        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            reduce_axes = tuple(range(x.ndim - 1))
            xf = x.astype(jnp.float32)
            mean = xf.mean(axis=reduce_axes)
            mean2 = (xf * xf).mean(axis=reduce_axes)
            if self.axis_name is not None:
                mean = lax.pmean(mean, self.axis_name)
                mean2 = lax.pmean(mean2, self.axis_name)
            var = mean2 - mean * mean
            if not self.is_initializing():
                ra_mean.value = mean
                ra_var.value = var
        # Fold the normalize into a per-channel affine y = x*a + b with the
        # COEFFICIENTS in float32 and the per-element arithmetic in the
        # compute dtype: normalizing in f32 materializes a full f32 copy of
        # every activation (a convert_element_type pass a layer), while the
        # bf16 affine fuses into the producing conv's epilogue.  Stock flax
        # BN computes the whole normalize in the compute dtype, so this is
        # strictly more precise than the nn.BatchNorm path it interchanges
        # with.
        a = lax.rsqrt(var + self.epsilon) * scale
        b = bias - mean * a
        x = x.astype(self.dtype)  # no-op for conv outputs already in dtype
        return x * a.astype(self.dtype) + b.astype(self.dtype)


class BatchNorm(BatchStatsNorm):
    """``BatchStatsNorm`` under the class name ``BatchNorm``: flax derives
    auto-generated module names from the class name (``BatchNorm_0`` …),
    so using this alias keeps fused-EMA param/stat trees *path-identical*
    to ``nn.BatchNorm`` ones — checkpoints interchange between the two
    paths."""


def ema_batch_stats(old_stats, batch_stats, momentum: float = 0.9):
    """One fused EMA over a whole ``batch_stats`` tree.

    ``new_running = momentum * old + (1 - momentum) * batch`` — the same
    update flax BN applies per layer, computed as a single elementwise op
    over the flattened tree.  Returns a tree with ``old_stats``'s
    structure.  The train step's stats carry becomes::

        logits, upd = model.apply({...}, x, train=True,
                                  mutable=["batch_stats"])
        new_stats = ema_batch_stats(stats, upd["batch_stats"])
    """
    flat_old, unravel = ravel_pytree(old_stats)
    flat_new, _ = ravel_pytree(batch_stats)
    return unravel(momentum * flat_old + (1.0 - momentum) * flat_new)
