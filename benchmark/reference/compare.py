"""The comparisons that decide `correct`: the system against the plain
float32 reference on the same weights and the same inputs, on one device,
outside the measured window.  Each returns rows {"name", "value", "limit"};
a row passes when value <= limit and value is finite.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def first_device_copy(tree):
    """The copy of a replicated (or the first shard of a sharded) tree that
    lives on its first device: no transfer, single-device arrays."""
    return jax.tree.map(lambda a: a.addressable_shards[0].data, tree)


def _norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(leaf.astype(jnp.float32)))
                        for leaf in jax.tree.leaves(tree)))


def system_on_one_device(loss_fn, batch_spec, has_aux: bool, device,
                         axis_name: str = "hvd", grad: bool = True):
    """The loss function handed to build_train_step, bound to a one-device
    mesh of the same axis name (cross-replica batch norm and the flash
    kernels' annotations need the axis), jitted: (params, batch) -> loss[,
    gradients]."""
    from horovod_tpu.parallel import data_parallel_mesh

    def scalar(params, batch):
        out = loss_fn(params, batch)
        return out[0] if has_aux else out

    def local(params, batch):
        if not grad:
            return lax.pmean(scalar(params, batch), axis_name)
        loss, grads = jax.value_and_grad(scalar)(params, batch)
        return lax.pmean(loss, axis_name), grads

    mesh = data_parallel_mesh([device], axis_name=axis_name)
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P(), batch_spec),
                                 out_specs=P()))


def reference_jit(fn):
    """`fn` jitted with every float32 product done in full precision."""
    jitted = jax.jit(fn)

    def call(*args):
        with jax.default_matmul_precision("highest"):
            return jitted(*args)

    return call


def loss_and_gradients(system, reference_loss, params, system_batch,
                       reference_batch, loss_rtol, grad_rtol, grad_norm_rtol,
                       prefix: str = ""):
    """Loss, gradient norm and gradient direction of the system against the
    reference.  `system(params, system_batch) -> (loss, grads)`."""
    loss_s, grads_s = system(params, system_batch)

    def against(params, batch, grads_s):
        loss_r, grads_r = jax.value_and_grad(reference_loss)(params, batch)
        diff = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b, grads_s,
                            grads_r)
        return loss_r, _norm(grads_s), _norm(grads_r), _norm(diff)

    loss_r, norm_s, norm_r, norm_diff = (
        float(x) for x in reference_jit(against)(params, reference_batch,
                                                 grads_s))
    loss_s = float(loss_s)
    return [
        {"name": prefix + "loss_rel_error", "limit": loss_rtol,
         "value": abs(loss_s - loss_r) / abs(loss_r),
         "system": loss_s, "reference": loss_r},
        {"name": prefix + "grad_norm_rel_error", "limit": grad_norm_rtol,
         "value": abs(norm_s / norm_r - 1.0),
         "system": norm_s, "reference": norm_r},
        {"name": prefix + "grad_rel_l2_error", "limit": grad_rtol,
         "value": norm_diff / norm_r},
    ]


def forward_loss(system, reference_loss, params, system_batch,
                 reference_batch, loss_rtol, prefix: str = ""):
    """Forward loss only, where the reference cannot hold the gradients.
    `system(params, system_batch) -> loss`."""
    loss_s = float(system(params, system_batch))
    loss_r = float(reference_jit(reference_loss)(params, reference_batch))
    return [{"name": prefix + "loss_rel_error", "limit": loss_rtol,
             "value": abs(loss_s - loss_r) / abs(loss_r),
             "system": loss_s, "reference": loss_r}]


def kernel_against(kernel, reference, shape, dtype, seed: int, fwd_atol,
                   grad_rtol, prefix: str):
    """An attention kernel, forward and gradients, against the reference's
    attention on the same (rounded) q, k, v of `shape`, both reduced with one
    random float32 weighting so that every output element counts.  One
    program: the inputs, both sides and the four errors."""

    def errors(key):
        keys = jax.random.split(key, 4)
        q, k, v = (jax.random.normal(key, shape, dtype) for key in keys[:3])
        weight = jax.random.normal(keys[3], shape, jnp.float32)
        wide = [t.astype(jnp.float32) for t in (q, k, v)]

        def weighted(fn):
            def total(q, k, v):
                out = fn(q, k, v).astype(jnp.float32)
                return (out * weight).sum(), out
            return jax.value_and_grad(total, argnums=(0, 1, 2), has_aux=True)

        (_, out), grads = weighted(kernel)(q, k, v)
        with jax.default_matmul_precision("highest"):
            (_, want_out), want_grads = weighted(reference)(*wide)
        return [jnp.abs(out - want_out).max()] + [
            jnp.abs(got.astype(jnp.float32) - want).max() / jnp.abs(want).max()
            for got, want in zip(grads, want_grads)]

    values = [float(x) for x in jax.jit(errors)(jax.random.PRNGKey(seed))]
    names = ["forward_max_abs_error"] + [f"d{x}_max_error_over_max"
                                         for x in "qkv"]
    limits = [fwd_atol] + [grad_rtol] * 3
    return [{"name": prefix + name, "limit": limit, "value": value}
            for name, limit, value in zip(names, limits, values)]
