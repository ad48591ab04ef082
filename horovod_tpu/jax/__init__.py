"""JAX binding: the compiled TPU data path.

The role of the framework bindings in the reference (e.g.
/root/reference/horovod/tensorflow/__init__.py — `allreduce`,
`DistributedOptimizer`, variable broadcast) re-designed TPU-first:

* **Inside `jit` / `shard_map`** (pass ``axis_name=``): `allreduce` lowers to
  `lax.psum`/`lax.pmean`, `allgather` to `lax.all_gather(tiled)`, and
  `broadcast` to a masked `psum` — all compiled by XLA into collectives over
  ICI.  Fusion and scheduling are XLA's job here; this path replaces the
  reference's background-engine hot loop
  (/root/reference/horovod/common/operations.cc:696-1229) for compiled
  programs.  What XLA does with the gradient all-reduces on the chip (how
  many, in which dtype, beside which compute) is in PERF.md section 6.
* **Outside `jit`** (no ``axis_name``): values round-trip through the C++
  collective engine (negotiation, fusion, ring transport over DCN), the same
  substrate the numpy/torch APIs use.  This serves eager setup work —
  parameter broadcast, metric averaging — exactly the role the engine plays
  for eagerly-issued tensors in the reference.

`DistributedOptimizer` wraps any `optax.GradientTransformation` and averages
gradients across workers before the update, the direct analogue of the
reference's optimizer wrappers
(/root/reference/horovod/tensorflow/__init__.py:134-208).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import horovod_tpu.common as _common
from horovod_tpu.common import (  # noqa: F401  (re-exported process API)
    HorovodInternalError,
    init,
    is_initialized,
    local_rank,
    local_size,
    mpi_threads_supported,
    rank,
    shutdown,
    size,
)

__all__ = [
    "init", "shutdown", "is_initialized", "rank", "size", "local_rank",
    "local_size", "mpi_threads_supported", "HorovodInternalError",
    "allreduce", "allgather", "broadcast", "allreduce_pytree",
    "broadcast_parameters", "broadcast_optimizer_state",
    "DistributedOptimizer",
]


def _is_tracer(x: Any) -> bool:
    return isinstance(x, jax.core.Tracer)


def _eager_to_host(tensor) -> np.ndarray:
    # jax bfloat16 arrays convert to ml_dtypes.bfloat16 numpy arrays, which
    # the engine's dtype table understands (common/dtypes.py).  _as_contig
    # preserves 0-d shapes (np.ascontiguousarray would promote to (1,)).
    return _common._as_contig(np.asarray(tensor))


def allreduce(tensor, average: bool = True, name: Optional[str] = None,
              axis_name: Optional[str] = None):
    """Sum (or mean) of per-worker contributions of ``tensor``.

    With ``axis_name`` inside a mapped computation this is a compiled XLA
    collective; otherwise an eager engine collective (requires `hvd.init()`).

    The compiled path is *varying-aware* (and therefore requires shard_map's
    default ``check_vma=True``): JAX's grad transpose already inserts the
    cross-shard `psum` when differentiating w.r.t. replicated parameters, so
    gradients reach the caller as the cross-worker **sum** with the mapped
    axis no longer in their varying set.  For such already-reduced values
    allreduce is sum→identity / mean→divide-by-N; for still-varying values it
    is a real `psum`/`pmean`.  Either way the result is the reduction of the
    per-shard contributions — allreduce is idempotent, like the engine path.

    So for the gradients of replicated parameters no collective is issued
    here: the sum over replicas is the one autodiff inserted, taken where
    each weight first meets the batch and in the dtype it has there (the
    compute dtype, e.g. bfloat16, when the model casts its weights), and
    this function only divides by the axis size.
    """
    if axis_name is not None:
        # One mesh axis or several (e.g. ("dp", "sp") for a 2-D mesh).
        axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        vma = getattr(getattr(tensor, "aval", None), "vma", None)
        # Axes absent from the varying set are already reduced (e.g. by the
        # grad transpose's automatic psum): the value is the cross-worker
        # sum over them, so only psum the still-varying axes and divide by
        # the full participant count when averaging.
        present = axes if vma is None else tuple(a for a in axes if a in vma)
        out = lax.psum(tensor, present) if present else tensor
        if average:
            denom = 1
            for a in axes:
                denom *= lax.axis_size(a)
            out = out / denom
        return out
    if _is_tracer(tensor):
        raise ValueError(
            "allreduce of a traced value requires axis_name= (the mapped "
            "mesh axis); the eager engine path cannot run under jit.")
    out = _common.allreduce(_eager_to_host(tensor), average=average, name=name)
    return jnp.asarray(out)


def allgather(tensor, name: Optional[str] = None,
              axis_name: Optional[str] = None):
    """Concatenate ``tensor`` from all workers along dimension 0.

    Workers may differ in dimension 0 only on the eager path (the engine
    negotiates per-rank sizes as the reference does,
    /root/reference/horovod/common/operations.cc:778-838); inside a mapped
    computation XLA requires equal shapes per shard.
    """
    if axis_name is not None:
        return lax.all_gather(tensor, axis_name, axis=0, tiled=True)
    if _is_tracer(tensor):
        raise ValueError(
            "allgather of a traced value requires axis_name= (the mapped "
            "mesh axis); the eager engine path cannot run under jit.")
    return jnp.asarray(_common.allgather(_eager_to_host(tensor), name=name))


def broadcast(tensor, root_rank: int, name: Optional[str] = None,
              axis_name: Optional[str] = None):
    """Every worker receives ``root_rank``'s value of ``tensor``."""
    if axis_name is not None:
        idx = lax.axis_index(axis_name)
        cast = tensor.dtype == jnp.bool_ if hasattr(tensor, "dtype") else False
        x = jnp.asarray(tensor)
        if cast:
            x = x.astype(jnp.uint8)
        picked = jnp.where(idx == root_rank, x, jnp.zeros_like(x))
        out = lax.psum(picked, axis_name)
        return out.astype(jnp.bool_) if cast else out
    if _is_tracer(tensor):
        raise ValueError(
            "broadcast of a traced value requires axis_name= (the mapped "
            "mesh axis); the eager engine path cannot run under jit.")
    out = _common.broadcast(_eager_to_host(tensor), root_rank=root_rank,
                            name=name)
    return jnp.asarray(out)


def _leaf_paths(tree):
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return leaves_with_paths


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
                    for p in path)


def allreduce_pytree(tree, average: bool = True,
                     name_prefix: str = "allreduce",
                     axis_name: Optional[str] = None):
    """Allreduce every array leaf of a pytree (names derived from tree paths
    so all ranks agree on collective identity, as the reference derives op
    names from tensor names, /root/reference/horovod/tensorflow/mpi_ops.py:65)."""
    def one(path, leaf):
        return allreduce(leaf, average=average,
                         name=f"{name_prefix}.{_path_str(path)}",
                         axis_name=axis_name)
    if axis_name is None:
        return jax.tree_util.tree_map_with_path(one, tree)
    # The compiled exchange carries its name into the HLO's op_name, where
    # a device trace finds it (docs/timeline.md).
    with jax.named_scope("hvd_grad_exchange"):
        return jax.tree_util.tree_map_with_path(one, tree)


def _bcast_leaf(path, leaf, root_rank: int, name_prefix: str):
    name = f"{name_prefix}.{_path_str(path)}"
    if isinstance(leaf, (jax.Array, np.ndarray)):
        out = _common.broadcast(_eager_to_host(leaf), root_rank, name=name)
        if isinstance(leaf, np.ndarray):
            return out
        return jnp.asarray(out)
    if isinstance(leaf, (bool, int, float)):
        # Scalars round-trip through tensors, as the reference's
        # broadcast_optimizer_state does for hyperparameters
        # (/root/reference/horovod/torch/__init__.py:161-228).
        out = _common.broadcast(np.asarray(leaf), root_rank, name=name)
        return type(leaf)(out.item())
    return leaf


def broadcast_parameters(params, root_rank: int = 0,
                         name_prefix: str = "broadcast_parameters"):
    """Replicate rank ``root_rank``'s parameter pytree on every worker.

    The rank-0 state-replication step of the reference
    (/root/reference/horovod/torch/__init__.py:127-158,
    horovod/tensorflow/__init__.py:89-131), for arbitrary JAX pytrees.
    Eager: call once after `hvd.init()` and before training.
    """
    _common._check_initialized(_common._load_lib())
    return jax.tree_util.tree_map_with_path(
        lambda p, l: _bcast_leaf(p, l, root_rank, name_prefix), params)


def broadcast_optimizer_state(opt_state, root_rank: int = 0):
    """Replicate rank ``root_rank``'s optax optimizer state (a pytree that
    may include scalar hyperparameters) on every worker."""
    return broadcast_parameters(opt_state, root_rank,
                                name_prefix="broadcast_optimizer_state")


def DistributedOptimizer(optimizer, axis_name: Optional[str] = None,
                         average: bool = True,
                         name_prefix: str = "DistributedOptimizer"):
    """Wrap an `optax.GradientTransformation` so updates see the cross-worker
    (mean) gradient.

    Counterpart of the reference's optimizer wrappers
    (/root/reference/horovod/tensorflow/__init__.py:134-208,
    horovod/torch/__init__.py:64-124).  Inside `shard_map` pass the mesh
    ``axis_name``.  Under the default ``check_vma=True`` the gradients of
    replicated parameters arrive already summed over the axis — by the
    `psum` that `shard_map`'s autodiff inserts, see :func:`allreduce` — so
    the wrapper divides by the axis size and issues no collective of its
    own; a leaf that still varies gets a real `psum`.  XLA groups and
    schedules those all-reduces, under the compiler options the enclosing
    `jax.jit` carries: `build_train_step` compiles a step over more than
    one TPU device so that each gradient over a megabyte is an asynchronous
    all-reduce of its own beside the remaining compute and the small ones
    share one; a `jax.jit` of your own without those options gets a few
    combined all-reduces the core waits in (PERF.md section 6, PR 29;
    ROADMAP S3, D12).  Without ``axis_name`` gradients are averaged eagerly
    through the engine.
    """
    import optax

    def init_fn(params):
        return optimizer.init(params)

    def update_fn(updates, state, params=None, **extra):
        reduced = allreduce_pytree(updates, average=average,
                                   name_prefix=name_prefix,
                                   axis_name=axis_name)
        return optimizer.update(reduced, state, params, **extra)

    return optax.GradientTransformation(init_fn, update_fn)
