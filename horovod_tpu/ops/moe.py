"""Rows grouped by expert, and one grouped matmul over them.

The local middle of a sparse-expert layer (``models.SparseExperts``): the
(token, choice) pairs a router produced are sorted by expert, the rows of the
experts THIS process holds first; each of those experts multiplies its own
contiguous run of rows (:func:`grouped_matmul`); rows of experts held
elsewhere are never multiplied.  An expert-parallel layer wraps this in two
all-to-alls; nothing here knows of other chips.

Shapes are static: the sorted buffer holds ``bound`` rows whatever the router
chose.  With ``bound`` = every (token, choice) pair nothing can fall outside
it; with a smaller bound the rows past it are left out AND COUNTED
(``Dispatch.rows_over_bound``), never silently.

:func:`grouped_matmul` is ``jax.lax.ragged_dot`` — which libtpu lowers to a
Mosaic kernel of its own (``%ragged-dot-none`` custom calls, 512-tiles) —
with the three products of its backward written out, so that every operand
is in the compute dtype (plain autodiff hands the kernel a float32
cotangent) and the rows past the last group, which the kernel leaves
unwritten, read as zero.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


def reduced_to_vma_of(primal, cotangent):
    """``cotangent`` summed over the mapped axes it varies over and
    ``primal`` does not.  A custom_vjp must return cotangents whose varying
    axes MATCH the primal's — the psum plain autodiff would insert is the
    backward rule's job."""
    extra = jax.typeof(cotangent).vma - jax.typeof(primal).vma
    if not extra:
        return cotangent
    # sorted: stable axis order -> stable jaxpr/compile cache
    return lax.psum(cotangent, tuple(sorted(extra)))


class Dispatch(NamedTuple):
    """Where each row of the sorted buffer came from.

    ``pair``: (bound,) index into the flat (token, choice) pairs, sorted by
    local expert; past ``group_sizes.sum()`` the pairs of experts held
    elsewhere (any order).  ``group_sizes``: (local experts,) rows each local
    expert multiplies, in buffer order.  ``rows_per_expert``: what the router
    sent each local expert (equal to ``group_sizes`` unless the bound cut
    some off).  ``rows_over_bound``: scalar, rows routed here that the
    buffer could not hold."""

    pair: jax.Array
    group_sizes: jax.Array
    rows_per_expert: jax.Array
    rows_over_bound: jax.Array


def dispatch_rows(expert_of_pair, first_expert: int, local_experts: int,
                  bound: int) -> Dispatch:
    """Sort the flat (token, choice) pairs by expert, this shard's experts
    ``[first_expert, first_expert + local_experts)`` first, and keep the
    first ``bound`` of them."""
    local = expert_of_pair - first_expert
    held_here = (local >= 0) & (local < local_experts)
    key = jnp.where(held_here, local, local_experts).astype(jnp.int32)
    pair = jnp.argsort(key, stable=True)[:bound].astype(jnp.int32)
    # A compare and a sum, not a scatter-add of ones: the TPU runs a
    # scatter row by row (0.6 ms a layer for 65,536 pairs, PR 26's trace).
    rows = (key[:, None] == jnp.arange(local_experts, dtype=jnp.int32)
            ).sum(axis=0, dtype=jnp.int32)
    ends = jnp.minimum(jnp.cumsum(rows), bound)
    sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    return Dispatch(pair, sizes, rows,
                    jnp.maximum(rows.sum() - bound, 0).astype(jnp.int32))


def _rows_of_groups(rows, group_sizes):
    """``rows`` with everything past the last group zeroed: the kernel
    writes only rows that belong to a group."""
    inside = lax.broadcasted_iota(jnp.int32, (rows.shape[0], 1), 0) \
        < group_sizes.sum()
    return jnp.where(inside, rows, jnp.zeros((), rows.dtype))


@jax.custom_vjp
def grouped_matmul(rows, weights, group_sizes):
    """``out[r] = rows[r] @ weights[g]`` for the r of group g, groups being
    consecutive runs of ``group_sizes[g]`` rows; rows past the last group are
    zero.  ``rows`` (m, k), ``weights`` (g, k, n) in one dtype, which is the
    result's (the kernel accumulates in float32 and writes that dtype)."""
    return _grouped_matmul_fwd(rows, weights, group_sizes)[0]


def _grouped_matmul_fwd(rows, weights, group_sizes):
    out = lax.ragged_dot(rows, weights, group_sizes,
                         preferred_element_type=rows.dtype)
    return _rows_of_groups(out, group_sizes), (rows, weights, group_sizes)


_D_WEIGHTS = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _grouped_matmul_bwd(res, d_out):
    rows, weights, group_sizes = res
    d_out = d_out.astype(rows.dtype)
    d_rows = lax.ragged_dot(d_out, weights.swapaxes(1, 2), group_sizes,
                            preferred_element_type=rows.dtype)
    d_rows = _rows_of_groups(d_rows, group_sizes)
    # Ragged along the contracted dimension: group g's weights get the rows
    # of group g and no others; rows past the last group reach none.
    d_weights = lax.ragged_dot_general(
        rows, d_out, group_sizes, _D_WEIGHTS,
        preferred_element_type=weights.dtype)
    # Under shard_map the rows vary over the mapped axes and the weights are
    # replicated: their cotangent is summed here, as autodiff would.
    return (reduced_to_vma_of(rows, d_rows),
            reduced_to_vma_of(weights, d_weights), None)


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
