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

How rows travel: :func:`dispatch_rows` sorts the pairs once and keeps the
permutation both ways — ``pair[r]``, the pair that buffer row r holds, and
``position[t, c]``, the place of pair (t, c) in the sorted order.  A pair is
``valid`` when its place is under ``group_sizes.sum()``: pairs of experts
held elsewhere and pairs the bound cut off are not, and contribute exactly
zero (their index is clamped into the buffer, their value masked).

Every movement between pair space (tokens x choices) and buffer space
(``bound`` rows) walks the SMALLER side (:func:`token_rows_to_buffer`,
:func:`buffer_rows_to_tokens`, each with a backward pass of its own;
:func:`top_choices` does the same for the router's top-k).  Into the buffer
that is always a gather of ``bound`` rows.  Back to the tokens it is one
indexed segment-sum in two forms, chosen from static shapes by
:func:`walks_rows`:

* under ``ROW_WALK_PAIRS_PER_ROW`` pairs a buffer row (a shard with a
  quarter of the experts: 2.7) a GATHER through ``position``, k-wide, masked
  where the pair has no row: the TPU runs a scatter-add row by row (2 ms for
  24,576 rows of 2,048, PR 26's trace) where the gather costs a sixth as much
  a row, and autodiff would turn every gather back into a scatter-add;
* at or over it (a shard with a sixteenth of the experts or less: 35 pairs a
  row at 8 of 512) a SCATTER-ADD of the buffer's rows through ``token_of_row``:
  the k-wide gather would read a row for every pair and throw all but
  ``bound`` of them away.  The weights' cotangent is then a write of at most
  ``bound`` scalars at distinct places, not a gather of every pair's.

Rows at or past ``group_sizes.sum()`` take no part in either form, by the
mover's own mask.

:func:`grouped_matmul` is ``jax.lax.ragged_dot`` — which libtpu lowers to a
Mosaic kernel of its own (``%ragged-dot-none`` custom calls, 512-tiles) —
with the three products of its backward written out, so that every operand
is in the compute dtype (plain autodiff hands the kernel a float32
cotangent) and the rows past the last group, which the kernel leaves
unwritten, read as zero.
"""

from __future__ import annotations

import functools
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def top_choices(probs, k: int):
    """``lax.top_k(probs, k)`` over the last axis: (weights, experts).  Its
    backward hands each chosen expert's probability its weight's cotangent
    by a compare and a sum over the k choices, where autodiff scatters
    them."""
    return lax.top_k(probs, k)


def _top_choices_fwd(probs, k):
    weight, expert = lax.top_k(probs, k)
    return (weight, expert), (probs, expert)


def _top_choices_bwd(k, res, cotangents):
    probs, expert = res
    d_weight = cotangents[0]
    chosen = expert[..., None] == jnp.arange(probs.shape[-1],
                                             dtype=expert.dtype)
    d_probs = jnp.where(chosen, d_weight[..., None],
                        jnp.zeros((), d_weight.dtype)).sum(axis=-2)
    return (reduced_to_vma_of(probs, d_probs.astype(probs.dtype)),)


top_choices.defvjp(_top_choices_fwd, _top_choices_bwd)


class Dispatch(NamedTuple):
    """The sorted order of the (token, choice) pairs, both ways.

    ``pair``: (bound,) index into the flat pairs, sorted by local expert;
    past ``group_sizes.sum()`` the pairs of experts held elsewhere (any
    order).  ``position``: the place of every pair in the whole sorted order
    (a permutation of the flat pairs, shaped as the experts were given:
    ``pair[position.reshape(-1)[p]] == p`` wherever the place is inside the
    buffer).  ``valid``: ``position < group_sizes.sum()`` — the pair's row is
    in the buffer and an expert here multiplies it.  ``group_sizes``: (local
    experts,) rows each local expert multiplies, in buffer order.
    ``rows_per_expert``: what the router sent each local expert (equal to
    ``group_sizes`` unless the bound cut some off).  ``rows_over_bound``:
    scalar, rows routed here that the buffer could not hold."""

    pair: jax.Array
    position: jax.Array
    valid: jax.Array
    group_sizes: jax.Array
    rows_per_expert: jax.Array
    rows_over_bound: jax.Array

    @property
    def token_of_row(self):
        """(bound,) the token whose row each buffer row is; ``position`` is
        (tokens, choices)."""
        return self.pair // self.position.shape[-1]

    @property
    def rows_walked(self) -> int:
        """Rows one pass from the buffer back to the tokens touches: the
        buffer's where :func:`walks_rows`, every pair's otherwise."""
        return self.pair.shape[0] if walks_rows(self) else self.position.size


def dispatch_rows(expert_of_pair, first_expert: int, local_experts: int,
                  bound: int) -> Dispatch:
    """Sort the (token, choice) pairs by expert, this shard's experts
    ``[first_expert, first_expert + local_experts)`` first, and keep the
    first ``bound`` of them."""
    local = expert_of_pair.reshape(-1) - first_expert
    held_here = (local >= 0) & (local < local_experts)
    key = jnp.where(held_here, local, local_experts).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    # The inverse permutation by a second sort (0.044 ms for 65,536 keys on
    # the v5e; scattering an iota took 0.12 ms more: my chip runs, PR 27).
    position = jnp.argsort(order).astype(jnp.int32).reshape(
        expert_of_pair.shape)
    # A compare and a sum, not a scatter-add of ones: the TPU runs a
    # scatter row by row (0.6 ms a layer for 65,536 pairs, PR 26's trace).
    rows = (key[:, None] == jnp.arange(local_experts, dtype=jnp.int32)
            ).sum(axis=0, dtype=jnp.int32)
    ends = jnp.minimum(jnp.cumsum(rows), bound)
    sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    return Dispatch(order[:bound], position, position < ends[-1], sizes, rows,
                    jnp.maximum(rows.sum() - bound, 0).astype(jnp.int32))


# Pairs a buffer row from which the way back to the tokens walks the buffer's
# rows (two scatter-adds and a scalar scatter a layer) and not the pairs (three
# k-wide gathers).  On the v5e a gathered pair costs 7-16 ns whatever its
# width, a scatter-added float32 row ~45 ns a 1,024 elements; the three passes
# together, alone in a program, ms gathers / ms row walk (my chip runs, PR 31):
#   2.7 pairs a row (8,192 tokens, k 8, a quarter of 64 experts, 24,576 rows):
#       2.19 / 5.14 at 2,048 wide, 1.44 / 2.71 at 1,024
#   5.3 (an eighth, 12,288 rows): 2.19 / 2.93 at 2,048, 1.44 / 1.25 at 1,024;
#       at 2,048 tokens (3,072 rows of 1,024) 0.56 / 0.22
#   8 (8,192 rows): 2.19 / 2.23 at 2,048, 1.44 / 0.90 at 1,024
#   10.7 (a sixteenth, 6,144 rows): 2.19 / 1.66 at 2,048, 1.44 / 0.73 at 1,024
#   35.2 (4,096 tokens, k 22, 8 of 512 experts, 2,560 rows): 5.32 / 0.77 at
#       2,048, 3.53 / 0.33 at 1,024
# 8 is the lowest ratio at which the row walk lost at neither width.
ROW_WALK_PAIRS_PER_ROW = 8


def walks_rows(sent: Dispatch) -> bool:
    """Whether the passes from the buffer back to the tokens walk the
    buffer's ``bound`` rows rather than every (token, choice) pair: static
    shapes only, ``n_shards / row_bound`` of an expert-parallel layout."""
    return sent.position.size >= ROW_WALK_PAIRS_PER_ROW * sent.pair.shape[0]


def _held_or(index, sent: Dispatch, out_of_range: int):
    """``index[r]`` for the buffer rows under ``group_sizes.sum()``,
    ``out_of_range`` for the rest: a scatter in ``mode="drop"`` then leaves
    those out, whatever they hold."""
    held = lax.iota(jnp.int32, sent.pair.shape[0]) < sent.group_sizes.sum()
    return jnp.where(held, index, out_of_range)


def _rows_summed_by_token(rows, sent: Dispatch):
    """(tokens, d) float32: ``sum over r < held, token_of_row[r] == t`` of
    ``rows[r]`` — a scatter-add of the buffer's rows."""
    tokens = sent.position.shape[0]
    return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[
        _held_or(sent.token_of_row, sent, tokens)].add(
            rows.astype(jnp.float32), mode="drop")


def _rows_scalars_to_pairs(scalars, sent: Dispatch):
    """(tokens, choices): ``scalars[r]`` at pair ``pair[r]`` for r < held,
    zero everywhere else — a write of at most ``bound`` scalars, each pair
    being in one row."""
    pairs = sent.position.size
    return jnp.zeros((pairs,), scalars.dtype).at[
        _held_or(sent.pair, sent, pairs)].set(
            scalars, mode="drop", unique_indices=True).reshape(
                sent.position.shape)


def _of_pairs(buffer, sent: Dispatch):
    """(tokens, choices, ...): the buffer's entry for every pair, zero where
    the pair has none.  The index of such a pair is clamped into the
    buffer."""
    index = jnp.minimum(sent.position, buffer.shape[0] - 1)
    valid = sent.valid.reshape(sent.valid.shape + (1,) * (buffer.ndim - 1))
    return jnp.where(valid, buffer[index], jnp.zeros((), buffer.dtype))


@jax.custom_vjp
def token_rows_to_buffer(flat, sent: Dispatch):
    """``rows[r] = flat[token_of_row[r]]``: each token's row, once for every
    buffer row that holds one of its pairs.  ``flat`` (tokens, d); the
    result (bound, d).  Backward: ``d_flat[t]`` is the sum over the token's
    choices of ``d_rows[position[t, c]]`` where valid, summed in float32 and
    rounded once to ``flat``'s dtype — a k-wide gather, or where
    :func:`walks_rows` a scatter-add of the rows under
    ``group_sizes.sum()``."""
    return flat[sent.token_of_row]


def _token_rows_to_buffer_fwd(flat, sent):
    return flat[sent.token_of_row], (flat, sent)


def _token_rows_to_buffer_bwd(res, d_rows):
    flat, sent = res
    if walks_rows(sent):
        d_flat = _rows_summed_by_token(d_rows, sent)
    else:
        d_flat = _of_pairs(d_rows, sent).astype(jnp.float32).sum(axis=1)
    return reduced_to_vma_of(flat, d_flat.astype(flat.dtype)), None


token_rows_to_buffer.defvjp(_token_rows_to_buffer_fwd,
                            _token_rows_to_buffer_bwd)


@jax.custom_vjp
def buffer_rows_to_tokens(out, weight, sent: Dispatch):
    """``mixed[t] = sum over c of weight[t, c] * out[position[t, c]]`` where
    valid: the experts' rows back in token order, each weighted by its
    router weight; products and sum in float32, rounded once to ``out``'s
    dtype.  ``out`` (bound, d), ``weight`` (tokens, choices) float32.
    Backward (products in float32 as well):
    ``d_out[r] = weight[pair[r]] * d_mixed[token_of_row[r]]`` and
    ``d_weight[t, c] = <d_mixed[t], out[position[t, c]]>`` where valid, zero
    elsewhere.  Where :func:`walks_rows` the forward is a scatter-add of the
    weighted rows under ``group_sizes.sum()`` and ``d_weight`` a write of
    their scalars; otherwise both are gathers over every pair."""
    return _buffer_rows_to_tokens_fwd(out, weight, sent)[0]


def _buffer_rows_to_tokens_fwd(out, weight, sent):
    if walks_rows(sent):
        weighted = weight.reshape(-1)[sent.pair][:, None] \
            * out.astype(jnp.float32)
        mixed = _rows_summed_by_token(weighted, sent)
    else:
        picked = _of_pairs(out, sent).astype(jnp.float32)
        mixed = (picked * weight[..., None]).sum(axis=1)
    return mixed.astype(out.dtype), (out, weight, sent)


def _buffer_rows_to_tokens_bwd(res, d_mixed):
    out, weight, sent = res
    d_weighted = d_mixed[sent.token_of_row].astype(jnp.float32)
    d_out = weight.reshape(-1)[sent.pair][:, None] * d_weighted
    # <d_mixed[t], out[r]> once per buffer row, in the pass that reads both
    # anyway; each valid pair then picks its row's scalar (gathering the
    # rows themselves a second time, k-wide, took 1.3 ms a layer more on the
    # v5e: my chip run, PR 27), or each row writes its own to its pair.
    along = (out.astype(jnp.float32) * d_weighted).sum(axis=-1)
    d_weight = _rows_scalars_to_pairs(along, sent) if walks_rows(sent) \
        else _of_pairs(along, sent)
    return (reduced_to_vma_of(out, d_out.astype(out.dtype)),
            reduced_to_vma_of(weight, d_weight.astype(weight.dtype)), None)


buffer_rows_to_tokens.defvjp(_buffer_rows_to_tokens_fwd,
                             _buffer_rows_to_tokens_bwd)


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
