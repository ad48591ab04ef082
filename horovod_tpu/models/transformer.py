"""Decoder-only Transformer LM, TPU-tuned, with optional sequence parallelism.

No reference counterpart (zhangzhao156/horovod predates LLM workloads); this
is the long-context flagship the task adds: bfloat16 compute on the MXU,
RoPE positions (no position table to shard), pre-norm blocks, and attention
that is either the fused Pallas :func:`~horovod_tpu.ops.flash_attention`
(single shard) or :func:`~horovod_tpu.ops.ring_attention` when the sequence
dimension is sharded over a mesh axis (``seq_axis=``) — context length then
scales linearly with the ring size.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.models.delta import DeltaConfig, DeltaMixer
from horovod_tpu.models.ssm import Mamba2Config, Mamba2Mixer
from horovod_tpu.ops import (blockwise_attention, flash_attention,
                             ring_attention)
from horovod_tpu.ops.attn_prep import normed_and_turned, prep_rows
from horovod_tpu.ops.attention import (Selected, _split_scale, mask_blocks,
                                       masked_flash_attention)
from horovod_tpu.ops.dsa import (Selection, head_probs, index_scores,
                                 indexer_kl, select)
from horovod_tpu.ops.moe import (GROUPED_KERNELS, WAYS_BACK,
                                 buffer_rows_to_tokens, column_slabs,
                                 dispatch_rows, grouped_matmul, pass_back,
                                 product_kernel, reduced_to_vma_of,
                                 scatters_whole_rows, token_rows_to_buffer,
                                 top_choices)


def embedding_lookup(table, tokens):
    """``table[tokens]``: the tokens' rows of ``table`` (vocab, width), as
    ``jnp.take`` gives them (``nn.Embed``'s lookup).  The table's cotangent
    is the scatter-add of the rows' cotangents at their tokens, in the
    table's dtype: autodiff's own for rows the TPU scatter-adds at the cost
    of their bytes (``ops.moe.scatters_whole_rows``), and past that width
    the same sums a slab of columns at a time, joined (:func:`_rows_taken`) —
    8.13 ms against 1.27 for 8,192 bf16 rows into a ``[19648, 2560]`` table
    in the Ling cell's step (my chip runs, PR 37; the table over
    ``ops.moe.WHOLE_ROW_WIDTH`` has the other widths)."""
    if scatters_whole_rows(table.shape[1]):
        return jnp.take(table, tokens, axis=0)
    return _rows_taken(table, tokens)


@jax.custom_vjp
def _rows_taken(table, tokens):
    return jnp.take(table, tokens, axis=0)


def _rows_taken_fwd(table, tokens):
    return jnp.take(table, tokens, axis=0), (table, tokens)


def _rows_taken_bwd(res, d_rows):
    # The table is kept for its shape, dtype and varying axes alone.
    table, tokens = res
    # Columns are summed independently: each element is the sum autodiff's
    # one scatter-add of whole rows would hold, of the same terms.
    d_table = jnp.concatenate(
        [jnp.zeros((table.shape[0], slab.shape[-1]),
                   table.dtype).at[tokens].add(slab)
         for slab in column_slabs(d_rows)], axis=1)
    # The join is written out, as autodiff's one scatter-add wrote the
    # table's cotangent out, and not fused into the table's AdamW update as
    # pads of the slabs (which XLA does with up to four): the slabs then die
    # at the join, and the on-chip memory they were scattered in is free
    # while that update runs.  Held to its end, OLMoE's four slabs of 51 MB
    # took the place of the head's prefetched operand, and the head's dW
    # fusion ran 18.4 ms for 13.9 (my chip runs, PR 37).
    d_table = lax.optimization_barrier(d_table)
    # Under shard_map the table is replicated and the tokens vary over the
    # data-parallel axis: the sum over it is owed here.
    return reduced_to_vma_of(table, d_table), None


_rows_taken.defvjp(_rows_taken_fwd, _rows_taken_bwd)


class TokenEmbed(nn.Embed):
    """``nn.Embed`` — its parameter, its initialiser, its values — with the
    rows taken through :func:`embedding_lookup`."""

    def __call__(self, tokens):
        (table,) = self.promote_dtype(self.embedding, dtype=self.dtype,
                                      inexact=False)
        return embedding_lookup(table, tokens)


@jax.custom_vjp
def _qkv_project(x, w):
    """Fused qkv projection returning the UNSTACKED (q, k, v) triple.

    Functionally identical to slicing ``einsum('bsd,djhe->jbhse')`` —
    but under plain autodiff those three slices transpose to pad+concat
    of the cotangents into a materialized j-stack (an activation-sized
    copy a layer).  The custom VJP computes dx as the
    sum of three per-slot matmuls and dW by stacking only the (small)
    weight gradients, so no activation-sized stack is ever built."""
    q, k, v = jnp.einsum("bsd,djhe->jbhse", x, w)
    return q, k, v


def _qkv_project_fwd(x, w):
    return _qkv_project(x, w), (x, w)


def _qkv_project_bwd(res, cots):
    x, w = res
    dx = sum(jnp.einsum("bhse,dhe->bsd", c, w[:, j])
             for j, c in enumerate(cots))
    dw = jnp.stack([jnp.einsum("bsd,bhse->dhe", x, c) for c in cots],
                   axis=1)  # (d, 3, h, e): params-sized, cheap to stack
    # Under shard_map the cotangents vary over the mapped axes while the
    # primal inputs may be replicated (w always is; x can be, e.g. when
    # only the batch is mapped elsewhere).
    return reduced_to_vma_of(x, dx), reduced_to_vma_of(w, dw)


_qkv_project.defvjp(_qkv_project_fwd, _qkv_project_bwd)


def _gate_and_gated(out, x, w):
    """(``out * sigmoid(x w)``, the gate itself), both rounded once to
    ``out``'s dtype: the product accumulates in float32, and the sigmoid and
    the multiply are float32 in its epilogue."""
    gate = nn.sigmoid(jnp.einsum("bsd,dhe->bhse", x, w,
                                 preferred_element_type=jnp.float32))
    return (out * gate).astype(out.dtype), gate.astype(out.dtype)


@jax.custom_vjp
def _output_gated(out, x, w):
    """``out * sigmoid(x w)``: the kernels' output ``out`` (b, h, s, e) times
    the gate of the layer's input ``x`` (b, s, d) through ``w`` (d, h, e), all
    three in the layer's dtype, which the result has too.

    Under plain autodiff the float32 gate (b, h, s, e) is written, kept for
    the backward, and met there by three more float32 passes of its size
    (``dy * gate``, ``dy * out``, the sigmoid's derivative) before two
    products that read a float32 operand: at 32 heads of 128 on 8,192 rows
    five arrays of 134 MB a layer.  Written out, the backward keeps the gate
    ROUNDED to the layer's dtype, as every other saved activation is, makes
    ``d_out`` and ``dz`` in one pass (float32 inside, rounded once) and hands
    ``dz`` to two plain products."""
    return _gate_and_gated(out, x, w)[0]


def _output_gated_fwd(out, x, w):
    # Both written by the product's own fusion: unheld, XLA writes the float32
    # gate instead and rounds it again in each of its three readers.
    gated, gate = lax.optimization_barrier(_gate_and_gated(out, x, w))
    return gated, (out, x, w, gate)


def _output_gated_bwd(res, d_gated):
    out, x, w, gate = res
    wide = d_gated.astype(jnp.float32)
    g = gate.astype(jnp.float32)
    d_out = (wide * g).astype(out.dtype)
    dz = (wide * out.astype(jnp.float32) * (g * (1.0 - g))).astype(out.dtype)
    # Written, both by the one pass that reads dy, out and the gate: left to
    # itself XLA computes dz again inside each product that reads it (an
    # element-wise producer in a weight-gradient fusion: `_head_norm`).
    d_out, dz = lax.optimization_barrier((d_out, dz))
    dx = jnp.einsum("bhse,dhe->bsd", dz, w)
    dw = jnp.einsum("bsd,bhse->dhe", x, dz)
    return (reduced_to_vma_of(out, d_out), reduced_to_vma_of(x, dx),
            reduced_to_vma_of(w, dw))


_output_gated.defvjp(_output_gated_fwd, _output_gated_bwd)


def _pair_swap(d: int, dtype):
    """The signed permutation ``S`` (d, d) of the last axis that swaps a
    pair's two components: ``(x @ S)[2i] = -x[2i+1]``, ``(x @ S)[2i+1] =
    x[2i]``.  One non-zero a column, so the product is exact in any dtype;
    ``S^T = -S``."""
    swap = np.zeros((d, d), np.float32)
    even = np.arange(0, d, 2)
    swap[even + 1, even] = -1.0
    swap[even, even + 1] = 1.0
    return jnp.asarray(swap, dtype)


class RopeScaling(NamedTuple):
    """YaRN's scaled rotary frequencies (arXiv:2309.00071), a static
    description :func:`rope` turns into a table at trace time: a model
    trained to ``original_positions`` reads ``factor`` times as far.  A pair
    that turns more than ``beta_fast`` times within ``original_positions``
    keeps its frequency, one that turns fewer than ``beta_slow`` times has it
    divided by ``factor``, and the pairs between take a linear ramp between
    the two (:func:`yarn_frequencies`).  Cosine and sine are multiplied by
    ``attention_factor`` (the scores of a layer whose q and k both turn
    carry its square); ``None``: ``0.1 ln(factor) + 1``."""

    factor: float
    original_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    @property
    def magnitude(self) -> float:
        if self.attention_factor is not None:
            return float(self.attention_factor)
        return 0.1 * float(np.log(self.factor)) + 1.0


def yarn_frequencies(base: float, pairs: int, scaling: RopeScaling):
    """(frequencies (pairs,) float64, low, high) of ``scaling`` over the
    plain ``base ** (-i / pairs)``: with ``c(r) = pairs ln(original_positions
    / (2 pi r)) / ln(base)`` the pair that turns ``r`` times within the
    original positions, ``low = floor(c(beta_fast))`` and ``high =
    ceil(c(beta_slow))`` (both within the head), pair ``i`` keeps ``1 -
    ramp_i`` of its plain frequency and takes ``ramp_i`` of the one divided
    by ``factor``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``."""
    plain = float(base) ** (-np.arange(pairs, dtype=np.float64) / pairs)

    def pair_turning(times):
        return pairs * np.log(scaling.original_positions
                              / (2 * np.pi * times)) / np.log(float(base))

    low = max(int(np.floor(pair_turning(scaling.beta_fast))), 0)
    high = min(int(np.ceil(pair_turning(scaling.beta_slow))), 2 * pairs - 1)
    ramp = np.clip((np.arange(pairs) - low) / max(high - low, 1e-3), 0, 1)
    return plain / scaling.factor * ramp + plain * (1 - ramp), low, high


def rotary_tables(positions, d: int, base, rotary_dim=None, scaling=None,
                  back: bool = False, shape=None):
    """(cos, sin): the float32 tables of :func:`rope` for a last axis of
    ``d``, ``positions.shape + (d,)`` (``shape``: reshaped to it), each
    frequency written twice, beside the pair it turns (``back``: the sine of
    the negative angles).  With ``rotary_dim`` only the first ``rotary_dim``
    channels turn, at the frequencies of a head that wide; the others pass
    (an angle of zero: cosine one, sine zero).  With ``scaling`` the
    frequencies are its table's (a constant of the program, whatever the
    sequence length) and cosine and sine carry its factor."""
    turning = d if rotary_dim is None else rotary_dim
    if d % 2 or turning % 2 or not 0 < turning <= d:
        raise ValueError(f"rope turns adjacent pairs: the last axis ({d}) "
                         f"and rotary_dim ({rotary_dim}) must be even, "
                         "rotary_dim within the axis")
    # (d,): each frequency written twice, beside the pair it turns.
    if scaling is None:
        freqs = base ** (-(jnp.arange(d) // 2).astype(jnp.float32)
                         / (turning // 2))
    else:
        table = yarn_frequencies(base, turning // 2, scaling)[0]
        freqs = jnp.asarray(np.repeat(np.pad(
            table, (0, (d - turning) // 2)), 2), jnp.float32)
    if rotary_dim is not None:
        freqs = jnp.where(jnp.arange(d) < turning, freqs, 0.0)
    angles = positions[..., None].astype(jnp.float32) * freqs
    if shape is None:
        shape = angles.shape
    cos = jnp.cos(angles).reshape(shape)
    sin = jnp.sin(-angles if back else angles).reshape(shape)
    if scaling is not None:
        # On the channels that turn alone: the others pass as they are.
        factor = jnp.where(jnp.arange(d) < turning, scaling.magnitude, 1.0)
        cos, sin = cos * factor, sin * scaling.magnitude
    return cos, sin


def _turned(x, positions, base, seq_dim, rotary_dim, scaling, back: bool):
    """``x``'s adjacent pairs turned by their angles (``back``: by the
    negative angles), float32 inside, rounded once; every operand keeps
    ``x``'s last axis (:func:`rotary_tables`)."""
    d = x.shape[-1]
    shape = [1] * x.ndim
    if positions.ndim == 2:  # per-batch-row offsets (decode mode)
        shape[0] = positions.shape[0]
    shape[seq_dim] = x.shape[seq_dim]
    shape[-1] = d
    cos, sin = rotary_tables(positions, d, base, rotary_dim, scaling, back,
                             shape)
    swapped = jnp.einsum("...d,de->...e", x, _pair_swap(d, x.dtype),
                         precision=lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos + swapped * sin).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def rope(x, positions, base: float = 10000.0, seq_dim: int = -2,
         rotary_dim: Optional[int] = None,
         scaling: Optional[RopeScaling] = None):
    """Rotary position embedding, ADJACENT-pair formulation: component
    pairs ``(x[2i], x[2i+1])`` rotate by the i-th frequency, in float32,
    rounded once to ``x.dtype``.  (The [even half | odd half] pairing is the
    same function up to a fixed permutation of the q/k projections' output
    axis: another function of the same weights.)

    The tensor never gets a last axis other than ``head_dim``: ``out = x *
    cos + (x @ S) * sin`` with the ``(seq, head_dim)`` tables holding each
    frequency twice and ``S`` the constant signed permutation that swaps a
    pair (:func:`_pair_swap`; exact, one non-zero a column).  A ``(half, 2)``
    view of the pairs cannot keep 2 in the TPU's lanes: the compiler then
    moves the sequence there and back with whole-tensor copies and pads
    around the flash kernels — on the chip 14 ms of a 213 ms step at 32 heads
    of 128 on 8,192 rows, and a 137.4 ms step 6.9 ms shorter without them at
    4 x 2,048 tokens and 16 heads of 64 (my chip runs, PR 42; PERF.md
    section 6).  The backward is the rotation by the
    negative angles, written out; the function is linear, and the positions
    are all it keeps.

    ``positions``: (seq,) global token positions — global, so
    sequence-sharded shards stay consistent — or (batch, seq) when every
    batch row sits at a different offset (the serving plane's continuous
    decode batch, where slot b's next token lives at its own cache
    length).  ``seq_dim`` names the sequence axis of ``x`` (-2 for
    (b, h, s, d), 1 for (b, s, h, d)).  ``rotary_dim``: a partial rotation —
    the first ``rotary_dim`` channels of the last axis turn as a head of that
    width would, the rest pass unchanged; the tensor keeps its whole last axis
    all the same (the channels that pass meet a cosine of one and a sine of
    zero), so nothing is sliced or joined.  ``None``: all of it.
    ``scaling``: a :class:`RopeScaling` in place of ``base``'s plain
    frequencies — a table computed at trace time, its attention factor on
    cosine and sine, forward and backward; ``None``: ``base`` alone, the
    program it was before there was one."""
    return _turned(x, positions, base, seq_dim, rotary_dim, scaling,
                   back=False)


def _rope_fwd(x, positions, base, seq_dim, rotary_dim, scaling):
    return _turned(x, positions, base, seq_dim, rotary_dim, scaling,
                   back=False), positions


def _rope_bwd(base, seq_dim, rotary_dim, scaling, positions, d_out):
    return _turned(d_out, positions, base, seq_dim, rotary_dim, scaling,
                   back=True), None


rope.defvjp(_rope_fwd, _rope_bwd)


@jax.custom_vjp
def _cotangent_written_out(x):
    """``x``; its cotangent passes a ``lax.optimization_barrier``, so the
    backward pass of what reads ``x`` ends in a written tensor and is not
    fused into the operands of the products that read that cotangent.
    Forward it is nothing."""
    return x


_cotangent_written_out.defvjp(
    lambda x: (x, None),
    lambda _, d_x: (lax.optimization_barrier(d_x),))


class DecodeContext(NamedTuple):
    """Per-step context for cached (KV) decode — the serving plane's
    iteration-level hook (docs/inference.md).

    ``k``/``v``: ``(n_layers, batch, heads, ctx_len, head_dim)`` — every
    layer's cached keys/values (post-rope, as the layers wrote them),
    gathered by the caller (the serving engine gathers its block-pool
    pages; a simple driver can pass a contiguous cache).  ``mask``:
    ``(batch, ctx_len)`` bool — which context positions are valid for
    each batch row (rows at different lengths share one padded buffer).
    ``positions``: ``(batch, new_len)`` int32 — the global positions of
    the new tokens per row (= the row's cache length + arange).
    """

    k: Any
    v: Any
    mask: Any
    positions: Any

    def layer(self, i: int):
        return self.k[i], self.v[i], self.mask, self.positions


def _decode_attention(q, k, v, mask, sm_scale):
    """Masked attention for the decode path: ``q`` (b, h, s, hd) against
    ``k``/``v`` (b, h, S, hd) under ``mask`` (b, s, S).  Plain einsum —
    decode steps are a handful of query rows, so a fused kernel would buy
    nothing — with float32 softmax internals regardless of storage dtype.
    Every query row attends at least to itself (the caller's mask always
    admits the within-chunk diagonal), so the softmax is never empty."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale
    logits = jnp.where(mask[:, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w,
                      v.astype(jnp.float32)).astype(q.dtype)



class MoEConfig(NamedTuple):
    """A sparse-expert MLP in place of the dense one (``TransformerLM(moe=)``).

    ``num_experts`` experts of width ``expert_width`` (gated: ``down(silu(gate
    x) * up x)``), ``experts_per_token`` of them per token, weighted by the
    router's float32 softmax over ALL experts as it stands (not renormalised
    over the chosen ones), no shared expert, no capacity factor.

    ``expert_shard=(i, n)``: this process holds experts ``[i*E/n, (i+1)*E/n)``
    and computes their part of the layer for the tokens routed to them — the
    local middle of an expert-parallel layer, whose two all-to-alls are the
    caller's; ``(0, 1)`` is the whole layer.  The router keeps its full width
    on every shard, and the n shards' outputs sum to the whole layer's.

    ``row_bound``: rows the sorted buffer holds, as a multiple of the
    ``tokens * experts_per_token / n`` a balanced router sends this shard
    (rounded up to 512 rows, at most every pair).  ``None``: every pair, so
    nothing can fall outside.  Rows over a bound are not computed and are
    COUNTED (``rows_over_bound`` in the ``intermediates`` collection): a
    caller that sets a bound checks that count.

    The fields after ``row_bound`` default to the layer above; set, they give
    the latent sparse-expert layer of Nemotron-3:

    * ``scoring="sigmoid"``: the scores are ``sigmoid`` of the router's
      float32 logits, each expert's its own.  The ``experts_per_token``
      largest of ``score + bias`` are chosen, ``bias`` being the
      ``selection_bias`` of the ``buffers`` collection where the caller
      passes one (a balance bias that is no parameter and takes no gradient;
      absent, zero); the weights are the chosen scores themselves.
    * ``renormalize``: the weights divided by their sum over the chosen;
      ``weight_scale``: then multiplied by it.
    * ``expert_act="relu2"``: experts ``down(relu(up x)**2)``, no gate.
    * ``latent_width``: the experts work in that width, between a projection
      down before the dispatch and one up after the combine, whole on every
      shard.
    * ``shared_width``: one more expert of that width (same activation, gated
      where the routed ones are; not routed, not weighted) on the layer's own
      input, whole on every shard; its output is added.
    * ``n_group``, ``topk_group`` (sigmoid scoring): the experts lie in
      ``n_group`` consecutive groups; a group's score is the sum of its two
      largest ``score + bias``, the ``topk_group`` best groups stay, and the
      ``experts_per_token`` largest ``score + bias`` among THEIR experts are
      chosen (DeepSeek-V3's group-limited choice, arXiv:2412.19437).  One
      group: a plain top-k, the program above.
    * ``shared_output_gate`` (with ``shared_width``): the shared expert's
      OUTPUT times ``sigmoid(x w_sg)``, one scalar a token from a ``d x 1``
      projection of the layer's input (Qwen3-Next; ``shared_gate`` is already
      the name of the shared expert's own SwiGLU gate projection)."""

    num_experts: int
    experts_per_token: int
    expert_width: int
    expert_shard: Tuple[int, int] = (0, 1)
    row_bound: Optional[float] = None
    scoring: str = "softmax"
    renormalize: bool = False
    weight_scale: float = 1.0
    expert_act: str = "gated_silu"
    latent_width: Optional[int] = None
    shared_width: Optional[int] = None
    n_group: int = 1
    topk_group: int = 1
    shared_output_gate: bool = False

    def buffer_rows(self, tokens: int) -> int:
        """Rows of the sorted buffer for ``tokens`` tokens."""
        pairs = tokens * self.experts_per_token
        if self.row_bound is None:
            return pairs
        balanced = self.row_bound * pairs / self.expert_shard[1]
        return min(pairs, -(-int(balanced) // 512) * 512)


def _kept_groups(scores, n_group: int, topk_group: int):
    """(``scores`` (tokens, experts) with every expert outside the token's
    ``topk_group`` best of ``n_group`` consecutive groups at -inf, those
    groups (tokens, topk_group)).  A group's score is the sum of its two
    largest; which groups stay takes no gradient."""
    grouped = scores.reshape(scores.shape[0], n_group, -1)
    best = lax.top_k(lax.stop_gradient(grouped), min(2, grouped.shape[-1]))[0]
    groups = lax.top_k(best.sum(-1), topk_group)[1]
    kept = (groups[..., None] == jnp.arange(n_group)).any(axis=-2)
    return jnp.where(kept[..., None], grouped, -jnp.inf).reshape(
        scores.shape), groups


class SparseExperts(nn.Module):
    """Router, dispatch, grouped expert matmuls and combine of one layer
    (``MoEConfig``), each under a ``jax.named_scope`` a trace can read.

    Rows travel by walking the smaller side (``ops/moe.py``): the (token,
    choice) pairs are sorted by expert, this shard's first; a token's row is
    gathered into every buffer row that holds one of its pairs; the experts'
    rows come back summed, weighted, per token — gathered through each pair's
    place in the sorted order (no scatter in the layer's program or in its
    gradient's; from a buffer of ``ops.moe.HELD_PAIRS_BUFFER_BYTES`` on, on
    a TPU, by the kernel ``hvd_moe_pair_rows``, which fetches the valid
    pairs' rows alone) or, from ``ops.moe.ROW_WALK_PAIRS_PER_ROW`` pairs a
    buffer row on, scatter-added through each row's token (rows wider than
    ``ops.moe.WHOLE_ROW_WIDTH`` a slab of columns at a time).  Pairs held
    elsewhere or cut off by a ``row_bound`` contribute exactly zero, forward
    and backward.

    Writes, where the caller makes the collection mutable: ``router`` —
    ``choices`` (pairs per expert, all experts), ``prob_sum`` (with sigmoid
    scoring the scores'), ``z_sum``, ``tokens``: what :func:`router_losses`
    reads; ``intermediates`` — ``chosen_experts`` (tokens, k), ``rows_per_
    local_expert``, ``rows_over_bound``, ``rows_walked`` (by one pass back:
    every pair's for ``pairs``, at most the buffer's otherwise), ``way_back``
    (the form the pass takes in this process, ``ops.moe.pass_back``'s, an
    index into ``ops.moe.WAYS_BACK``: one of four),
    ``experts_kernel`` (the kernel the grouped products took, an index into
    ``ops.moe.GROUPED_KERNELS``), and with more than one group
    ``groups_chosen`` (tokens, topk_group).

    Two more scopes where the configuration asks for them: ``hvd_moe_latent``
    (both projections around the experts' width) and ``hvd_moe_shared``."""

    config: MoEConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        shard, n_shards = cfg.expert_shard
        if cfg.num_experts % n_shards or not 0 <= shard < n_shards:
            raise ValueError(f"expert_shard {cfg.expert_shard} does not "
                             f"divide {cfg.num_experts} experts")
        local = cfg.num_experts // n_shards
        d, k = x.shape[-1], cfg.experts_per_token
        flat = x.reshape(-1, d).astype(self.dtype)
        tokens = flat.shape[0]
        bound = cfg.buffer_rows(tokens)

        if cfg.scoring not in ("softmax", "sigmoid") \
                or cfg.expert_act not in ("gated_silu", "relu2"):
            raise ValueError(f"unknown scoring or expert_act in {cfg}")
        if cfg.shared_output_gate and not cfg.shared_width:
            raise ValueError("shared_output_gate gates a shared expert: it "
                             "wants shared_width")
        if cfg.n_group > 1 and (cfg.scoring != "sigmoid"
                                or cfg.num_experts % cfg.n_group
                                or not 0 < cfg.topk_group <= cfg.n_group):
            raise ValueError(f"n_group {cfg.n_group} wants sigmoid scoring, "
                             f"to divide {cfg.num_experts} experts and at "
                             f"most as many groups kept, not {cfg}")
        gated = cfg.expert_act == "gated_silu"
        act = nn.silu if gated else (lambda t: jnp.square(nn.relu(t)))
        inner = cfg.latent_width or d

        w_router = self.param("router_kernel", nn.initializers.lecun_normal(),
                              (d, cfg.num_experts), jnp.float32)
        fan_in = nn.initializers.lecun_normal(in_axis=1, out_axis=2,
                                              batch_axis=0)
        if gated:
            w_gate = self.param("gate_kernel", fan_in,
                                (local, inner, cfg.expert_width), jnp.float32)
        w_up = self.param("up_kernel", fan_in,
                          (local, inner, cfg.expert_width), jnp.float32)
        w_down = self.param("down_kernel", fan_in,
                            (local, cfg.expert_width, inner), jnp.float32)

        with jax.named_scope("hvd_moe_router"):
            logits = jnp.dot(flat, w_router.astype(self.dtype),
                             preferred_element_type=jnp.float32)
            if cfg.scoring == "softmax":
                probs = jax.nn.softmax(logits, axis=-1)
                weight, expert = top_choices(probs, k)
            else:
                probs = jax.nn.sigmoid(logits)
                bias = self.get_variable("buffers", "selection_bias") \
                    if self.has_variable("buffers", "selection_bias") else None
                biased = probs if bias is None else probs + bias
                if cfg.n_group > 1:
                    biased, groups = _kept_groups(biased, cfg.n_group,
                                                  cfg.topk_group)
                    self.sow("intermediates", "groups_chosen", groups)
                weight, expert = top_choices(biased, k)
                if bias is not None:
                    # The chosen experts' own bias by a compare and a sum: the
                    # TPU gathers scalars one by one (0.6 ms a layer for
                    # 90,112 of them: my chip runs, PR 30).
                    chosen = expert[..., None] == jnp.arange(cfg.num_experts)
                    weight = weight - jnp.where(chosen, bias, 0.0).sum(-1)
            if cfg.renormalize:
                weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-20)
            if cfg.weight_scale != 1.0:
                weight = weight * cfg.weight_scale
            self.sow("intermediates", "chosen_experts", expert)
            choices = (expert[..., None] == jnp.arange(cfg.num_experts)
                       ).sum(axis=(0, 1), dtype=jnp.float32)
            self.sow("router", "choices", choices)
            self.sow("router", "prob_sum", probs.sum(axis=0))
            self.sow("router", "z_sum",
                     jnp.square(jax.nn.logsumexp(logits, axis=-1)).sum())
            self.sow("router", "tokens", jnp.float32(tokens))
        narrow = flat
        if cfg.latent_width:
            with jax.named_scope("hvd_moe_latent"):
                narrow = nn.Dense(inner, use_bias=False, dtype=self.dtype,
                                  name="latent_down")(flat)
        with jax.named_scope("hvd_moe_dispatch"):
            sent = dispatch_rows(expert, shard * local, local, bound)
            rows = token_rows_to_buffer(narrow, sent)
            self.sow("intermediates", "rows_per_local_expert",
                     sent.rows_per_expert)
            self.sow("intermediates", "rows_over_bound",
                     sent.rows_over_bound)
            back = pass_back(rows, sent)
            self.sow("intermediates", "rows_walked", sent.rows_walked(back))
            self.sow("intermediates", "way_back", WAYS_BACK.index(back))
        with jax.named_scope("hvd_moe_experts"):
            sizes = sent.group_sizes
            self.sow("intermediates", "experts_kernel",
                     GROUPED_KERNELS.index(product_kernel(rows, w_up)))
            if gated:
                gate = grouped_matmul(rows, w_gate.astype(self.dtype), sizes)
                up = grouped_matmul(rows, w_up.astype(self.dtype), sizes)
                hidden = act(gate) * up
            else:
                hidden = act(grouped_matmul(rows, w_up.astype(self.dtype),
                                            sizes))
            out = grouped_matmul(hidden, w_down.astype(self.dtype), sizes)
        with jax.named_scope("hvd_moe_combine"):
            mixed = buffer_rows_to_tokens(out, weight, sent)
        if cfg.latent_width:
            with jax.named_scope("hvd_moe_latent"):
                mixed = nn.Dense(d, use_bias=False, dtype=self.dtype,
                                 name="latent_up")(mixed)
        if cfg.shared_width:
            with jax.named_scope("hvd_moe_shared"):
                def wide(name):
                    return nn.Dense(cfg.shared_width, use_bias=False,
                                    dtype=self.dtype, name=name)(flat)

                shared = act(wide("shared_gate")) * wide("shared_up") \
                    if gated else act(wide("shared_up"))
                shared = nn.Dense(d, use_bias=False, dtype=self.dtype,
                                  name="shared_down")(shared)
                if cfg.shared_output_gate:
                    w_sg = self.param(
                        "shared_output_gate_kernel",
                        nn.initializers.lecun_normal(), (d, 1), jnp.float32)
                    shared = (shared * nn.sigmoid(jnp.dot(
                        flat, w_sg.astype(self.dtype),
                        preferred_element_type=jnp.float32))
                              ).astype(self.dtype)
                mixed = mixed + shared
        return mixed.reshape(x.shape)


# What a selecting layer counts, by the names it sows behind ``dsa_``.
SELECTION_COUNTS = Selection._fields[1:]


class IndexerConfig(NamedTuple):
    """A learned selection of keys in front of an attention layer (the
    indexer of DeepSeek sparse attention): ``heads`` query heads of
    ``head_dim`` on ONE key head score every earlier key, and each query
    keeps its ``topk`` best (``Attention(indexer=)``)."""

    heads: int
    head_dim: int
    topk: int


class Attention(nn.Module):
    """Causal self-attention of one layer: the q/k/v projections (with the
    q/k norm where asked for) under the scope ``hvd_attn_qkv``, each rotation
    (:func:`rope`: q's, and k's — a grouped layer's in front of its repeat)
    under ``hvd_attn_rotate``, the attention itself (flash, blockwise, ring
    or cached decode) under ``hvd_attn_attend``, the output gate's projection
    and its sigmoid-multiply, where there is one, under ``hvd_attn_gate``,
    the output projection under ``hvd_attn_out`` — names a trace can read,
    forward and backward."""

    n_heads: int
    dtype: Any = jnp.bfloat16
    seq_axis: Optional[str] = None
    use_flash: bool = True
    # Under sequence parallelism: how K/V shards travel the ring —
    # "ppermute" (XLA collective permute), "rdma", or "fused" (rotation
    # DMA inside the flash kernel; ops/ring_flash.py).
    ring_impl: str = "ppermute"
    # Sow each layer's (post-rope) K/V into the "intermediates"
    # collection: the sharded ring-prefill path reads them back to fill
    # the serving plane's KV cache (serving/prefill.py).
    capture_kv: bool = False
    # RMSNorm with a learned scale over the WHOLE d_model-wide q and k
    # projections, before the split into heads and the rotation (OLMoE).
    qk_norm: bool = False
    norm_eps: float = 1e-6
    # Grouped-query attention: ``n_kv_heads`` key/value heads, query head j
    # reading key/value head ``j // (n_heads / n_kv_heads)``.  None: as many
    # as query heads, in the fused ``qkv_kernel`` above.
    n_kv_heads: Optional[int] = None
    # False: no rotary embedding (a model whose other layers carry position).
    rope: bool = True
    # The rotary base (:func:`rope`'s), and its scaled frequencies
    # (:class:`RopeScaling`; ``None``: the base's own).
    rope_theta: float = 10000.0
    rope_scaling: Optional[RopeScaling] = None
    # A partial rotation (:func:`rope`'s ``rotary_dim``): the first
    # ``rotary_dim`` channels of a head turn, the rest pass.  ``None``: the
    # whole head.  Training only: no ring, no cached decode.
    rotary_dim: Optional[int] = None
    # ``(i, n)``: this process holds query heads ``[i H/n, (i+1) H/n)`` and
    # the key/value heads they read (a key/value head that several shards'
    # queries read is held by each of them) — the local part of a layer that
    # is tensor-parallel over ``n`` chips, whose ``n`` outputs sum to the
    # whole layer's; the sum is the caller's.  ``(0, 1)``: the whole layer.
    head_shard: Tuple[int, int] = (0, 1)
    # The mapped mesh axis the ``n`` head shards lie over, where the caller
    # runs them side by side (``shard_map``): what crosses heads inside the
    # layer is then summed over it — ``qk_norm``'s mean square, one float a
    # token for q and one for k, so that the statistic is the WHOLE
    # projection's.  None (one shard alone, as a one-chip share of such a
    # layer runs): the statistic is over the heads held, the layer without
    # its exchange.
    head_shard_axis: Optional[str] = None
    # A head's width where it is not ``d_model // n_heads`` (the projections
    # are then ``n_heads * head_dim`` wide, not ``d_model``).
    head_dim: Optional[int] = None
    # The softmax's scale, ``softmax(sm_scale q k^T)``, where it is not
    # ``head_dim ** -0.5`` (a muP model's ``attention_multiplier``).  On one
    # sequence shard: the ring has its own.
    sm_scale: Optional[float] = None
    # A sliding window: query ``t`` sees the keys ``s`` with ``0 <= t - s <
    # window`` (:func:`~horovod_tpu.ops.flash_attention`, whose kernels
    # neither compute nor fetch a block wholly outside that band).  A layer
    # with one sows ``attn_blocks_visited`` and ``attn_blocks_causal`` into
    # ``intermediates``: the (query block, key block) pairs a head's forward
    # kernel visits, and what the causal kernel would under the same blocks
    # (:func:`~horovod_tpu.ops.attention.mask_blocks`; both the scan's
    # every-block count where the shape leaves the kernels).  Training only:
    # no ring, no cached decode.
    window: Optional[int] = None
    # RMSNorm over EACH head's ``head_dim`` of q and of k, one learned
    # ``head_dim``-wide scale for q and one for k that every head shares,
    # before the rotation (beside ``qk_norm``, which norms the whole
    # projection at once).
    head_norm: bool = False
    # An output gate: ``concat_h(o_h) * sigmoid(x W_g)`` elementwise, ``W_g``
    # as wide as the query projection, before the output projection
    # (:func:`_output_gated`, whose backward is written out).
    gate: bool = False
    # Block diffusion's training pass: the rows are ``[clean; noised]``, two
    # copies of ``seq / 2`` positions each; row ``i`` turns at position ``i
    # mod (seq / 2)`` and sees what the block mask of this block length shows
    # it (:func:`~horovod_tpu.ops.flash_attention`'s ``block_diffusion``, in
    # place of the causal mask).  The layer sows ``attn_blocks_visited`` and
    # ``attn_blocks_causal`` as a windowed one does, the second what a causal
    # kernel would visit over all ``seq`` rows.  Training only.
    block_diffusion: Optional[int] = None
    # A learned selection (:class:`IndexerConfig`): on the layer's input with
    # its gradient stopped, ``q_I = rope(x W_qI)`` as ``heads`` heads, ``k_I =
    # rope(LayerNorm(x W_kI))`` one head, ``w = x W_w / sqrt(heads
    # head_dim)``; the score ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] .
    # k_I[s])`` (scope ``hvd_dsa_index``); query ``t`` sees the ``min(t + 1,
    # topk)`` earlier keys that score highest, the same for every head (scope
    # ``hvd_dsa_select``; the flash kernels take the selection as their
    # mask's operand, :class:`~horovod_tpu.ops.attention.Selected`).  The
    # layer sows ``dsa_kl`` into ``intermediates``: ``mean_t KL(p[t] ||
    # softmax over the selection of I[t])``, ``p`` the attention's own
    # probabilities meaned over the heads, gradient stopped (scope
    # ``hvd_dsa_kl``) — the indexer's loss, which reaches the indexer's four
    # parameters and no other, as the attention's output reaches every other
    # and none of those (:func:`indexer_loss` sums the layers').  It also
    # sows ``dsa_keys_selected``, ``dsa_keys_causal``, ``dsa_threshold_ties``,
    # ``dsa_tiles_live`` and ``dsa_tiles_causal``
    # (:func:`record_attention_selection`), and the selection itself,
    # ``dsa_selection``, ``int8[batch, seq, seq]``.  A sequence of at most ``topk``
    # positions selects nothing: the layer is the causal one to the last bit,
    # ``dsa_kl`` is 0 and the indexer takes no gradient.  Training on the
    # flash kernels only: no ring, no cached decode, no window.
    indexer: Optional[IndexerConfig] = None

    def _indexed(self, x, positions):
        """(q_I, k_I, w) of the indexer on ``x`` (b, seq, d), gradient
        stopped: (b, heads, seq, e) and (b, seq, e) in the compute type, (b,
        seq, heads) float32."""
        heads, e, _ = self.indexer
        d = x.shape[-1]
        w_q = self.param(
            "index_q_kernel", nn.initializers.lecun_normal(
                in_axis=0, out_axis=(1, 2)), (d, heads, e), jnp.float32)
        w_k = self.param("index_k_kernel", nn.initializers.lecun_normal(),
                         (d, e), jnp.float32)
        scale = self.param("index_k_norm_scale", nn.initializers.ones, (e,),
                           jnp.float32)
        bias = self.param("index_k_norm_bias", nn.initializers.zeros, (e,),
                          jnp.float32)
        w_w = self.param("index_w_kernel", nn.initializers.lecun_normal(),
                         (d, heads), jnp.float32)
        x = lax.stop_gradient(x).astype(self.dtype)
        q = jnp.einsum("bsd,dhe->bhse", x, w_q.astype(self.dtype))
        k = jnp.einsum("bsd,de->bse", x, w_k.astype(self.dtype),
                       preferred_element_type=jnp.float32)
        k = k - k.mean(axis=-1, keepdims=True)
        k = (k * lax.rsqrt(jnp.mean(jnp.square(k), axis=-1, keepdims=True)
                           + self.norm_eps) * scale + bias).astype(self.dtype)
        w = jnp.einsum("bsd,dh->bsh", x, w_w.astype(self.dtype),
                       preferred_element_type=jnp.float32) \
            * (heads ** -0.5 * e ** -0.5)
        turn = functools.partial(rope, positions=positions,
                                 base=self.rope_theta, seq_dim=-2,
                                 scaling=self.rope_scaling)
        return turn(q), turn(k), w

    def _grouped_projections(self, x, head_dim, rotate, positions, q_factor):
        """(q, k, v), each (b, local query heads, seq, head_dim), from a
        ``q_kernel`` and a ``kv_kernel`` of this shard's heads; a key/value
        head is normed and turned (``rotate``) once and then repeated for
        every query head that reads it (the repeat's transpose sums their
        cotangents before the inverse rotation).  q is returned unturned —
        or, with ``positions`` (the rows', where `__call__` takes q's
        per-head norm and its turn as one pass:
        :func:`~horovod_tpu.ops.attn_prep.normed_and_turned`), turned as k
        is and times ``q_factor``, a power of two that rides q's norm scale
        through the pass."""
        shard, n_shards = self.head_shard
        kv_heads = self.n_kv_heads or self.n_heads
        if self.n_heads % kv_heads or self.n_heads % n_shards \
                or not 0 <= shard < n_shards \
                or (kv_heads % n_shards and n_shards % kv_heads):
            raise ValueError(
                f"head_shard {self.head_shard} does not divide "
                f"{self.n_heads} query heads over {kv_heads} key/value heads")
        heads = self.n_heads // n_shards
        kv_local = max(1, kv_heads // n_shards)
        d = x.shape[-1]
        w_q = self.param(
            "q_kernel", nn.initializers.lecun_normal(in_axis=0,
                                                     out_axis=(1, 2)),
            (d, heads, head_dim), jnp.float32)
        w_kv = self.param(
            "kv_kernel",
            nn.initializers.lecun_normal(in_axis=0, out_axis=(1, 2, 3)),
            (d, 2, kv_local, head_dim), jnp.float32)
        x = x.astype(self.dtype)
        q = jnp.einsum("bsd,dhe->bhse", x, w_q.astype(self.dtype))
        k, v = jnp.einsum("bsd,djhe->jbhse", x, w_kv.astype(self.dtype))
        if positions is not None:
            with jax.named_scope("hvd_attn_rotate"):
                q_scale = self.param("q_head_norm_scale",
                                     nn.initializers.ones, (head_dim,),
                                     jnp.float32)
                q = normed_and_turned(
                    q, q_factor * q_scale, *rotary_tables(
                        positions, head_dim, self.rope_theta,
                        scaling=self.rope_scaling), self.norm_eps)
        elif self.head_norm:
            q = self._head_norm("q_head_norm_scale", q)
        if self.head_norm:
            k = self._head_norm("k_head_norm_scale", k)
        return (q, jnp.repeat(rotate(k), heads // kv_local, axis=1),
                jnp.repeat(v, heads // kv_local, axis=1))

    @nn.compact
    def __call__(self, x, decode_ctx=None):
        b, s, d = x.shape
        head_dim = self.head_dim or d // self.n_heads
        n_heads = self.n_heads // self.head_shard[1]
        if self.sm_scale is not None and self.seq_axis is not None:
            raise ValueError("sm_scale= does not compose with sequence "
                             "parallelism")
        if (self.window is not None or self.block_diffusion is not None
                or self.rotary_dim is not None) \
                and (decode_ctx is not None or self.seq_axis is not None):
            raise ValueError("window=, block_diffusion= and rotary_dim= "
                             "compose with neither decode_ctx= nor sequence "
                             "parallelism")
        if self.block_diffusion is not None and (self.window is not None
                                                 or s % 2):
            raise ValueError("block_diffusion= is a mask of its own over an "
                             "even number of rows, [clean; noised]: it takes "
                             "no window=")
        if self.indexer is not None and (
                decode_ctx is not None or self.seq_axis is not None
                or self.window is not None or self.block_diffusion is not None
                or not self.use_flash):
            raise ValueError("indexer= selects keys for the causal flash "
                             "kernels: it composes with neither decode_ctx=, "
                             "sequence parallelism, window= nor "
                             "block_diffusion=")
        if decode_ctx is not None:
            k_ctx, v_ctx, ctx_mask, positions = decode_ctx
        elif self.seq_axis is not None:
            positions = lax.axis_index(self.seq_axis) * s + jnp.arange(s)
        elif self.block_diffusion is not None:
            # A copy's row i stands at position i of the sequence.
            positions = jnp.arange(s) % (s // 2)
        else:
            positions = jnp.arange(s)

        def rotate(t):
            if not self.rope:
                return t
            with jax.named_scope("hvd_attn_rotate"):
                return rope(t, positions, self.rope_theta, -2,
                            self.rotary_dim, self.rope_scaling)

        grouped = self.n_kv_heads is not None or self.head_shard != (0, 1)
        # A grouped layer's per-head norm of q and q's rotation are one pass
        # each way where the pass pays (`ops.attn_prep.prep_rows`: heads of
        # 128 turned whole, on whole sequences at the rows' positions); the
        # key heads, the cached decode, the ring and the other widths keep
        # the composition.
        prepared = grouped and self.head_norm and not self.qk_norm \
            and prep_rows(s, head_dim, self.rotary_dim, self.rope,
                          decode_ctx is None and self.seq_axis is None) \
            is not None
        # The kernels scale q by the power of two in ``head_dim ** -0.5`` on
        # its way in, a pass of its own behind a custom call: the one pass
        # takes it along, exactly, and the kernels are told what is left.
        own_scale = head_dim ** -0.5 if self.sm_scale is None \
            else self.sm_scale
        q_factor, sm_scale = _split_scale(own_scale) if prepared \
            else (1.0, self.sm_scale)
        with jax.named_scope("hvd_attn_qkv"):
            if grouped:
                q, k, v = self._grouped_projections(
                    x, head_dim, rotate, positions if prepared else None,
                    q_factor)
            else:
                # One fused qkv projection whose einsum emits q/k/v
                # *head-major* ('jbhse'), and the output projection's einsum
                # reads the kernels' output head-major: no transpose is
                # written between them and the attention kernel.  The
                # compiler writes some all the same: it keeps this einsum's
                # output sequence-minor and transposes q, k, v, dq, dk and
                # dO to the kernels' head-minor operands — seven layout
                # copies a layer, 5.4 ms of a 130 ms step at 4 x 2,048 and
                # head 64 on the chip (PERF.md section 6, PR 42).
                # Per-matrix fan-in init matches separate q/k/v Dense
                # layers (fan_in = d).
                w_qkv = self.param(
                    "qkv_kernel",
                    nn.initializers.lecun_normal(in_axis=0,
                                                 out_axis=(1, 2, 3)),
                    (d, 3, self.n_heads, head_dim), jnp.float32)
                # (b, heads, seq, head_dim) each; custom VJP avoids the
                # activation-sized cotangent stack the sliced einsum would
                # build.
                q, k, v = _qkv_project(x.astype(self.dtype),
                                       w_qkv.astype(self.dtype))
            if self.qk_norm:
                q = self._projection_norm("q_norm_scale", q)
                k = self._projection_norm("k_norm_scale", k)
            if self.head_norm and not grouped:
                q = self._head_norm("q_head_norm_scale", q)
                k = self._head_norm("k_head_norm_scale", k)

        new_kv = chosen = None
        if self.indexer is not None:
            with jax.named_scope("hvd_dsa_index"):
                indexed = self._indexed(x, positions)
                scores = index_scores(*indexed) \
                    if s > self.indexer.topk else None
            if scores is not None:
                with jax.named_scope("hvd_dsa_select"):
                    chosen = select(scores, self.indexer.topk)
        with jax.named_scope("hvd_attn_attend"):
            if not prepared:
                q = rotate(q)
            if not grouped:
                k = rotate(k)
            if chosen is not None:
                out, lse = masked_flash_attention(
                    q, k, v, Selected(self.indexer.topk), chosen.chosen,
                    sm_scale=sm_scale)
            elif decode_ctx is not None:
                ctx_len = k_ctx.shape[-2]
                # Context keys all precede the new chunk; within the chunk
                # positions are consecutive, so causality is a lower
                # triangle.
                mask = jnp.concatenate([
                    jnp.broadcast_to(ctx_mask[:, None, :], (b, s, ctx_len)),
                    jnp.broadcast_to(jnp.tril(jnp.ones((s, s), bool))[None],
                                     (b, s, s)),
                ], axis=-1)
                keys = jnp.concatenate([k_ctx.astype(k.dtype), k], axis=-2)
                vals = jnp.concatenate([v_ctx.astype(v.dtype), v], axis=-2)
                out = _decode_attention(q, keys, vals, mask, own_scale)
                new_kv = (k, v)
            elif self.seq_axis is not None:
                if self.capture_kv:
                    self.sow("intermediates", "kv", (k, v))
                out = ring_attention(q, k, v, axis_name=self.seq_axis,
                                     causal=True, rotate_impl=self.ring_impl)
            else:
                diffusion = self.block_diffusion
                if self.capture_kv:
                    self.sow("intermediates", "kv", (k, v))
                masks = dict(causal=True, window=self.window) \
                    if diffusion is None else dict(block_diffusion=diffusion)
                attend = flash_attention if self.use_flash \
                    else blockwise_attention
                out = attend(q, k, v, sm_scale=sm_scale, **masks)
                if self.window is not None or diffusion is not None:
                    blocks = mask_blocks(s, head_dim, **masks) \
                        if self.use_flash else None
                    every = -(-s // min(512, s))   # the scan: every block
                    visited, causal = blocks or (every, every)
                    self.sow("intermediates", "attn_blocks_visited",
                             jnp.int32(visited))
                    self.sow("intermediates", "attn_blocks_causal",
                             jnp.int32(causal))
        if self.indexer is not None:
            kl = jnp.float32(0.0)
            if chosen is not None:
                with jax.named_scope("hvd_dsa_kl"):
                    kl = indexer_kl(*indexed, scores, chosen.chosen,
                                    head_probs(q, k, lse, chosen.chosen,
                                               sm_scale))
                for name, count in zip(SELECTION_COUNTS, chosen[1:]):
                    self.sow("intermediates", "dsa_" + name, count)
                self.sow("intermediates", "dsa_selection", chosen.chosen)
            self.sow("intermediates", "dsa_kl", kl)
        if self.gate:
            w_g = self.param(
                "gate_kernel", nn.initializers.lecun_normal(
                    in_axis=0, out_axis=(1, 2)),
                (d, n_heads, head_dim), jnp.float32)
            with jax.named_scope("hvd_attn_gate"):
                out = _output_gated(out, x.astype(self.dtype),
                                    w_g.astype(self.dtype))
        w_o = self.param(
            "o_kernel",
            nn.initializers.lecun_normal(in_axis=(0, 1), out_axis=2),
            (n_heads, head_dim, d), jnp.float32)
        with jax.named_scope("hvd_attn_out"):
            proj = jnp.einsum("bhse,hed->bsd", out, w_o.astype(self.dtype))
        return proj if new_kv is None else (proj, new_kv)

    def _head_norm(self, name, t):
        """RMSNorm over the last axis of ``t`` (b, heads, seq, head_dim),
        float32 inside; ONE (head_dim,) scale for all heads."""
        scale = self.param(name, nn.initializers.ones, (t.shape[3],),
                           jnp.float32)
        # With the rotation's backward a product, XLA fused this norm's
        # last backward step into the operand of q's weight-gradient and dx
        # products: 5.86 -> 14.68 ms a step in the SDAR cell (my chip runs,
        # PR 42; PERF.md section 6).
        wide = _cotangent_written_out(t).astype(jnp.float32)
        mean_sq = jnp.mean(jnp.square(wide), axis=-1, keepdims=True)
        return (wide * lax.rsqrt(mean_sq + self.norm_eps)
                * scale).astype(t.dtype)

    def _projection_norm(self, name, t):
        """RMSNorm over heads and head_dim together of ``t`` (b, heads, seq,
        head_dim), float32 inside; the scale is (heads, head_dim).  Under
        ``head_shard_axis`` the sum of squares and the channel count are
        summed over that axis: every shard divides by the mean square of all
        the projection's channels."""
        scale = self.param(name, nn.initializers.ones,
                           (t.shape[1], t.shape[3]), jnp.float32)
        wide = t.astype(jnp.float32)
        if self.head_shard_axis is None:
            mean_sq = jnp.mean(jnp.square(wide), axis=(1, 3), keepdims=True)
        else:
            mean_sq = lax.psum(
                jnp.sum(jnp.square(wide), axis=(1, 3), keepdims=True),
                self.head_shard_axis) / lax.psum(
                    t.shape[1] * t.shape[3], self.head_shard_axis)
        return (wide * lax.rsqrt(mean_sq + self.norm_eps)
                * scale[:, None, :]).astype(t.dtype)


class LatentConfig(NamedTuple):
    """Sizes of a latent-attention layer (``TransformerLM(latent=)``;
    DeepSeek-V2's multi-head latent attention, arXiv:2405.04434): the
    key/value latent's ``kv_rank``, a head's non-rotary ``nope_dim`` and
    rotary ``rope_dim`` of query and key, its value's ``v_dim``, the rotary
    base; ``q_rank``, the QUERY latent's width (DeepSeek-V3's ``q_lora_rank``;
    unset, the query comes straight from the hidden state); ``gate``, whether
    the layer has its head-wise output gate (DeepSeek-V3's has none)."""

    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float = 10000.0
    q_rank: Optional[int] = None
    gate: bool = True


class LatentAttention(nn.Module):
    """Attention whose keys and values come from one low-rank latent a token,
    with a head-wise output gate where ``config.gate`` and the query through a
    latent of its own where ``config.q_rank``.  ``u`` the layer's normed
    input, H heads:

        [q_nope | q_rope]_h = u W_q                   nope_dim + rope_dim
          or, with q_rank:  c_q = RMSNorm(u W_qa),  [q_nope | q_rope]_h = c_q W_qb
        [c | k_rope] = u W_kva,  c = RMSNorm(c)       kv_rank + rope_dim
        [k_nope | v]_h = c W_kvb                      nope_dim + v_dim
        q_h = [q_nope | rope(q_rope)],  k_h = [k_nope | rope(k_rope)]
        o_h = causal softmax(q_h k_h^T (nope_dim + rope_dim)^-1/2) v_h
        out = concat_h(o_h * sigmoid(u W_g)_h) W_o    (no gate: concat_h(o_h) W_o)

    ONE ``k_rope`` a token serves every head; the rotation is over all of
    ``rope_dim`` (adjacent pairs, :func:`rope`).  Query and key are
    ``nope_dim + rope_dim`` wide and the value ``v_dim``: the flash kernels
    take the two widths as they are (``ops/attention.py``).

    ``head_shard=(i, n)``: heads ``[i H/n, (i+1) H/n)`` — their slices of
    ``W_q`` (``W_qb``), ``W_kvb``, ``W_g`` and ``W_o``; ``W_kva``, ``W_qa``
    and the two latents' norms are whole on every shard.  The ``n`` outputs
    sum to the whole layer's; the sum is the caller's.  Parameters:
    ``q_kernel`` (with a query latent ``q_a_kernel``, ``q_norm_scale``,
    ``q_b_kernel`` in its place), ``kv_a_kernel``, ``kv_norm_scale``,
    ``kv_b_kernel``, ``gate_kernel`` (where gated), ``o_kernel``.  Scopes:
    ``hvd_mla_q_latent`` (``W_qa`` and its norm; with a query latent only),
    ``hvd_mla_q_proj``, ``hvd_mla_kv_latent``, ``hvd_mla_attend``,
    ``hvd_mla_out_proj``."""

    n_heads: int
    config: LatentConfig
    dtype: Any = jnp.bfloat16
    use_flash: bool = True
    norm_eps: float = 1e-6
    head_shard: Tuple[int, int] = (0, 1)

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        shard, n_shards = self.head_shard
        if self.n_heads % n_shards or not 0 <= shard < n_shards:
            raise ValueError(f"head_shard {self.head_shard} does not divide "
                             f"{self.n_heads} heads")
        heads = self.n_heads // n_shards
        b, s, d = x.shape
        per_head = nn.initializers.lecun_normal(in_axis=0, out_axis=(1, 2))
        if cfg.q_rank is None:
            w_q = self.param("q_kernel", per_head,
                             (d, heads, cfg.nope_dim + cfg.rope_dim),
                             jnp.float32)
        else:
            w_qa = self.param("q_a_kernel", nn.initializers.lecun_normal(),
                              (d, cfg.q_rank), jnp.float32)
            q_scale = self.param("q_norm_scale", nn.initializers.ones,
                                 (cfg.q_rank,), jnp.float32)
            w_q = self.param("q_b_kernel", per_head,
                             (cfg.q_rank, heads, cfg.nope_dim + cfg.rope_dim),
                             jnp.float32)
        w_kva = self.param("kv_a_kernel", nn.initializers.lecun_normal(),
                           (d, cfg.kv_rank + cfg.rope_dim), jnp.float32)
        latent_scale = self.param("kv_norm_scale", nn.initializers.ones,
                                  (cfg.kv_rank,), jnp.float32)
        w_kvb = self.param("kv_b_kernel", per_head,
                           (cfg.kv_rank, heads, cfg.nope_dim + cfg.v_dim),
                           jnp.float32)
        if cfg.gate:
            w_gate = self.param("gate_kernel", nn.initializers.lecun_normal(),
                                (d, heads), jnp.float32)
        w_o = self.param(
            "o_kernel",
            nn.initializers.lecun_normal(in_axis=(0, 1), out_axis=2),
            (heads, cfg.v_dim, d), jnp.float32)
        x = x.astype(self.dtype)
        positions = jnp.arange(s)

        def turned(t):
            return rope(t, positions, cfg.rope_theta)

        def normed(latent, scale):
            wide = latent.astype(jnp.float32)
            mean_sq = jnp.mean(jnp.square(wide), axis=-1, keepdims=True)
            return (wide * lax.rsqrt(mean_sq + self.norm_eps)
                    * scale).astype(self.dtype)

        q_in = x
        if cfg.q_rank is not None:
            with jax.named_scope("hvd_mla_q_latent"):
                q_in = normed(jnp.dot(x, w_qa.astype(self.dtype)), q_scale)
        with jax.named_scope("hvd_mla_q_proj"):
            q = jnp.einsum("bsd,dhe->bhse", q_in, w_q.astype(self.dtype))
            q = jnp.concatenate([q[..., :cfg.nope_dim],
                                 turned(q[..., cfg.nope_dim:])], axis=-1)
        with jax.named_scope("hvd_mla_kv_latent"):
            latent, k_rope = jnp.split(
                jnp.dot(x, w_kva.astype(self.dtype)), [cfg.kv_rank], axis=-1)
            latent = normed(latent, latent_scale)
            kv = jnp.einsum("bsr,rhe->bhse", latent, w_kvb.astype(self.dtype))
            shared = jnp.broadcast_to(turned(k_rope[:, None]),
                                      (b, heads, s, cfg.rope_dim))
            k = jnp.concatenate([kv[..., :cfg.nope_dim], shared], axis=-1)
            v = kv[..., cfg.nope_dim:]
        with jax.named_scope("hvd_mla_attend"):
            out = flash_attention(q, k, v, causal=True) if self.use_flash \
                else blockwise_attention(q, k, v, causal=True)
        with jax.named_scope("hvd_mla_out_proj"):
            if cfg.gate:
                gate = nn.sigmoid(jnp.einsum(
                    "bsd,dh->bhs", x, w_gate.astype(self.dtype),
                    preferred_element_type=jnp.float32))
                out = (out * gate[..., None]).astype(self.dtype)
            return jnp.einsum("bhse,hed->bsd", out, w_o.astype(self.dtype))


class GatedMLP(nn.Module):
    """The dense gated MLP ``down(silu(gate x) * up x)`` of width ``d_ff``,
    no biases; under the scope ``hvd_mlp``, as :class:`Block`'s dense MLP."""

    d_ff: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        def wide(name):
            return nn.Dense(self.d_ff, use_bias=False, dtype=self.dtype,
                            name=name)(x)

        with jax.named_scope("hvd_mlp"):
            return nn.Dense(x.shape[-1], use_bias=False, dtype=self.dtype,
                            name="down")(nn.silu(wide("gate")) * wide("up"))


class Block(nn.Module):
    n_heads: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    seq_axis: Optional[str] = None
    use_flash: bool = True
    ring_impl: str = "ppermute"
    capture_kv: bool = False
    moe: Optional[MoEConfig] = None  # sparse experts instead of up/down
    qk_norm: bool = False
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, x, decode_ctx=None):
        h = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                       name="attn_norm")(x)
        attn = Attention(self.n_heads, self.dtype, self.seq_axis,
                         self.use_flash, self.ring_impl, self.capture_kv,
                         self.qk_norm, self.norm_eps, name="attn")
        new_kv = None
        if decode_ctx is None:
            x = x + attn(h)
        else:
            a, new_kv = attn(h, decode_ctx)
            x = x + a
        h = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                       name="mlp_norm")(x)
        if self.moe is not None:
            h = SparseExperts(self.moe, self.dtype, name="moe")(h)
        else:
            with jax.named_scope("hvd_mlp"):
                h = nn.Dense(self.d_ff, use_bias=False, dtype=self.dtype,
                             name="up")(h)
                h = nn.gelu(h)
                h = nn.Dense(x.shape[-1], use_bias=False, dtype=self.dtype,
                             name="down")(h)
        x = x + h
        return x if new_kv is None else (x, new_kv)


def _kept_by_a_recomputing_layer(primitive, *_, **params) -> bool:
    """The ``jax.checkpoint`` policy of a :class:`MixerLayer` under
    ``recompute``: what it keeps of its forward pass beside its input.

    - The router's DECISION, ``top_k``'s outputs (1 MB a layer at 16,384
      tokens).  Not for its time: computed again, the probabilities need not
      round as they did the first time (XLA fuses the second pass its own
      way and may skip a rounding), a near-tie between the k-th and the next
      expert then falls the other way, and ONE token that changes its expert
      moves every later row of the sorted buffer.  Whatever else is kept by
      buffer row would then be read in another order than it was written.
    - The outputs of the grouped expert products (``ragged_dot_general``, or
      the ``hvd_grouped_fwd`` kernel where ``ops.moe.product_kernel`` chose
      it), rows in that order.
    - The outputs of the flash FORWARD kernels (a layer's output and its
      rows' log-sum-exp, which the backward kernels read).

    Everything else — norms, projections, rotations, the router's
    probabilities, the rows' movement, every element-wise pass — is computed
    again from the layer's input.

    Decided by traces of the Mellum2 cell (16,384 tokens, four layers; my
    chip runs, PR 49, PERF.md section 6): with NOTHING kept a step is 561 ms
    at 9.36 GB, the forward kernels' second calls 31 ms of it; with their
    outputs kept (0.69 GB) 530 ms; with the grouped products' kept too
    (1.37 GB) 495 ms.  Kept WITHOUT the router's decision those products gave
    gradients 0.49 from the reference's (0.023 is the system's) under a right
    loss and a right gradient norm — the rows of one pass against the order
    of the other — and with it 0.025.  A policy by primitive and not by
    ``checkpoint_name``: a name is an equation in every model's program, a
    recomputing one's or not, and this leaves the others' as they were."""
    if primitive.name == "pallas_call":
        return str(params["name"]).startswith(("hvd_flash_fwd",
                                               "hvd_grouped_fwd"))
    return primitive.name in ("top_k", "ragged_dot_general")


class LayerKind(NamedTuple):
    """A row of :data:`LAYER_KINDS`: the ``mixer`` class a layer of the kind
    runs, its ``arguments`` by keyword as a function of the model's
    :class:`LayerOptions`, and the option the kind ``wants`` (is refused
    without)."""

    mixer: type
    arguments: Callable[[Any], dict]
    wants: Optional[str] = None


def _attention_kind(passes=None, rotated=False, own_rope=False) -> LayerKind:
    """The row of a kind that runs :class:`Attention` at the model's sizes
    (``n_heads``, ``n_kv_heads``, ``head_dim``, ``head_shard``,
    ``head_shard_axis``, ``qk_norm``, ``head_norm``, ``attn_gate``,
    ``rotary_dim``, ``attn_scale``).  The kinds differ in data:
    ``passes``, the ONE of ``window`` / ``block_diffusion`` / ``indexer`` the
    kind hands on (and wants set; the other two stay unset whatever the model
    holds); ``rotated``, whether it turns even where the model's ``rope`` is
    off; ``own_rope``, whether the model's ``window_rope``, a ``(theta,
    scaling)`` pair, replaces ``rope_theta`` / ``rope_scaling`` where set."""
    def arguments(o):
        theta, scaling = o.window_rope \
            if own_rope and o.window_rope is not None \
            else (o.rope_theta, o.rope_scaling)
        return dict(
            n_heads=o.n_heads, dtype=o.dtype, use_flash=o.use_flash,
            qk_norm=o.qk_norm, norm_eps=o.norm_eps, n_kv_heads=o.n_kv_heads,
            rope=o.rope or rotated, rope_theta=theta, rope_scaling=scaling,
            rotary_dim=o.rotary_dim, head_shard=o.head_shard,
            head_shard_axis=o.head_shard_axis, head_dim=o.head_dim,
            head_norm=o.head_norm, gate=o.attn_gate, sm_scale=o.attn_scale,
            **({passes: getattr(o, passes)} if passes else {}))

    return LayerKind(Attention, arguments, wants=passes)


def _delta_kind(gate) -> LayerKind:
    """The row of a kind that runs :class:`DeltaMixer` of ``delta``'s sizes
    (:class:`DeltaConfig`) under ``gate``."""
    return LayerKind(DeltaMixer, lambda o: dict(
        **o.delta._asdict(), gate=gate, head_shard=o.head_shard,
        dtype=o.dtype, norm_eps=o.norm_eps))


# The kinds of a per-layer pattern (``TransformerLM(layers=)``): what a
# :class:`MixerLayer` of each runs between its norm and its residual.  A new
# kind is a row here and its mixer.
LAYER_KINDS = {
    # A Mamba-2 mixer of ``ssm``'s sizes (:class:`Mamba2Config`).
    "ssm": LayerKind(Mamba2Mixer, lambda o: dict(
        **o.ssm._asdict(), head_shard=o.head_shard, dtype=o.dtype,
        norm_eps=o.norm_eps)),
    # Sees every earlier key and rotates where ``rope`` says, at
    # ``rope_theta`` under ``rope_scaling``.
    "attention": _attention_kind(),
    # The sparse experts of ``moe`` (:class:`MoEConfig`).
    "experts": LayerKind(SparseExperts,
                         lambda o: dict(config=o.moe, dtype=o.dtype)),
    # A Kimi-delta mixer under its channel gate, of ``delta``'s sizes
    # (:class:`DeltaConfig`).
    "delta": _delta_kind("channel"),
    # Of ``latent``'s sizes (:class:`LatentConfig`).
    "latent_attention": LayerKind(LatentAttention, lambda o: dict(
        n_heads=o.n_heads, config=o.latent, dtype=o.dtype,
        use_flash=o.use_flash, norm_eps=o.norm_eps,
        head_shard=o.head_shard)),
    # A dense gated MLP of ``d_ff``.
    "gated_mlp": LayerKind(GatedMLP,
                           lambda o: dict(d_ff=o.d_ff, dtype=o.dtype)),
    # Under the sliding ``window`` and ALWAYS rotated — ``rope=False`` with a
    # ``window`` is full layers without rotation among rotated windowed ones
    # — at ``window_rope`` where that is set (a model whose full layers turn
    # at YaRN's frequencies and whose windowed ones at the plain ones; unset,
    # the two kinds agree).
    "window_attention": _attention_kind("window", rotated=True,
                                        own_rope=True),
    # Under the ``block_diffusion`` mask over ``[clean; noised]`` rows,
    # rotated where ``rope`` says.
    "blockdiff_attention": _attention_kind("block_diffusion"),
    # A Gated DeltaNet mixer, the head gate over grouped heads
    # (``delta.value_heads`` over ``delta.heads`` key heads).
    "gated_delta": _delta_kind("head"),
    # Behind the model's ``indexer``, a learned selection of each query's
    # keys (:class:`IndexerConfig`), rotated where ``rope`` says: such a
    # model trains on ``next_token_loss + indexer_loss(intermediates)``.
    "selected_attention": _attention_kind("indexer"),
}


# ``post_norm`` -> (the mixer's input is normed, its output is): where a
# pattern's layers have their norms.
NORM_PLACEMENTS = {False: (True, False), True: (True, True),
                   "only": (False, True)}


class MixerLayer(nn.Module):
    __doc__ = (
        """One layer of a per-layer pattern (``TransformerLM(layers=)``): ONE
    mixer, one residual and its norms where ``options.post_norm``, a key of
    :data:`NORM_PLACEMENTS`, puts them: ``False`` ``x + mixer(RMSNorm(x))``,
    the pre-norm; ``True`` ``x + RMSNorm(mixer(RMSNorm(x)))``, the mixer's
    output normed again (``post_norm``'s own scale) before the add; ``"only"``
    ``x + RMSNorm(mixer(x))``, the output's norm alone on a mixer that reads
    the bare residual stream (the parameter tree then has no ``norm``); a
    published layer of two sublayers is two consecutive entries;
    ``options.residual_scale``, where set, multiplies what joins the stream,
    ``x + residual_scale * (...)``.  ``options``
    is the model's :class:`LayerOptions`; ``kind`` is a key of
    :data:`LAYER_KINDS`, whose row says what the mixer is built from: """
        + ", ".join(f"``{kind!r}`` {row.mixer.__name__}"
                    for kind, row in LAYER_KINDS.items())
        + """.

    ``options.recompute`` — ``True``, every layer; a tuple of kinds, the
    layers of those kinds alone (a job that has the memory for its MLPs'
    activations and not for its mixers' recomputes the mixers): the layer's
    forward pass is computed again in the
    backward pass (``jax.checkpoint`` around norm, mixer and residual) and
    only its input ``x`` is kept between the two, with the router's decision
    and the outputs of the grouped expert products and of the flash forward
    kernels (:func:`_kept_by_a_recomputing_layer` has why).  Loss,
    gradients and what the layer sows are the unset layer's:
    the same operations in the same order, sown once.""")

    kind: str
    options: LayerOptions

    @nn.compact
    def __call__(self, x):
        chosen = self.options.recompute
        if chosen is True or (chosen and self.kind in chosen):
            return nn.remat(MixerLayer._forward,
                            policy=_kept_by_a_recomputing_layer)(self, x)
        return self._forward(x)

    @nn.nowrap
    def _forward(self, x):
        o = self.options
        if o.post_norm not in NORM_PLACEMENTS:
            raise ValueError(f"post_norm {o.post_norm!r} is none of "
                             f"{tuple(NORM_PLACEMENTS)}")
        normed_in, normed_out = NORM_PLACEMENTS[o.post_norm]
        h = nn.RMSNorm(epsilon=o.norm_eps, dtype=o.dtype,
                       name="norm")(x) if normed_in else x
        if self.kind not in LAYER_KINDS:
            raise ValueError(f"layer kind {self.kind!r} is none of "
                             f"{tuple(LAYER_KINDS)}")
        row = LAYER_KINDS[self.kind]
        if row.wants is not None and getattr(o, row.wants) is None:
            raise ValueError(f"a {self.kind!r} layer wants {row.wants}=")
        out = row.mixer(**row.arguments(o), name="mixer")(h)
        if normed_out:
            out = nn.RMSNorm(epsilon=o.norm_eps, dtype=o.dtype,
                             name="post_norm")(out)
        if o.residual_scale is not None:
            out = out * o.residual_scale
        return x + out


class TransformerLM(nn.Module):
    """Causal LM over token ids ``(batch, seq[, sharded over seq_axis])``.
    The embedding lookup runs under the scope ``hvd_embed`` and the head's
    matmul under ``hvd_lm_head`` (``final_norm`` is outside both)."""

    vocab_size: int
    d_model: int = 512
    n_layers: int = 6
    n_heads: int = 8
    d_ff: Optional[int] = None
    dtype: Any = jnp.bfloat16
    seq_axis: Optional[str] = None  # mapped mesh axis of sequence shards
    use_flash: bool = True
    ring_impl: str = "ppermute"  # K/V rotation under sequence parallelism
    capture_kv: bool = False  # sow per-layer K/V (ring prefill capture)
    # Storage dtype of the returned logits.  The MXU accumulation is
    # always float32; bfloat16 STORAGE halves the dominant HBM stream of
    # the LM step (the (batch, seq, vocab) logits tensor and its
    # cotangent round-trip HBM several times between the head matmul,
    # the softmax-CE, and the two backward matmuls — and the backward
    # matmuls consume bf16 operands anyway).  next_token_loss upcasts to
    # f32 internally, so the only precision loss is one bf16 rounding of
    # each logit (~0.4% relative).  Against float32 storage its effect
    # on the step is not measured on this machine: every LM cell of the
    # benchmark stores bf16 logits (PERF.md).
    logits_dtype: Any = jnp.float32
    # Every layer's MLP as sparse experts (MoEConfig; d_ff is then unused),
    # RMSNorm of the whole q and k projections, and the epsilon of every
    # RMSNorm.  The defaults are the dense block above, parameter for
    # parameter.  With ``moe`` the router's statistics are written to the
    # ``router`` collection: apply with ``mutable=["router"]`` and hand that
    # collection to :func:`moe_next_token_loss`.
    moe: Optional[MoEConfig] = None
    qk_norm: bool = False
    norm_eps: float = 1e-6
    # A per-layer pattern in place of ``n_layers`` blocks: a tuple of layer
    # kinds, the keys of :data:`LAYER_KINDS`, whose rows say what each kind
    # runs and which of this model's fields it reads; each layer is ONE mixer
    # behind one norm and one residual (:class:`MixerLayer`, which has what
    # ``post_norm`` — ``False``, ``True`` or ``"only"``: where a layer's norms
    # stand — and ``recompute`` do to every layer).  Unset, the model
    # is the block above, parameter for parameter.  ``embed_scale``
    # multiplies the embedding rows as they are looked up (a muP model's
    # ``sqrt(d_model)``), patterns and blocks alike.  ``block_diffusion``,
    # the ``"blockdiff_attention"`` layers' block length, makes the model a
    # block-diffusion one: ``__call__(tokens, noised=...)`` runs the pattern
    # over ``[tokens; noised]``, both copies of every sequence in one pass of
    # twice the positions, and gives logits for the NOISED copy alone
    # (``final_norm`` and the head run on that half;
    # :func:`masked_diffusion_loss` is its loss).
    # A pattern trains on one sequence shard and has no cached decode: a
    # recurrent layer's state is no key/value cache.
    layers: Optional[Tuple[str, ...]] = None
    ssm: Optional[Mamba2Config] = None
    n_kv_heads: Optional[int] = None
    rope: bool = True
    head_shard: Tuple[int, int] = (0, 1)
    head_shard_axis: Optional[str] = None
    delta: Optional[DeltaConfig] = None
    latent: Optional[LatentConfig] = None
    head_dim: Optional[int] = None
    window: Optional[int] = None
    head_norm: bool = False
    attn_gate: bool = False
    post_norm: Union[bool, str] = False
    embed_scale: Optional[float] = None
    block_diffusion: Optional[int] = None
    rope_theta: float = 10000.0
    rotary_dim: Optional[int] = None
    rope_scaling: Optional[RopeScaling] = None
    window_rope: Optional[Tuple[float, Optional[RopeScaling]]] = None
    recompute: Union[bool, Tuple[str, ...]] = False
    indexer: Optional[IndexerConfig] = None
    # A looped model: the pattern runs ``loops`` times over ONE set of
    # weights, ``final_norm`` after every pass, and the normed state is what
    # the next pass reads (``h_t = final_norm(layers(h_{t-1}))``).  The
    # parameter tree is the one-pass model's; the compiled program holds the
    # pattern's bodies once, inside a loop over the passes (``nn.scan`` with
    # the parameters broadcast), and what a layer sows is stacked a pass.
    # Unset, nothing of it is traced.  With ``loops`` alone the logits are the
    # last pass's (and ``targets=`` is refused).  ``exit_gate`` adds one gate a model, ``z_t = w_g . h_t +
    # b_g`` a position in float32 (``exit_gate_kernel``, ``exit_gate_bias``;
    # scope ``hvd_exit_gate``), applies the head to every pass's state and
    # returns ``(logits, gate_logits)``, ``(loops, batch, seq, vocab)`` and
    # ``(loops, batch, seq)`` — or, called with ``targets=``, ``(per-token
    # cross-entropies, gate_logits)``, both ``(loops, batch, seq)``, a pass's
    # head and cross-entropy under ``jax.checkpoint`` so that ONE pass's
    # logits are alive at a time, forward and backward:
    # :func:`looped_exit_loss` takes the pair.  The gate logits are sown as
    # ``exit_gate_logits`` (:func:`record_exit_distribution`).  Training over
    # a pattern on one sequence shard only.
    loops: Optional[int] = None
    exit_gate: bool = False
    # A muP model's three other multipliers and its tied head (Granite's
    # ``residual_multiplier``, ``logits_scaling``, ``attention_multiplier``,
    # ``tie_word_embeddings``; ``embedding_multiplier`` is ``embed_scale``).
    # Unset, nothing of them is traced and no parameter changes its name.
    # ``tie_head``: the head reads the EMBEDDING's table, ``logits = x e^T``;
    # the tree has no ``lm_head_kernel``, and the table's gradient is the sum
    # of the lookup's and the head's, one AdamW state (blocks and patterns,
    # ``targets=`` too).  ``logits_divisor``: the head's float32 product is
    # DIVIDED by it before it is stored or exponentiated, on every path
    # (``_head_logits``, a looped model's per-pass head,
    # :func:`fused_next_token_loss`).  ``residual_scale``: every pattern
    # entry's output times it before it joins the stream
    # (:class:`MixerLayer`).  ``attn_scale``: the softmax scale of a
    # pattern's :class:`Attention` layers in place of ``head_dim ** -0.5``
    # (``Attention(sm_scale=)``).  The last two are a pattern's: a model of
    # blocks refuses them.
    tie_head: bool = False
    residual_scale: Optional[float] = None
    logits_divisor: Optional[float] = None
    attn_scale: Optional[float] = None
    # Multi-token-prediction modules (DeepSeek-V3, arXiv:2412.19437, section
    # 2.2): ``(depth, kinds)``, ``depth`` modules in a chain behind the
    # pattern and ``final_norm``, each ONE more block of the pattern entries
    # ``kinds`` (``("latent_attention", "experts")``: a whole published
    # layer) with weights of its own.  Module ``k`` (from 1) reads, a
    # position ``i``, the embedding of token ``i + k`` (``tokens`` rolled by
    # ``k``: the SAME table) and the state before it — the main model's normed
    # state, or module ``k - 1``'s — and predicts token ``i + k + 1`` through
    # the SAME head:
    #
    #     h  = [RMSNorm_e(E[t_{i+k}]) | RMSNorm_h(state_i)] W_eh     2 d -> d
    #     h  = the block over h, causal (``MixerLayer``s, ``recompute`` too)
    #     state_i = RMSNorm_f(h_i);   logits_i = state_i W_head
    #
    # Parameters ``mtp_<k-1>_embed_norm``, ``_state_norm``, ``_proj``,
    # ``_layer_<j>`` and ``_final_norm`` beside the pattern's; ONE ``embed``
    # and ONE ``lm_head_kernel``, each taking the sum of every use's gradient.
    # ``__call__(tokens)`` returns ``(logits, module 1's, ...)``, every
    # position kept (a sequence's last ``k`` positions read a token of its
    # start, reach no other position and have no target):
    # :func:`mtp_next_token_loss` takes them with the same ``tokens``.
    # ``__call__(tokens, targets=)`` returns the mean losses ``(main, module
    # 1's, ...)`` through :func:`fused_next_token_loss`, ``targets`` the
    # tokens shifted by one as for any model; the LAST position's target is
    # not read (the main loss leaves one position out, module ``k``'s ``k +
    # 1``, as :func:`mtp_next_token_loss` does), and the losses are sown as
    # ``mtp_losses`` (:func:`record_mtp_losses`).  Everything a module adds
    # runs under the scope ``hvd_mtp`` (``hvd_mtp_proj`` inside it for the two
    # norms and ``W_eh``).  Over a per-layer pattern only; unset, nothing of
    # it is traced.
    mtp: Optional[Tuple[int, Tuple[str, ...]]] = None

    @nn.compact
    def __call__(self, tokens, targets=None, decode_ctx=None, noised=None):
        if self.mtp is not None and (
                self.layers is None or self.loops is not None
                or decode_ctx is not None or noised is not None
                or self.seq_axis is not None):
            raise ValueError(
                "mtp= (multi-token-prediction modules) runs behind a "
                "per-layer pattern (layers=); it composes with neither "
                "decode_ctx=, noised= / block_diffusion=, loops= nor "
                "sequence parallelism.")
        if self.loops is not None:
            return self._looped(tokens, targets, decode_ctx, noised)
        if self.layers is not None and (decode_ctx is not None
                                        or self.seq_axis is not None):
            raise ValueError(
                "layers= (a per-layer pattern) composes with neither "
                "decode_ctx= nor sequence parallelism.")
        if (noised is None) != (self.block_diffusion is None) or (
                noised is not None and (self.layers is None
                                        or noised.shape != tokens.shape)):
            raise ValueError(
                "block_diffusion= and noised= (the noised copy of tokens, "
                "shaped alike) come together, over a per-layer pattern.")
        if noised is not None:
            tokens = jnp.concatenate([tokens, noised], axis=1)
        if targets is not None and self.seq_axis is not None:
            raise ValueError(
                "targets= (fused head+loss) is unsupported under sequence "
                "parallelism: it has no axis_name-aware normalization; "
                "compute logits and use next_token_loss(..., axis_name=...) "
                "instead.")
        if decode_ctx is not None and (targets is not None
                                       or self.seq_axis is not None):
            raise ValueError(
                "decode_ctx= (cached KV decode) composes with neither "
                "targets= nor sequence parallelism: decode is an "
                "inference-only, single-shard path (docs/inference.md).")
        if self.layers is None and (self.residual_scale is not None
                                    or self.attn_scale is not None):
            raise ValueError("residual_scale= and attn_scale= are read by a "
                             "per-layer pattern's layers (layers=).")
        options = self._layer_options()
        x, embed = self._embedded(tokens)
        new_ks, new_vs = [], []
        if self.layers is not None:
            x = self._pattern(x, options)
        for i in range(0 if self.layers is not None else self.n_layers):
            block = Block(
                n_heads=self.n_heads, d_ff=options.d_ff, dtype=self.dtype,
                seq_axis=self.seq_axis, use_flash=self.use_flash,
                ring_impl=self.ring_impl, capture_kv=self.capture_kv,
                moe=self.moe, qk_norm=self.qk_norm, norm_eps=self.norm_eps,
                name=f"layer_{i}")
            if decode_ctx is None:
                x = block(x)
            else:
                x, (k_new, v_new) = block(x, decode_ctx.layer(i))
                new_ks.append(k_new)
                new_vs.append(v_new)
        if noised is not None:
            x = x[:, noised.shape[1]:]      # the clean copy is context alone
        x = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                       name="final_norm")(x)
        w = self._head_kernel(embed)
        if self.mtp is not None:
            return self._with_mtp(tokens, targets, x, w, embed, options)
        if targets is not None:
            # Fused head+loss: see fused_next_token_loss.
            return fused_next_token_loss(x, w, targets, dtype=self.dtype,
                                         logits_divisor=self.logits_divisor)
        logits = _head_logits(x, w, self.dtype, self.logits_dtype,
                              self.logits_divisor)
        if decode_ctx is not None:
            # (n_layers, batch, heads, new_len, head_dim) each: the new
            # chunk's K/V for the caller to persist into its cache.
            return logits, (jnp.stack(new_ks), jnp.stack(new_vs))
        return logits

    @nn.nowrap
    def _layer_options(self) -> LayerOptions:
        """This model's fields as the one value its pattern's layers read,
        ``d_ff`` at its default where unset."""
        return LayerOptions(**{name: getattr(self, name)
                               for name in LayerOptions._fields})._replace(
                                   d_ff=self.d_ff or 4 * self.d_model)

    @nn.nowrap
    def _embedded(self, tokens):
        """(the tokens' rows, scaled where ``embed_scale`` says; the table's
        module, for a tied head)."""
        embed = TokenEmbed(self.vocab_size, self.d_model, dtype=self.dtype,
                           name="embed")
        return self._rows(embed, tokens), embed

    @nn.nowrap
    def _rows(self, embed, tokens):
        with jax.named_scope("hvd_embed"):
            x = embed(tokens)
            if self.embed_scale is not None:
                x = (x * self.embed_scale).astype(self.dtype)
        return x

    @nn.nowrap
    def _with_mtp(self, tokens, targets, x, w, embed, options):
        """``__call__`` behind ``final_norm`` under ``mtp``: the field's
        comment has what it computes and returns."""
        depth, kinds = self.mtp

        def head(x, k):
            if targets is None:
                return _head_logits(x, w, self.dtype, self.logits_dtype,
                                    self.logits_divisor)
            return fused_next_token_loss(
                x, w, jnp.roll(targets, -k, axis=1) if k else targets,
                dtype=self.dtype, logits_divisor=self.logits_divisor,
                no_target=k + 1)

        def norm(k, name, t):
            return nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                              name=f"mtp_{k}_{name}")(t)

        out = [head(x, 0)]
        with jax.named_scope("hvd_mtp"):
            for k in range(depth):
                rows = self._rows(embed, jnp.roll(tokens, -(k + 1), axis=1))
                with jax.named_scope("hvd_mtp_proj"):
                    x = nn.Dense(
                        self.d_model, use_bias=False, dtype=self.dtype,
                        name=f"mtp_{k}_proj")(jnp.concatenate(
                            [norm(k, "embed_norm", rows),
                             norm(k, "state_norm", x)], axis=-1))
                for j, kind in enumerate(kinds):
                    x = MixerLayer(kind, options,
                                   name=f"mtp_{k}_layer_{j}")(x)
                x = norm(k, "final_norm", x)
                out.append(head(x, k + 1))
        if targets is not None:
            self.sow("intermediates", "mtp_losses", jnp.stack(out))
        return tuple(out)

    @nn.nowrap
    def _head_kernel(self, embed):
        """The head's parameter, float32: the logits accumulate in float32
        for a numerically stable softmax, but the matmul runs in bfloat16 on
        the MXU (an f32xf32 matmul costs multiple MXU passes, and the lm_head
        is ~1/3 of the model's FLOPs at vocab 32k).  Under ``tie_head`` the
        table of ``embed``, transposed: no parameter of the head's own."""
        if self.tie_head:
            return embed.embedding.T
        return self.param(
            "lm_head_kernel",
            nn.initializers.variance_scaling(1.0, "fan_in",
                                             "truncated_normal"),
            (self.d_model, self.vocab_size), jnp.float32)

    @nn.nowrap
    def _pattern(self, x, options):
        for i, kind in enumerate(self.layers):
            x = MixerLayer(kind, options, name=f"layer_{i}")(x)
        return x

    @nn.nowrap
    def _looped(self, tokens, targets, decode_ctx, noised):
        """``__call__`` under ``loops``: the field's comment has what it
        computes and returns."""
        if self.layers is None or decode_ctx is not None \
                or noised is not None or self.seq_axis is not None \
                or self.block_diffusion is not None or self.loops < 1 \
                or (targets is not None and not self.exit_gate):
            raise ValueError(
                "loops= runs a per-layer pattern (layers=) one or more times; "
                "it composes with neither decode_ctx=, noised= / "
                "block_diffusion= nor sequence parallelism, and takes "
                "targets= only with exit_gate=True.")
        options = self._layer_options()
        x, embed = self._embedded(tokens)
        w = self._head_kernel(embed)
        gate = None
        if self.exit_gate:
            # z = w_g . h + b_g: at 1 / sqrt(d_model) an element over a normed
            # state the seeded logit is of unit size, lambda about a half.
            gate = (self.param("exit_gate_kernel",
                               nn.initializers.normal(self.d_model ** -0.5),
                               (self.d_model,), jnp.float32),
                    self.param("exit_gate_bias", nn.initializers.zeros, (),
                               jnp.float32))
            with jax.named_scope("hvd_lm_head"):
                w = w.astype(self.dtype)          # once, not once a pass

        def one_pass(model, x, w, gate, targets):
            x = model._pattern(x, options)
            x = nn.RMSNorm(epsilon=model.norm_eps, dtype=model.dtype,
                           name="final_norm")(x)
            if gate is None:
                return x, None
            with jax.named_scope("hvd_exit_gate"):
                # Float32 on the vector unit: a float32 matmul is bfloat16
                # passes on the MXU.
                z = (x.astype(jnp.float32) * gate[0]).sum(axis=-1) + gate[1]
            if targets is None:
                return x, (_head_logits(x, w, model.dtype, model.logits_dtype,
                                        model.logits_divisor), z)
            return x, (jax.checkpoint(
                _head_token_xent, static_argnums=(3, 4, 5))(
                    x, w, targets, model.dtype, model.logits_dtype,
                    model.logits_divisor), z)

        x, out = nn.scan(
            one_pass, variable_broadcast="params",
            variable_axes={"intermediates": 0}, split_rngs={"params": False},
            in_axes=nn.broadcast, length=self.loops)(
                self, x, w, gate, targets)
        if gate is not None:
            self.sow("intermediates", "exit_gate_logits", out[1])
            return out
        return _head_logits(x, w, self.dtype, self.logits_dtype,
                            self.logits_divisor)


def _model_fields_as_one_value():
    """What a pattern's layers read of their model (``MixerLayer(kind,
    options)``; the rows of :data:`LAYER_KINDS`): a ``namedtuple`` type of
    every field of :class:`TransformerLM` that has a default (but flax's own
    ``parent`` and ``name``), by its name and with its default, hashable — an
    option is declared once, as the model's field.  The model builds the
    value from its fields once a call, with ``d_ff`` at ``4 * d_model`` where
    unset (``TransformerLM._layer_options``); a layer on its own takes
    ``LayerOptions(n_heads=..., ...)`` by keyword."""
    fields = [field for field in dataclasses.fields(TransformerLM)
              if field.default is not dataclasses.MISSING
              and field.name not in ("parent", "name")]
    return collections.namedtuple(
        "LayerOptions", [field.name for field in fields],
        defaults=[field.default for field in fields])


LayerOptions = _model_fields_as_one_value()


def _head_logits(x, w, dtype, logits_dtype, divisor=None):
    """The head's product as :class:`TransformerLM` runs it: accumulated in
    float32, divided there by ``divisor`` where there is one
    (``logits_divisor``), stored in ``logits_dtype``."""
    with jax.named_scope("hvd_lm_head"):
        logits = jnp.einsum("bsd,dv->bsv", x.astype(dtype), w.astype(dtype),
                            preferred_element_type=jnp.float32)
        if divisor is not None:
            logits = logits / divisor
        return logits.astype(logits_dtype)


def _head_token_xent(x, w, targets, dtype, logits_dtype, divisor=None):
    """Per-token cross-entropy ``(batch, seq)`` of one pass's state through
    the head; a looped model runs it under ``jax.checkpoint``, which keeps
    ``x`` and computes the logits again in the backward pass."""
    logits = _head_logits(x, w, dtype, logits_dtype, divisor)
    with jax.named_scope("hvd_token_xent"):
        return _token_xent(logits, targets)


def fused_next_token_loss(hidden, w, targets, dtype=jnp.bfloat16,
                          n_chunks: int = 8, logits_divisor=None,
                          no_target: int = 0):
    """Mean cross-entropy computed head-chunk by head-chunk.

    The full-logits path materializes a ``(batch, seq, vocab)`` float32
    tensor (1 GiB at batch 8 / seq 1024 / vocab 32k) that HBM round-trips
    several times (softmax, correct-class gather, d-logits).  Here the
    token dimension is split into chunks inside a rematerialized
    ``lax.scan``: each chunk's logits live only transiently, the forward
    keeps a scalar, and the backward recomputes one chunk's logits at a
    time — O(tokens/n_chunks * vocab) peak memory, same math.  (The model
    invokes this when ``targets`` is passed to ``__call__``.)

    This trades one extra head matmul (the remat recompute) for the logits
    round-trips, so use it when the logits tensor does not fit comfortably
    (long sequences, big vocab, large batch), not as a throughput knob: no
    benchmark cell runs it, and its speed against the full-logits path is
    not measured on this machine (PERF.md section 7).  It returns the MEAN, a
    scalar: a loss that weighs each token's cross-entropy by something the
    model computes (:func:`looped_exit_loss`) needs the per-token values,
    which a looped model takes from its own head a pass under
    ``jax.checkpoint`` (``TransformerLM(loops=, exit_gate=True)``).

    Head and loss are one loop here, so the whole of it runs under the
    head's scope, ``hvd_lm_head``; there is no ``hvd_token_xent`` inside.
    ``logits_divisor`` divides each chunk's float32 logits
    (``TransformerLM(logits_divisor=)``); ``w`` is whatever the head reads, a
    tied model's transposed table too.  ``no_target``: that many positions at
    the end of every sequence have no target (a model with ``mtp``); they
    pass through the chunks as the others do, so that every chunk keeps its
    shape, and count for nothing in the mean.
    """
    B, S, D = hidden.shape
    tokens = B * S
    if tokens % n_chunks:
        n_chunks = 1

    def chunk(total, xt):
        x, t = xt[:2]
        logits = jnp.einsum("md,dv->mv", x.astype(dtype), wb,
                            preferred_element_type=jnp.float32)
        if logits_divisor is not None:
            logits = logits / logits_divisor
        lse = jax.nn.logsumexp(logits, axis=-1)
        correct = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        loss = lse - correct
        if no_target:
            loss = jnp.where(xt[2], loss, 0.0)
        return total + loss.sum(), None

    with jax.named_scope("hvd_lm_head"):
        xc = hidden.reshape(n_chunks, tokens // n_chunks, D)
        tc = targets.reshape(n_chunks, tokens // n_chunks)
        wb = w.astype(dtype)
        operands = (xc, tc)
        if no_target:
            operands += (jnp.broadcast_to(jnp.arange(S) < S - no_target,
                                          (B, S)).reshape(tc.shape),)
        total, _ = lax.scan(jax.checkpoint(chunk),
                            jnp.zeros((), jnp.float32), operands)
        return total / (B * (S - no_target))


@jax.custom_vjp
def _token_xent(logits, targets):
    """Per-token softmax cross-entropy, float32 internals over the logits
    as stored; see :func:`next_token_loss` for what its backward keeps."""
    return _token_xent_fwd(logits, targets)[0]


def _token_xent_fwd(logits, targets):
    row_max = logits.max(axis=-1).astype(jnp.float32)
    sum_exp = jnp.exp(logits.astype(jnp.float32)
                      - row_max[..., None]).sum(axis=-1)
    # Gathered from the STORED logits and cast after: a gather from the
    # float32 cast makes XLA write that cast to HBM.
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    loss = jnp.log(sum_exp) - (picked.astype(jnp.float32) - row_max)
    return loss, (logits, targets, row_max, sum_exp)


def _token_xent_bwd(res, g):
    logits, targets, row_max, sum_exp = res
    classes = lax.broadcasted_iota(targets.dtype, logits.shape,
                                   logits.ndim - 1)
    exp = jnp.exp(logits.astype(jnp.float32) - row_max[..., None])
    d_logits = (exp * (g / sum_exp)[..., None]
                - jnp.where(classes == targets[..., None], g[..., None], 0.0)
                ).astype(logits.dtype)
    return reduced_to_vma_of(logits, d_logits), None


_token_xent.defvjp(_token_xent_fwd, _token_xent_bwd)


def next_token_loss(logits, targets, mask=None, axis_name=None):
    """Mean cross-entropy of ``logits`` against aligned ``targets``.

    Shift once globally before sharding (``inputs = tokens[:, :-1]``,
    ``targets = tokens[:, 1:]``) so sequence-sharded shards stay aligned
    across shard boundaries.  Unmasked, per-shard means `pmean` exactly
    (equal shard sizes).  With a ``mask`` (padding weighted out), pass the
    mapped ``axis_name`` (or tuple) too: shards may hold different numbers
    of valid tokens, so the local sum is normalized by the *global mean*
    token count per shard — the subsequent `pmean` then reproduces the
    exact global weighted mean instead of over-weighting padded shards.

    The softmax is float32 inside whatever dtype the logits are stored in,
    and the per-token cross-entropy has its own backward pass
    (``jax.custom_vjp``).  It keeps the logits AS STORED, the targets, and
    each token's maximum and sum of exponentials in float32, recomputes the
    exponentials from them, and writes the logits' cotangent in the logits'
    dtype (the one rounding autodiff's transpose of ``.astype`` makes) — the
    arithmetic of plain autodiff through optax's cross-entropy, step for
    step.  Autodiff itself keeps float32 tensors of the logits' size for
    the backward pass, which XLA writes out to HBM (4 GB of a 13 GB step at
    8,192 tokens x 50,304 classes, PERF.md).  The target's logit is gathered
    from the stored logits and cast after: gathered from the float32 cast,
    XLA materialises that cast.  A ``custom_vjp`` has no forward mode:
    ``jax.jvp`` / ``jacfwd`` / ``hessian`` of this loss raise (nothing in
    ``horovod_tpu/``, ``examples/`` or ``tests/`` takes them).

    The cross-entropy is per token (``_token_xent``) and the mean is taken
    here, at once: :func:`masked_diffusion_loss` and a looped model's head
    (``TransformerLM(loops=, exit_gate=True)``, for :func:`looped_exit_loss`)
    weigh the same per-token values their own way.
    """
    with jax.named_scope("hvd_token_xent"):
        loss = _token_xent(logits, targets)
    if mask is None:
        return loss.mean()
    mask = mask.astype(loss.dtype)
    count = mask.sum()
    if axis_name is not None:
        axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        n_shards = 1
        for a in axes:
            n_shards *= lax.axis_size(a)
        count = lax.psum(count, axes) / n_shards
    return (loss * mask).sum() / jnp.maximum(count, 1.0)


def mtp_next_token_loss(logits, tokens, weight: float = 0.3,
                        with_terms: bool = False):
    """The loss of a model with multi-token-prediction modules
    (``TransformerLM(mtp=)``; DeepSeek-V3, arXiv:2412.19437, equations
    24–25) on what ``model(tokens)`` returned, ``logits = (main, module
    1's, ..., module D's)``, and the SAME ``tokens`` ``(batch, seq)``:

        L_main = mean_{i < seq-1}   CE(main_i,     t_{i+1})
        L_k    = mean_{i < seq-1-k} CE(module_k_i, t_{i+1+k})
        L      = L_main + weight / D * sum_k L_k

    Every set of logits keeps all ``seq`` positions; the positions that have
    no target (one for the main head, ``k + 1`` for module ``k``) are left
    out of its mean.  Each term is :func:`next_token_loss`'s per-token
    cross-entropy under a mask.  ``weight`` is the report's lambda (0.3 in
    its first phase, 0.1 after).  ``with_terms``: ``(L, (L_main, L_1,
    ...))``, the terms a model called with ``targets=`` returns."""
    seq = tokens.shape[1]

    def term(k, logits):
        return next_token_loss(
            logits, jnp.roll(tokens, -(k + 1), axis=1),
            mask=jnp.broadcast_to(jnp.arange(seq) < seq - 1 - k,
                                  tokens.shape))

    terms = tuple(term(k, one) for k, one in enumerate(logits))
    loss = terms[0] + weight / len(terms[1:]) * sum(terms[1:])
    return (loss, terms) if with_terms else loss


def masked_diffusion_loss(logits, targets, masked, level):
    """The masked-diffusion bound of a block-diffusion model (BD3-LM,
    arXiv:2503.09573), on the logits of the noised copy: ``(1 / L) sum_i m_i /
    t_i * -log softmax(logits_i)[x_i]`` a sequence, the mean over sequences —
    ``targets`` the data tokens ``x`` (batch, L), ``masked`` ``m`` (1 where the
    noised copy holds the mask token in the token's place), ``level`` ``t``
    (the masking probability of the token's block, in (0, 1]).  The mean is
    over ALL ``L`` data tokens, not over the masked ones.  With every token
    masked at ``t = 1`` it is :func:`next_token_loss` of aligned targets.  The
    per-token cross-entropy is :func:`next_token_loss`'s own (float32 inside,
    its own backward pass); the whole runs under the scope
    ``hvd_diffusion_loss``."""
    with jax.named_scope("hvd_diffusion_loss"):
        with jax.named_scope("hvd_token_xent"):
            loss = _token_xent(logits, targets)
        weight = masked.astype(loss.dtype) / level.astype(loss.dtype)
        return (loss * weight).mean()


def log_exit_distribution(gate_logits):
    """``log p`` of a looped model's exit distribution, ``(loops, ...)`` from
    gate logits ``z`` of that shape, in float32: with ``lambda_t =
    sigmoid(z_t)``, ``p_1 = lambda_1``, ``p_t = lambda_t prod_{j<t} (1 -
    lambda_j)`` for ``1 < t < loops``, and the last pass takes what is left,
    ``p_T = prod_{j<T} (1 - lambda_j)`` (``z_T`` is unused) — so ``p`` sums to
    one a position.  In logarithms (``log_sigmoid``), so that a saturated gate
    gives no infinity."""
    z = gate_logits[:-1].astype(jnp.float32)
    none = jnp.zeros((1,) + z.shape[1:], jnp.float32)
    stayed = jnp.concatenate([none, jnp.cumsum(jax.nn.log_sigmoid(-z), 0)])
    return stayed + jnp.concatenate([jax.nn.log_sigmoid(z), none])


def looped_exit_loss(per_token_ce, gate_logits, beta: float = 0.1):
    """The first-stage objective of a looped language model with learned exits
    (Ouro, arXiv:2510.25741): ``mean over positions of [sum_t p_t CE_t - beta
    H(p)]`` — ``per_token_ce`` the passes' per-token cross-entropies and
    ``gate_logits`` the exit gate's logits, both ``(loops, batch, seq)`` as
    ``TransformerLM(loops=, exit_gate=True)`` returns them under ``targets=``,
    ``p`` :func:`log_exit_distribution`'s, ``H(p) = -sum_t p_t log p_t``.
    Plain float32 arithmetic over its two arguments, under the scope
    ``hvd_exit_loss``; the gradient reaches the model through the
    cross-entropies (weighed by ``p``) and through the gate (by ``CE_t`` and
    the entropy's slope)."""
    with jax.named_scope("hvd_exit_loss"):
        log_p = log_exit_distribution(gate_logits)
        p = jnp.exp(log_p)
        expected = (p * per_token_ce.astype(jnp.float32)).sum(axis=0)
        entropy = -(p * log_p).sum(axis=0)
        return (expected - beta * entropy).mean()


def _sown(tree, name):
    """Every value sown under ``name`` anywhere in a flax collection, in
    layer order (``layer_2`` before ``layer_10``)."""
    found = []
    for key in sorted(tree, key=lambda k: (len(k), k)):
        value = tree[key]
        if key == name:
            found.extend(value if isinstance(value, (tuple, list))
                         else [value])
        elif hasattr(value, "keys"):
            found.extend(_sown(value, name))
    return found


def record_expert_rows(intermediates) -> dict:
    """Read the counters the sparse-expert layers wrote to the
    ``intermediates`` collection of one ``apply(..., mutable=
    ["intermediates"])`` — OUTSIDE the compiled step, on concrete arrays —
    and, when the metrics registry is on (``HVD_TPU_METRICS=1``), mirror them
    into ``hvd.metrics_snapshot()["moe"]``.  Returns ``{"rows_per_local_
    expert": [[rows of each local expert] per layer], "rows_over_bound":
    int, "rows_walked": [rows a pass back to the tokens touches, per
    layer], "way_back": [that pass's form, one of ``ops.moe.WAYS_BACK``, per
    layer], "experts_kernel": [the kernel the layer's grouped products took,
    one of ``ops.moe.GROUPED_KERNELS``, per layer]}`` (the last is not
    mirrored)."""
    from horovod_tpu.common import metrics as _metrics

    rows = [[int(n) for n in layer]
            for layer in _sown(intermediates, "rows_per_local_expert")]
    over = sum(int(n) for n in _sown(intermediates, "rows_over_bound"))
    walked = [int(n) for n in _sown(intermediates, "rows_walked")]
    forms = [WAYS_BACK[int(i)] for i in _sown(intermediates, "way_back")]
    if _metrics.registry.enabled:
        _metrics.registry.set_moe_rows(rows, over, walked, forms)
    kernels = [GROUPED_KERNELS[int(i)]
               for i in _sown(intermediates, "experts_kernel")]
    return {"rows_per_local_expert": rows, "rows_over_bound": over,
            "rows_walked": walked, "way_back": forms,
            "experts_kernel": kernels}


def record_attention_blocks(intermediates) -> dict:
    """Read what the attention layers wrote to the ``intermediates``
    collection of one ``apply(..., mutable=["intermediates"])`` — outside the
    compiled step — and, when the metrics registry is on
    (``HVD_TPU_METRICS=1``), mirror it into
    ``hvd.metrics_snapshot()["attention"]``.  Returns ``{"blocks_visited":
    [the (query block, key block) pairs a head's forward kernel visits, per
    windowed or block-diffusion layer], "blocks_causal": [what the causal
    kernel would under the same blocks, per such layer]}``."""
    from horovod_tpu.common import metrics as _metrics

    seen = {kind: [int(n) for n in _sown(intermediates, "attn_" + kind)]
            for kind in ("blocks_visited", "blocks_causal")}
    if _metrics.registry.enabled:
        _metrics.registry.set_attention_blocks(**seen)
    return seen


def indexer_loss(intermediates):
    """The indexers' loss of a model with ``"selected_attention"`` layers:
    the sum over those layers of the ``dsa_kl`` each sowed into the
    ``intermediates`` collection of one ``apply(..., mutable=
    ["intermediates"])`` — inside the loss function, where its gradient
    reaches the indexers' parameters and no other."""
    return sum(_sown(intermediates, "dsa_kl"))


def record_attention_selection(intermediates) -> dict:
    """Read what the ``"selected_attention"`` layers counted into the
    ``intermediates`` collection of one ``apply(..., mutable=
    ["intermediates"])`` — outside the compiled step — and, when the metrics
    registry is on (``HVD_TPU_METRICS=1``), mirror it into
    ``hvd.metrics_snapshot()["attention"]``.  A list a layer that selected
    (more positions than its ``topk``), over the batch: ``keys_selected``
    the (query, key) pairs kept of ``keys_causal``; ``threshold_ties`` the
    pairs kept beyond ``topk`` a row, keys tied at the threshold;
    ``tiles_live`` the (512, 512) tiles that hold a kept pair of
    ``tiles_causal`` on and under the diagonal — what a kernel that skipped
    dead tiles would visit."""
    from horovod_tpu.common import metrics as _metrics

    seen = {kind: [int(n) for n in _sown(intermediates, "dsa_" + kind)]
            for kind in SELECTION_COUNTS}
    if _metrics.registry.enabled:
        _metrics.registry.set_attention_selection(**seen)
    return seen


def record_delta_steps(intermediates) -> dict:
    """Read what the Gated DeltaNet layers with a scaled step
    (``DeltaConfig(beta_scale=)`` other than 1) counted into the
    ``intermediates`` collection of one ``apply(..., mutable=
    ["intermediates"])`` — outside the compiled step — and, when the metrics
    registry is on (``HVD_TPU_METRICS=1``), mirror it into
    ``hvd.metrics_snapshot()["delta"]``.  A list a such layer, over the
    batch: ``beta_over_one`` the (token, value head) steps with ``beta > 1``
    of ``beta_steps``.  A model without such a layer gives two empty
    lists."""
    from horovod_tpu.common import metrics as _metrics

    seen = {kind: [int(n) for n in _sown(intermediates, "gdn_" + kind)]
            for kind in ("beta_over_one", "beta_steps")}
    if _metrics.registry.enabled:
        _metrics.registry.set_delta_steps(**seen)
    return seen


def record_ssm_carry(intermediates) -> dict:
    """Read what the Mamba-2 layers counted into the ``intermediates``
    collection of one ``apply(..., mutable=["intermediates"])`` — outside the
    compiled step — and, when the metrics registry is on
    (``HVD_TPU_METRICS=1``), mirror it into ``hvd.metrics_snapshot()["ssm"]``.
    A list a Mamba-2 layer, over the batch: of the (sequence, chunk, head)
    triples ``chunks`` the ``chunks_carried`` that pass on more than
    ``models.ssm.CARRY_LIVE`` of the state that entered the chunk (``exp`` of
    the chunk's summed ``dt A``): where few do, the chunk-by-chunk carry moves
    nothing and the layer is local to its chunk.  A model without such a
    layer gives two empty lists."""
    from horovod_tpu.common import metrics as _metrics

    seen = {kind: [int(n) for n in _sown(intermediates, "ssm_" + kind)]
            for kind in ("chunks_carried", "chunks")}
    if _metrics.registry.enabled:
        _metrics.registry.set_ssm_carry(**seen)
    return seen


def record_mtp_losses(intermediates) -> dict:
    """Read the mean losses a model with multi-token-prediction modules
    (``TransformerLM(mtp=)``) sowed in one ``apply(..., targets=...,
    mutable=["intermediates"])`` — outside the compiled step — and, when the
    metrics registry is on (``HVD_TPU_METRICS=1``), mirror them into
    ``hvd.metrics_snapshot()["mtp"]``.  ``{"main": the next-token loss,
    "modules": [module 1's, ...]}``: on seeded weights every module's loss
    is near the main one (the logarithm of the vocabulary), and a module
    that stopped training reads off it.  A model without modules, or a call
    without ``targets=`` (its losses are the caller's,
    :func:`mtp_next_token_loss`), gives ``{"main": None, "modules": []}``."""
    from horovod_tpu.common import metrics as _metrics

    sown = _sown(intermediates, "mtp_losses")
    seen = {"main": None, "modules": []}
    if sown:
        main, *modules = (float(x) for x in sown[0])
        seen = {"main": main, "modules": modules}
    if _metrics.registry.enabled:
        _metrics.registry.set_mtp_losses(**seen)
    return seen


def record_exit_distribution(intermediates) -> dict:
    """Read the exit gate's logits a looped model wrote to the
    ``intermediates`` collection of one ``apply(..., mutable=
    ["intermediates"])`` — outside the compiled step, on concrete arrays.
    Returns ``{"mean_p": [mean over positions of p_t, per pass], "entropy":
    mean H(p), "expected_passes": mean sum_t t p_t}`` (between 1 and
    ``loops``: how deep the gate sends a token), ``p``
    :func:`log_exit_distribution`'s."""
    (z,) = _sown(intermediates, "exit_gate_logits")
    log_p = log_exit_distribution(z)
    p = jnp.exp(log_p)
    mean_p = [float(x) for x in p.mean(axis=tuple(range(1, p.ndim)))]
    return {"mean_p": mean_p,
            "entropy": float(-(p * log_p).sum(axis=0).mean()),
            "expected_passes": sum(t * x for t, x in enumerate(mean_p, 1))}


def router_losses(router):
    """(load-balancing loss, router z-loss) of a sparse-expert model, from
    the ``router`` collection its layers wrote (``model.apply(...,
    mutable=["router"])[1]["router"]``), over all layers' tokens together:

    * load balancing, ``E * sum_e f_e * P_e``: ``f_e`` the share of all
      (token, choice) pairs that chose expert e, ``P_e`` the mean router
      probability of e; 1 when both are uniform;
    * z-loss: the mean over tokens of ``logsumexp(router logits)**2``.

    Under data parallelism each shard's statistics are its own tokens'."""
    choices = sum(_sown(router, "choices"))
    prob_sum = sum(_sown(router, "prob_sum"))
    tokens = sum(_sown(router, "tokens"))
    share = choices / choices.sum()
    balance = choices.shape[0] * (share * (prob_sum / tokens)).sum()
    return balance, sum(_sown(router, "z_sum")) / tokens


def moe_next_token_loss(logits, targets, router, load_balance_coef=0.01,
                        z_coef=0.001, mask=None, axis_name=None):
    """:func:`next_token_loss` plus ``load_balance_coef`` times the
    load-balancing loss and ``z_coef`` times the router z-loss of
    :func:`router_losses` (OLMoE: 0.01 and 0.001)."""
    balance, z = router_losses(router)
    return (next_token_loss(logits, targets, mask, axis_name)
            + load_balance_coef * balance + z_coef * z)
