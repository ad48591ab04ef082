"""A learned selection of keys (the indexer of DeepSeek sparse attention): the
pieces between an indexer's projections and the flash kernels' selection
operand (:class:`~horovod_tpu.ops.attention.Selected`), and the indexer's own
loss.

* :func:`index_scores` — ``I[t, s] = sum_j w[t, j] relu(q_j[t] . k[s])`` over
  the causal (512, 512) tiles, the heads contracted and summed inside a tile
  (kernel ``hvd_dsa_index``): ``[seq, seq]`` float32 is written where the
  einsum writes ``[heads, seq, seq]``.  Tiles above the diagonal are not
  visited and hold whatever the buffer held: every reader masks them.
* :func:`select` — the ``min(t + 1, topk)``-th largest score of each row by
  bisection on the scores' ordered bit patterns (32 compare-and-count passes,
  a half-word at a time, XLA), the selection ``I >= tau`` as ``int8`` and the counts a layer sows.
  Exact; a tie at the threshold keeps every key of the tie.
* :func:`head_probs` — the attention's own probabilities summed over the
  heads, from q, k and the rows' final log-sum-exp as the flash backward
  recomputes them (kernel ``hvd_dsa_probs``): the indexer's target.
* :func:`indexer_kl` — ``mean_t KL(p[t] || softmax over the selection of
  I[t])`` with its gradient in closed form, ``dI = (softmax(I) - p) / rows``,
  carried to the indexer's q, k and weights by the score product's backward
  (kernels ``hvd_dsa_index_bwd_dq``, ``hvd_dsa_index_bwd_dk``) in the FORWARD
  pass: what waits for the backward pass is three small arrays, and no
  ``[seq, seq]`` array outlives its layer but the ``int8`` selection.

Every entry is jitted over its static arguments, so a model's layers of one
shape trace and lower each kernel once (as ``ops.moe._tiled_call``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common.metrics import kernel_trace
from horovod_tpu.ops.attention import (_FIRST, _LAST, Causal, _pick_block,
                                       _split_scale, _tile_table)

INDEX_TILE = 512     # the score product's tiles, queries and keys
_LANES = 128


class Selection(NamedTuple):
    """What :func:`select` decides: ``chosen`` the selection as ``int8[batch,
    seq, seq]`` (zero above the diagonal), and the counts over the batch, by
    the names a layer sows them under behind ``dsa_`` — ``keys_selected``
    (query, key) pairs kept of ``keys_causal``, ``threshold_ties`` the pairs
    kept beyond ``topk`` a row (keys tied at the threshold), ``tiles_live``
    the `INDEX_TILE` tiles that hold a kept pair of ``tiles_causal`` on and
    under the diagonal."""

    chosen: jax.Array
    keys_selected: jax.Array
    keys_causal: jax.Array
    threshold_ties: jax.Array
    tiles_live: jax.Array
    tiles_causal: jax.Array


def _tile(seq: int, most: int = INDEX_TILE) -> int:
    block = _pick_block(seq, most)
    if seq % block or block % 128:
        raise ValueError(f"{seq} rows are no whole tiles of a multiple of "
                         "128: the selection's kernels run on their grid "
                         "alone")
    return block


def _table(seq, block_q, block_k, by_key=False):
    return _tile_table(seq // block_q, seq // block_k, block_q, block_k,
                       Causal(), by_key)


def _edges(tab_ref):
    flags = tab_ref[2, pl.program_id(1)]
    return (flags & _FIRST) != 0, (flags & _LAST) != 0


def _always(body):
    """``body()`` under a `cond` on what is true at every step: inside
    `shard_map` the interpreter lets refs that vary over a mesh axis meet
    indices and scratch that do not only there (as
    `ops.attention._when_live`)."""
    pl.when(pl.program_id(1) >= 0)(body)


def _head_products(q_ref, k, heads):
    """``q_j k^T`` of the tile, float32, a head at a time."""
    for j in range(heads):
        q = q_ref[j]
        yield j, q, lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# The score product.
# ---------------------------------------------------------------------------

def _index_kernel(tab_ref, q_ref, k_ref, w_ref, o_ref, *, heads):
    @_always
    def _():
        w = w_ref[...]
        total = jnp.zeros(o_ref.shape, jnp.float32)
        for j, _, x in _head_products(q_ref, k_ref[...], heads):
            total += w[:, j:j + 1] * jnp.maximum(x, 0.0)
        o_ref[...] = total


def _index_dq_kernel(tab_ref, q_ref, k_ref, w_ref, g_ref, dq_ref, dw_ref,
                     dq_acc, dw_acc, *, heads):
    """By query tile, keys inner: ``dq_j += (g w_j [x_j > 0]) k`` and ``dw_j
    += sum_s g relu(x_j)``, head ``j``'s weight gradient in lane ``j``."""
    first, last = _edges(tab_ref)

    @pl.when(first)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @_always
    def _():
        k, w, g = k_ref[...], w_ref[...], g_ref[...]
        lane = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
        for j, _, x in _head_products(q_ref, k, heads):
            live = x > 0.0
            row = jnp.sum(jnp.where(live, x * g, 0.0), axis=1, keepdims=True)
            dw_acc[...] += jnp.where(lane == j, row, 0.0)
            dq_acc[j] += lax.dot_general(
                jnp.where(live, g * w[:, j:j + 1], 0.0).astype(k.dtype), k,
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)
        dw_ref[...] = dw_acc[...]


def _index_dk_kernel(tab_ref, q_ref, k_ref, w_ref, g_ref, dk_ref, dk_acc, *,
                     heads):
    """By key tile, queries inner: ``dk += sum_j (g w_j [x_j > 0])^T q_j``."""
    first, last = _edges(tab_ref)

    @pl.when(first)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)

    @_always
    def _():
        w, g = w_ref[...], g_ref[...]
        for j, q, x in _head_products(q_ref, k_ref[...], heads):
            dk_acc[...] += lax.dot_general(
                jnp.where(x > 0.0, g * w[:, j:j + 1], 0.0).astype(q.dtype), q,
                (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)


def _index_specs(heads, width, block_q, block_k):
    """q (batch, heads, seq, width), k (batch, seq, width), w (batch, seq,
    heads) and a (batch, seq, seq) array under the grid (batch, the causal
    table's steps)."""
    return [
        pl.BlockSpec((None, heads, block_q, width),
                     lambda b, s, tab: (b, 0, tab[0, s], 0)),
        pl.BlockSpec((None, block_k, width),
                     lambda b, s, tab: (b, tab[1, s], 0)),
        pl.BlockSpec((None, block_q, heads),
                     lambda b, s, tab: (b, tab[0, s], 0)),
        pl.BlockSpec((None, block_q, block_k),
                     lambda b, s, tab: (b, tab[0, s], tab[1, s]))]


@functools.partial(jax.jit, static_argnums=(3,))
def _index_scores(q, k, w, interpret):
    batch, heads, seq, width = q.shape
    block = _tile(seq)
    table = _table(seq, block, block)
    q_spec, k_spec, w_spec, out_spec = _index_specs(heads, width, block,
                                                    block)
    call = pl.pallas_call(
        functools.partial(_index_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch, table.shape[1]),
            in_specs=[q_spec, k_spec, w_spec], out_specs=out_spec),
        out_shape=jax.ShapeDtypeStruct((batch, seq, seq), jnp.float32,
                                       vma=jax.typeof(q).vma),
        interpret=interpret, name="hvd_dsa_index")
    with kernel_trace("hvd_dsa_index"):
        return call(table, q, k, w)


def index_scores(q, k, w, interpret=None):
    """``I[b, t, s] = sum_j w[b, t, j] relu(q[b, j, t] . k[b, s])`` for ``s <=
    t``'s tiles: ``q`` (batch, heads, seq, width) and ``k`` (batch, seq,
    width) in the compute type, ``w`` (batch, seq, heads) float32; float32
    out.  Above the diagonal's tiles the result is not written.  No gradient
    is defined: :func:`indexer_kl` carries the indexer's."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _index_scores(lax.stop_gradient(q), lax.stop_gradient(k),
                         lax.stop_gradient(w.astype(jnp.float32)),
                         bool(interpret))


@functools.partial(jax.jit, static_argnums=(4,))
def _index_backward(q, k, w, g, interpret):
    """(dq, dk, dw) of :func:`index_scores` under the cotangent ``g`` (batch,
    seq, seq), zero wherever a pair takes none; dw float32."""
    batch, heads, seq, width = q.shape
    block = _tile(seq)
    rows = block // 2 if block >= 256 else block   # dq's tiles: half the rows
    vma = jax.typeof(q).vma
    table = _table(seq, rows, block)
    dq_call = pl.pallas_call(
        functools.partial(_index_dq_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch, table.shape[1]),
            in_specs=_index_specs(heads, width, rows, block),
            out_specs=[
                pl.BlockSpec((None, heads, rows, width),
                             lambda b, s, tab: (b, 0, tab[0, s], 0)),
                pl.BlockSpec((None, rows, _LANES),
                             lambda b, s, tab: (b, tab[0, s], 0))],
            scratch_shapes=[pltpu.VMEM((heads, rows, width), jnp.float32),
                            pltpu.VMEM((rows, _LANES), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
                   jax.ShapeDtypeStruct((batch, seq, _LANES), jnp.float32,
                                        vma=vma)],
        interpret=interpret, name="hvd_dsa_index_bwd_dq")
    by_key = _table(seq, block, block, by_key=True)
    dk_call = pl.pallas_call(
        functools.partial(_index_dk_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch, by_key.shape[1]),
            in_specs=_index_specs(heads, width, block, block),
            out_specs=pl.BlockSpec((None, block, width),
                                   lambda b, s, tab: (b, tab[1, s], 0)),
            scratch_shapes=[pltpu.VMEM((block, width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(k.shape, k.dtype, vma=vma),
        interpret=interpret, name="hvd_dsa_index_bwd_dk")
    with kernel_trace("hvd_dsa_index_bwd_dq"):
        dq, dw = dq_call(table, q, k, w, g)
    with kernel_trace("hvd_dsa_index_bwd_dk"):
        dk = dk_call(by_key, q, k, w, g)
    return dq, dk, dw[..., :heads]


# ---------------------------------------------------------------------------
# The selection.
# ---------------------------------------------------------------------------

def _ordered(x):
    """float32 as uint32 in the floats' own order (-0.0 read as 0.0)."""
    bits = lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x), jnp.uint32)
    return bits ^ jnp.where(bits >> 31 == 1, jnp.uint32(0xFFFFFFFF),
                            jnp.uint32(0x80000000))


@functools.partial(jax.jit, static_argnums=(1,))
def select(scores, topk: int) -> Selection:
    """The keys each query keeps: ``chosen[b, t, s] = (s <= t) and (t < topk
    or I[b, t, s] >= tau[b, t])``, ``tau`` the ``topk``-th largest of ``I[b,
    t, :t + 1]``.  ``scores`` (batch, seq, seq) float32, read on and under
    the diagonal only; ``seq > topk``."""
    batch, seq, _ = scores.shape
    if not 0 < topk < seq:
        raise ValueError(f"topk={topk} of {seq} rows selects nothing: the "
                         "layer is the causal one")
    rows = jnp.arange(seq)[:, None]
    causal = jnp.arange(seq)[None, :] <= rows
    # -inf above the diagonal: under every finite score, never kept (a row
    # past ``topk`` holds more than ``topk`` of those)
    keys = _ordered(jnp.where(causal[topk:], scores[:, topk:], -jnp.inf))

    # Bit by bit from the top: the largest key that `topk` of the row's reach
    # — the upper half-words first, then the lower ones of the keys that hold
    # the upper half found, so a pass reads two bytes a key and not four.
    # Written out, 32 fusions and no loop: a `while` and its body both stand
    # on a trace's operations line, and the shares that sum them read double.
    def narrowed(halves, need):
        found = jnp.zeros_like(halves[..., 0])
        for bit in range(15, -1, -1):
            higher = found | jnp.uint16(1 << bit)
            reach = (halves >= higher[..., None]).sum(-1, dtype=jnp.int32)
            found = jnp.where(reach >= need, higher, found)
        return found

    upper = (keys >> 16).astype(jnp.uint16)
    top = narrowed(upper, topk)
    above = (upper > top[..., None]).sum(-1, dtype=jnp.int32)
    # 0 where the upper half is another: no candidate of a pass is 0
    lower = jnp.where(upper == top[..., None], keys.astype(jnp.uint16), 0)
    tau = (top.astype(jnp.uint32) << 16) | narrowed(lower, topk - above)
    tail = keys >= tau[..., None]
    chosen = jnp.concatenate(
        [jnp.broadcast_to(causal[:topk], (batch, topk, seq)), tail],
        axis=1).astype(jnp.int8)
    kept = tail.sum(dtype=jnp.int32)
    block = _tile(seq)
    tiles = chosen.reshape(batch, seq // block, block, seq // block,
                           block).any(axis=(2, 4))
    n = seq // block
    return Selection(
        chosen, kept + batch * (topk * (topk + 1) // 2),
        jnp.int32(batch * (seq * (seq + 1) // 2)),
        kept - batch * (seq - topk) * topk,
        tiles.sum(dtype=jnp.int32), jnp.int32(batch * (n * (n + 1) // 2)))


# ---------------------------------------------------------------------------
# The target, and the loss.
# ---------------------------------------------------------------------------

def _probs_kernel(tab_ref, q_ref, k_ref, lse_ref, chosen_ref, o_ref, total, *,
                  heads, scale_r):
    """Grid (batch, the causal table's steps, heads): ``sum_h exp(s_h -
    lse_h)`` of the tile in scratch, masked once (the selection is every
    head's) and written at the last head."""
    head = pl.program_id(2)

    @pl.when(head == 0)
    def _():
        total[...] = jnp.zeros_like(total)

    @_always
    def _():
        s = lax.dot_general(q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if scale_r != 1.0:
            s *= scale_r
        total[...] += jnp.exp(s - lse_ref[...][0][:, None])

    @pl.when(head == heads - 1)
    def _():
        o_ref[...] = jnp.where(chosen_ref[...].astype(jnp.int32) != 0,
                               total[...] * (1.0 / heads), 0.0)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head_probs(q, k, lse, chosen, sm_scale, interpret):
    batch, heads, seq, width = q.shape
    block = _tile(seq)
    table = _table(seq, block, block)
    p2, scale_r = _split_scale(sm_scale)        # as the flash kernels do
    lse8 = jnp.broadcast_to(lse[:, :, None, :], (batch, heads, 8, seq))
    call = pl.pallas_call(
        functools.partial(_probs_kernel, heads=heads, scale_r=scale_r),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch, table.shape[1], heads),
            in_specs=[
                pl.BlockSpec((None, None, block, width),
                             lambda b, s, h, tab: (b, h, tab[0, s], 0)),
                pl.BlockSpec((None, None, block, width),
                             lambda b, s, h, tab: (b, h, tab[1, s], 0)),
                pl.BlockSpec((None, None, 8, block),
                             lambda b, s, h, tab: (b, h, 0, tab[0, s])),
                pl.BlockSpec((None, block, block),
                             lambda b, s, h, tab: (b, tab[0, s], tab[1, s]))],
            out_specs=pl.BlockSpec(
                (None, block, block),
                lambda b, s, h, tab: (b, tab[0, s], tab[1, s])),
            scratch_shapes=[pltpu.VMEM((block, block), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((batch, seq, seq), jnp.float32,
                                       vma=jax.typeof(q).vma),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name="hvd_dsa_probs")
    with kernel_trace("hvd_dsa_probs"):
        return call(table, (q * p2).astype(q.dtype), k, lse8, chosen)


def head_probs(q, k, lse, chosen, sm_scale=None, interpret=None):
    """``p[b, t, s] = (1 / heads) sum_h exp(q_h[t] . k_h[s] sm_scale - lse_h[t])``
    where ``chosen``, else 0, on the causal tiles (above them nothing is
    written): the probabilities of an attention whose rows' log-sum-exp is
    ``lse``, recomputed as its backward kernels do.  ``q``, ``k`` (batch,
    heads, seq, width), ``lse`` (batch, heads, seq) float32, ``chosen``
    (batch, seq, seq) int8.  No gradient."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    q, k, lse = (lax.stop_gradient(t) for t in (q, k, lse))
    return _head_probs(q, k, lse, chosen, float(sm_scale), bool(interpret))


def _kl_and_cotangent(scores, chosen, probs):
    """(mean over rows of ``KL(p || softmax over the chosen of I)``, its
    gradient by ``I``)."""
    keep = chosen != 0
    rows = scores.shape[0] * scores.shape[1]
    logits = jnp.where(keep, scores, -jnp.inf)
    log_q = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    p = jnp.where(keep, probs, 0.0)
    terms = jnp.where(p > 0.0, p * (jnp.log(jnp.where(p > 0.0, p, 1.0))
                                    - jnp.where(keep, log_q, 0.0)), 0.0)
    return terms.sum() / rows, (jnp.where(keep, jnp.exp(log_q), 0.0) - p) \
        / rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _indexer_kl(q, k, w, scores, chosen, probs, interpret):
    return _kl_and_cotangent(scores, chosen, probs)[0]


def _indexer_kl_fwd(q, k, w, scores, chosen, probs, interpret):
    kl, g = _kl_and_cotangent(scores, chosen, probs)
    with jax.named_scope("hvd_dsa_index"):    # the score product's, wherever
        return kl, _index_backward(q, k, w, g, interpret)


def _indexer_kl_bwd(interpret, grads, g):
    return tuple((g * d.astype(jnp.float32)).astype(d.dtype) for d in grads) \
        + (None, None, None)


_indexer_kl.defvjp(_indexer_kl_fwd, _indexer_kl_bwd)


@functools.partial(jax.jit, static_argnums=(6,))
def _indexer_kl_call(q, k, w, scores, chosen, probs, interpret):
    return _indexer_kl(q, k, w, scores, chosen, probs, interpret)


def indexer_kl(q, k, w, scores, chosen, probs, interpret=None):
    """``mean_t KL(probs[t] || softmax over chosen[t] of scores[t])``, a
    float32 scalar whose gradient reaches the indexer's ``q``, ``k`` and ``w``
    (:func:`index_scores`' operands; ``scores`` is what it gave for them)
    and nothing else: ``dL/dI = (softmax(I) - p) / rows`` in closed form, through
    the score product's backward, computed with the loss."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _indexer_kl_call(q, k, w.astype(jnp.float32),
                            lax.stop_gradient(scores), chosen,
                            lax.stop_gradient(probs), bool(interpret))
