"""Fused attention: Pallas TPU kernel + differentiable blockwise fallback.

Layout convention: ``(batch, num_heads, seq, head_dim)`` throughout.

The Pallas kernel tiles queries and keys into MXU-sized blocks and keeps the
online-softmax state (running max, normalizer, accumulator) in VMEM scratch
across the key-block grid dimension, so attention needs O(block) on-chip
memory instead of materializing the (seq, seq) score matrix in HBM.

Both :func:`flash_attention` and :func:`blockwise_attention` use the
flash-attention backward algorithm (Dao et al., arXiv:2205.14135): the
forward saves only the output and the per-row logsumexp, and the backward
recomputes each key block's probabilities on the fly — O(seq) residual
memory, where differentiating *through* the forward scan would save every
block's probability matrix (O(seq^2 / block)).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common.metrics import kernel_trace

NEG_INF = -1e30  # big-negative instead of -inf: keeps exp() NaN-free when a
# whole row is masked (fully-masked causal blocks)
POS_BIG = 1e30   # logsumexp sentinel for fully-masked rows: exp(s - POS_BIG)
# underflows to exactly 0 for any finite s


@dataclasses.dataclass(frozen=True)
class Mask:
    """Which keys a query sees: one static, hashable value that owns
    everything a kind of mask decides, so that the reference, the scan, the
    kernels' schedule and the arithmetic inside a tile read ONE definition.
    This class is the mask that hides nothing; a kind subclasses it (frozen:
    the schedule's cache and ``custom_vjp`` key on the value) and writes

    * :meth:`seen` — THE definition, position by position;
    * :meth:`tiles` — the same over (query tile, key tile) pairs, on numpy
      from static shapes: ``live`` where some query of the tile sees some key
      of it, ``whole`` where every query sees every key.  Every query tile
      and every key tile must be live somewhere;
    * :meth:`cut` — the same inside one LIVE tile of a kernel, on the tile's
      first rows (traced scalars); on a whole tile it is the identity;

    and, where they differ, the scalars below.  `tests/test_flash_table.py`
    holds the three forms of every kind against each other (`check_tables`)
    and runs one this module has never heard of.

    A kind whose decision is DATA keeps its static part in the value (hashable
    as ever) and declares ``operands``: that many arrays ride beside q, k and
    v (:func:`masked_flash_attention`) to the three ``pallas_call`` sites,
    each under the block spec :meth:`specs` gives it, and reach :meth:`seen`
    whole and :meth:`cut` as the tile's slice, behind the arguments above.
    Its :meth:`tiles` is what the static part leaves ``live`` and, in place of
    ``whole``, the tiles the static part ALONE decides: with ``whole_body``
    the table flags them, and the second body cuts them by ``flagged`` (a
    static kind) and reads no operand.  A kind without operands pays nothing
    for this: no call site looks at it."""

    suffix = ""         # behind a kernel's name in a trace
    copies = 1          # the blocks divide ``rows // copies``: no tile lies
    #                     across two copies of the sequence
    square = False      # the kernels need as many queries as keys
    whole_body = False  # the table flags whole tiles (`_WHOLE`) and a kernel
    #                     holds a second body for them, with no `cut`
    operands = 0        # arrays of data the kind's `seen` and `cut` take
    flagged = None      # the static kind whose `cut` a flagged tile takes
    #                     (None: the tile is whole and nothing cuts it)

    @staticmethod
    def of(q_len, k_len, causal=False, window=None, block_diffusion=None):
        """The mask that ``flash_attention``'s keywords name, as
        :meth:`checked` gives it at ``q_len`` queries and ``k_len`` keys."""
        if block_diffusion is not None:
            if causal or window is not None:
                raise ValueError(
                    f"block_diffusion={block_diffusion!r} is a mask of its "
                    "own: it takes neither causal= nor window=")
            mask = BlockDiffusion(block_diffusion, q_len // 2)
        elif window is not None and not causal:
            raise ValueError(f"window={window!r} wants causal=True")
        else:
            mask = Causal(window) if causal else Mask()
        return mask.checked(q_len, k_len)

    @staticmethod
    def bounds(num_q, num_k, block_q, block_k):
        """(q_lo, q_hi, k_lo, k_hi): the first and last row of every query
        tile, as a column, and of every key tile, as a row, for `tiles`."""
        q_lo = np.arange(num_q)[:, None] * block_q
        k_lo = np.arange(num_k)[None, :] * block_k
        return q_lo, q_lo + block_q - 1, k_lo, k_lo + block_k - 1

    def checked(self, q_len, k_len):
        """The mask as the kernels take it at this shape, in the canonical
        form the programs depend on, or a ValueError.  ``k_len`` None: the
        keys are a shard at an offset (the scan under the ring), so nothing
        about the whole sequence is known."""
        return self

    def seen(self, q_pos, k_pos):
        """True where the query row at ``q_pos`` sees the key row at
        ``k_pos``; broadcasts against both."""
        return jnp.bool_(True)

    def tiles(self, num_q, num_k, block_q, block_k):
        """(live, whole): boolean ``(num_q, num_k)`` matrices."""
        every = np.ones((num_q, num_k), bool)
        return every, every

    def cut(self, s, q_start, k_start, block_q, block_k):
        """The logits ``s`` of the ``(block_q, block_k)`` tile at rows
        ``q_start`` and ``k_start``, NEG_INF where the query does not see the
        key."""
        return s

    def specs(self, block_q, block_k, heads):
        """One ``pl.BlockSpec`` an operand, over the grid ``(batch * heads,
        the table's steps)`` with the table the index maps' first scalar
        (`_tile_spec`): the slice of the operand that `cut` takes at a
        step."""
        return ()


@dataclasses.dataclass(frozen=True)
class Causal(Mask):
    """Query ``t`` sees the keys ``s <= t``; with ``window``, those with ``0
    <= t - s < window``: itself and the ``window - 1`` before it."""

    window: Optional[int] = None

    @property
    def suffix(self):
        return "" if self.window is None else "_window"

    @property
    def square(self):
        return self.window is not None

    def checked(self, q_len, k_len):
        if self.window is None:
            return self
        if self.window < 1:
            raise ValueError(f"window={self.window!r} wants at least one key "
                             "(the query's own)")
        # A window that hides no key the causal mask shows IS the causal
        # mask, program for program.
        whole = k_len is not None and self.window >= max(q_len, k_len)
        return Causal() if whole else Causal(int(self.window))

    def seen(self, q_pos, k_pos):
        if self.window is None:
            return q_pos >= k_pos
        return (q_pos >= k_pos) & (q_pos - k_pos < self.window)

    def tiles(self, num_q, num_k, block_q, block_k):
        q_lo, q_hi, k_lo, k_hi = self.bounds(num_q, num_k, block_q, block_k)
        most = num_q * block_q if self.window is None else self.window - 1
        return ((q_hi - k_lo >= 0) & (q_lo - k_hi <= most),
                (q_lo - k_hi >= 0) & (q_hi - k_lo <= most))

    def cut(self, s, q_start, k_start, block_q, block_k):
        if self.window is None:
            return jnp.where(
                q_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0) >= k_start
                + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1),
                s, NEG_INF)
        # a band: one difference serves both bounds
        diff = (q_start - k_start) + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0) - jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        return jnp.where((diff >= 0) & (diff < self.window), s, NEG_INF)


@dataclasses.dataclass(frozen=True)
class BlockDiffusion(Mask):
    """Block diffusion's training pass: rows ``[0, half)`` are a sequence's
    clean copy, rows ``[half, 2 half)`` its noised copy, position ``p`` of
    either copy lies in block ``p // block``.  A clean query sees the clean
    keys of its own block and of the earlier ones; a noised query sees the
    clean keys of the EARLIER blocks and the noised keys of its OWN block; no
    clean query sees a noised key.  With ``d`` a query's block less a key's:
    clean on clean sees ``d >= 0``, noised on clean ``d >= 1``, noised on
    noised ``d == 0``."""

    block: int
    half: int

    suffix = "_blockdiff"
    copies = 2
    square = True
    whole_body = True

    def checked(self, q_len, k_len):
        if k_len is None:
            raise ValueError("block_diffusion= takes whole sequences: no "
                             "offsets")
        if self.block < 1 or q_len != k_len or q_len != 2 * self.half:
            raise ValueError(
                f"block_diffusion={self.block!r} is a mask over [clean; "
                "noised] rows: an even number, queries and keys alike, in "
                "blocks of at least one")
        return BlockDiffusion(int(self.block), self.half)

    def seen(self, q_pos, k_pos):
        block, half = self.block, self.half
        q_noised, k_noised = q_pos >= half, k_pos >= half
        q_block = (q_pos - jnp.where(q_noised, half, 0)) // block
        k_block = (k_pos - jnp.where(k_noised, half, 0)) // block
        return jnp.where(k_noised, q_noised & (k_block == q_block),
                         jnp.where(q_noised, k_block < q_block,
                                   k_block <= q_block))

    def tiles(self, num_q, num_k, block_q, block_k):
        # the blocks divide ``half``, so a tile lies in one copy
        block, half = self.block, self.half
        q_lo, q_hi, k_lo, k_hi = self.bounds(num_q, num_k, block_q, block_k)
        q_noised, k_noised = q_lo >= half, k_lo >= half
        q_rel, k_rel = q_noised * half, k_noised * half
        d_min = (q_lo - q_rel) // block - (k_hi - k_rel) // block
        d_max = (q_hi - q_rel) // block - (k_lo - k_rel) // block
        least = q_noised & ~k_noised
        most = np.where(k_noised, 0, half)
        copies = q_noised | ~k_noised      # no clean query sees a noised key
        return (copies & (d_max >= least) & (d_min <= most),
                copies & (d_min >= least) & (d_max <= most))

    def cut(self, s, q_start, k_start, block_q, block_k):
        # from the block ids of the tile's rows (``block_q`` of them) and
        # columns, not of its pairs
        block, half = self.block, self.half
        q_noised, k_noised = q_start >= half, k_start >= half

        def blocks_of(rel, shape, axis):
            pos = rel + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
            if block & (block - 1) == 0:
                return jax.lax.shift_right_logical(pos,
                                                   block.bit_length() - 1)
            return lax.div(pos, block)

        diff = blocks_of(q_start - jnp.where(q_noised, half, 0),
                         (block_q, 1), 0) \
            - blocks_of(k_start - jnp.where(k_noised, half, 0),
                        (1, block_k), 1)
        return jnp.where(
            (diff >= jnp.where(q_noised & ~k_noised, 1, 0))
            & (diff <= jnp.where(k_noised, 0, half)), s, NEG_INF)


@dataclasses.dataclass(frozen=True)
class Selected(Mask):
    """A learned selection under the causal mask: query ``t`` sees the key
    ``s <= t`` where ``t < topk`` (a row with at most ``topk`` earlier keys
    keeps them all) or where the operand, the selection as ``int8[batch, seq,
    seq]``, is not zero at ``[t, s]`` — the same for every head.  The static
    part is ``topk`` and the causal shape: the live tiles are the causal
    kind's, whatever the data, and a query tile that ends under ``topk`` rows
    is flagged, cut as :class:`Causal` cuts it and fetches no operand (its
    spec stays on the first tile that does)."""

    topk: int

    suffix = "_selected"
    square = True
    whole_body = True
    operands = 1
    flagged = Causal()

    def checked(self, q_len, k_len):
        if k_len is None or q_len != k_len:
            raise ValueError("a selection is over whole sequences, queries "
                             "and keys alike: no offsets")
        if self.topk < 1:
            raise ValueError(f"topk={self.topk!r} wants at least one key")
        # Every row keeps every earlier key: the causal mask, program for
        # program.
        return Causal() if self.topk >= q_len else Selected(int(self.topk))

    def seen(self, q_pos, k_pos, selection):
        return (q_pos >= k_pos) & ((q_pos < self.topk)
                                   | (selection[..., q_pos, k_pos] != 0))

    def tiles(self, num_q, num_k, block_q, block_k):
        q_lo, q_hi, k_lo, _ = self.bounds(num_q, num_k, block_q, block_k)
        live = q_hi - k_lo >= 0
        return live, live & (q_hi < self.topk)

    def specs(self, block_q, block_k, heads):
        first = self.topk // block_q       # the first tile row that reads it

        def tile(b, s, tab, *_):
            reads = (tab[2, s] & _WHOLE) == 0
            return (b // heads, jnp.where(reads, tab[0, s], first),
                    jnp.where(reads, tab[1, s], 0))

        return (pl.BlockSpec((1, block_q, block_k), tile),)

    def cut(self, s, q_start, k_start, block_q, block_k, selection):
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        diff = (q_start - k_start) + rows - jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        chosen = selection.astype(jnp.int32) != 0
        if self.topk % block_q:            # a tile astride row ``topk``
            chosen |= q_start + rows < self.topk
        return jnp.where((diff >= 0) & chosen, s, NEG_INF)


class _Fed:
    """A kind with operands as a kernel's body sees it: the kind's static
    part, and `cut` with the operands' tiles of the grid's step behind its
    arguments."""

    def __init__(self, mask, tiles):
        self.mask, self.tiles = mask, tiles
        self.whole_body, self.flagged = mask.whole_body, mask.flagged

    def cut(self, s, q_start, k_start, block_q, block_k):
        return self.mask.cut(s, q_start, k_start, block_q, block_k,
                             *(_rd(tile) for tile in self.tiles))


def _feed(kernel, mask, in_specs, block_q, block_k, heads):
    """(kernel, in_specs) of a call under a kind with operands: the operands'
    specs behind the call's own, and a kernel that takes their refs out of
    its list and hands its body the mask with them (`_Fed`)."""
    first, last = len(in_specs), len(in_specs) + mask.operands

    def fed(tab_ref, *refs):
        return kernel(tab_ref, *refs[:first], *refs[last:],
                      mask=_Fed(mask, refs[first:last]))

    return fed, list(in_specs) + list(mask.specs(block_q, block_k, heads))


def block_diffusion_mask(q_pos, k_pos, block: int, half: int):
    """True where the query row at ``q_pos`` sees the key row at ``k_pos``
    under block diffusion (:class:`BlockDiffusion`: blocks of ``block``, two
    copies of ``half`` rows).  ``q_pos`` and ``k_pos`` broadcast against each
    other."""
    return BlockDiffusion(block, half).seen(q_pos, k_pos)


def mha_reference(q, k, v, causal: bool = False,
                  sm_scale: Optional[float] = None,
                  window: Optional[int] = None,
                  block_diffusion: Optional[int] = None):
    """O(seq^2)-memory reference attention (for tests and tiny shapes), under
    :func:`flash_attention`'s masks as one explicit boolean matrix."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    mask = Mask.of(q.shape[2], k.shape[2], causal, window, block_diffusion)
    # precision="highest": on TPU the default matmul precision truncates f32
    # operands to bf16 passes; the reference must be at least as accurate as
    # the kernels it validates.
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest").astype(jnp.float32) * sm_scale
    s = jnp.where(mask.seen(jnp.arange(q.shape[2])[:, None],
                            jnp.arange(k.shape[2])[None, :]), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      precision="highest")


# ---------------------------------------------------------------------------
# Blockwise attention: lax.scan online softmax.  Differentiable on any
# backend; the building block ring_attention reuses per ring step.
# ---------------------------------------------------------------------------


def _block_attend(q, k, v, m, l, acc, mask, sm_scale):
    """One online-softmax update of (m, l, acc) with a (q_len, k_len) block.

    ``mask`` is True where attention is allowed (or None for dense).
    Shapes: q (..., q_len, d), k/v (..., k_len, d); m/l (..., q_len);
    acc (..., q_len, d); all statistics in float32.
    """
    # preferred_element_type=f32: half-precision operands ride the MXU's
    # native passes while the accumulation (and, crucially, the backward
    # cotangents) stay float32 — a bf16 result here is both less accurate
    # and produces NaN gradients in the transposed scan on TPU.
    s = jnp.einsum("...qd,...kd->...qk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # Guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1.
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l_new = l * alpha + p.sum(axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _finalize(m, l, acc, dtype):
    # Fully-masked rows have l == 0; emit zeros, not NaN.
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe_l[..., None]).astype(dtype)


def _lse_of(m, l):
    """Per-row logsumexp; POS_BIG sentinel for fully-masked (l == 0) rows so
    the backward's exp(s - lse) is exactly 0 there."""
    return jnp.where(l == 0.0, POS_BIG, m + jnp.log(jnp.maximum(l, 1e-37)))


def _kv_blocks(k, v, block, n_blocks, pad):
    if pad:
        k = jnp.pad(k, [(0, 0)] * (k.ndim - 2) + [(0, pad), (0, 0)])
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 2) + [(0, pad), (0, 0)])
    kb = k.reshape(*k.shape[:-2], n_blocks, block, k.shape[-1])
    vb = v.reshape(*v.shape[:-2], n_blocks, block, v.shape[-1])
    # scan over the block axis: move it to the front.
    return jnp.moveaxis(kb, -3, 0), jnp.moveaxis(vb, -3, 0)


def _block_mask(i, block, q_pos, k_offset, k_len, mask):
    k_pos = k_offset + i * block + jnp.arange(block)
    padding = (k_pos < k_offset + k_len)[None, :]
    return padding & mask.seen(q_pos[:, None], k_pos[None, :])


def _blockwise_fwd_impl(q, k, v, mask, sm_scale, block_size, q_offset,
                        k_offset):
    """Forward scan; returns (out, lse) with lse the per-row logsumexp."""
    q_len, k_len = q.shape[-2], k.shape[-2]
    block = min(block_size, k_len)
    n_blocks = (k_len + block - 1) // block
    kb, vb = _kv_blocks(k, v, block, n_blocks, n_blocks * block - k_len)
    q_pos = q_offset + jnp.arange(q_len)
    m0 = jnp.full(q.shape[:-1], NEG_INF, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], jnp.float32)
    acc0 = jnp.zeros(q.shape[:-2] + (q_len, v.shape[-1]), jnp.float32)

    def step(carry, inputs):
        m, l, acc = carry
        i, kblk, vblk = inputs
        seen = _block_mask(i, block, q_pos, k_offset, k_len, mask)
        m, l, acc = _block_attend(q, kblk, vblk, m, l, acc, seen, sm_scale)
        return (m, l, acc), None

    (m, l, acc), _ = lax.scan(
        step, (m0, l0, acc0), (jnp.arange(n_blocks), kb, vb))
    return _finalize(m, l, acc, q.dtype), _lse_of(m, l)


def _attention_bwd_impl(q, k, v, out, lse, g, mask, sm_scale, block_size,
                        q_offset, k_offset):
    """Flash-attention backward: recompute each key block's probabilities
    from (q, k, lse); residual memory O(seq)."""
    q_len, k_len = q.shape[-2], k.shape[-2]
    d = q.shape[-1]
    block = min(block_size, k_len)
    n_blocks = (k_len + block - 1) // block
    kb, vb = _kv_blocks(k, v, block, n_blocks, n_blocks * block - k_len)
    q_pos = q_offset + jnp.arange(q_len)
    g32 = g.astype(jnp.float32)
    # D_i = sum_j dOut_ij * Out_ij  (the softmax-jacobian diagonal term).
    D = jnp.sum(g32 * out.astype(jnp.float32), axis=-1)

    def step(dq, inputs):
        i, kblk, vblk = inputs
        s = jnp.einsum("...qd,...kd->...qk", q, kblk,
                       preferred_element_type=jnp.float32) * sm_scale
        seen = _block_mask(i, block, q_pos, k_offset, k_len, mask)
        s = jnp.where(seen, s, NEG_INF)
        p = jnp.exp(s - lse[..., None])
        p = jnp.where(seen, p, 0.0)
        dv_blk = jnp.einsum("...qk,...qd->...kd", p, g32,
                            preferred_element_type=jnp.float32)
        dp = jnp.einsum("...qd,...kd->...qk", g32, vblk,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - D[..., None]) * sm_scale
        dq = dq + jnp.einsum("...qk,...kd->...qd", ds, kblk,
                             preferred_element_type=jnp.float32)
        dk_blk = jnp.einsum("...qk,...qd->...kd", ds, q,
                            preferred_element_type=jnp.float32)
        return dq, (dk_blk, dv_blk)

    dq0 = jnp.zeros(q.shape[:-2] + (q_len, d), jnp.float32)
    dq, (dkb, dvb) = lax.scan(step, dq0, (jnp.arange(n_blocks), kb, vb))
    # (n_blocks, ..., block, d) -> (..., n_blocks*block, d) -> clip padding
    dk = jnp.moveaxis(dkb, 0, -3).reshape(*k.shape[:-2], n_blocks * block, d)
    dv = jnp.moveaxis(dvb, 0, -3).reshape(*v.shape[:-2], n_blocks * block,
                                          v.shape[-1])
    return (dq.astype(q.dtype), dk[..., :k_len, :].astype(k.dtype),
            dv[..., :k_len, :].astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _blockwise(q, k, v, mask, sm_scale, block_size, q_offset, k_offset):
    return _blockwise_fwd_impl(q, k, v, mask, sm_scale, block_size, q_offset,
                               k_offset)[0]


def _blockwise_fwd(q, k, v, mask, sm_scale, block_size, q_offset, k_offset):
    out, lse = _blockwise_fwd_impl(q, k, v, mask, sm_scale, block_size,
                                   q_offset, k_offset)
    return out, (q, k, v, out, lse)


def _blockwise_bwd(mask, sm_scale, block_size, q_offset, k_offset, res, g):
    q, k, v, out, lse = res
    return _attention_bwd_impl(q, k, v, out, lse, g, mask, sm_scale,
                               block_size, q_offset, k_offset)


_blockwise.defvjp(_blockwise_fwd, _blockwise_bwd)


def blockwise_attention(q, k, v, causal: bool = False,
                        sm_scale: Optional[float] = None,
                        block_size: int = 512,
                        q_offset: int = 0, k_offset: int = 0,
                        window: Optional[int] = None,
                        block_diffusion: Optional[int] = None):
    """Memory-efficient attention as a `lax.scan` over key/value blocks.

    ``causal``, ``window`` and ``block_diffusion``: :func:`flash_attention`'s
    masks, here a boolean over the same scan (every block is still walked:
    the CPU path and the tests use this one).

    ``q_offset``/``k_offset`` give the global sequence positions of the
    first query/key row — this is what lets :func:`ring_attention` apply a
    correct causal mask to rotated K/V shards.  O(seq) residual memory in
    both directions (flash backward).  Note: the flash backward is a
    `jax.custom_vjp`, so reverse-mode only; traced (non-static) offsets
    fall back to plain differentiation through the scan.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    shard = True        # keys at an offset: nothing is known of the whole
    attend = _blockwise
    try:
        q_offset, k_offset = int(q_offset), int(k_offset)
        shard = bool(q_offset or k_offset)
    except (TypeError, jax.errors.ConcretizationTypeError):
        # Traced offsets can't be custom_vjp static args; keep the plain
        # (through-scan) differentiable path for this corner.
        attend = lambda *args: _blockwise_fwd_impl(*args)[0]  # noqa: E731
    mask = Mask.of(q.shape[-2], None if shard else k.shape[-2], causal,
                   window, block_diffusion)
    return attend(q, k, v, mask, sm_scale, block_size, q_offset, k_offset)


# ---------------------------------------------------------------------------
# Pallas TPU kernel.
# ---------------------------------------------------------------------------


def _rd(ref):
    """Read a block ref squeezing unit dims: (1, n, d) and (1, n, 1, d)
    (the bshd layout's head slot) both load as (n, d)."""
    x = ref[...]
    return x.reshape([s for s in x.shape if s != 1])


def _st(ref, val):
    ref[...] = val.reshape(ref.shape).astype(ref.dtype)


def _split_scale(sm_scale: float):
    """Split ``sm_scale`` into an exact power-of-two factor (applied to q
    in the storage dtype — exact even in bf16) and a float32 residual in
    [1, 2) applied to the logits inside the kernel.  For head dims that
    are powers of 4 (64, 256, ...) the residual is exactly 1.0 and the
    kernels skip the extra (block_q, block_k) pass entirely; other scales
    (head_dim 128, 96, ...) keep full f32 accuracy instead of rounding q
    to bf16 under a non-representable scale (ADVICE r4)."""
    import math

    m, e = math.frexp(sm_scale)  # sm_scale = m * 2**e, m in [0.5, 1)
    return 2.0 ** (e - 1), m * 2.0


_FIRST, _LAST, _WHOLE = 1, 2, 4   # a table step's flags


@functools.lru_cache(maxsize=None)
def _tile_table(num_q, num_k, block_q, block_k, mask, by_key=False,
                every=False):
    """A flash kernel's schedule: the live (query tile, key tile) pairs of
    the mask in the order the kernel walks them, an int32 ``(3, steps)``
    table — row 0 the query tile of each step, row 1 its key tile, row 2 its
    flags (``_FIRST`` / ``_LAST`` step of its row of the walk; under a mask
    whose kernels hold an unmasked body beside the masked one
    (``whole_body``), also ``_WHOLE``: the mask leaves the tile whole).  The
    forward and
    the dq kernel walk row by QUERY tile, keys inner; the combined backward
    and the dk/dv kernel (``by_key``) row by KEY tile, queries inner; either
    way a row's tiles ascend, so each accumulation runs in the order a
    rectangular grid gave it.  The table is the grid's second axis (scalar
    prefetch, SMEM): no step computes nothing, and a tile is copied only for
    a step that uses it.  ``every``: all pairs — the ring, whose shard
    offsets are traced values, decides a tile's fate on the device."""
    live, whole = (Mask() if every else mask).tiles(num_q, num_k, block_q,
                                                    block_k)
    assert live.any(0).all() and live.any(1).all(), "a row with no tile"
    if by_key:
        k_tile, q_tile = np.nonzero(live.T)
        outer = k_tile
    else:
        q_tile, k_tile = np.nonzero(live)
        outer = q_tile
    edge = np.flatnonzero(np.diff(outer)) + 1
    flags = np.where(whole[q_tile, k_tile], _WHOLE, 0) \
        if mask.whole_body else np.zeros_like(outer)
    flags[np.r_[0, edge]] |= _FIRST
    flags[np.r_[edge - 1, len(outer) - 1]] |= _LAST
    table = np.stack([q_tile, k_tile, flags]).astype(np.int32)
    table.flags.writeable = False
    return table


def _attend_block(q_ref, k_ref, v_ref, m_scratch, l_scratch, acc_scratch,
                  q_start, k_start, mask, block_q, block_k,
                  single_k=False, scale_r=1.0, masked=True):
    """One online-softmax block update of the VMEM (m, l, acc) state
    (``masked=False``: the second body, for a tile the mask leaves whole,
    with no mask arithmetic).

    Shared by the single-shard flash kernel and the fused ring-flash step
    (ops/ring_flash.py) — the only difference between them is where
    ``q_start``/``k_start`` come from (the table's tile at the grid's step vs
    the ring's grid position and scalar-prefetched absolute shard offsets).

    VPU economy (the kernel is elementwise-bound at head_dim 64 — the MXU
    finishes each block's two dots in ~1/3 of the time the softmax passes
    take): ``q`` arrives PRE-SCALED by the power-of-two part of sm_scale
    (one (seq, d) pass at the wrapper instead of a (seq, seq) pass here;
    ``scale_r`` is the f32 residual, exactly 1.0 for power-of-4 head
    dims — see :func:`_split_scale`); fully-masked rows are
    neutralized by clamping the softmax reference ``m_safe`` per ROW
    (block_q elements) instead of a second (block_q, block_k) ``where``
    on p — masked elements already underflow via exp(NEG_INF - m_safe);
    and ``single_k=True`` (one key block, the tuned whole-k layout) skips
    the online-rescale multiplies entirely."""
    q = _rd(q_ref)  # (block_q, d), pre-scaled by the pow2 part of sm_scale
    k = _rd(k_ref)  # (block_k, d)
    v = _rd(v_ref)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if scale_r != 1.0:
        s *= scale_r
    if masked:
        s = mask.cut(s, q_start, k_start, block_q, block_k)
    elif mask.flagged is not None:
        s = mask.flagged.cut(s, q_start, k_start, block_q, block_k)
    if single_k:
        m_new = s.max(axis=-1)
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - m_safe[:, None])
        l_new = p.sum(axis=-1)
        acc_scratch[...] = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        m_prev = m_scratch[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        # m_safe keeps fully-masked rows at zero mass: exp(NEG_INF - 0)
        # underflows to 0 for every element AND for alpha (m_prev is
        # NEG_INF too), so no (block_q, block_k) re-mask of p is needed.
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        alpha = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe[:, None])
        l_new = l_scratch[:, 0] * alpha + p.sum(axis=-1)
        acc_scratch[...] = (
            acc_scratch[...] * alpha[:, None]
            + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
    m_scratch[...] = jnp.broadcast_to(m_new[:, None], m_scratch.shape)
    l_scratch[...] = jnp.broadcast_to(l_new[:, None], l_scratch.shape)


def _init_state(m_scratch, l_scratch, acc_scratch):
    m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
    l_scratch[...] = jnp.zeros_like(l_scratch)
    acc_scratch[...] = jnp.zeros_like(acc_scratch)


def _finalize_flash(o_ref, lse_ref, m_scratch, l_scratch, acc_scratch,
                    block_q):
    l = l_scratch[:, 0]
    safe_l = jnp.where(l == 0.0, 1.0, l)
    _st(o_ref, acc_scratch[...] / safe_l[:, None])
    # 8 identical sublanes: a (1, block_q) block would violate the TPU
    # (8, 128) output tiling.
    lse_ref[...] = jnp.broadcast_to(
        _lse_of(m_scratch[:, 0], l)[None, :], (8, block_q)).reshape(
        lse_ref.shape)


def _step_of(tab_ref, block_q, block_k, mask, offsets_ref=None):
    """(first query row, first key row, first step of its row, last step of
    its row, the mask leaves the tile whole, query tile) of the table's step
    the grid stands on.  ``whole`` is None but under a mask whose tables flag
    it (``whole_body``); the rows are absolute where the ring hands its
    shards' ``offsets_ref``."""
    step = pl.program_id(1)
    q_tile, flags = tab_ref[0, step], tab_ref[2, step]
    q_start, k_start = q_tile * block_q, tab_ref[1, step] * block_k
    if offsets_ref is not None:
        q_start, k_start = offsets_ref[0] + q_start, offsets_ref[1] + k_start
    first, last, whole = ((flags & bit) != 0
                          for bit in (_FIRST, _LAST, _WHOLE))
    return (q_start, k_start, first, last,
            whole if mask.whole_body else None, q_tile)


def _when_live(run, whole, body):
    """``body(masked)`` under ``pl.when(run)``: the ring's predicate on its
    traced offsets, or None — a table of live tiles, whose every step
    computes.  ``whole`` None: one body, ``body(True)``, whatever mask the
    kernel has in every tile.  Else a tile the mask cuts takes the masked
    body, ``body(True)``, and a whole one the unmasked, ``body(False)``."""
    if run is None:
        # Still a `cond`, on what is true at every step: inside `shard_map`
        # the interpreter lets scratch, which varies over no mesh axis, meet
        # operands, which do, only there.
        run = pl.program_id(1) >= 0
    if whole is None:
        pl.when(run)(lambda: body(True))
    else:
        pl.when(run & ~whole)(lambda: body(True))
        pl.when(run & whole)(lambda: body(False))


def _flash_kernel(tab_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scratch,
                  l_scratch, acc_scratch, *, mask, block_q, block_k,
                  single_k, scale_r=1.0):
    """Grid ``(bh, steps)``: the steps are the table's (`_tile_table`, query
    tiles outer), live tiles only — the first step of a query tile's row
    starts the online-softmax state, the last writes out and lse.  One body,
    masked in every tile, or, under a mask with ``whole_body``, two, by the
    table's flag.  ``single_k``: every row is one tile (the whole-k layout),
    which skips the state's rescale."""
    q_start, k_start, first, last, whole, _ = _step_of(
        tab_ref, block_q, block_k, mask)

    if not single_k:
        @pl.when(first)
        def _():
            _init_state(m_scratch, l_scratch, acc_scratch)

    def attend(masked):
        _attend_block(q_ref, k_ref, v_ref, m_scratch, l_scratch,
                      acc_scratch, q_start, k_start, mask, block_q, block_k,
                      single_k=single_k, scale_r=scale_r, masked=masked)

    _when_live(None, whole, attend)

    @pl.when(last)
    def _():
        _finalize_flash(o_ref, lse_ref, m_scratch, l_scratch, acc_scratch,
                        block_q)


def _bwd_block_math(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                    mask, q_start, k_start, block_q, block_k, scale_r,
                    masked=True):
    """Shared flash-backward block recompute (Dao et al. alg. 2 inner
    body), used by the combined kernel, both split kernels, and the fused
    ring backward (ops/ring_flash.py).

    ``q`` arrives pre-scaled by the power-of-two part of sm_scale;
    ``scale_r`` is the f32 residual (see :func:`_split_scale`), applied
    once to s (matching the forward's pre-activation) and once to ds —
    ds_r = r * dL/ds — so dk = ds_r^T q' and dq' = ds_r k are exact in
    q' units (the wrapper rescales dq by the pow2 factor once).

    Returns ``(pb, ds, q, do, k)``: the probability block cast to v's
    dtype (for dv += pb^T do), the scaled ds block cast to q's dtype
    (for dk/dq dots), and the loaded q/do/k blocks — returned so callers
    don't re-read the refs (a second ``_rd`` costs extra scoped VMEM)."""
    q = _rd(q_ref)          # (block_q, d), pre-scaled (pow2 part)
    do = _rd(do_ref)        # (block_q, d)
    lse = _rd(lse_ref)[0]   # (block_q,)
    delta = _rd(delta_ref)[0]
    k = _rd(k_ref)          # (block_k, d)
    v = _rd(v_ref)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if scale_r != 1.0:
        s *= scale_r
    if masked:
        s = mask.cut(s, q_start, k_start, block_q, block_k)
    elif mask.flagged is not None:
        s = mask.flagged.cut(s, q_start, k_start, block_q, block_k)
    p = jnp.exp(s - lse[:, None])  # POS_BIG lse zeroes masked rows
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None])
    if scale_r != 1.0:
        ds *= scale_r
    return p.astype(v.dtype), ds.astype(q.dtype), q, do, k


def _flash_bwd_dkdv_kernel(tab_ref, q_ref, do_ref, lse_ref, delta_ref, k_ref,
                           v_ref, dk_ref, dv_ref, dk_scratch, dv_scratch, *,
                           mask, block_q, block_k, scale_r):
    """Split backward, dk/dv half: O(block) scoped memory — the long-seq
    path where the combined kernel's whole-seq dq scratch exceeds the
    chip's scoped-VMEM ceiling (see _bwd_plan).  Grid ``(bh, steps)`` over
    the table's live tiles, key tiles outer: a key tile's queries accumulate
    from its first step to its last."""
    q_start, k_start, first, last, whole, _ = _step_of(
        tab_ref, block_q, block_k, mask)

    @pl.when(first)
    def _():
        dk_scratch[...] = jnp.zeros_like(dk_scratch)
        dv_scratch[...] = jnp.zeros_like(dv_scratch)

    def accumulate(masked):
        pb, ds, q, do, _k = _bwd_block_math(
            q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, mask,
            q_start, k_start, block_q, block_k, scale_r, masked=masked)
        dv_scratch[...] += jax.lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scratch[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _when_live(None, whole, accumulate)

    @pl.when(last)
    def _():
        _st(dk_ref, dk_scratch[...])
        _st(dv_ref, dv_scratch[...])


def _flash_bwd_dq_kernel(tab_ref, q_ref, do_ref, lse_ref, delta_ref, k_ref,
                         v_ref, dq_ref, dq_scratch, *, mask, block_q,
                         block_k, scale_r, dq_scale=1.0):
    """Split backward, dq half: accumulates one query tile over its key
    tiles — O(block) scoped memory (long-seq path, see _bwd_plan).  Grid
    ``(bh, steps)`` over the table's live tiles, query tiles outer."""
    q_start, k_start, first, last, whole, _ = _step_of(
        tab_ref, block_q, block_k, mask)

    @pl.when(first)
    def _():
        dq_scratch[...] = jnp.zeros_like(dq_scratch)

    def accumulate(masked):
        _pb, ds, _q, _do, k = _bwd_block_math(
            q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, mask,
            q_start, k_start, block_q, block_k, scale_r, masked=masked)
        dq_scratch[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _when_live(None, whole, accumulate)

    @pl.when(last)
    def _():
        # pow2 rescale folded into the f32 flush (see the combined
        # kernel's _flush_dq note).
        _st(dq_ref, dq_scratch[...] * dq_scale if dq_scale != 1.0
            else dq_scratch[...])


def _combined_bwd_kernel(tab_ref, *refs, mask, block_q, block_k, steps, bh,
                         ring, run, rotate, barrier, axis_name, mesh_axes,
                         scale_r, dq_scale=1.0):
    """Flash backward with dk/dv AND dq from ONE probability recompute.

    Grid ``(bh, steps)`` over the table's tiles (`_tile_table`, key tiles
    outer, queries inner), so dk/dv accumulate in scratch from a key tile's
    first step to its last and flush there; dq accumulates in a
    whole-sequence VMEM scratch and flushes once per bh row, at the table's
    last step.  The split dkdv/dq kernel pair
    pays the s/p/dp/ds recompute twice; here it is paid once (kernel
    times on today's chip: PERF.md sections 5 and 7).

    ``ring``: a step of the fused ring-flash backward (ops/ring_flash.py).
    ``offsets_ref`` carries the absolute [q_offset, k_offset] of the shards
    for masking across them — traced values, so the table holds every pair
    and whether a tile computes is decided here, by the ring's ``run(first
    query row, first key row)``, the kernel's one dynamic predicate (None:
    every tile computes).  With ``rotate=True`` the K/V rotation DMA to the
    right neighbour starts at the first grid step, flies under the gradient
    compute, and is waited at the last.  ``q`` arrives pre-scaled by the
    pow2 part of sm_scale; dq is emitted in q' units (callers rescale once).
    """
    offsets_ref = None
    if ring:
        offsets_ref, *refs = refs
    if rotate:
        (q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
         k_full, v_full, dk_ref, dv_ref, dq_ref, k_next, v_next,
         dk_scratch, dv_scratch, dq_scratch, sems) = refs
    else:
        (q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
         dk_ref, dv_ref, dq_ref,
         dk_scratch, dv_scratch, dq_scratch) = refs
    b = pl.program_id(0)
    step = pl.program_id(1)
    q_start, k_start, first, last, whole, q_tile = _step_of(
        tab_ref, block_q, block_k, mask, offsets_ref)

    if rotate:
        from horovod_tpu.ops.rdma import _device_id

        my = jax.lax.axis_index(axis_name)
        n = lax.axis_size(axis_name)
        dst, id_type = _device_id(jax.lax.rem(my + 1, n), axis_name,
                                  mesh_axes)
        src, _ = _device_id(jax.lax.rem(my - 1 + n, n), axis_name,
                            mesh_axes)

        @pl.when((b == 0) & (step == 0))
        def _start_rotation():
            if barrier:
                bar = pltpu.get_barrier_semaphore()
                pltpu.semaphore_signal(
                    bar, inc=1, device_id=src, device_id_type=id_type)
                pltpu.semaphore_wait(bar, 1)
            pltpu.make_async_remote_copy(
                src_ref=k_full, dst_ref=k_next, send_sem=sems.at[0],
                recv_sem=sems.at[1], device_id=dst,
                device_id_type=id_type).start()
            pltpu.make_async_remote_copy(
                src_ref=v_full, dst_ref=v_next, send_sem=sems.at[2],
                recv_sem=sems.at[3], device_id=dst,
                device_id_type=id_type).start()

    @pl.when(step == 0)
    def _zero_dq():
        dq_scratch[...] = jnp.zeros_like(dq_scratch)

    @pl.when(first)
    def _zero_dkdv():
        dk_scratch[...] = jnp.zeros_like(dk_scratch)
        dv_scratch[...] = jnp.zeros_like(dv_scratch)

    def accumulate(masked):
        pb, ds, q, do, k = _bwd_block_math(
            q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, mask,
            q_start, k_start, block_q, block_k, scale_r, masked=masked)
        dv_scratch[...] += jax.lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scratch[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        row = pl.ds(q_tile * block_q, block_q)
        dq_scratch[row, :] = dq_scratch[row, :] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _when_live(run and run(q_start, k_start), whole, accumulate)

    @pl.when(last)
    def _flush_dkdv():
        # _st casts: the scratch accumulates in f32, the output dtype is
        # the caller's grad_dtype (input dtype for the single-shard path
        # — saving an XLA-side cast+relayout pass over each gradient —
        # f32 for the ring path, whose partials keep accumulating).
        _st(dk_ref, dk_scratch[...])
        _st(dv_ref, dv_scratch[...])

    @pl.when(step == steps - 1)
    def _flush_dq():
        # dq accumulated in q' units; the pow2 rescale folds into the
        # flush IN F32, before the grad_dtype cast — no extra XLA pass
        # over dq, and no overflow for narrow-exponent dtypes (fp16).
        # Ring callers keep dq_scale=1.0 (partials sum across steps
        # first) and rescale once outside.
        _st(dq_ref, dq_scratch[...] * dq_scale if dq_scale != 1.0
            else dq_scratch[...])

    if rotate:
        @pl.when((b == bh - 1) & (step == steps - 1))
        def _finish_rotation():
            pltpu.make_async_remote_copy(
                src_ref=k_full, dst_ref=k_next, send_sem=sems.at[0],
                recv_sem=sems.at[1], device_id=dst,
                device_id_type=id_type).wait()
            pltpu.make_async_remote_copy(
                src_ref=v_full, dst_ref=v_next, send_sem=sems.at[2],
                recv_sem=sems.at[3], device_id=dst,
                device_id_type=id_type).wait()


def _tile_spec(row, block, d):
    """BlockSpec over (batch*heads, seq, d) operands whose block of ``block``
    rows is the tile row ``row`` of the table (0 the query's, 1 the key's)
    names at the grid's step; the scalar-prefetch refs are an index map's
    last arguments, the table the first of them.  While the tile stays,
    nothing is copied.

    (A strided (1, block, 1, d) spec reading (b, s, h, d) directly would
    skip the host-side transposes, but Mosaic requires the second-minor
    block dim to be a multiple of 8 or the full array dim — a 1-wide head
    slot is not lowerable, so the bshd layout transposes at the wrapper
    instead; see flash_attention.)"""
    return pl.BlockSpec((1, block, d),
                        lambda b, s, tab, *_: (b, tab[row, s], 0))


def _lse_spec(block_q):
    """`_tile_spec` for the (batch*heads, 8, seq) row statistics."""
    return pl.BlockSpec((1, 8, block_q),
                        lambda b, s, tab, *_: (b, 0, tab[0, s]))


def _tiled_call(kernel, table, bh, *, out_shape, name, interpret, prefetch=(),
                compiler_params=None, **specs):
    """``pl.pallas_call`` over the grid ``(bh, the table's steps)``, the table
    the first scalar-prefetch operand (``prefetch``: the ring's offsets
    behind it); the call takes the kernel's other operands, and marks the
    seconds Python takes to trace the body (`kernel_trace`)."""
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(prefetch),
            grid=(bh, table.shape[1]), **specs),
        out_shape=out_shape, compiler_params=compiler_params,
        interpret=interpret, name=name)

    def traced(*operands):
        with kernel_trace(name):
            return call(table, *prefetch, *operands)

    return traced


def _combined_bwd_call(q, do, lse8, delta8, k_cur, v_cur, q_offset=None,
                       k_offset=None, *, mask, block_q, block_k, run=None,
                       rotate=False, collective_id=None, axis_name=None,
                       mesh_axes=(), interpret, scale_r=1.0,
                       grad_dtype=jnp.float32, dq_scale=1.0,
                       name="hvd_flash_bwd", operands=(), heads=1):
    """pallas_call wrapper for `_combined_bwd_kernel` over (bh, sl, d)
    operands (q pre-scaled by the pow2 part of sm_scale; ``do`` and ``v_cur``
    may have another width than ``q`` and ``k_cur``, and ``dv`` then has
    theirs).  Returns
    (dk, dv, dq[, k_next, v_next]) with the gradients in ``grad_dtype``
    (accumulation is always f32 in scratch; only the flush casts, after
    applying ``dq_scale`` to dq in f32).  ``name`` is the kernel's name in
    a device trace: the fused ring's backward step passes its own.  The grid
    is ``(bh, the table's steps)``: the mask's live tiles, key tiles outer —
    or, with the ring's ``q_offset`` and ``k_offset`` (traced, so the live
    tiles are not known here), every pair, the ring's ``run`` deciding in the
    kernel.  ``operands``: the arrays of a kind that has some (`Mask`), the
    same for each of a batch row's ``heads``; not under the ring."""
    bh, sl, d = q.shape
    d_v = v_cur.shape[-1]
    ring = q_offset is not None
    table = _tile_table(sl // block_q, sl // block_k, block_q, block_k, mask,
                        by_key=True, every=ring)
    steps = table.shape[1]
    kernel = functools.partial(
        _combined_bwd_kernel, mask=mask, block_q=block_q,
        block_k=block_k, steps=steps, bh=bh, ring=ring, run=run,
        rotate=rotate, barrier=rotate and not interpret,
        axis_name=axis_name, mesh_axes=mesh_axes, scale_r=scale_r,
        dq_scale=dq_scale)
    in_specs = [
        _tile_spec(0, block_q, d),                         # q
        _tile_spec(0, block_q, d_v),                       # do
        _lse_spec(block_q),                                # lse
        _lse_spec(block_q),                                # delta
        _tile_spec(1, block_k, d),                         # k (blocked)
        _tile_spec(1, block_k, d_v),                       # v (blocked)
    ]
    out_shapes = [
        jax.ShapeDtypeStruct((bh, sl, d), grad_dtype),     # dk
        jax.ShapeDtypeStruct((bh, sl, d_v), grad_dtype),   # dv
        jax.ShapeDtypeStruct((bh, sl, d), grad_dtype),     # dq
    ]
    out_specs = [
        _tile_spec(1, block_k, d),                         # dk
        _tile_spec(1, block_k, d_v),                       # dv
        pl.BlockSpec((1, sl, d), lambda b, s, *_: (b, 0, 0)),  # dq
    ]
    scratch_shapes = [
        pltpu.VMEM((block_k, d), jnp.float32),             # dk accumulator
        pltpu.VMEM((block_k, d_v), jnp.float32),           # dv accumulator
        pltpu.VMEM((sl, d), jnp.float32),                  # whole-seq dq
    ]
    prefetch = [jnp.stack([jnp.asarray(q_offset, jnp.int32),
                           jnp.asarray(k_offset, jnp.int32)])] if ring else []
    args = [q, do, lse8, delta8, k_cur, v_cur]
    if operands:
        kernel, in_specs = _feed(kernel, mask, in_specs, block_q, block_k,
                                 heads)
        args += operands
    if rotate:
        in_specs += [
            pl.BlockSpec(memory_space=pl.ANY),             # k (DMA src)
            pl.BlockSpec(memory_space=pl.ANY),             # v (DMA src)
        ]
        out_shapes += [
            jax.ShapeDtypeStruct(k_cur.shape, k_cur.dtype),  # k_next
            jax.ShapeDtypeStruct(v_cur.shape, v_cur.dtype),  # v_next
        ]
        out_specs += [
            pl.BlockSpec(memory_space=pl.ANY),             # k_next
            pl.BlockSpec(memory_space=pl.ANY),             # v_next
        ]
        scratch_shapes += [pltpu.SemaphoreType.DMA((4,))]
        args += [k_cur, v_cur]
    vma = jax.typeof(q).vma
    out_shapes = [jax.ShapeDtypeStruct(s.shape, s.dtype, vma=vma)
                  for s in out_shapes]
    return _tiled_call(
        kernel, table, bh, prefetch=prefetch, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch_shapes,
        out_shape=out_shapes, interpret=interpret, name=name,
        compiler_params=pltpu.CompilerParams(
            collective_id=(collective_id if rotate and not interpret
                           else None),
            has_side_effects=rotate,
            # None, the parameter's default, but for a call past the budget
            # a kernel has without asking: every other call hands Mosaic
            # the parameters it always has.
            vmem_limit_bytes=_combined_vmem_limit(sl, d, block_q, block_k,
                                                  d_v)),
    )(*args)


_MAX_BLOCK = 1024  # largest block edge the VMEM calibration covers
# The most scoped VMEM a kernel here asks Mosaic for (`_combined_vmem_limit`):
# half of a v5e core's 128 MiB.  No band of `_bwd_plan` passes it (54.1 MiB at
# 8,192 rows of 256 lanes in 1,024-blocks is the most a band asks; 62.9 the
# most a probe of the sweeps did), so it is asserted, not clipped.
_MAX_VMEM_LIMIT = 64 << 20
# What a Mosaic kernel on a v5e has when it asks for nothing, MiB: the
# default of ``HVD_TPU_VMEM_LIMIT_MB``.
_DEFAULT_VMEM_MB = 16.0


def _pick_block(seq_len: int, maximum: int = 512) -> int:
    """Largest kernel-grid block <= maximum that divides the sequence:
    keeps common non-512-multiple lengths (640, 768, 1152, ...) on the
    Pallas kernel instead of silently demoting them to the blockwise
    fallback."""
    for b in (1024, 768, 512, 384, 256, 128):
        if b <= maximum and seq_len % b == 0:
            return b
    return min(maximum, seq_len)  # ragged: the fallback path handles it


def _vmem_budget_bytes() -> int:
    """Scoped-VMEM planning budget, bytes: what a kernel has WITHOUT asking
    Mosaic for more.  Default 16 MiB — the v5e scoped-allocation default the
    compile sweep calibrated against; ``HVD_TPU_VMEM_LIMIT_MB`` overrides it
    for chips with a different default (or to leave headroom under other
    scoped users).  A plan is clamped under it, but for the one band whose
    call names its own limit (`_combined_vmem_limit`, 16,384 rows) — and that
    band is taken only where the budget is the default's or more: set below
    it, the name says the chip has less than the bands were calibrated for,
    no call asks for more, and the band goes to the pair as it did."""
    return int(float(os.environ.get("HVD_TPU_VMEM_LIMIT_MB")
                     or _DEFAULT_VMEM_MB) * (1 << 20))


def _lanes(d: int) -> int:
    """The lanes Mosaic lays a head of ``d`` out in: whole 128-lane tiles
    (64 in 128, 192 in 256)."""
    return -(-d // 128) * 128


def _plan_vmem_bytes(mode: str, q_len: int, d: int, block_q: int,
                     block_k: int, d_v: Optional[int] = None) -> int:
    """Conservative scoped-VMEM estimate for a backward plan, bytes.
    ``d`` is the width of q and k (and of dq, dk), ``d_v`` that of v, do and
    dv where it differs (latent attention: 192 and 128).

    Mosaic's real allocation is not a closed form (see _bwd_plan), so
    this models the structural upper bound: every revolving block window
    double-buffered at f32 width with head_dim padded to the 128-lane
    tile, the combined kernel's whole-seq dq charged three ways (scratch
    + a double-buffered output window — the term whose growth is exactly
    the seq-8192 compile failure).  Calibrated against the compile sweep
    (tools/vmem_sweep.py): every measured-pass band lands under 16 MiB
    here and the measured 23.2 MiB seq-8192/1024-block failure lands
    over, so clamping to this estimate can only reject plans the frontier
    also rejects.  It charges the dq window and every other window at
    float32, so a bf16 call needs less than it says: at 16,384 x 128 lanes
    in (512, 512) it reads 27.6 MiB and the kernel compiles with a limit of
    24 MiB (issue 57's compiles); the call asks for the estimate and a margin
    all the same (`_combined_vmem_limit`), and all 100 probes of the
    2026-10-03 re-run whose estimate is past the default (8,192 rows with a
    1,024-block edge, 11,520, 12,288 and 16,384 rows: `_bwd_plan`) compile
    with what it asks."""
    lanes = _lanes(d)
    both = lanes + _lanes(d_v or d)     # a q/k-wide and a v-wide window
    w, db = 4, 2              # f32 worst case; double-buffered windows
    lse = db * w * 8 * 2 * block_q          # lse8 + delta8 windows
    if mode == "combined":
        wins = db * w * both * (block_q + block_k  # q,do,k,v in
                                + block_k)         # dk,dv out
        dq = (db + 1) * w * lanes * q_len   # whole-seq out window + scratch
        scratch = w * both * block_k        # dk/dv accumulators
        return wins + lse + dq + scratch
    # Split kernels run back to back; scoped peak is the larger one.
    dkdv = (db * w * both * (block_q + 2 * block_k) + lse
            + w * both * block_k)
    dqk = (db * w * (both * (block_q + block_k) + lanes * block_q) + lse
           + w * lanes * block_q)
    return max(dkdv, dqk)


def _combined_vmem_limit(q_len: int, d: int, block_q: int, block_k: int,
                         d_v: Optional[int] = None) -> Optional[int]:
    """The ``vmem_limit_bytes`` the combined backward asks Mosaic for at this
    shape and these blocks, or None where it asks for nothing: what
    :func:`_plan_vmem_bytes` computes fits the budget a kernel has without
    asking (`_vmem_budget_bytes`).  Past it the call asks for the computed
    need and a margin — four float32 ``(block_q, block_k)`` tiles, the body's
    s, p, dp and ds, which the structural estimate does not charge and Mosaic
    puts on the kernel's stack.  The call (:func:`_combined_bwd_call`) asks
    for this and nothing else does (the fused ring reads it to refuse a shard
    that would ask), so there is one source for the number."""
    need = _plan_vmem_bytes("combined", q_len, d, block_q, block_k, d_v)
    if need <= _vmem_budget_bytes():
        return None
    limit = need + 4 * 4 * block_q * block_k
    assert limit <= _MAX_VMEM_LIMIT, (
        f"combined flash backward at {q_len} rows of {d} in ({block_q}, "
        f"{block_k}) blocks would ask for {limit >> 20} MiB of scoped VMEM: "
        "past every band of _bwd_plan")
    return limit


def _fwd_vmem_bytes(q_len: int, d: int, block_q: int,
                    block_k: int, d_v: Optional[int] = None) -> int:
    """Same structural estimate for the forward kernel (q in + out + k/v
    windows, lse output, online-softmax scratch); the output and its
    accumulator have v's width."""
    lanes_v = _lanes(d_v or d)
    w, db = 4, 2
    return (db * w * (_lanes(d) + lanes_v) * (block_q + block_k)
            + db * w * 8 * block_q                       # lse out
            + w * block_q * (2 * 128 + lanes_v))         # m/l/acc scratch


def _clamp_blocks(mode: str, q_len: int, d: int, block_q: int,
                  block_k: int, estimate=_plan_vmem_bytes):
    """Step a plan's blocks down until ``estimate`` fits the budget.
    Returns the fitted (block_q, block_k), or None when even 128-blocks
    cannot fit (combined's whole-seq dq term: the caller demotes to
    split).  Warns when it changes the requested plan — a clamp means
    the tuned choice would have been the r04 compile-time OOM."""
    budget = _vmem_budget_bytes()
    bq, bk = block_q, block_k
    while estimate(mode, q_len, d, bq, bk) > budget:
        if bq >= bk and bq > 128:
            bq = _pick_block(q_len, bq // 2)
        elif bk > 128:
            bk = _pick_block(q_len, bk // 2)
        elif mode == "combined":
            return None
        else:
            break  # nothing below 128-blocks; the grid is as small as it gets
    if (bq, bk) != (block_q, block_k):
        warnings.warn(
            f"attention {mode} blocks ({block_q}, {block_k}) at "
            f"seq {q_len}/head_dim {d} exceed the scoped-VMEM budget "
            f"({budget >> 20} MiB, HVD_TPU_VMEM_LIMIT_MB); clamped to "
            f"({bq}, {bk})", stacklevel=3)
    return bq, bk


def _bwd_plan(q_len: int, d: int, block_q: int, block_k: int,
              bh: int = 1, d_v: Optional[int] = None):
    """Choose the flash-backward execution mode and blocks against the
    16 MiB of scoped VMEM a kernel has without asking and, in two bands, the
    limit its call asks Mosaic for (`_combined_vmem_limit`).

    Calibrated by a compile sweep for v5e (tools/vmem_sweep.py; the
    bands the benchmark's cells use are compiled for a described chip in
    tests/test_ops.py).  Mosaic's scoped allocation for the combined
    kernel is NOT a simple closed form — it grows with the whole-seq dq
    scratch (head_dim <= 128 pads to 128 lanes, so sequence length
    enters as ``q_len * lanes``), with block size, and
    NON-MONOTONICALLY with the batch*heads grid dimension (measured:
    seq 8192 at 1024-blocks is 23.2 MiB at bh=16 but 16.5 MiB at
    bh=32; seq 8192 at 512-blocks fits at bh<=32 and exceeds by 0.17
    MiB at bh=64) — so the bands below come from the measured pass/fail
    frontier with margin, not a model:

    A head is laid out in whole 128-lane tiles (`_lanes`: 64 in 128, 192 in
    256), so seq enters as ``rows128 = q_len * lanes / 128``.  Heads past 128
    lanes fail WITHOUT ASKING at shapes whose 128-lane equivalents fit
    (measured d=256: 17.9 MiB at seq 1024/bh 64 with 1024-blocks, 18.8 MiB
    at seq 2048/bh 64 with (512, 1024) — where d=64 passes both at bh up to
    1024), which kept them on the pair until the call could ask (PR 57) and
    the wide-head region was swept and timed (PR 67, below):

    ==========  ====================  ==========  ==========================
    lanes       rows128               bh          choice
    ==========  ====================  ==========  ==========================
    128         <= 2048               <= 1024     combined, tuned blocks (1024)
    128         <= 4096               <= 512      combined (512, 1024)
    128         <= 8192               <= 32       combined (512, 512)
    128         8192 < .. <= 16384    <= 128      combined (512, 512), ASKING
    256         <= 8192, ASKING       <= 128      combined (512, 512), ASKING
    256         8192 < .. <= 16384    <= 128      combined (1024, 1024), ASKING
    otherwise                         any         split, tuned blocks (1024)
    ==========  ====================  ==========  ==========================

    ASKING (PR 57): the band's whole-sequence dq does not fit what a kernel
    has without asking, so its call names ``vmem_limit_bytes`` — what
    :func:`_plan_vmem_bytes` computes and a margin, 31.6 MiB of the core's
    128 at 16,384 rows (:func:`_combined_vmem_limit`, well inside
    `_MAX_VMEM_LIMIT`).  The band is outside the clamp under
    ``HVD_TPU_VMEM_LIMIT_MB`` by design (a call that names its limit is not
    bound by what a kernel has without asking), and is entered only where
    that budget is the default's 16 MiB or more: set lower, the band's rows
    take the pair as they did before it.  The bands above it ask for nothing
    and lower to the text they always lowered to.

    WIDE HEADS (PR 67): two tiles of lanes — latent attention's 192 (q, k) /
    128 (v), which the estimate charges as 256 / 128, and heads of 256 — take
    the combined kernel up to the whole-sequence dq the asking band holds
    (8,192 rows of 256 lanes), at the bh the sweep probed, under the same
    rule of the budget, and ONLY WHERE THE CALL ASKS: 8,192 rows of 192 / 128
    ask for 50.6 MiB in their 1,024-blocks, 4,096 of 256 for 23.1 in
    512-blocks, 2,048 of either for nothing.  Blocks by measurement, the
    backward alone in a program, bf16, causal (``tools/flash_bwd_sweep.py
    --d-v``, medians of 10 on a v5e, my chip run, PR 67), ms — the pair in
    1,024-blocks / the combined kernel in (512, 512), (512, 1024),
    (1024, 1024): JoyAI's (1, 32, 8192, 192 / 128) 21.91 / 17.35, 17.24,
    **16.75**; Ling's (1, 4, 8192, 192 / 128) 3.449 / **2.822**, 2.890,
    2.835; Qwen3-Next's (1, 16, 4096, 256) 4.595 (4.435 in 512-blocks) /
    **3.495**, 3.629, 3.561 — the combined kernel takes 0.76 to 0.82 of the
    pair's time at every shape, so 256 lanes enter the band with 192 / 128.
    At 8,192 rows the 1,024-blocks win where there is work to tell (bh 32:
    3.5 % of the kernel, and IN THE JOYAI STEP 28,057 tok/s/chip against
    27,700 in (512, 512), +1.29 %, two pairs, the parent 25,391) and tie at
    bh 4; at 4,096 rows the (512, 512) blocks win by 1.9 % (a 1,024-tile on
    the diagonal computes its masked half, 4 of a head's 10 tiles there
    against 8 of 36): the band takes 1,024-blocks past 4,096 rows and 512 up
    to there.  A wide head short enough to ask for nothing keeps
    the pair — those kernels compile too (55 probes below), but nobody has
    timed them, the old frontier above was theirs, and "a wide-head plan
    always asks" is what lets the fused ring (`ops/ring_flash.py`, whose
    rotating kernel was never probed past 128 lanes) refuse one by the rule
    it has.  Past 256 lanes, past the probes' bh and past 8,192 rows
    (16,384 rows of 192 / 128 compile at 57.3 MiB in (512, 512), 7 MiB under
    `_MAX_VMEM_LIMIT`, and not in 1,024-blocks; no cell, no timing): the
    pair.

    Blocks by measurement, the backward alone in
    a program at (1, 32, 16384, 128) bf16 (``tools/flash_bwd_sweep.py``,
    medians of 10 on a v5e, my chip run, PR 57), causal / under a window of
    1,024, ms: the pair in 1,024-blocks 50.71 / 13.59 and in 512-blocks
    54.98 / 11.71; combined (512, 512) 38.63 / 8.69, (512, 1024) 36.74 /
    10.04, (1024, 1024) 35.18 / 9.69.  One causal and three banded calls
    (Mellum2's layers): 91.5 for the pair, 64.7 and 64.2 combined in 512- and
    in 1,024-blocks — a tie, taken in 512-blocks (the smaller limit, and a
    1,024-key band in three tiles where 1,024-blocks walk two); blocks by
    mask (1,024 causal, 512 banded: 61.2) would save 4.7 % more, under the
    5 % that would have paid for a plan that reads the mask.

    Re-run 2026-09-30 (PR 44: the kernels' grids became ``(bh, live
    tiles)`` tables, each kernel keeping the bodies it had;
    ``tools/vmem_sweep.py --full``, libtpu 0.0.34 compiling for a described
    v5e, at commit 585dfaa and at the change; the plan's blocks and the
    benchmark cells' shapes under their three masks also on the chip): 156
    probes — d in {64, 128} x (seq: bh) in {1024: 128, 1024; 2048: 64, 1024;
    4096: 32, 128, 512; 8192: 16, 32, 64, 128; 16384: 8, 128} x six block
    pairs from (256, 256) to (1024, 1024), each forced onto the combined
    kernel.  The frontier did not move with the grid: probe for probe, parent
    and change pass (124) and fail (32) alike.  The combined kernel compiles
    at every probe up to seq 8192 except (1024, 1024) at 8192 (every bh), and
    at no probe at seq 16384 — this compiler is more permissive than the
    bands (8192 in (512, 512) passes at bh 64 and 128; 4096 passes in
    1024-blocks), which stay as calibrated: they choose every benchmark
    cell's blocks.

    Re-run 2026-10-03 (PR 57: the combined call asks for the scoped VMEM its
    plan computes; ``tools/vmem_sweep.py --full``, libtpu 0.0.34 compiling for
    a described v5e): 208 probes — the 156 above, 16,384 rows at bh 8, 16, 32,
    64 and 128 where it had 8 and 128, and 12,288 rows (bh 32) and 11,520
    (bh 8, its 384-blocks) inside the new band.  **208 pass, none fails**: the
    108 whose estimate fits the default ask for nothing and pass as they did;
    the 100 past it — every probe at 16,384, 12,288 and 11,520 rows, and 8,192
    rows with a 1,024-block edge, the 32 failures of the run above among them
    — compile with the limit their call names (19.7 to 47.1 MiB).  Outside
    the bands and not in the sweep (issue 57's compiles): 32,768 rows compile
    at 96–100 MiB and 65,536 at 110 MiB, past `_MAX_VMEM_LIMIT`; 4,096 rows of
    256 lanes at 32 MiB (wide heads stayed on the pair until PR 67).

    Wide heads, 2026-10-05 (PR 67: ``tools/vmem_sweep.py --wide``, libtpu
    0.0.34 compiling for a described v5e, the estimate's widths rounded up to
    whole tiles): 240 probes — (d, d_v) in {(192, 128), (256, 256)} x rows
    {2,048, 4,096, 8,192, 16,384} x bh {4, 16, 32, 64, 128} x the six block
    pairs, each forced onto the combined kernel.  **Every probe whose call
    may be made compiles, 215 of 215**: the 55 that ask for nothing (2,048
    rows in all but the largest blocks and 4,096 in (256, 256)) and the 160
    that name their limit (18.5 to 62.9 MiB; every probe at 8,192 rows, 33.3
    MiB in (512, 512) and 50.6 in (1024, 1024) at 192 / 128); the other 25
    — 16,384 rows with a 1,024-block edge — would ask for 65 to 78 MiB, past
    `_MAX_VMEM_LIMIT`, and the call refuses them itself.  bh moved nothing.

    ``mode`` is ``"combined"`` (one probability recompute per block,
    whole-seq dq scratch — preferred where it fits because it recomputes
    once; the benchmark's cells up to 16,384 rows of heads up to 128 run it,
    PERF.md section 3) or ``"split"`` (dkdv + dq kernel pair, O(block)
    scoped memory: full 1024-blocks compile at every probed extreme — seq to
    64k, bh to 256, d to 256).  No cell of the benchmark runs the pair since
    PR 67 (Ling's and JoyAI's 192 / 128 and Qwen3-Next's 256 ran it until
    then).  Until PR 57 Mellum2's ran it at head 128 —
    16,384 rows, bh 32, 1,024-blocks — where the causal pair took 27.2 + 21.5
    ms a layer for a forward call's 19.2 and read 57.4 % of the backward's
    roofline, the banded pair 29.0 % (ledger, PR 56).  That was the pair's
    second pass over the probabilities showing (2.5 forwards' work counted,
    3.5 done), and the two at ONE shape are the table above: in equal
    512-blocks the combined kernel takes 0.70 and 0.74 of the pair's time
    (five products a tile where the pair does seven: 0.71).

    ``d_v``: the width of v, do and dv where it is not ``d`` (q, k, dq, dk).
    The bands above are entered with the WIDER of the two, and
    :func:`_plan_vmem_bytes` charges each window at its own width: latent
    attention's 192 and 128 enter as 256 lanes and are charged 256 and
    128."""
    wide = max(d, d_v or d)
    estimate = functools.partial(_plan_vmem_bytes, d_v=d_v)
    rows128 = q_len * _lanes(wide) // 128
    may_ask = _vmem_budget_bytes() >= _DEFAULT_VMEM_MB * (1 << 20)
    if wide <= 128:
        # Each band is gated at its CALIBRATED bh bound (the table
        # above); anything beyond falls through to split, which
        # compiles everywhere — never extrapolate the combined kernel
        # past a probed region (the r4 lesson).  The band choice is then
        # backstopped against the COMPUTED budget (_plan_vmem_bytes):
        # a shrunken HVD_TPU_VMEM_LIMIT_MB, or a band edge the sweep's
        # granularity missed, clamps blocks down (warning) or demotes to
        # split instead of handing Mosaic a plan that cannot compile.  The
        # asking band is outside that clamp — its call names its own limit
        # — and is entered only under a budget of the default or more, so a
        # shrunken one sends its rows to the pair too.
        choice = None
        if rows128 <= 2048 and bh <= 1024:
            choice = (block_q, block_k)
        elif rows128 <= 4096 and bh <= 512:
            choice = (_pick_block(q_len, min(block_q, 512)),
                      _pick_block(q_len, min(block_k, 1024)))
        elif rows128 <= 8192 and bh <= 32:
            choice = (_pick_block(q_len, min(block_q, 512)),
                      _pick_block(q_len, min(block_k, 512)))
        elif 8192 < rows128 <= 16384 and bh <= 128 and may_ask:
            # Past what a kernel has without asking: the call names its
            # limit (`_combined_vmem_limit`: the blocks' need and a margin,
            # or nothing under a budget raised past the need).
            return ("combined",
                    _pick_block(q_len, min(block_q, 512)),
                    _pick_block(q_len, min(block_k, 512)))
        if choice is not None:
            fitted = _clamp_blocks("combined", q_len, d, *choice,
                                   estimate=estimate)
            if fitted is not None:
                return ("combined",) + fitted
            warnings.warn(
                f"combined attention backward at seq {q_len}/head_dim "
                f"{d} cannot fit the scoped-VMEM budget "
                f"({_vmem_budget_bytes() >> 20} MiB) at any block size "
                "(whole-seq dq scratch); demoting to the split kernels",
                stacklevel=2)
    elif wide <= 256 and rows128 <= 16384 and bh <= 128 and may_ask:
        # The wide-head band (PR 67): a whole-sequence dq no larger than
        # the asking band's above, two 128-lane tiles wide, in the blocks
        # the timing table chose — 1,024 past 4,096 rows, 512 up to there.
        # Entered only where the call ASKS (the estimate at these blocks is
        # past what a kernel has without asking: 4,096 rows are, 2,048 are
        # not): every wide-head combined plan names its limit, which is how
        # the fused ring knows to refuse one.
        edge = 1024 if rows128 > 8192 else 512
        blocks = (_pick_block(q_len, min(block_q, edge)),
                  _pick_block(q_len, min(block_k, edge)))
        if _combined_vmem_limit(q_len, d, *blocks, d_v) is not None:
            return ("combined",) + blocks
    fitted = _clamp_blocks("split", q_len, d, _pick_block(q_len, block_q),
                           _pick_block(q_len, block_k), estimate=estimate)
    return ("split",) + fitted


def _split_bwd_call(q, do, lse8, delta8, k, v, *, mask, block_q,
                    block_k, interpret, scale_r, grad_dtype=jnp.float32,
                    dq_scale=1.0, operands=(), heads=1):
    """Split flash backward over (bh, sl, d) operands (q pre-scaled by
    the pow2 part of sm_scale): two pallas_calls — dk/dv (key tiles outer,
    queries inner) and dq (query tiles outer, keys inner) — each with
    O(block) scoped VMEM, so any
    sequence length compiles.  Pays the s/p/dp/ds recompute twice (at
    16,384 rows of 128 it takes 1.3 to 1.6 times the combined kernel's time:
    the table in `_bwd_plan`, my chip run, PR 57); the combined kernel is
    preferred wherever its whole-seq dq scratch fits the scoped VMEM it has
    or asks for — up to 16,384 rows of heads up to 128 lanes, 8,192 of heads
    up to 256 — and this pair runs past that: longer sequences, heads past
    256 lanes, bh past a band's probes, a wide head whose combined call would
    ask for nothing (see _bwd_plan; the re-runs of ``tools/vmem_sweep.py``
    left it no probe the combined kernel fails).  Returns (dk, dv, dq) in
    ``grad_dtype`` (f32
    accumulation in scratch; the flush casts).  Each kernel's grid is its
    table's live tiles (`_tile_table`)."""
    bh, sl, d = q.shape
    d_v = v.shape[-1]              # do, v and dv; q, k, dq and dk have d
    tiles = (sl // block_q, sl // block_k, block_q, block_k, mask)
    in_specs = [_tile_spec(0, block_q, d), _tile_spec(0, block_q, d_v),
                _lse_spec(block_q), _lse_spec(block_q),
                _tile_spec(1, block_k, d), _tile_spec(1, block_k, d_v)]
    # vma: inside shard_map (build_train_step) the default check refuses
    # an out_shape that does not say how it varies; as q does.
    grad_shape = jax.ShapeDtypeStruct((bh, sl, d), grad_dtype,
                                      vma=jax.typeof(q).vma)
    dv_shape = jax.ShapeDtypeStruct((bh, sl, d_v), grad_dtype,
                                    vma=jax.typeof(q).vma)
    common = dict(mask=mask, block_q=block_q, block_k=block_k,
                  scale_r=scale_r)
    dkdv_kernel = functools.partial(_flash_bwd_dkdv_kernel, **common)
    dq_kernel = functools.partial(_flash_bwd_dq_kernel, dq_scale=dq_scale,
                                  **common)
    if operands:
        dkdv_kernel, _ = _feed(dkdv_kernel, mask, in_specs, block_q, block_k,
                               heads)
        dq_kernel, in_specs = _feed(dq_kernel, mask, in_specs, block_q,
                                    block_k, heads)
    dk, dv = _tiled_call(
        dkdv_kernel,
        _tile_table(*tiles, by_key=True), bh,
        in_specs=in_specs,
        out_specs=(_tile_spec(1, block_k, d), _tile_spec(1, block_k, d_v)),
        out_shape=(grad_shape, dv_shape),
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d_v), jnp.float32)],
        interpret=interpret,
        name="hvd_flash_bwd_dkdv" + mask.suffix,
    )(q, do, lse8, delta8, k, v, *operands)
    dq = _tiled_call(
        dq_kernel,
        _tile_table(*tiles), bh,
        in_specs=in_specs,
        out_specs=_tile_spec(0, block_q, d),
        out_shape=grad_shape,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="hvd_flash_bwd_dq" + mask.suffix,
    )(q, do, lse8, delta8, k, v, *operands)
    return dk, dv, dq


# The most (query tile, key tile) pairs a schedule's table may list: it pads
# to 16 bytes a step in SMEM, 1 MiB on a v5e (compiled for a described chip:
# 57,970 steps pass, 65,341 do not).
_TABLE_STEPS = 56 * 1024


def _off_grid(q_len, k_len, block_q, block_k, mask) -> bool:
    """Whether the blocks leave the kernels' grid: ragged tails, blocks off
    the TPU tiling (the lse output block puts ``block_q`` in the 128-lane
    dimension), a tile that would lie across two of the mask's copies (the
    blocks divide ONE copy's rows), or more tile pairs, counted before any
    mask, than a table holds (`_TABLE_STEPS`: 128-blocks past 30,000
    rows)."""
    pairs = (q_len // block_q) * (k_len // block_k)
    q_len, k_len = q_len // mask.copies, k_len // mask.copies
    return bool(q_len % block_q or k_len % block_k
                or block_q % 128 or block_k % 128 or pairs > _TABLE_STEPS)


def _backward_blocks(q_len, k_len, d, d_v, block_q, block_k, bh, mask):
    """(mode, block_q, block_k) as :func:`_bwd_plan` gives the backward at
    ``flash_attention``'s blocks, or None where the shape leaves the kernels
    for the scan."""
    block_q = min(block_q, q_len)
    block_k = min(block_k, k_len)
    if _off_grid(q_len, k_len, block_q, block_k, mask) or q_len != k_len:
        return None
    # One width: the call as every caller and test stand-in has known it.
    widths = {} if d_v == d else {"d_v": d_v}
    plan = _bwd_plan(q_len, d, block_q, block_k, bh, **widths)
    # The plan may step blocks down past what divides this length (rare
    # non-power-of-two long seqs): the scan impl handles it.
    return None if _off_grid(q_len, k_len, *plan[1:], mask) else plan


def _flash_backward(q, k, v, out, lse, g, mask, sm_scale, block_q,
                    block_k, interpret, operands=()):
    """Pallas flash backward.  Two kernel strategies, chosen per shape by
    :func:`_bwd_plan` against the scoped VMEM a kernel has or asks for: the
    combined kernel computes dk/dv AND dq from a single probability recompute
    per block (whole-seq dq scratch), the split dkdv/dq pair recomputes twice
    but needs only O(block) scoped memory (past 16,384 rows of 128 lanes or
    8,192 of 256).  Residual
    memory is O(seq) either way (Dao et al. alg. 2)."""
    batch, heads, q_len, d = q.shape
    k_len, d_v = k.shape[2], v.shape[-1]
    plan = _backward_blocks(q_len, k_len, d, d_v, block_q, block_k,
                            batch * heads, mask)
    if plan is None:
        if operands:
            raise ValueError(_OPERANDS_OFF_GRID)
        return _attention_bwd_impl(q, k, v, out, lse, g, mask, sm_scale,
                                   max(min(block_k, k_len), 128), 0, 0)
    mode, block_q, block_k = plan
    fed = dict(operands=operands, heads=heads) if operands else {}
    bh = batch * heads
    # Pre-scaled q (see _flash_forward): exact pow2 factor on q, f32
    # residual inside the kernel; dq comes back in q' units and is
    # rescaled once below.
    p2, scale_r = _split_scale(sm_scale)
    qr = (q * p2).astype(q.dtype).reshape(bh, q_len, d)
    kr = k.reshape(bh, k_len, d)
    vr = v.reshape(bh, k_len, d_v)
    dor = g.reshape(bh, q_len, d_v)
    # delta_i = sum_d dOut_id * Out_id; 8 broadcast sublanes keep the
    # (8, 128) tiling legal, same trick as the forward's lse output.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, q_len)
    delta8 = jnp.broadcast_to(delta[:, None, :], (bh, 8, q_len))
    lse8 = jnp.broadcast_to(lse.reshape(bh, q_len)[:, None, :],
                            (bh, 8, q_len))
    # Gradients emitted directly in the input dtype, with the pow2 dq
    # rescale folded into the kernels' f32 flush, which saves XLA-side
    # cast+relayout and rescale passes over the 3 gradients.  The
    # f32-multiply-then-cast order also keeps narrow-exponent dtypes
    # (fp16) finite where cast-then-scale could overflow in q' units.
    # Mixed input dtypes keep the old f32 emission (dk must not round
    # through q.dtype when k is wider).
    same_dtype = q.dtype == k.dtype == v.dtype
    grad_dtype = q.dtype if same_dtype else jnp.float32
    if mode == "combined":
        dk, dv, dq = _combined_bwd_call(
            qr, dor, lse8, delta8, kr, vr, mask=mask,
            block_q=block_q, block_k=block_k, interpret=interpret,
            scale_r=scale_r, grad_dtype=grad_dtype, dq_scale=p2,
            # A masked call's name keeps the prefix a trace is read by.
            name="hvd_flash_bwd" + mask.suffix, **fed)
    else:
        dk, dv, dq = _split_bwd_call(
            qr, dor, lse8, delta8, kr, vr, mask=mask,
            block_q=block_q, block_k=block_k, interpret=interpret,
            scale_r=scale_r, grad_dtype=grad_dtype, dq_scale=p2, **fed)
    return (dq.astype(q.dtype).reshape(q.shape),
            dk.astype(k.dtype).reshape(k.shape),
            dv.astype(v.dtype).reshape(v.shape))


def _forward_blocks(q_len, k_len, d, d_v, block_q, block_k, mask):
    """The (block_q, block_k) the forward kernel runs ``flash_attention``'s
    blocks at, or None where the shape leaves the kernel for the scan."""
    block_q = min(block_q, q_len)
    block_k = min(block_k, k_len)
    if _off_grid(q_len, k_len, block_q, block_k, mask) \
            or (mask.square and q_len != k_len):
        # Ragged tails or blocks off the TPU tiling grid: the blockwise path
        # handles them without padding gymnastics (the kernel targets the
        # aligned hot path).
        return None
    # Backstop explicit oversized blocks against the scoped-VMEM budget
    # (the default <=1024 blocks peak ~6 MiB and never clamp).
    blocks = _clamp_blocks(
        "forward", q_len, d, block_q, block_k,
        estimate=lambda _m, s, dd, bq, bk: _fwd_vmem_bytes(s, dd, bq, bk,
                                                           d_v))
    # clamped past what divides the rows (a copy's)
    return None if _off_grid(q_len, k_len, *blocks, mask) else blocks


def _flash_forward(q, k, v, mask, sm_scale, block_q, block_k, interpret,
                   operands=()):
    """Returns (out, lse); routes off-grid shapes to the blockwise impl
    (``operands``: a kind's arrays, `Mask`, which stay on the grid)."""
    batch, heads, q_len, d = q.shape
    k_len, d_v = k.shape[2], v.shape[-1]
    blocks = _forward_blocks(q_len, k_len, d, d_v, block_q, block_k, mask)
    if blocks is None:
        if operands:
            raise ValueError(_OPERANDS_OFF_GRID)
        return _blockwise_fwd_impl(q, k, v, mask, sm_scale,
                                   max(min(block_k, k_len), 128), 0, 0)
    block_q, block_k = blocks
    bh = batch * heads
    # Pre-scale q by the exact power-of-two part of sm_scale: one
    # (seq, d) multiply here replaces a (seq, seq) pass inside the
    # kernel; the f32 residual (1.0 for power-of-4 head dims) is applied
    # to the logits in-kernel, so non-pow2 scales lose no precision.
    p2, scale_r = _split_scale(sm_scale)
    qr = (q * p2).astype(q.dtype).reshape(bh, q_len, d)
    kr = k.reshape(bh, k_len, d)
    vr = v.reshape(bh, k_len, d_v)
    vma = jax.typeof(q).vma  # see _split_bwd_call
    table = _tile_table(q_len // block_q, k_len // block_k, block_q, block_k,
                        mask)
    kernel = functools.partial(
        _flash_kernel, mask=mask, block_q=block_q, block_k=block_k,
        single_k=table.shape[1] == q_len // block_q, scale_r=scale_r)
    in_specs = [_tile_spec(0, block_q, d), _tile_spec(1, block_k, d),
                _tile_spec(1, block_k, d_v)]
    if operands:
        kernel, in_specs = _feed(kernel, mask, in_specs, block_q, block_k,
                                 heads)
    out, lse = _tiled_call(
        kernel, table, bh,
        in_specs=in_specs,
        out_specs=(_tile_spec(0, block_q, d_v), _lse_spec(block_q)),
        out_shape=(
            jax.ShapeDtypeStruct((bh, q_len, d_v), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, 8, q_len), jnp.float32, vma=vma),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running normalizer
            pltpu.VMEM((block_q, d_v), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
        name="hvd_flash_fwd" + mask.suffix,
    )(qr, kr, vr, *operands)
    return (out.reshape(batch, heads, q_len, d_v),
            lse[:, 0, :].reshape(batch, heads, q_len))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, mask, sm_scale, block_q, block_k, interpret):
    return _flash_forward(q, k, v, mask, sm_scale, block_q, block_k,
                          interpret)[0]


def _flash_fwd(q, k, v, mask, sm_scale, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, mask, sm_scale, block_q, block_k,
                              interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(mask, sm_scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, mask, sm_scale, block_q,
                           block_k, interpret)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)

_OPERANDS_OFF_GRID = (
    "a mask with operands runs on the kernels' grid alone: as many queries "
    "as keys, in whole blocks of a multiple of 128 rows")


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_attention_fed(q, k, v, operands, mask, sm_scale, block_q, block_k,
                         interpret):
    """`_flash_attention` under a kind with ``operands`` (a tuple of arrays,
    which take no gradient): (out, the rows' log-sum-exp).  The second is
    there for a pass that reads the probabilities again, and takes no
    cotangent."""
    return _flash_forward(q, k, v, mask, sm_scale, block_q, block_k,
                          interpret, operands)


def _flash_fed_fwd(q, k, v, operands, mask, sm_scale, block_q, block_k,
                   interpret):
    out, lse = _flash_forward(q, k, v, mask, sm_scale, block_q, block_k,
                              interpret, operands)
    return (out, lse), (q, k, v, operands, out, lse)


def _flash_fed_bwd(mask, sm_scale, block_q, block_k, interpret, res, g):
    q, k, v, operands, out, lse = res
    grads = _flash_backward(q, k, v, out, lse, g[0], mask, sm_scale, block_q,
                            block_k, interpret, operands)
    return (*grads, tuple(
        jnp.zeros_like(o) if jnp.issubdtype(o.dtype, jnp.inexact)
        else np.zeros(o.shape, jax.dtypes.float0) for o in operands))


_flash_attention_fed.defvjp(_flash_fed_fwd, _flash_fed_bwd)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _fed_call(mask, sm_scale, block_q, block_k, interpret, q, k, v,
              operands):
    """`_flash_attention_fed` jitted: a model's layers of one shape trace
    and lower its kernels once (as `ops.moe._tiled_call`, and for its
    reason)."""
    return _flash_attention_fed(q, k, v, operands, mask, sm_scale, block_q,
                                block_k, interpret)


def masked_flash_attention(q, k, v, mask: Mask, *operands,
                           sm_scale: Optional[float] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """:func:`flash_attention` under ``mask``, any kind of :class:`Mask`, with
    the ``operands`` the kind declares (:class:`Selected`: the selection as
    ``int8[batch, seq, seq]``) — ``(out, lse)``, ``(batch, heads, seq,
    head_dim)`` operands and output, ``lse`` the rows' float32 log-sum-exp
    ``(batch, heads, seq)``.  The kernels carry the kind's suffix behind
    their names (``hvd_flash_fwd_selected``, ``hvd_flash_bwd_selected``,
    ``hvd_flash_bwd_dkdv_selected``, ``hvd_flash_bwd_dq_selected``); q, k and
    v take gradients, the operands and ``lse`` none.  A kind with operands
    stays on the kernels' grid (a ValueError elsewhere)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    mask = mask.checked(q.shape[-2], k.shape[-2])
    if len(operands) != mask.operands:
        raise ValueError(f"{mask!r} takes {mask.operands} operand(s), not "
                         f"{len(operands)}")
    blocks = _default_blocks(q.shape[-2], k.shape[-2], block_q, block_k, mask)
    return _fed_call(mask, float(sm_scale), *blocks, bool(interpret), q, k, v,
                     tuple(operands))


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    layout: str = "bhsd",
                    window: Optional[int] = None,
                    block_diffusion: Optional[int] = None):
    """Fused multi-head attention.

    The mask is one of :class:`Mask`'s kinds (which owns what the kind means
    everywhere: docs/api.md, "adding a mask"): none; ``causal=True``
    (:class:`Causal`), with ``window=W`` a sliding window of the query's own
    key and the ``W - 1`` before it — ``W`` need not divide by the block, and
    ``W >= seq`` IS the causal call, program for program; or
    ``block_diffusion=B`` (:class:`BlockDiffusion`, with neither of the
    others): the rows are ``[clean; noised]``, two copies of ``L`` positions
    in blocks of ``B``, a block-diffusion model's training pass, whose blocks
    divide ``L``.  A windowed call's kernels carry ``_window`` behind their
    names in a trace (``hvd_flash_fwd``, ``hvd_flash_bwd``,
    ``hvd_flash_bwd_dkdv``, ``hvd_flash_bwd_dq``), block diffusion's
    ``_blockdiff``; :func:`mask_blocks` counts what the forward visits.

    Under every mask a kernel's grid is ``(batch * heads, live tiles)``: the
    (query block, key block) pairs the mask touches, listed on the host from
    the static shapes (:func:`_tile_table`) and handed to the grid by scalar
    prefetch — the causal mask's pairs on and under the diagonal, a window's
    band, block diffusion's two runs a row (24 of the 64 pairs of
    1,024-blocks at ``L = 4,096``, where a causal walk over ``2 L`` takes
    36).  A pair wholly outside the mask is neither a grid step nor a copy,
    forward or backward.  The causal and the banded kernels hold ONE body,
    which masks every tile it is given; block diffusion's hold two, and the
    table's flag sends a tile the mask leaves whole to the one with no mask
    arithmetic (:func:`flash_grid_steps` counts a shape's live pairs, grid
    steps and the rectangles the tables replaced).

    ``v``'s last axis may differ from ``q``'s and ``k``'s (latent attention:
    a 192-wide query and key, a 128-wide value); the output has ``v``'s, and
    nothing is padded.  ``layout="bhsd"`` takes ``(batch, heads, seq,
    head_dim)``;
    ``layout="bshd"`` accepts ``(batch, seq, heads, head_dim)`` — the
    shape QKV projections naturally produce — and returns the same layout.
    (Internally bshd transposes to bhsd: Mosaic's block tiling cannot
    address a 1-wide head slot, so a transpose-free strided read is not
    lowerable; the option exists so callers never have to think about
    head-major conventions.)

    A Pallas kernel (MXU-tiled blocks, VMEM online-softmax state):
    compiled by Mosaic on a TPU backend, run by the Pallas interpreter
    elsewhere (``interpret=None`` asks the backend; pass it explicitly to
    compile for a described chip).  Three documented routings leave the
    kernel for the mathematically identical :func:`blockwise_attention`
    scan — ragged block tails, more tiles than a schedule's table holds
    (:func:`_off_grid`), and float16 on the compiled path — so a
    caller who must know which ran reads the compiled HLO for
    ``tpu_custom_call`` (chip_smoke.py does).  Differentiable
    with the flash backward (logsumexp residual + per-block recompute,
    O(seq) memory).  Default blocks: up to 1024 each, the largest
    candidate dividing the sequence — larger blocks amortize the fixed
    cost of a grid step (the static schedule for a described v5e counts
    fewest bundles per pair at (1024, 1024): PERF.md section 7) and
    whole-k key blocks skip the online-softmax rescale (the kernel's
    single_k path).  The BACKWARD re-plans blocks per shape against the
    scoped VMEM a kernel has (16 MiB without asking; at 16,384 rows the
    combined kernel asks Mosaic for what its plan computes) and switches to
    the split dkdv/dq kernel pair past 16,384 rows or 128 lanes (see
    :func:`_bwd_plan`: a tuned block choice that fits the forward need not
    compile for the backward at seq 8192; a mask changes no plan).
    """
    if layout not in ("bhsd", "bshd"):
        raise ValueError(f"unknown layout {layout!r}")
    if layout == "bshd":
        t = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
        return t(flash_attention(t(q), t(k), t(v), causal=causal,
                                 sm_scale=sm_scale, block_q=block_q,
                                 block_k=block_k, interpret=interpret,
                                 window=window,
                                 block_diffusion=block_diffusion))
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    mask = Mask.of(q.shape[-2], k.shape[-2], causal, window, block_diffusion)
    if not interpret and jnp.float16 in (q.dtype, k.dtype, v.dtype):
        # float16 is not a native TPU type and Mosaic refuses the kernel
        # outright (verified on v5e: even the forward fails to compile) —
        # route to the mathematically identical scan implementation
        # instead of crashing at compile time.  bf16 is the supported
        # half-precision on TPU.
        return blockwise_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                   window=window,
                                   block_diffusion=block_diffusion)
    return _flash_attention(
        q, k, v, mask, sm_scale,
        *_default_blocks(q.shape[-2], k.shape[-2], block_q, block_k, mask),
        interpret)


def _default_blocks(q_len, k_len, block_q, block_k, mask):
    """The blocks a call takes where it names none: of the rows the blocks
    must divide (one copy's).  An explicit block past _MAX_BLOCK is cut to it
    as the default is: the chip's compiler refuses both passes there (scoped
    VMEM: 2048-row blocks at seq 2048 fail the backward, 4096 at seq 4096 the
    forward — compiled for a described v5e, tests/test_ops.py), whatever the
    structural estimates say (ADVICE r5 #2)."""
    if block_q is None or block_q > _MAX_BLOCK:
        # 1024-row query blocks: a grid step has a fixed cost, so the
        # largest block that compiles does the fewest of them (bundles
        # per pair by block shape: PERF.md section 7).
        block_q = _pick_block(q_len // mask.copies, maximum=_MAX_BLOCK)
    if block_k is None or block_k > _MAX_BLOCK:
        # Whole-k key blocks skip the online-softmax rescale entirely
        # (the kernel's single_k fast path) and the backward's key loop.
        block_k = _pick_block(k_len // mask.copies, maximum=_MAX_BLOCK)
    return block_q, block_k


def _live_tiles(seq, blocks, mask):
    """The (query tile, key tile) pairs of ``seq`` rows in ``blocks`` that a
    mask touches."""
    return int(mask.tiles(seq // blocks[0], seq // blocks[1],
                          *blocks)[0].sum())


def mask_blocks(seq: int, d: int, d_v: Optional[int] = None,
                causal: bool = False, window: Optional[int] = None,
                block_diffusion: Optional[int] = None,
                block_q: Optional[int] = None, block_k: Optional[int] = None):
    """(visited, causal): the (query block, key block) pairs one head's
    FORWARD kernel visits for ``flash_attention`` under these mask keywords
    at ``seq`` rows of width ``d`` (``seq`` counts both copies under
    ``block_diffusion``) in the blocks it takes, and the pairs the causal
    kernel visits over the same rows in the same blocks — 21 and 36 at 8,192
    rows with a window of 2,048 in 1,024-blocks, 70 and 136 in 512-blocks; 24
    and 36 for block diffusion over two copies of 4,096, 80 and 136 in
    512-blocks.  None where the call leaves the kernel for the scan, which
    walks every block."""
    mask = Mask.of(seq, seq, causal, window, block_diffusion)
    blocks = _forward_blocks(
        seq, seq, d, d_v or d,
        *_default_blocks(seq, seq, block_q, block_k, mask), mask)
    if blocks is None:
        return None
    return _live_tiles(seq, blocks, mask), _live_tiles(seq, blocks, Causal())


def flash_grid_steps(seq: int, d: int, bh: int, d_v: Optional[int] = None,
                     causal: bool = False, window: Optional[int] = None,
                     block_diffusion: Optional[int] = None,
                     block_q: Optional[int] = None,
                     block_k: Optional[int] = None):
    """``{kernel's name in a trace: (live, steps, rectangle)}`` for the
    kernels that ``flash_attention`` and its gradient run at ``(batch, heads,
    seq, d)`` operands (``bh = batch * heads``; ``seq`` counts both copies
    under ``block_diffusion``) — the forward, and the combined backward or
    the split pair as :func:`_bwd_plan` decides: ``live`` the (query tile,
    key tile) pairs of the kernel's blocks that the mask touches, ``steps``
    the steps one ``bh`` row of the kernel's grid takes (its table's length),
    ``rectangle`` all the pairs of its blocks, which a grid over (query
    tiles, key tiles) would step through.  The grid is the table of the live
    pairs, so the first two are equal: 36 and 136 for the causal forward and
    backward at 8,192 rows of width 64, of rectangles of 64 and 256.  A pass
    that leaves the kernels for the scan has no entry."""
    mask = Mask.of(seq, seq, causal, window, block_diffusion)
    block_q, block_k = _default_blocks(seq, seq, block_q, block_k, mask)
    tables = {}
    blocks = _forward_blocks(seq, seq, d, d_v or d, block_q, block_k, mask)
    if blocks is not None:
        tables["hvd_flash_fwd"] = blocks, False
    plan = _backward_blocks(seq, seq, d, d_v or d, block_q, block_k, bh, mask)
    if plan is not None and plan[0] == "combined":
        tables["hvd_flash_bwd"] = plan[1:], True
    elif plan is not None:
        tables["hvd_flash_bwd_dkdv"] = plan[1:], True
        tables["hvd_flash_bwd_dq"] = plan[1:], False
    return {name + mask.suffix: (
                _live_tiles(seq, blocks, mask),
                _tile_table(seq // blocks[0], seq // blocks[1], *blocks,
                            mask, by_key).shape[1],
                (seq // blocks[0]) * (seq // blocks[1]))
            for name, (blocks, by_key) in tables.items()}
