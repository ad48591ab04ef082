"""Block-granular KV cache for the serving plane (docs/inference.md).

Two halves, split so the scheduler stays a pure-Python unit:

* :class:`BlockPool` — host-side bookkeeping: a fixed population of
  fixed-size token blocks, allocated all-or-nothing per request growth and
  freed on retirement.  Pool exhaustion is an admission/scheduling signal
  (requests stay queued, running requests preempt), never a crash — the
  vLLM/PagedAttention memory model (Kwon et al., SOSP'23) over our engine.

* The paged device store — ONE packed buffer for every layer's K and V
  (``(n_layers, 2, num_blocks + 1, block_tokens, heads, head_dim)``):
  2·L·B per-sequence tensors become one array, so the jitted step carries
  one argument where it would carry hundreds, gathered per step by block
  table and scattered by (block, offset).  The last block is a write-off target:
  masked lanes of a scatter and table padding both land there, so the
  jitted decode step keeps a fixed shape regardless of which slots are
  live.  :func:`gather_context` / :func:`scatter_new` are pure ``jnp``
  functions used inside the engine's jitted step.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence


class BlockPool:
    """Fixed pool of KV blocks, ``block_tokens`` tokens each.

    Allocation is all-or-nothing (a partial grant would leave a request
    unable to run but holding memory) and LIFO on the free list, so block
    ids stay deterministic across ranks replaying the same admission
    sequence — the scheduler's block tables travel in the broadcast batch
    plan, so determinism here is convenience (debuggability), not
    correctness.
    """

    def __init__(self, num_blocks: int, block_tokens: int):
        if num_blocks < 1 or block_tokens < 1:
            raise ValueError(
                f"need num_blocks >= 1 and block_tokens >= 1, got "
                f"{num_blocks}/{block_tokens}")
        self.num_blocks = num_blocks
        self.block_tokens = block_tokens
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._in_use = 0
        self.peak_in_use = 0

    @property
    def blocks_in_use(self) -> int:
        return self._in_use

    @property
    def blocks_free(self) -> int:
        return len(self._free)

    def blocks_for_tokens(self, tokens: int) -> int:
        """Blocks needed to hold `tokens` cache entries."""
        return max(0, math.ceil(tokens / self.block_tokens))

    def alloc(self, n: int) -> Optional[List[int]]:
        """`n` fresh block ids, or None when the pool cannot satisfy all
        of them (all-or-nothing; the caller queues or preempts)."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            return None
        taken = [self._free.pop() for _ in range(n)]
        self._in_use += n
        self.peak_in_use = max(self.peak_in_use, self._in_use)
        return taken

    def free(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            if not (0 <= b < self.num_blocks):
                raise ValueError(f"block id {b} out of range")
            self._free.append(b)
        self._in_use -= len(blocks)
        assert self._in_use >= 0, "double free"


# ---------------------------------------------------------------------------
# Paged device store (jax; imported lazily so the pure scheduler/pool units
# never pull jax in).
# ---------------------------------------------------------------------------


def init_pages(n_layers: int, n_heads: int, head_dim: int, num_blocks: int,
               block_tokens: int, dtype):
    """The packed page buffer: ``(L, 2, num_blocks + 1, bt, h, hd)``
    zeros; index 0 of axis 1 is K, index 1 is V; block ``num_blocks`` is
    the trash block (see module docstring)."""
    import jax.numpy as jnp

    return jnp.zeros((n_layers, 2, num_blocks + 1, block_tokens,
                      n_heads, head_dim), dtype)


def gather_context(pages, tables):
    """Per-layer K/V context for a decode batch.

    ``tables``: ``(B, max_blocks)`` int32 block ids, padded with the
    trash block.  Returns ``(k_ctx, v_ctx)``, each ``(L, B, heads,
    max_blocks * block_tokens, head_dim)`` — position ``p`` of the
    flattened axis is token ``p`` of that row's cache (tables are kept in
    token order), so the caller's validity mask is just ``p < length``.
    """
    import jax.numpy as jnp

    n_layers, _, _, bt, h, hd = pages.shape
    batch, nb = tables.shape
    ctx = pages[:, :, tables]                       # (L, 2, B, nb, bt, h, hd)
    ctx = ctx.reshape(n_layers, 2, batch, nb * bt, h, hd)
    ctx = jnp.swapaxes(ctx, 3, 4)                   # (L, 2, B, h, S, hd)
    return ctx[:, 0], ctx[:, 1]


def scatter_new(pages, k_new, v_new, tables, lengths, n_new):
    """Write a step's fresh K/V into the pages.

    ``k_new``/``v_new``: ``(L, B, heads, chunk, head_dim)`` (the model's
    decode output).  Row ``b``'s token ``j`` lands at cache position
    ``lengths[b] + j``; lanes with ``j >= n_new[b]`` (padding, idle
    slots) are routed to the trash block, so the write is shape-static.
    """
    import jax.numpy as jnp

    bt = pages.shape[3]
    trash = pages.shape[2] - 1
    chunk = k_new.shape[3]
    pos = lengths[:, None] + jnp.arange(chunk)[None, :]        # (B, chunk)
    block_slot = pos // bt
    # Clip before take_along_axis: an idle slot's garbage position could
    # index past the table; its write is trash-routed below anyway.
    block_slot = jnp.clip(block_slot, 0, tables.shape[1] - 1)
    block = jnp.take_along_axis(tables, block_slot, axis=1)
    off = pos % bt
    valid = jnp.arange(chunk)[None, :] < n_new[:, None]
    block = jnp.where(valid, block, trash)
    # new_kv -> (L, 2, B, chunk, h, hd) to line up with the advanced-index
    # result shape of pages[:, :, block, off].
    new_kv = jnp.stack([k_new, v_new], axis=1)
    new_kv = jnp.swapaxes(new_kv, 3, 4)
    return pages.at[:, :, block, off].set(new_kv)
