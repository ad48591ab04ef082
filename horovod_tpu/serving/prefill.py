"""Bulk (ring-attention) prefill for long prompts (docs/inference.md).

Chunked prefill (the default) walks a prompt through the decode step
``prefill_chunk`` tokens at a time — simple, fixed-shape, but O(prompt)
steps.  For long contexts the serving plane instead runs ONE sequence-
sharded forward over :func:`~horovod_tpu.ops.ring_attention`: the prompt
is split over the device mesh's sequence axis, each shard computes its
layers' K/V locally (projections are position-local; only attention
communicates, around the ring), and the captured per-layer K/V is written
straight into the KV pages.  On a TPU pod slice the mesh spans ranks over
ICI; on a host (and in the CPU test environment) it spans the local
devices.  Enabled by ``HVD_TPU_SERVE_RING_MIN_TOKENS`` > 0 for prompts at
least that long.

The prompt itself cannot ride the fixed-size batch plan, so it travels in
a side broadcast padded to a bucketed length — only a handful of extra
negotiation-cache signatures ever exist, and steady-state decode stays on
the single ``serve.plan`` signature.
"""

from __future__ import annotations

import math
from typing import List, Tuple

import numpy as np

import horovod_tpu as hvd
from horovod_tpu.serving import engine as _engine

# Prompt-buffer bucket: multiples of 256 keep the side-broadcast signature
# count tiny and divide evenly by any power-of-two sequence mesh <= 256.
PROMPT_BUCKET = 256


def bucket_len(n: int) -> int:
    return max(PROMPT_BUCKET, math.ceil(n / PROMPT_BUCKET) * PROMPT_BUCKET)


def broadcast_prompt(feed: List[int], real_len: int) -> Tuple[np.ndarray,
                                                              int]:
    """Root-broadcast a bulk-prefill prompt in a bucketed buffer (rank 0
    passes the tokens; workers pass the empty buffer and receive)."""
    buf = np.zeros(bucket_len(real_len), np.int32)
    if feed:
        buf[:real_len] = feed[:real_len]
    out = hvd.broadcast(buf, 0, name=f"serve.prompt.{len(buf)}")
    return out, real_len


def scatter_bulk(pages, k_all, v_all, table, real_len: int, trash: int):
    """Write a captured whole-prompt K/V into the pages.

    ``k_all``/``v_all``: ``(L, 1, heads, padded_len, head_dim)`` from the
    sharded forward; positions past ``real_len`` (bucket padding) are
    routed to the trash block."""
    import jax.numpy as jnp

    bt = pages.shape[3]
    padded = k_all.shape[3]
    pos = np.arange(padded)
    slots = np.minimum(pos // bt, len(table) - 1)
    blocks = np.where(pos < real_len, np.asarray(table)[slots], trash)
    off = pos % bt
    new_kv = jnp.stack([k_all[:, 0], v_all[:, 0]], axis=1)  # (L,2,h,P,hd)
    new_kv = jnp.swapaxes(new_kv, 2, 3)                     # (L,2,P,h,hd)
    return pages.at[:, :, jnp.asarray(blocks), jnp.asarray(off)].set(new_kv)


class RingPrefill:
    """Compiled whole-prompt prefill, one executable per bucketed length.

    Picks the largest power-of-two sequence mesh the local devices allow
    (1 device = plain single-shard forward, same capture path)."""

    def __init__(self, spec: "_engine.ModelSpec", cfg, params):
        import jax

        self.spec = spec
        self.params = params
        n_dev = len(jax.devices())
        self.n_sp = 1 << (max(n_dev, 1).bit_length() - 1)
        self._compiled = {}

    def _extract_kv(self, inter):
        """Stack the sown per-layer (k, v) into (L, b, h, s, hd) pairs."""
        import jax.numpy as jnp

        ks, vs = [], []
        for i in range(self.spec.n_layers):
            k, v = inter[f"layer_{i}"]["attn"]["kv"][0]
            ks.append(k)
            vs.append(v)
        return jnp.stack(ks), jnp.stack(vs)

    def _build(self, padded: int):
        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        if self.n_sp == 1 or padded % self.n_sp:
            model = _engine.build_model(self.spec, capture_kv=True)

            def single(tokens):
                logits, state = model.apply(
                    {"params": self.params}, tokens,
                    mutable=["intermediates"])
                k, v = self._extract_kv(state["intermediates"])
                return logits, k, v

            return jax.jit(single)

        mesh = Mesh(np.array(jax.devices()[:self.n_sp]), ("sp",))
        model = _engine.build_model(self.spec, seq_axis="sp",
                                    capture_kv=True)

        def shard(tokens):
            logits, state = model.apply(
                {"params": self.params}, tokens, mutable=["intermediates"])
            k, v = self._extract_kv(state["intermediates"])
            return logits, k, v

        mapped = jax.shard_map(
            shard, mesh=mesh,
            in_specs=(P(None, "sp"),),
            out_specs=(P(None, "sp", None),
                       P(None, None, None, "sp", None),
                       P(None, None, None, "sp", None)))
        return jax.jit(mapped)

    def __call__(self, buf: np.ndarray, real_len: int):
        """Returns ``(k_all, v_all, sampled)``: the captured K/V for the
        whole padded prompt and the greedy token after its last real
        position."""
        import jax.numpy as jnp

        padded = len(buf)
        fn = self._compiled.get(padded)
        if fn is None:
            fn = self._compiled[padded] = self._build(padded)
        logits, k_all, v_all = fn(jnp.asarray(buf, jnp.int32)[None, :])
        sampled = int(jnp.argmax(logits[0, real_len - 1]))
        return k_all, v_all, sampled
