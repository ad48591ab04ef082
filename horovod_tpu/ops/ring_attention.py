"""Ring attention: sequence-parallel attention over a mesh axis.

Long-context strategy (Liu et al., Ring Attention with Blockwise
Transformers, arXiv:2310.01889), absent from the reference (SURVEY §5.7)
and added here as a first-class TPU capability: the sequence dimension is
sharded over the mesh axis; each device keeps its query shard and passes
its key/value shard around the ring with `lax.ppermute` (which XLA lowers
to ICI neighbour transfers it may schedule beside the attention compute;
none of the three rotations is timed on the chip yet, PERF.md section 7),
merging partial results with the same online-softmax statistics the flash kernel
uses.  Peak memory per device is O(seq/N) — context length scales linearly
with the ring size.

Use inside `shard_map` with the sequence dimension sharded along
``axis_name``; differentiable end-to-end (ppermute transposes to the
reverse rotation).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops.attention import (
    NEG_INF,
    _block_attend,
    _finalize,
)


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   sm_scale: Optional[float] = None,
                   rotate_impl: str = "ppermute"):
    """Attention over a sequence sharded along ``axis_name``.

    Args:
      q, k, v: local shards, ``(batch, heads, seq_local, head_dim)``; the
        global sequence is the concatenation of shards in mesh-axis order.
      axis_name: the mapped mesh axis carrying the sequence shards.
      causal: apply a causal mask over *global* positions.
      sm_scale: softmax scale; default ``head_dim ** -0.5``.
      rotate_impl: how K/V shards travel the ring — ``"ppermute"`` (XLA
        collective permute, default: the compiler schedules it, as an
        async start/done pair where it chooses to), ``"rdma"``
        (:func:`horovod_tpu.ops.rdma.ring_permute`: one raw Pallas remote
        DMA per rotation, for hardware where explicit transfer control
        beats XLA's scheduling), or ``"fused"``
        (:func:`horovod_tpu.ops.ring_flash.fused_ring_attention`: ONE
        Pallas program per ring step that starts the rotation DMA, flash-
        attends the current shard while it flies, and waits at the end;
        shapes it cannot run raise
        ``FusedRingUnsupported``).  Differentiable in every mode.  Which
        rotation a compiled program really holds is read from its HLO:
        ``collective-permute`` for ppermute, ``tpu_custom_call`` for the
        other two (chip_smoke.py --chips 4 reports it).

    Returns:
      The local output shard, same shape/dtype as ``q``.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if rotate_impl == "fused":
        from horovod_tpu.ops.ring_flash import fused_ring_attention

        return fused_ring_attention(q, k, v, axis_name, causal=causal,
                                    sm_scale=sm_scale)
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    seq_local = q.shape[-2]

    q_pos = my_idx * seq_local + jnp.arange(seq_local)
    m0 = jnp.full(q.shape[:-1], NEG_INF, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], jnp.float32)
    acc0 = jnp.zeros(q.shape[:-2] + (seq_local, q.shape[-1]), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    if rotate_impl == "ppermute":
        def rotate(t, phase):
            del phase
            return lax.ppermute(t, axis_name, perm)
    elif rotate_impl == "rdma":
        from horovod_tpu.ops.rdma import _ambient_mesh_axes, ring_permute

        if (jax.default_backend() != "tpu"
                and len(_ambient_mesh_axes(axis_name)) > 1):
            # Interpret-mode remote DMA only supports single-axis meshes
            # (upstream dma_start_p limitation); fall back to ppermute on
            # CPU dp x sp meshes, as the fused backend does.
            return ring_attention(q, k, v, axis_name, causal=causal,
                                  sm_scale=sm_scale,
                                  rotate_impl="ppermute")

        def rotate(t, phase):
            # Barrier-namespace discipline (see rdma.py): the K and V
            # rotation chains are independent of each other, so each
            # gets its own namespace PAIR (K: phases 0/1, V: 2/3) and
            # alternates within the pair per step.  Adjacent rotations
            # of one chain — the only orderings data dependence forces —
            # then always differ, forward, backward (the VJP flips
            # within the pair), and across the fwd/bwd seam, regardless
            # of how jax orders the traced transposes or how the
            # scheduler interleaves the two chains at runtime.
            return ring_permute(t, axis_name, phase=phase)
    else:
        raise ValueError(f"unknown rotate_impl {rotate_impl!r}")

    # Unrolled ring loop (n is the static mesh-axis size): each step's
    # ppermute can then be scheduled by XLA as an async collective-permute
    # overlapped with the next step's attention compute, which a
    # lax.fori_loop carry would serialize.  Each step's attention is
    # rematerialized in the backward pass (jax.checkpoint): without it the
    # VJP saves every step's (seq_local, seq_local) probability block —
    # O(seq^2 / n) per device, defeating the ring's memory scaling.  The
    # mask is built *inside* the checkpointed step from the scalar shard
    # index, so it is recomputed too, not stored as a residual.
    def step_attend(q, k_cur, v_cur, m, l, acc, kv_idx):
        mask = None
        if causal:
            k_pos = kv_idx * seq_local + jnp.arange(seq_local)
            mask = q_pos[:, None] >= k_pos[None, :]
        return _block_attend(q, k_cur, v_cur, m, l, acc, mask, sm_scale)

    attend = jax.checkpoint(step_attend)
    k_cur, v_cur, m, l, acc = k, v, m0, l0, acc0
    for t in range(n):
        # After t right-rotations this device holds the shard that
        # originated on device (my_idx - t) mod n.
        m, l, acc = attend(q, k_cur, v_cur, m, l, acc, (my_idx - t) % n)
        if t < n - 1:  # rotate K/V to the right neighbour
            k_cur = rotate(k_cur, t % 2)
            v_cur = rotate(v_cur, 2 + t % 2)
    return _finalize(m, l, acc, q.dtype)
