"""Fused ring-flash attention: rotation DMA issued inside the kernel.

The separable ring attention (`ops/ring_attention.py`) alternates
whole-shard rotate (ppermute / rdma) and whole-shard attend steps; XLA can
overlap them across steps, but each rotation is still a standalone
collective the scheduler must place.  This module fuses one ring step into
ONE Pallas program: the kernel *starts* the async remote copy of the
current K/V shard to the right neighbour, computes the shard's flash
attention while the DMA flies, and *waits* for the transfer only at the
final grid step — the start-DMA → attend → wait-DMA pattern of hand-
written TPU collective kernels (cf. the collective-matmul examples in the
Pallas guide).  The transfer is issued before the attention compute and
awaited after it by construction, not by scheduler luck; how much of it
the compute hides on the chip is not measured (no benchmark cell runs a
ring: PERF.md section 7).

Per ring step the kernel returns the shard-local attention output and its
per-row logsumexp; consecutive steps merge at the JAX level with the
standard flash-merge identity::

    lse = logaddexp(lse_1, lse_2)
    out = out_1 * exp(lse_1 - lse) + out_2 * exp(lse_2 - lse)

The backward is fused the same way (round 4): each ring step is ONE
Pallas program that starts the K/V rotation DMA, recomputes the step's
probability block once from the saved (out, lse) residuals — feeding BOTH
the dk/dv and the dq gradient blocks, where the split single-shard
backward pays that recompute twice — and waits for the DMA at the final
grid step.  The dk/dv partial accumulators travel between step kernels as
float32 ``lax.ppermute`` rotations (following their K/V shard around the
ring, one extra rotation delivering each shard's total to its owner):
a trailing in-kernel DMA could not overlap anything — the accumulator is
only complete at kernel end — while the XLA-level rotation of step t can
hide under the step-t+1 kernel.  Unlike the round-3 composed backward,
nothing re-runs the forward: out/lse are residuals, exactly the flash
backward recompute strategy (Dao et al., arXiv:2205.14135) extended
across the ring.

Correctness of the remote DMA relies on the same ready-handshake barrier
and phase-alternating collective_id scheme as ``ops/rdma.py`` (reserved
ids 15/16 here; 13/14 belong to rdma) — see the invariant discussion
there.  Interpret mode (CPU test meshes) skips the barrier, as rdma does.

No reference counterpart (SURVEY §5.7: the reference has no sequence
parallelism); this is the exceeds-reference flagship.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common.metrics import kernel_trace
from horovod_tpu.ops.attention import (NEG_INF, POS_BIG, Causal, Mask,
                                       _attend_block, _bwd_plan,
                                       _combined_bwd_call,
                                       _combined_vmem_limit, _finalize_flash,
                                       _init_state, _pick_block, _split_scale)
from horovod_tpu.ops.rdma import _ambient_mesh_axes, _device_id

_COLLECTIVE_IDS = (15, 16)  # phase-alternating barrier namespaces


class FusedRingUnsupported(ValueError):
    """The fused ring kernel cannot run this shape or dtype.  Raised at
    trace time, naming the cause, instead of rerouting to another ring:
    a caller who asked for ``rotate_impl="fused"`` and silently got the
    separable ring would time, and trust, the wrong kernel.  Ask for
    ``rotate_impl="ppermute"`` where the message says so."""


def _step_kernel(*refs, causal, block_q, block_k, num_q_blocks,
                 num_k_blocks, bh, rotate, barrier, phase, axis_name,
                 mesh_axes, scale_r):
    """One ring step: start K/V DMA to the right neighbour, flash-attend
    the current shard, wait the DMA at the end.

    Grid: (bh, num_q, num_k).  ``offsets_ref`` (SMEM, scalar-prefetch):
    [q_offset, k_offset] — the absolute sequence positions of this
    device's q shard and of the k/v shard it currently holds (for causal
    masking across shards).  The last (non-rotating) ring step takes no
    DMA refs/semaphores at all.
    """
    if rotate:
        (offsets_ref, q_ref, k_ref, v_ref, k_full, v_full,
         o_ref, lse_ref, k_next, v_next,
         m_scratch, l_scratch, acc_scratch, sems) = refs
    else:
        (offsets_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_scratch, l_scratch, acc_scratch) = refs
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    if rotate:
        my = lax.axis_index(axis_name)
        n = lax.axis_size(axis_name)
        dst, id_type = _device_id(lax.rem(my + 1, n), axis_name, mesh_axes)
        src, _ = _device_id(lax.rem(my - 1 + n, n), axis_name, mesh_axes)

        @pl.when((b == 0) & (qi == 0) & (ki == 0))
        def _start_rotation():
            if barrier:
                # Ready handshake (see ops/rdma.py): signal my *source*
                # ("you may write into my k_next/v_next"), wait for the
                # matching signal from my *destination*.
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

    @pl.when(ki == 0)
    def _():
        _init_state(m_scratch, l_scratch, acc_scratch)

    if causal:
        q_start = offsets_ref[0] + qi * block_q  # absolute positions
        k_start = offsets_ref[1] + ki * block_k
        run = k_start <= q_start + block_q - 1
    else:
        q_start = k_start = 0
        run = True

    @pl.when(run)
    def _():
        # single_k skips the online rescale; the unconditional init above
        # still covers whole-shard-masked ring steps (run stays False).
        _attend_block(q_ref, k_ref, v_ref, m_scratch, l_scratch,
                      acc_scratch, q_start, k_start,
                      Causal() if causal else Mask(),
                      block_q, block_k, single_k=num_k_blocks == 1,
                      scale_r=scale_r)

    @pl.when(ki == num_k_blocks - 1)
    def _():
        _finalize_flash(o_ref, lse_ref, m_scratch, l_scratch, acc_scratch,
                        block_q)

    if rotate:
        @pl.when((b == bh - 1) & (qi == num_q_blocks - 1)
                 & (ki == num_k_blocks - 1))
        def _finish_rotation():
            # Reconstructing the descriptor with the same refs/semaphores
            # waits on the copies started at the first grid step.
            pltpu.make_async_remote_copy(
                src_ref=k_full, dst_ref=k_next, send_sem=sems.at[0],
                recv_sem=sems.at[1], device_id=dst,
                device_id_type=id_type).wait()
            pltpu.make_async_remote_copy(
                src_ref=v_full, dst_ref=v_next, send_sem=sems.at[2],
                recv_sem=sems.at[3], device_id=dst,
                device_id_type=id_type).wait()


def _row_spec(block, d, row):
    # PrefetchScalarGridSpec passes the scalar-prefetch ref as the LAST
    # index_map argument.
    return pl.BlockSpec((1, block, d),
                        lambda b, qi, ki, s: (b, row(qi, ki), 0))


def _bwd_ring_step(q, do, lse8, delta8, k_cur, v_cur, q_offset, k_offset, *,
                   causal, block_q, block_k, rotate, phase,
                   axis_name, interpret, scale_r):
    """One fused backward ring step over (bh, seq_local, d) shards (q
    arrives pre-scaled by the pow2 part of sm_scale).  Returns (dk, dv,
    dq, k_next, v_next) — dk/dv/dq float32 contributions for the
    CURRENTLY HELD shard (dq in q' units); k_next/v_next only when
    rotating.  The kernel is attention.py's combined backward
    (`_combined_bwd_kernel`) invoked with rotate=True: one probability
    recompute feeds dk/dv and dq while the K/V rotation DMA flies."""
    barrier = rotate and not interpret
    results = _combined_bwd_call(
        q, do, lse8, delta8, k_cur, v_cur, q_offset, k_offset,
        mask=Causal() if causal else Mask(), block_q=block_q, block_k=block_k,
        # The shard's tiles above the (shifted) diagonal compute nothing.
        run=(lambda q_start, k_start: q_start + block_q - 1 >= k_start)
        if causal else None, rotate=rotate,
        collective_id=_COLLECTIVE_IDS[phase % 2] if barrier else None,
        axis_name=axis_name, mesh_axes=_ambient_mesh_axes(axis_name),
        interpret=interpret, scale_r=scale_r, name="hvd_ring_flash_bwd")
    if rotate:
        dk, dv, dq, k_next, v_next = results
        return dk, dv, dq, k_next, v_next
    dk, dv, dq = results
    return dk, dv, dq, None, None


def _ring_flash_step(q, k_cur, v_cur, q_offset, k_offset, *,
                     causal, block_q, block_k, rotate, phase, axis_name,
                     interpret, scale_r):
    """One fused ring step over (bh, seq_local, d) shards (q arrives
    pre-scaled by the pow2 part of sm_scale).  Returns (out, lse,
    k_next, v_next) — k_next/v_next only when rotating."""
    bh, sl, d = q.shape
    block_q = _pick_block(sl, block_q)
    block_k = _pick_block(sl, block_k)
    assert sl % block_q == 0 and sl % block_k == 0, (
        "fused_ring_attention routes ragged shard lengths to the "
        "separable path before reaching the kernel")
    num_q, num_k = sl // block_q, sl // block_k
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(k_offset, jnp.int32)])

    kernel = functools.partial(
        _step_kernel, causal=causal, block_q=block_q,
        block_k=block_k, num_q_blocks=num_q, num_k_blocks=num_k, bh=bh,
        rotate=rotate, barrier=rotate and not interpret, phase=phase,
        axis_name=axis_name, mesh_axes=_ambient_mesh_axes(axis_name),
        scale_r=scale_r)
    out_shapes = [
        jax.ShapeDtypeStruct((bh, sl, d), q.dtype),        # out
        jax.ShapeDtypeStruct((bh, 8, sl), jnp.float32),    # lse (8 sublanes)
    ]
    in_specs = [
        _row_spec(block_q, d, lambda qi, ki: qi),   # q
        _row_spec(block_k, d, lambda qi, ki: ki),   # k (blocked)
        _row_spec(block_k, d, lambda qi, ki: ki),   # v (blocked)
    ]
    out_specs = [
        _row_spec(block_q, d, lambda qi, ki: qi),   # out
        pl.BlockSpec((1, 8, block_q), lambda b, qi, ki, s: (b, 0, qi)),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_q, 128), jnp.float32),    # running max
        pltpu.VMEM((block_q, 128), jnp.float32),    # running normalizer
        pltpu.VMEM((block_q, d), jnp.float32),      # output accumulator
    ]
    args = [offsets, q, k_cur, v_cur]
    if rotate:
        in_specs += [
            pl.BlockSpec(memory_space=pl.ANY),      # k (whole, DMA src)
            pl.BlockSpec(memory_space=pl.ANY),      # v (whole, DMA src)
        ]
        out_shapes += [
            jax.ShapeDtypeStruct(k_cur.shape, k_cur.dtype),  # k_next
            jax.ShapeDtypeStruct(v_cur.shape, v_cur.dtype),  # v_next
        ]
        out_specs += [
            pl.BlockSpec(memory_space=pl.ANY),      # k_next (DMA dst)
            pl.BlockSpec(memory_space=pl.ANY),      # v_next (DMA dst)
        ]
        scratch_shapes += [pltpu.SemaphoreType.DMA((4,))]  # k/v send+recv
        args += [k_cur, v_cur]
    vma = jax.typeof(q).vma
    out_shapes = [jax.ShapeDtypeStruct(s.shape, s.dtype, vma=vma)
                  for s in out_shapes]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, num_q, num_k),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    barrier = rotate and not interpret
    compiler_params = pltpu.CompilerParams(
        # collective_id may only be set when the kernel takes the custom
        # barrier (the non-rotating last step has no barrier).
        collective_id=_COLLECTIVE_IDS[phase % 2] if barrier else None,
        has_side_effects=True)
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=compiler_params,
        interpret=interpret,
        name="hvd_ring_flash_fwd",
    )
    with kernel_trace("hvd_ring_flash_fwd"):
        results = call(*args)
    if rotate:
        out, lse, k_next, v_next = results
        return out, lse[:, 0, :], k_next, v_next
    out, lse = results
    return out, lse[:, 0, :], None, None


def _phase_closer_kernel(after_ref, o_ref, *, axis_name, mesh_axes):
    del after_ref  # an ordering dependence only
    my = lax.axis_index(axis_name)
    n = lax.axis_size(axis_name)
    src, id_type = _device_id(lax.rem(my - 1 + n, n), axis_name, mesh_axes)
    bar = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(bar, inc=1, device_id=src,
                           device_id_type=id_type)
    pltpu.semaphore_wait(bar, 1)
    o_ref[...] = jnp.zeros_like(o_ref)


def _phase_closer(axis_name, after):
    """Barrier-only invocation on phase 1: appended when a fused forward
    used an ODD number of rotating steps (even ring sizes), so every
    fused call's barrier-phase stream starts on 0 and ends on 1 — the
    cyclic alternation invariant (ops/rdma.py) then holds across
    repeated executions of the same compiled program (training loops
    re-run the jitted step; the junction last-phase -> first-phase must
    differ).

    Returns a float32 zero that the caller must ADD INTO THE PASS'S
    RESULT, and takes the last step's output as ``after``.  The installed
    JAX drops a pallas_call whose outputs nobody reads
    (``has_side_effects`` only guards against XLA), and without a data
    dependence on both sides nothing keeps the barrier at the end of the
    pass; threaded through the dataflow it runs after the last step and
    before whatever consumes the attention output."""
    call = pl.pallas_call(
        functools.partial(_phase_closer_kernel, axis_name=axis_name,
                          mesh_axes=_ambient_mesh_axes(axis_name)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32,
                                       vma=jax.typeof(after).vma),
        compiler_params=pltpu.CompilerParams(
            collective_id=_COLLECTIVE_IDS[1], has_side_effects=True),
        name="hvd_ring_flash_closer",
    )
    with kernel_trace("hvd_ring_flash_closer"):
        zeros = call(after)
    return zeros[0, 0]


def _rotation_phases(n: int):
    """Barrier-phase schedule for one fused ring pass over ``n`` devices.

    Returns ``(phases, needs_closer)``: ``phases[t]`` is the barrier
    namespace (0/1 -> collective_ids 15/16) of rotating step ``t`` (the
    last step doesn't rotate), and ``needs_closer`` says whether a
    trailing :func:`_phase_closer` on phase 1 is required so the pass's
    barrier stream has even length — the cyclic-alternation invariant
    (ops/rdma.py): consecutive barrier invocations, INCLUDING the
    junctions forward->backward and end-of-step->next-step of a re-run
    jitted program, must never share a namespace, or a lagging device's
    ready-wait could be satisfied by a neighbour's next-invocation
    signal.  Pure so tests can pin the schedule
    (tests/test_ops.py::test_ring_flash_phase_stream_alternates)."""
    phases = [t % 2 for t in range(n - 1)]
    return phases, len(phases) % 2 == 1


def _merge(o1, lse1, o2, lse2):
    """Flash-merge two partial attention results.  POS_BIG lse rows carry
    zero mass (fully masked).  Returns the merged output in FLOAT32 — the
    running accumulator must stay f32 across the whole ring (an n-device
    ring would otherwise accumulate n-1 bf16 roundings, drifting from the
    separable path's single final cast); callers cast once at the end."""
    e1 = jnp.where(lse1 > POS_BIG / 2, NEG_INF, lse1)
    e2 = jnp.where(lse2 > POS_BIG / 2, NEG_INF, lse2)
    m = jnp.maximum(e1, e2)
    both_empty = m <= NEG_INF / 2
    m_safe = jnp.where(both_empty, 0.0, m)
    w1 = jnp.where(e1 <= NEG_INF / 2, 0.0, jnp.exp(e1 - m_safe))
    w2 = jnp.where(e2 <= NEG_INF / 2, 0.0, jnp.exp(e2 - m_safe))
    total = w1 + w2
    safe_total = jnp.where(total == 0.0, 1.0, total)
    out = (o1.astype(jnp.float32) * (w1 / safe_total)[..., None]
           + o2.astype(jnp.float32) * (w2 / safe_total)[..., None])
    lse = jnp.where(both_empty, POS_BIG, m_safe + jnp.log(safe_total))
    return out, lse


def _fused_forward(q, k, v, axis_name, causal, sm_scale, block_q, block_k,
                   interpret):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    sl = q.shape[-2]
    batch, heads = q.shape[0], q.shape[1]
    bh = batch * heads
    # Pre-scaled q (ops/attention.py): exact pow2 factor on q, f32
    # residual applied to the logits inside the kernel.
    p2, scale_r = _split_scale(sm_scale)
    qr = (q * p2).astype(q.dtype).reshape(bh, sl, q.shape[-1])
    k_cur = k.reshape(bh, sl, k.shape[-1])
    v_cur = v.reshape(bh, sl, v.shape[-1])
    q_off = my * sl

    phases, needs_closer = _rotation_phases(n)
    out = lse = None
    for t in range(n):
        kv_idx = lax.rem(my - t + n, n)
        k_off = kv_idx * sl
        o_t, lse_t, k_next, v_next = _ring_flash_step(
            qr, k_cur, v_cur, q_off, k_off,
            causal=causal, block_q=block_q, block_k=block_k,
            rotate=t < n - 1, phase=phases[t] if t < n - 1 else 0,
            axis_name=axis_name, interpret=interpret, scale_r=scale_r)
        if t < n - 1:
            k_cur, v_cur = k_next, v_next
        if out is None:
            out, lse = o_t, lse_t
        else:
            out, lse = _merge(out, lse, o_t, lse_t)
    if not interpret and needs_closer:
        # Even ring: odd number of rotating steps [0,1,...,0] — close the
        # barrier-phase stream on 1 so repeated executions alternate.
        out = out + _phase_closer(axis_name, o_t)
    return (out.reshape(q.shape).astype(q.dtype),
            lse.reshape(q.shape[:-1]))


def _fused_backward(q, k, v, out, lse, g, axis_name, causal, sm_scale,
                    block_q, block_k, interpret):
    """Fused ring backward: per ring step ONE Pallas program rotates K/V
    by in-kernel DMA while computing the shard's dk/dv and dq blocks from
    the saved (out, lse); the float32 dk/dv partials follow their shard
    around the ring as ppermute rotations between kernels."""
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    batch, heads, sl, d = q.shape
    bh = batch * heads
    p2, scale_r = _split_scale(sm_scale)
    qr = (q * p2).astype(q.dtype).reshape(bh, sl, d)  # q' units
    dor = g.reshape(bh, sl, d)
    k_cur = k.reshape(bh, sl, d)
    v_cur = v.reshape(bh, sl, d)
    q_off = my * sl
    # delta_i = sum_d dOut_id * Out_id, broadcast to 8 sublanes alongside
    # lse (the single-shard flash backward's tiling trick).
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, sl)
    delta8 = jnp.broadcast_to(delta[:, None, :], (bh, 8, sl))
    lse8 = jnp.broadcast_to(lse.reshape(bh, sl)[:, None, :], (bh, 8, sl))

    perm = [(i, (i + 1) % n) for i in range(n)]
    phases, needs_closer = _rotation_phases(n)
    dq_total = None
    acc_k = acc_v = None
    for t in range(n):
        kv_idx = lax.rem(my - t + n, n)
        k_off = kv_idx * sl
        dk_t, dv_t, dq_t, k_next, v_next = _bwd_ring_step(
            qr, dor, lse8, delta8, k_cur, v_cur, q_off, k_off,
            causal=causal, block_q=block_q,
            block_k=block_k, rotate=t < n - 1,
            phase=phases[t] if t < n - 1 else 0,
            axis_name=axis_name, interpret=interpret, scale_r=scale_r)
        if t < n - 1:
            k_cur, v_cur = k_next, v_next
        dq_total = dq_t if dq_total is None else dq_total + dq_t
        if acc_k is None:
            acc_k, acc_v = dk_t, dv_t
        else:
            # The accumulators chase their K/V shard: rotate one hop (the
            # shard moved while the kernel ran), then add this device's
            # contribution for the shard it now holds.  XLA schedules the
            # ppermute of step t-1 alongside the step-t kernel.
            acc_k = lax.ppermute(acc_k, axis_name, perm) + dk_t
            acc_v = lax.ppermute(acc_v, axis_name, perm) + dv_t
    if n > 1:
        # After step n-1, shard j's totals sit one hop left of owner j.
        acc_k = lax.ppermute(acc_k, axis_name, perm)
        acc_v = lax.ppermute(acc_v, axis_name, perm)
    if not interpret and needs_closer:
        # same stream invariant as the forward
        dq_total = dq_total + _phase_closer(axis_name, dq_t)
    # dq accumulated in q' = p2*q units; rescale once.
    return ((dq_total * p2).reshape(q.shape).astype(q.dtype),
            acc_k.reshape(k.shape).astype(k.dtype),
            acc_v.reshape(v.shape).astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fused_ring_attention(q, k, v, axis_name, causal, sm_scale, block_q,
                          block_k, interpret):
    return _fused_forward(q, k, v, axis_name, causal, sm_scale, block_q,
                          block_k, interpret)[0]


def _fused_fwd(q, k, v, axis_name, causal, sm_scale, block_q, block_k,
               interpret):
    out, lse = _fused_forward(q, k, v, axis_name, causal, sm_scale,
                              block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _fused_bwd(axis_name, causal, sm_scale, block_q, block_k, interpret,
               res, g):
    q, k, v, out, lse = res
    return _fused_backward(q, k, v, out, lse, g, axis_name, causal,
                           sm_scale, block_q, block_k, interpret)


_fused_ring_attention.defvjp(_fused_fwd, _fused_bwd)


def fused_ring_attention(q, k, v, axis_name: str, causal: bool = False,
                         sm_scale: Optional[float] = None,
                         block_q: int = 512, block_k: int = 512,
                         interpret: Optional[bool] = None):
    """Ring attention with the rotation DMA fused into the flash kernel.

    Same contract as :func:`horovod_tpu.ops.ring_attention` (shards of
    ``(batch, heads, seq_local, head_dim)`` inside ``shard_map`` over
    ``axis_name``).  Raises :class:`FusedRingUnsupported` for what the
    kernel cannot run: float16 (compiled path), shard lengths that do not
    tile into MXU blocks, and local shards too long, or heads too wide (past
    128 lanes), for the backward's whole-shard dq scratch.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    sl = q.shape[-2]
    d = q.shape[-1]
    if not interpret and jnp.float16 in (q.dtype, k.dtype, v.dtype):
        # The chip's compiler refuses float16 kernels outright, as in
        # flash_attention.
        raise FusedRingUnsupported(
            "float16 is not a native TPU type and Mosaic refuses the fused "
            "ring kernel for it; use bfloat16, or rotate_impl=\"ppermute\"")
    bq, bk = _pick_block(sl, block_q), _pick_block(sl, block_k)
    if sl % bq or sl % bk or (not interpret and (bq % 128 or bk % 128)):
        raise FusedRingUnsupported(
            f"local shard length {sl} does not tile into blocks the MXU "
            f"takes (multiples of 128; picked {bq}x{bk}); pad the sequence, "
            "or use rotate_impl=\"ppermute\"")
    # The fused backward step is the combined kernel — whole-shard dq
    # scratch in VMEM.  Long local shards where that cannot compile
    # (attention._bwd_plan, calibrated against the 16 MiB scoped-VMEM
    # ceiling) are refused here, by name, instead of failing at Mosaic
    # compile time on the backward pass (ADVICE r4).
    bh = q.shape[0] * q.shape[1]
    mode, bq, bk = _bwd_plan(sl, d, bq, bk, bh)
    # A plan whose call would ask Mosaic for more than the default (past
    # 8,192 rows a shard; every combined plan of a head past 128 lanes, which
    # the plan sends to that kernel only where it asks) has been probed
    # without the rotation only: refused as the split pair's shapes are.
    asking = mode == "combined" \
        and _combined_vmem_limit(sl, d, bq, bk) is not None
    if mode != "combined" or sl % bq or sl % bk or asking:
        raise FusedRingUnsupported(
            f"local shard length {sl} at head_dim {d} and batch*heads "
            f"{bh} is outside the shapes whose whole-shard dq scratch the "
            "fused backward holds in the scoped VMEM a kernel has without "
            "asking (attention._bwd_plan "
            f"chose {mode!r}{', asking for more than the default' * asking}"
            "); use more ring devices, or rotate_impl=\"ppermute\"")
    if interpret and len(_ambient_mesh_axes(axis_name)) > 1:
        # The CPU interpreter's remote DMA only supports single-axis
        # meshes (upstream dma_start_p limitation), so a dp x sp test
        # mesh runs the separable ring.  Compiled kernels use MESH device
        # ids and never come here.
        from horovod_tpu.ops.ring_attention import ring_attention

        return ring_attention(q, k, v, axis_name, causal=causal,
                              sm_scale=sm_scale, rotate_impl="ppermute")
    return _fused_ring_attention(q, k, v, axis_name, causal, sm_scale,
                                 bq, bk, interpret)
