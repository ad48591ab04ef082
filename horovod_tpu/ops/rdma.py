"""Raw inter-chip RDMA collectives as Pallas kernels.

``ring_permute`` rotates each device's shard to its ring neighbour with a
single ``pltpu.make_async_remote_copy`` — the hand-rolled equivalent of
``lax.ppermute`` with the shift-by-one permutation, issued as one direct
HBM-to-HBM DMA over ICI instead of going through XLA's collective-permute
machinery.  It is the communication primitive for an RDMA-backed ring
attention (``ring_attention(..., rotate_impl="rdma")``): on hardware where
XLA's collective-permute scheduling is the bottleneck, the explicit DMA
gives the kernel author the overlap control (start early, wait late).

Differentiable: the VJP of a right rotation is a left rotation of the
cotangent, mirroring ``ppermute``'s transpose.

Requirements: must run inside ``shard_map`` over ``axis_name`` on a TPU
mesh (or in interpret mode on any mesh, which is how the unit tests
exercise it without multi-chip hardware).  On real TPUs the kernel takes a
neighbour barrier first (remote DMA writes into the peer's buffer, so both
sides must have entered the kernel); barrier semaphores need a
``collective_id``, reserved here as 13/14/17/18 (15/16 belong to
ops/ring_flash.py).

Barrier-namespace discipline: consecutive invocations in one DEPENDENCY
CHAIN (a sequence of rotations where each consumes the previous's output)
must alternate namespaces, so a lagging device's ready-wait can never be
satisfied by a neighbour's next-invocation signal.  Two namespaces per
chain suffice — program order within a chain is forced by data
dependence.  Chains that are INDEPENDENT of each other (ring_attention's
K and V streams) get disjoint namespace pairs: their runtime interleaving
is scheduler-chosen, so sharing a namespace across chains would let one
chain's signal satisfy the other's wait.  This also divorces correctness
from jax's tracing order: current jax traces custom_vjp transposes
grouped per cotangent chain (not interleaved with program order), which
broke the old global-alternation scheme.

ON HARDWARE: this module and ops/ring_flash.py, which shares the barrier
scheme, first ran on physical chips at PR 21 — one four-chip v5e host, the
512-wide LM at 2048 rows a chip, losses equal to the one-device flash loss
(chip_smoke.py --chips 4).  The barrier/phase invariants are pinned by
interpret-mode tests (tests/test_ops.py
::test_rdma_phase_alternates_through_backward and
::test_ring_flash_phase_stream_alternates) and by compiles for a described
four-chip mesh (::test_ring_variants_compile_on_mesh).  One shape on one
host is not a validation of the scheme: the namespaces of this module's
chains still repeat from one attention call to the next (ROADMAP.md D3),
longer rings and other shapes are unrun, and ``lax.ppermute`` stays the
default rotation.

No reference counterpart (SURVEY §5.7: the reference has no sequence
parallelism at all); this exceeds it.
"""

from __future__ import annotations

import functools

import jax
import jax.experimental.pallas as pl
from jax import lax
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common.metrics import kernel_trace

# Barrier namespaces: phases 0/1 = chain A (ids 13/14), phases 2/3 =
# chain B (ids 17/18).  ``phase ^ 1`` flips within a chain — the VJP's
# move — while ``phase // 2`` names the chain.
_COLLECTIVE_IDS = (13, 14, 17, 18)


def _device_id(ring_idx, ring_axis, mesh_axes):
    """(device_id, device_id_type) addressing ``ring_idx`` along the ring
    axis.  Single-axis meshes use scalar LOGICAL ids (what interpret mode
    supports); multi-axis meshes use MESH coordinates over every axis —
    a LOGICAL id computed from the ring axis alone would address the
    wrong device on a dp x sp mesh."""
    if len(mesh_axes) == 1:
        return ring_idx, pltpu.DeviceIdType.LOGICAL
    coords = tuple(ring_idx if ax == ring_axis else lax.axis_index(ax)
                   for ax in mesh_axes)
    return coords, pltpu.DeviceIdType.MESH


def _ambient_mesh_axes(axis_name):
    """Axis names of the surrounding shard_map mesh (falls back to the
    ring axis alone outside any mesh context)."""
    names = tuple(jax.sharding.get_abstract_mesh().axis_names)
    return names if axis_name in names else (axis_name,)


def _permute_kernel(x_ref, o_ref, send_sem, recv_sem, *, axis_name,
                    shift, barrier, mesh_axes):
    my = lax.axis_index(axis_name)
    n = lax.axis_size(axis_name)
    dst, id_type = _device_id(lax.rem(my + shift, n), axis_name, mesh_axes)
    if barrier:
        # Ready handshake: I may DMA into `dst` only once `dst` has
        # entered this kernel (its output buffer is live).  Every device
        # signals its *source* ("you may write to me") and waits for the
        # matching signal from its *destination*.  A stale signal from a
        # later invocation cannot satisfy this wait: invocations alternate
        # barrier namespaces (collective_id), and for `dst` to reach the
        # invocation-after-next it would need its own destination — and,
        # chasing the chain the whole way around the ring — *this* device
        # to have advanced too, a contradiction.
        src, _ = _device_id(lax.rem(my - shift + n, n), axis_name,
                            mesh_axes)
        sem = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(sem, inc=1, device_id=src,
                               device_id_type=id_type)
        pltpu.semaphore_wait(sem, 1)
    copy = pltpu.make_async_remote_copy(
        src_ref=x_ref, dst_ref=o_ref, send_sem=send_sem, recv_sem=recv_sem,
        device_id=dst, device_id_type=id_type)
    copy.start()
    copy.wait()


def _ring_permute_raw(x, axis_name, shift, interpret, phase):
    shift = shift % lax.axis_size(axis_name)  # static: axis sizes are known
    kernel = functools.partial(_permute_kernel, axis_name=axis_name,
                               shift=shift, barrier=not interpret,
                               mesh_axes=_ambient_mesh_axes(axis_name))
    # Propagate the varying-mesh-axes annotation so shard_map's vma check
    # accepts the pallas output (the result varies exactly as the input).
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       vma=jax.typeof(x).vma),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.CompilerParams(
            collective_id=_COLLECTIVE_IDS[phase % 4],
            has_side_effects=True),
        interpret=interpret,
        name="hvd_rdma_permute",
    )
    with kernel_trace("hvd_rdma_permute"):
        return call(x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _ring_permute(x, axis_name, shift, interpret, phase):
    return _ring_permute_raw(x, axis_name, shift, interpret, phase)


def _ring_permute_fwd(x, axis_name, shift, interpret, phase):
    return _ring_permute_raw(x, axis_name, shift, interpret, phase), None


def _ring_permute_bwd(axis_name, shift, interpret, phase, _res, g):
    # The transpose of "send my shard +shift" is "send the cotangent
    # -shift" — identical to ppermute's transpose rule.  The barrier
    # namespace is FLIPPED within the chain (phase ^ 1 keeps phase // 2,
    # the chain id): the transposed rotations execute in reverse
    # dependency order, so the chain's last forward rotation (phase p) is
    # immediately followed by its own backward rotation — with the flip
    # that backward uses p^1, and since the chain's forward phases
    # alternate ..., p^1, p, the composed fwd+bwd chain stays strictly
    # alternating, seam included.  Without the flip, two adjacent
    # same-chain invocations would share a semaphore namespace and a
    # lagging device's ready-wait could be satisfied by a neighbour's
    # *next*-invocation signal, licensing a DMA into a buffer that is
    # not yet live.
    return (_ring_permute_raw(g, axis_name, -shift, interpret, phase ^ 1),)


_ring_permute.defvjp(_ring_permute_fwd, _ring_permute_bwd)


def ring_permute(x, axis_name: str, shift: int = 1,
                 interpret: bool = None, phase: int = 0):
    """Rotate ``x``'s shards ``shift`` positions up the mesh ring.

    Equivalent to ``lax.ppermute(x, axis_name, [(i, (i+shift) % n)])``,
    executed as one Pallas async remote copy per device.  Differentiable.
    Must be called inside ``shard_map`` over ``axis_name``.  Callers
    issuing a *sequence* of dependent rotations should alternate
    ``phase`` between consecutive calls of that chain (0,1,0,... or
    2,3,2,...) so adjacent invocations use distinct semaphore
    namespaces; an INDEPENDENT concurrent chain must use the other
    namespace pair (``phase // 2`` differs) — see the module docstring.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _ring_permute(x, axis_name, shift, interpret, phase)
