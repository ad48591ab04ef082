"""TPU compute kernels and memory-efficient attention.

No reference counterpart (zhangzhao156/horovod ships no kernels — all its
compute lives in the wrapped frameworks); this package is the TPU-native
compute layer the task's long-context requirement adds on top of the
collective substrate:

* :func:`flash_attention` — fused Pallas attention kernel (MXU-tiled,
  online softmax, O(seq) memory).
* :func:`blockwise_attention` — differentiable pure-JAX blockwise attention
  (the same math as a `lax.scan`, usable on any backend and as the
  recompute path for flash attention's VJP).
* :func:`ring_attention` — sequence-parallel attention over a mesh axis:
  K/V shards rotate around the ICI ring via `lax.ppermute` while each
  device's queries stay put (Liu et al., Ring Attention, arXiv:2310.01889).
* :func:`fused_ring_attention` — ring attention with the rotation DMA
  fused INTO the flash kernel (start DMA -> attend -> wait), one Pallas
  program per ring step (`ring_attention(..., rotate_impl="fused")`);
  raises :class:`FusedRingUnsupported` for what the kernel cannot run.
"""

from horovod_tpu.ops.attention import (  # noqa: F401
    blockwise_attention,
    flash_attention,
    mha_reference,
)
from horovod_tpu.ops.ring_attention import ring_attention  # noqa: F401
from horovod_tpu.ops.ring_flash import (  # noqa: F401
    FusedRingUnsupported,
    fused_ring_attention,
)
