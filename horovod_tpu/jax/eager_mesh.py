"""XLA data plane for eager collectives.

The north-star TPU mapping of the reference's NCCL data plane
(/root/reference/horovod/common/operations.cc:861-1100): eagerly issued
tensors execute as *compiled XLA collectives* over the accelerator fabric
(ICI on a pod; gloo/gRPC on CPU test meshes) instead of the engine's TCP
ring.  Enabled with ``HVD_TPU_XLA_DATA_PLANE=1``; the TCP engine remains
the control plane (negotiation, error paths) and the fallback for dtypes
XLA does not carry (f64 with x64 disabled, bool).

Dispatch-order agreement
------------------------
Every rank must issue the *same sequence* of compiled collectives or the
fabric deadlocks.  Each plane op therefore enqueues a tiny int64 allreduce
(``__xp.<name>``) through the TCP engine carrying a per-rank metadata slot:
``vec[rank] = hash(op, dtype, shape, root)`` and ``vec[size+rank] = dim0``.
The engine's coordinator negotiates these exactly like any other tensor
(the reference's MPIRequest counting, operations.cc:268-301) and — because
response lists are built by rank 0 and broadcast — completes them in an
order that is identical on every rank.  The engine stamps each completion
with a (tick, seq) pair (engine.cc CompleteEntry); the plane dispatches
XLA programs only for ops in *closed* ticks, sorted by seq, with fusion
buckets that never straddle a tick.  Any prefix a rank dispatches early is
therefore a prefix of what every other rank will dispatch: interleaved
poll-while-enqueue patterns (torch hooks firing in different orders,
polling one handle while another rank enqueues more) cannot diverge.

The metadata hash doubles as the cross-rank shape/dtype/root consistency
check (the reference's ConstructMPIResponse validation,
operations.cc:301-503): a mismatch surfaces as a typed ``ValueError`` on
every rank instead of an opaque XLA error or a hang.  The per-rank dim0
slots carry ragged allgather geometry, so eager allgather rides the plane
too (the reference's MPI_Allgatherv displs, operations.cc:778-838).
Because these ``__xp.*`` metadata ops negotiate through the same rank-0
coordinator as engine collectives, they feed the coordinator's
announce-order accounting for free: plane collectives show up in
``metrics_snapshot()["skew"]`` (last-to-announce counts, skew histogram)
and in rank 0's NEGOTIATE timeline rows exactly like engine ones.

Metadata cache (steady state)
-----------------------------
Training repeats the identical collective sequence every step, so after
the first step the metadata allreduce re-derives an agreement every rank
already holds.  A ``(name, my_hash)``-keyed cache (insert-only, filled in
dispatch order, which is prefix-consistent across ranks) lets repeat
ops replay the verified agreement through a negotiation-only engine noop
(``OP_NOOP``): zero ``__xp.*`` data movement, and — once the engine's own
response cache warms — a per-op cache *bit* on the wire instead of a
string request.  A rank whose metadata changed misses locally and submits
the real ``__xp.`` op; the coordinator converts that split into a typed
mismatched-metadata error on every rank.  Allgathers never cache: their
ragged per-rank dim0 must keep flowing through the metadata allreduce.
``HVD_TPU_RESPONSE_CACHE=0`` disables (docs/performance.md).

Tensor fusion
-------------
flush() concatenates consecutive same-dtype allreduces of one tick into a
single flat buffer — one compiled all-reduce per bucket, the analogue of
the fusion buffer (operations.cc:1607-1642, docs/tensor-fusion.md) — under
``HOROVOD_FUSION_THRESHOLD``.  Executables cache by (op, padded length,
dtype), the NCCL-communicator-cache analogue (operations.cc:212); buffer
lengths are padded to ~12.5%-granular pseudo-log buckets so steady-state
training reuses one executable per step.
"""

from __future__ import annotations

import collections
import ctypes
import hashlib
import os
import threading
import time
from typing import List, Optional

import numpy as np

from horovod_tpu.common import metrics as _metrics
from horovod_tpu.common import postmortem as _postmortem

_lock = threading.Lock()
_plane = None  # the initialized XlaDataPlane

# Compiled-executable cache bound (_jit_for): steady-state training reuses
# a handful of (op, padded length, dtype) keys, but a pathological shape
# stream (e.g. per-sample ragged allgathers) used to grow the dict — and
# jax's compilation cache behind it — without bound.  LRU past this.
_JIT_CACHE_CAPACITY = 128

# Wire compression (docs/performance.md#wire-compression): the plane
# mirrors the engine's negotiated scheme with jnp casts — f32 allreduce
# buckets past the min-bytes floor dispatch in the wire dtype and the
# compiled program widens back to f32 before summing (f32 accumulation,
# like the engine's per-hop f32 accumulate).  Mode codes are the engine's
# CompressionMode values, read per closed tick over the same lockstep
# seam the fusion threshold rides, so every rank compresses the same
# buckets the same way.  fp8 saturates at ±448 before the cast (ml_dtypes
# overflows to nan; one clipped outlier must not poison a fused bucket —
# the engine's encoder saturates identically).
_FP8_MAX = 448.0
_WIRE_DTYPES = {}
_WIRE_MODE_NAMES = {1: "bf16", 2: "fp8"}
try:
    import ml_dtypes as _ml_dtypes

    _WIRE_DTYPES = {1: np.dtype(_ml_dtypes.bfloat16),
                    2: np.dtype(_ml_dtypes.float8_e4m3fn)}
except ImportError:  # pragma: no cover - ml_dtypes ships with jax
    pass


def quantize_error_feedback(values: np.ndarray, mode: int):
    """Quantize f32 ``values`` to the wire dtype for ``mode`` (1=bf16,
    2=fp8-e4m3fn, saturating) and return ``(wire, residual)``.  The
    residual EXACTLY carries the rounding error in f32 arithmetic
    (``values == wire.astype(f32) + residual`` element-wise, saturation
    clipping excepted): the quantized value is within a fraction of the
    input's magnitude, so the subtraction is exact by Sterbenz's lemma.
    Feeding the residual into the next step's pre-compression add is the
    1-bit-SGD-style error feedback that keeps lossy wire formats
    converging like fp32."""
    wire_dtype = _WIRE_DTYPES[mode]
    v = np.clip(values, -_FP8_MAX, _FP8_MAX) if mode == 2 else values
    wire = v.astype(wire_dtype)
    residual = values - wire.astype(np.float32)
    return wire, residual


def _meta_hash(kind: str, dtype, shape, root: int) -> int:
    payload = repr((kind, np.dtype(dtype).str, tuple(shape), root)).encode()
    digest = hashlib.blake2b(payload, digest_size=8).digest()
    return int.from_bytes(digest, "little") & ((1 << 62) - 1)


def _bucket_len(n: int, minimum: int = 256) -> int:
    """Pad a flat buffer length to a pseudo-log bucket (8 steps per octave,
    <=12.5% waste) so the executable cache stays small without doubling
    fabric traffic the way pure power-of-two padding would."""
    if n <= minimum:
        return minimum
    p = 1 << (int(n) - 1).bit_length()  # next power of two >= n
    half = p >> 1
    step = max(half // 8, 1)
    return half + -(-(n - half) // step) * step


class _Batch:
    """One dispatched XLA program; its host copy is materialized once and
    shared by every handle whose segment lives in it.  Fused batches are
    shared by many handles, which may be waited from different threads —
    the lock keeps the lazy materialization single-shot."""

    def __init__(self, arr, t_disp: float = 0.0):
        self._arr = arr
        self._host = None
        self._mu = threading.Lock()
        self._t_disp = t_disp  # metrics: dispatch timestamp (0.0 = off)

    def ready(self) -> bool:
        with self._mu:
            return self._host is not None or self._arr.is_ready()

    def host(self) -> np.ndarray:
        with self._mu:
            if self._host is None:
                self._host = np.asarray(self._arr)
                self._arr = None
                if self._t_disp:
                    _metrics.registry.observe(
                        "dispatch_sec", time.perf_counter() - self._t_disp)
            return self._host


class _PlaneOp:
    __slots__ = ("name", "kind", "payload", "root", "handle", "neg_raw",
                 "neg_in", "neg_out", "my_hash", "seq", "tick", "dim0s",
                 "t_enq", "t_neg", "cached")

    def __init__(self, name, kind, payload, root, handle):
        self.name = name
        self.kind = kind  # "ar" | "bc" | "ag"
        self.payload = payload  # compute-dtype, C-contiguous
        self.root = root
        self.handle = handle
        self.cached = False  # metadata-cache hit: negotiation-only noop
        self.neg_raw = -1
        self.neg_in = None  # pinned until negotiation completes
        self.neg_out = None
        self.my_hash = 0
        self.seq = None  # engine completion stamps once negotiated
        self.tick = None
        self.dim0s = None  # per-rank dim0 (allgather geometry)
        # Metrics timestamps (0.0 = metrics disabled at enqueue): t_enq at
        # submission, t_neg when the negotiation stamp lands.
        self.t_enq = 0.0
        self.t_neg = 0.0


class XlaHandle:
    """Duck-type of horovod_tpu.common.Handle for XLA-plane collectives."""

    def __init__(self, plane, op_kind: str, name: str,
                 out: Optional[np.ndarray], average: bool, size: int,
                 dtype, shape):
        self._plane = plane
        self._kind = op_kind
        self._name = name
        self._out = out
        self._average = average
        self._size = size
        self._dtype = dtype  # caller-visible dtype (pre-widening)
        self._shape = tuple(shape)
        self._batch: Optional[_Batch] = None
        self._off = 0
        self._n = 0
        self._ag_pad = 0  # allgather: padded per-rank dim0
        self._ag_dim0s = None
        self._error: Optional[Exception] = None
        self._finished = False
        self._tl_started = False  # timeline op row opened at dispatch
        # Metrics: end-to-end wait latency from enqueue (0.0 = off).
        self._t0 = time.perf_counter() if _metrics.registry.enabled else 0.0
        # Negotiation (tick, seq) stamp, mirrored from the engine metadata
        # op at dispatch time (duck-type parity with common.Handle).
        self.completion_tick: Optional[int] = None
        self.completion_seq: Optional[int] = None

    # plane-side plumbing -------------------------------------------------
    def _fail(self, err: Exception) -> None:
        self._error = err

    def _set_result(self, batch: _Batch, off: int, n: int,
                    tick: Optional[int] = None,
                    seq: Optional[int] = None) -> None:
        self._batch = batch
        self._off = off
        self._n = n
        self.completion_tick = tick
        self.completion_seq = seq

    # public handle API ---------------------------------------------------
    def done(self) -> bool:
        if self._finished:
            return True
        self._plane.flush()
        if self._error is not None:
            return True
        return self._batch is not None and self._batch.ready()

    def wait(self) -> np.ndarray:
        if self._finished:
            raise ValueError(f"handle for '{self._name}' already waited on")
        self._finished = True
        self._plane._wait_dispatch(self)
        if self._error is not None:
            if self._tl_started:
                from horovod_tpu import common

                # Close the op row opened at dispatch so the trace does
                # not show the tensor as running forever.
                common._lib.hvd_tpu_timeline_op_end(self._name.encode(), 0)
            raise self._error
        tl_lib = None
        if self._tl_started:
            from horovod_tpu import common

            tl_lib = common._lib
            tl_lib.hvd_tpu_timeline_activity_start(self._name.encode(),
                                                   b"DEVICE_WAIT")
        host = self._batch.host()
        if tl_lib is not None or self._t0:
            # This op's own extent, not the shared fused buffer's size
            # (which would over-report by the fusion factor).
            # Caller-visible width: bf16/f16 allreduce widens the compute
            # buffer to f32, but the tensor the caller moved is half that.
            itemsize = np.dtype(self._dtype).itemsize
            if self._kind == "ag":
                my_bytes = int(np.prod(self._shape)) * itemsize
            else:
                my_bytes = self._n * itemsize
            if tl_lib is not None:
                tl_lib.hvd_tpu_timeline_activity_end(self._name.encode())
                tl_lib.hvd_tpu_timeline_op_end(self._name.encode(),
                                               int(my_bytes))
            if self._t0:
                _metrics.registry.record_bytes_out("xla", int(my_bytes))
                _metrics.registry.observe(
                    "wait_sec", time.perf_counter() - self._t0)
        if self._kind == "ag":
            pad = self._ag_pad
            blocks = [host[r * pad:r * pad + int(d)]
                      for r, d in enumerate(self._ag_dim0s)]
            return np.concatenate(blocks).reshape(self._shape)
        seg = host[self._off:self._off + self._n].reshape(self._shape)
        if self._average:
            if np.issubdtype(self._dtype, np.integer):
                seg = (seg / self._size).astype(self._dtype)
            else:
                seg = (seg / np.asarray(self._size, seg.dtype)).astype(
                    self._dtype)
        else:
            seg = seg.astype(self._dtype, copy=False)
        if self._out is not None:
            np.copyto(self._out, seg)
            return self._out
        return np.ascontiguousarray(seg) if seg.ndim else seg.copy()


class XlaDataPlane:
    def __init__(self, mesh, spec_sharded, spec_replicated, rank, size,
                 fusion_threshold, spec_proc_only=None, local_chips=1):
        self._mesh = mesh
        # ar/bc inputs shard the flat payload across this process's local
        # chips too ("hvd_local"), engaging every chip's ICI bandwidth;
        # allgather keeps the ragged payload replicated per process.
        self._in_sharding = spec_sharded
        self._in_sharding_proc = spec_proc_only or spec_sharded
        self._out_sharding = spec_replicated
        self._local_chips = int(local_chips)
        self._rank = rank
        self._size = size
        self._fusion_threshold = int(fusion_threshold)
        from horovod_tpu.common.config import Config

        # Snapshot once: _wait_dispatch is per-handle hot path; <=0
        # disables the stall warning (the conventional "off" value).
        cfg = Config.from_env()
        self._stall_sec = cfg.stall_warning_sec
        # Hard deadline for the dispatch wait (XLA-plane parity with the
        # engine's coordinated abort): past it the handle FAILS with
        # CollectiveTimeoutError instead of polling forever.  <= 0 = off.
        self._timeout_sec = cfg.collective_timeout_sec
        self._fns = collections.OrderedDict()  # LRU-bounded compile cache
        # Metadata cache (docs/performance.md): name -> verified my_hash.
        # A repeat op whose hash matches replays the cached cross-rank
        # agreement through a negotiation-only engine noop and skips the
        # "__xp.*" metadata allreduce entirely.  Entries are inserted in
        # DISPATCH order — the one sequence that is prefix-consistent
        # across ranks (module docstring) — and are insert-only/immutable
        # (see _meta_update), so every rank's cache holds the same
        # entries and a hit on one rank is a hit on all (a divergence
        # would surface as the engine's typed cached-vs-changed-metadata
        # error, never a hang).  Allgathers are excluded: their per-rank
        # dim0 may legitimately change step to step, and that geometry
        # must keep flowing through the metadata allreduce.
        cfg_cap = cfg.effective_cache_capacity
        self._meta_cache = {} if cfg_cap > 0 else None
        self._meta_capacity = cfg_cap
        # Online autotuning (docs/performance.md#autotuning): the engine's
        # fusion threshold can change at tick boundaries, and the plane's
        # bucket boundaries must follow it IDENTICALLY on every rank (a
        # fused bucket is one compiled collective — a split into
        # old-threshold and new-threshold camps would dispatch mismatched
        # programs).  Memoized per tick: the engine's applied-parameter
        # history is append-only, so a closed tick's threshold is stable.
        self._tick_thresholds: dict = {}
        # Single-process ops carry tick -1 (no negotiation): their
        # threshold is the live engine value, read ONCE per flush — not
        # per op, the bucketing loop is the dispatch hot path.
        self._live_threshold: Optional[int] = None
        # Wire compression (docs/performance.md#wire-compression): the
        # mode is the engine's lockstep-broadcast state, looked up per
        # closed tick exactly like the fusion threshold so autotuned mode
        # changes move every rank's dispatch format at the same tick
        # boundary.  Residuals are the per-tensor f32 error-feedback
        # buffers; comp_stats mirrors the engine's wire/payload byte and
        # per-mode bucket accounting for metrics_snapshot()["compression"].
        self._comp_min_bytes = int(cfg.compression_min_bytes)
        self._tick_comp: dict = {}
        self._live_comp: Optional[int] = None
        self._residuals: dict = {}
        self.comp_stats = {"wire_bytes": 0, "payload_bytes": 0,
                           "ops": {"none": 0, "bf16": 0, "fp8": 0}}
        self._mu = threading.RLock()  # guards _fns, _pending, _local_seq
        self._pending: List[_PlaneOp] = []
        # Ops withdrawn by a timed-out wait, pinned so the engine's raw
        # pointers into their negotiation buffers stay valid (see
        # _fail_timed_out).  Timeouts are terminal for the job; bounded in
        # practice by the handful of ops outstanding at abort time.
        self._abandoned: List[_PlaneOp] = []
        # One stall = one abort event in the metrics, no matter how many
        # outstanding handles time out on it (the engine's latched abort
        # is synced separately and counts as its own detection event).
        self._abort_recorded = False
        self._local_seq = 0  # single-process ordering (no negotiation)
        # Observability: dispatches counts compiled-program launches;
        # fused_tensors counts ops carried by them (tests assert N small
        # allreduces ride 1 dispatch).
        self.stats = {"dispatches": 0, "fused_tensors": 0}

    # -- negotiation over the TCP control plane ---------------------------

    def _negotiate(self, op: _PlaneOp) -> None:
        """Enqueue the metadata allreduce for `op` through the engine."""
        from horovod_tpu import common
        from horovod_tpu.common import dtypes as _dt

        if self._size == 1:
            op.seq = self._local_seq
            self._local_seq += 1
            op.tick = -1  # always closed
            op.dim0s = np.asarray(
                [op.payload.shape[0] if op.payload.ndim else 0], np.int64)
            if op.t_enq:
                op.t_neg = time.perf_counter()
                _metrics.registry.observe("negotiation_sec",
                                          op.t_neg - op.t_enq)
            return
        dim0 = op.payload.shape[0] if op.payload.ndim else 0
        shape = (op.payload.shape[1:] if op.kind == "ag"
                 else op.payload.shape)
        op.my_hash = _meta_hash(op.kind, op.handle._dtype, shape, op.root)
        if self._meta_cache is not None and op.kind != "ag":
            if self._meta_cache.get(op.name) == op.my_hash:
                # Metadata-cache hit: every rank holding this verified
                # agreement replays it through a negotiation-only engine
                # noop — global dispatch order still comes from the
                # engine's completion stamps, but no metadata allreduce
                # runs and, once the engine's own response cache warms, no
                # string negotiation either.  A rank whose metadata
                # changed misses here and submits the real "__xp." op; the
                # engine's coordinator then converts the split into the
                # typed mismatched-metadata error (engine.cc).
                dims = (ctypes.c_longlong * 1)(2 * self._size)
                raw = common._lib.hvd_tpu_enqueue(
                    common.OP_NOOP, ("__xp." + op.name).encode(),
                    None, None, dims, 1, _dt.numpy_to_code(np.dtype(np.int64)),
                    -1, 0)
                if raw < 0:
                    raise common.HorovodInternalError("engine is shut down")
                op.cached = True
                op.neg_raw = raw
                _metrics.registry.record_cache("xla", "hits")
                if _postmortem.plane_ring.enabled:
                    _postmortem.plane_ring.record("cache_hit", op.name)
                return
            _metrics.registry.record_cache("xla", "misses")
        vec = np.zeros(2 * self._size, np.int64)
        vec[self._rank] = op.my_hash
        vec[self._size + self._rank] = dim0
        out = np.zeros_like(vec)
        dims = (ctypes.c_longlong * 1)(2 * self._size)
        raw = common._lib.hvd_tpu_enqueue(
            common.OP_ALLREDUCE, ("__xp." + op.name).encode(),
            vec.ctypes.data_as(ctypes.c_void_p),
            out.ctypes.data_as(ctypes.c_void_p),
            dims, 1, _dt.numpy_to_code(vec.dtype), -1, 0)
        if raw < 0:
            raise common.HorovodInternalError("engine is shut down")
        op.neg_raw = raw
        op.neg_in = vec
        op.neg_out = out

    def _poll_negotiations(self) -> None:
        """Collect completion stamps for negotiated ops (non-blocking)."""
        from horovod_tpu import common

        lib = common._lib
        for op in self._pending:
            if op.seq is not None or self._size == 1:
                continue
            if lib.hvd_tpu_poll(op.neg_raw) != 1:
                continue
            code = lib.hvd_tpu_status(op.neg_raw)
            if code != common.ST_OK:
                msg = lib.hvd_tpu_error(op.neg_raw).decode()
                op.handle._fail(common._status_error(code, msg, op.name))
                if _postmortem.plane_ring.enabled:
                    _postmortem.plane_ring.record("error", op.name, code)
                op.seq = -1  # consumed; never dispatched
                # A name that negotiated to an error (e.g. the cached-vs-
                # changed-metadata mismatch) must renegotiate from
                # scratch: drop the stale agreement.  The error reaches
                # every rank, so every cache evicts together.
                if self._meta_cache is not None:
                    self._meta_cache.pop(op.name, None)
            elif op.cached:
                # Negotiation-only replay: the cross-rank agreement was
                # verified when the entry was stored; only the ordering
                # stamps matter here.
                op.seq = int(lib.hvd_tpu_completion_seq(op.neg_raw))
                op.tick = int(lib.hvd_tpu_completion_tick(op.neg_raw))
                if op.t_enq:
                    op.t_neg = time.perf_counter()
                    _metrics.registry.observe("negotiation_sec",
                                              op.t_neg - op.t_enq)
            else:
                op.seq = int(lib.hvd_tpu_completion_seq(op.neg_raw))
                op.tick = int(lib.hvd_tpu_completion_tick(op.neg_raw))
                hashes = op.neg_out[:self._size]
                op.dim0s = op.neg_out[self._size:].copy()
                if not (hashes == op.my_hash).all():
                    bad = [r for r in range(self._size)
                           if hashes[r] != op.my_hash]
                    op.handle._fail(ValueError(
                        f"collective '{op.name}' failed: mismatched "
                        f"op/shape/dtype/root across ranks (ranks {bad} "
                        f"disagree with rank {self._rank}); every rank must "
                        f"submit the same collective with the same dtype "
                        f"and shape."))
                    op.seq = -1
                    if self._meta_cache is not None:
                        self._meta_cache.pop(op.name, None)
                if op.seq != -1 and op.t_enq:
                    op.t_neg = time.perf_counter()
                    _metrics.registry.observe("negotiation_sec",
                                              op.t_neg - op.t_enq)
            lib.hvd_tpu_release(op.neg_raw)
            op.neg_raw = -1
            op.neg_in = op.neg_out = None

    # -- dispatch ---------------------------------------------------------

    def flush(self) -> None:
        """Dispatch every op whose negotiation tick has closed, in the
        engine's completion order.  Ticks close simultaneously (in program
        order) on every rank, so the dispatched sequence — including fusion
        bucket boundaries, which never straddle a tick — is prefix-consistent
        across ranks no matter when each rank happens to flush."""
        from horovod_tpu import common

        with self._mu:
            # Snapshot the closed-tick horizon BEFORE polling: completions
            # of tick t are stored before ticks_done advances past t
            # (engine.cc RunLoopOnce), so every op in a tick this snapshot
            # closes is observable by the poll below — reading the counter
            # after polling could admit a later-seq op from a tick whose
            # earlier-seq op was polled too early, breaking the cross-rank
            # prefix property.
            if self._size == 1:
                ticks_done = 0  # local ticks are -1: always closed
            else:
                ticks_done = int(common._lib.hvd_tpu_ticks_done())
            self._live_threshold = None  # re-read at most once per flush
            self._live_comp = None
            self._poll_negotiations()
            ready = [op for op in self._pending
                     if op.seq is not None and op.seq >= 0
                     and op.tick < ticks_done]
            failed = [op for op in self._pending if op.seq == -1]
            dispatched = set()
            ready.sort(key=lambda o: o.seq)
            # Metadata-cache maintenance rides dispatch order — the one
            # sequence that is prefix-consistent across ranks — so every
            # rank stores, touches, and evicts the same entries in the
            # same order (see _meta_update).
            for op in ready:
                self._meta_update(op)
            bucket: List[_PlaneOp] = []
            bucket_key = None
            bucket_bytes = 0
            for op in ready:
                nbytes = op.payload.nbytes
                if op.kind == "ag":
                    key = ("ag", id(op))  # never fused
                else:
                    key = (op.kind, op.tick, op.payload.dtype.str, op.root)
                if (key != bucket_key
                        or bucket_bytes + nbytes
                        > self._threshold_for(op.tick)):
                    if bucket:
                        self._dispatch(bucket)
                    bucket = []
                    bucket_key = key
                    bucket_bytes = 0
                bucket.append(op)
                bucket_bytes += nbytes
                dispatched.add(id(op))
            if bucket:
                self._dispatch(bucket)
            consumed = dispatched | {id(op) for op in failed}
            self._pending = [op for op in self._pending
                             if id(op) not in consumed]

    def _threshold_for(self, tick: int) -> int:
        """Fusion threshold in force at engine tick `tick`.  The autotuner
        mutates the threshold in lockstep at tick boundaries (every rank
        applies the same broadcast at the same tick index), so keying the
        bucket limit off the op's completion tick keeps plane bucket
        boundaries cross-rank deterministic even while the knob moves.
        Without autotuning the engine history holds only the initial
        value, so this degrades to the static threshold.  `tick` < 0
        (single-process: no negotiation) reads the live value."""
        from horovod_tpu import common

        if common._lib is None:  # engine never loaded: static fallback
            return self._fusion_threshold
        if tick < 0:
            if self._live_threshold is None:
                self._live_threshold = int(
                    common._lib.hvd_tpu_autotune_fusion_threshold())
            return self._live_threshold
        thr = self._tick_thresholds.get(tick)
        if thr is None:
            thr = int(common._lib.hvd_tpu_fusion_threshold_at(tick))
            if len(self._tick_thresholds) > 4096:
                self._tick_thresholds.clear()
            self._tick_thresholds[tick] = thr
        return thr

    def _compression_for(self, tick: int) -> int:
        """Wire-compression mode in force at engine tick `tick`, memoized
        like :meth:`_threshold_for`: the mode mutates only in lockstep at
        tick boundaries, so keying the dispatch format off the op's
        completion tick keeps every rank compiling and launching the same
        program for the same bucket even while the autotuner moves the
        knob.  Size-1 jobs move no wire bytes — always uncompressed."""
        from horovod_tpu import common

        if common._lib is None or self._size == 1:
            return 0
        if tick < 0:
            if self._live_comp is None:
                self._live_comp = int(common._lib.hvd_tpu_compression_mode())
            return self._live_comp
        mode = self._tick_comp.get(tick)
        if mode is None:
            mode = int(common._lib.hvd_tpu_compression_mode_at(tick))
            if len(self._tick_comp) > 4096:
                self._tick_comp.clear()
            self._tick_comp[tick] = mode
        return mode

    def _wait_dispatch(self, handle: XlaHandle) -> None:
        """Block until `handle`'s op is dispatched (or failed).  Bounded by
        the engine cycle time; the reference's synchronize is the same poll
        loop (/root/reference/horovod/torch/mpi_ops.cc:393-399).  Like the
        engine's coordinator sweep (engine.cc CheckForStalledTensors), a
        wait that exceeds ``stall_warning_sec`` logs which negotiations are
        still outstanding — a peer that never submits the matching
        collective would otherwise spin here silently forever."""
        stall_sec = self._stall_sec
        timeout_sec = self._timeout_sec
        start = last_warn = time.monotonic()
        while True:
            self.flush()
            if handle._error is not None or handle._batch is not None:
                return
            now = time.monotonic()
            if timeout_sec > 0 and now - start >= timeout_sec:
                self._fail_timed_out(handle, now - start)
                return
            if stall_sec > 0 and now - last_warn >= stall_sec:
                last_warn = now
                with self._mu:
                    waiting = [op.name for op in self._pending
                               if op.seq is None]
                # Ungated (like the engine's sweep records): tests and
                # operators read metrics_snapshot()["stalls"] without
                # opting into full metrics collection.
                _metrics.registry.record_stall(handle._name, now - start)
                if _postmortem.plane_ring.enabled:
                    _postmortem.plane_ring.record(
                        "stall", handle._name, int(now - start))
                import sys

                print(
                    f"WARNING: XLA-plane wait for '{handle._name}' has "
                    f"stalled for {now - start:.0f}s; negotiations still "
                    f"pending: {waiting or '[none — tick not closed]'}. "
                    f"One or more ranks may not have submitted this "
                    f"collective.", file=sys.stderr, flush=True)
            time.sleep(0.001)

    def _fail_timed_out(self, handle: XlaHandle, waited_sec: float) -> None:
        """Dispatch-wait deadline (HVD_TPU_COLLECTIVE_TIMEOUT_SEC) hit:
        fail the handle with a typed error naming the negotiations still
        outstanding, and withdraw its op from the pending queue so a later
        flush cannot dispatch a collective its waiter already abandoned
        (the peers that DID time out would never dispatch the match, and a
        half-dispatched bucket wedges the fabric)."""
        from horovod_tpu import common

        with self._mu:
            waiting = [op.name for op in self._pending if op.seq is None]
            mine = [op for op in self._pending if op.handle is handle]
            self._pending = [op for op in self._pending
                             if op.handle is not handle]
            # The withdrawn op's negotiation may still be pending inside
            # the engine, which holds raw pointers into neg_in/neg_out —
            # pin the op (buffers and all) until shutdown rather than
            # freeing memory the engine thread could still write.
            self._abandoned.extend(mine)
            record_abort = not self._abort_recorded
            self._abort_recorded = True
        _metrics.registry.record_stall(handle._name, waited_sec)
        if record_abort:
            _metrics.registry.record_abort("timeout")
        if _postmortem.plane_ring.enabled:
            _postmortem.plane_ring.record("abort", handle._name,
                                          int(waited_sec))
        # The plane-side deadline is a typed abort too: leave the dump
        # (write-once; the engine path may already have claimed it).
        _postmortem.write_postmortem("timeout")
        handle._fail(common.CollectiveTimeoutError(
            f"collective '{handle._name}' failed: XLA-plane dispatch wait "
            f"exceeded HVD_TPU_COLLECTIVE_TIMEOUT_SEC "
            f"({waited_sec:.1f}s > {self._timeout_sec:.1f}s); negotiations "
            f"still pending: {waiting or '[none — tick not closed]'}. One "
            f"or more ranks never submitted the matching collective; the "
            f"wait was aborted instead of hanging."))

    def _meta_update(self, op: _PlaneOp) -> None:
        """Store `op`'s verified cross-rank agreement.  INSERT-ONLY and
        IMMUTABLE: entries are added in dispatch order (prefix-consistent
        across ranks) until the capacity is reached, never churn-evicted
        and never re-hashed in place.  An LRU eviction or in-place
        refresh would be applied at rank-local moments — two ranks
        mid-flush could disagree about it, and a fully consistent program
        would then split into cached/uncached camps and die with the
        typed mismatched-metadata error.  A stable entry set keeps the
        hit/miss decision identical on every rank; entries leave only
        through per-name error eviction (the typed error reaches every
        rank's op together).  Names beyond the capacity, and names whose
        metadata changed after caching, simply keep paying the metadata
        allreduce (the engine's response cache still makes its
        negotiation cheap).  Allgathers never cache: their ragged
        per-rank dim0 must keep flowing through the metadata exchange."""
        # Size 1 never negotiates (no hash is computed): nothing to cache.
        if self._meta_cache is None or op.kind == "ag" or self._size == 1:
            return
        if (op.name not in self._meta_cache
                and len(self._meta_cache) < self._meta_capacity):
            self._meta_cache[op.name] = op.my_hash

    def _jit_for(self, kind: str, length_or_shape, dtype, root: int = 0):
        import jax

        key = (kind, length_or_shape, np.dtype(dtype).str, root)
        fn = self._fns.get(key)
        if fn is None:
            if kind == "ar":
                fn = jax.jit(lambda a: a.sum(axis=0),
                             out_shardings=self._out_sharding)
            elif kind == "arc":
                # Compressed allreduce: the buffer arrives in the wire
                # dtype (bf16/fp8) and widens back to f32 BEFORE the sum
                # — f32 accumulation, mirroring the engine's per-hop f32
                # accumulate (docs/performance.md#wire-compression).
                import jax.numpy as jnp

                fn = jax.jit(lambda a: a.astype(jnp.float32).sum(axis=0),
                             out_shardings=self._out_sharding)
            elif kind == "bc":
                fn = jax.jit(lambda a: a[root],
                             out_shardings=self._out_sharding)
            else:  # "ag": resharding identity compiles to an all-gather
                fn = jax.jit(lambda a: a.reshape((-1,) + a.shape[2:]),
                             out_shardings=self._out_sharding)
            self._fns[key] = fn
            # LRU bound: a pathological shape stream (per-sample ragged
            # allgathers) used to grow this — and jax's compile cache
            # behind it — without limit.
            while len(self._fns) > _JIT_CACHE_CAPACITY:
                self._fns.popitem(last=False)
        else:
            self._fns.move_to_end(key)
        return fn

    def _global_array(self, local: np.ndarray, replicated: bool = False):
        import jax

        sharding = self._in_sharding_proc if replicated else self._in_sharding
        return jax.make_array_from_process_local_data(
            sharding, local[np.newaxis],
            (self._size,) + local.shape)

    _TL_OP_NAMES = {"ar": "XLA_ALLREDUCE", "bc": "XLA_BROADCAST",
                    "ag": "XLA_ALLGATHER"}

    def _dispatch(self, bucket: List[_PlaneOp]) -> None:
        # Timeline: plane execution phases land in the same Chrome-tracing
        # file as the engine's NEGOTIATE events (the `__xp.*` rows), per
        # REAL tensor name: BUCKET_BUILD -> XLA_DISPATCH here, DEVICE_WAIT
        # + op end in XlaHandle.wait().  Mirrors the reference's
        # ACTIVITY_START_ALL around every execution phase
        # (operations.cc:680-692).
        from horovod_tpu import common

        lib = common._lib
        tl = bool(lib and lib.hvd_tpu_timeline_enabled())
        if tl:
            op_name = self._TL_OP_NAMES[bucket[0].kind].encode()
            for op in bucket:
                lib.hvd_tpu_timeline_op_start(op.name.encode(), op_name)
                lib.hvd_tpu_timeline_activity_start(op.name.encode(),
                                                    b"BUCKET_BUILD")
                op.handle._tl_started = True
        self._dispatch_inner(bucket, lib if tl else None)

    def _dispatch_inner(self, bucket: List[_PlaneOp], tl_lib) -> None:
        kind = bucket[0].kind
        mx = _metrics.registry.enabled
        if mx:
            # Queue/bucket residency: negotiation stamp -> dispatch.  Ops
            # enqueued while metrics were off carry t_neg == 0.0 and skip.
            now = time.perf_counter()
            for op in bucket:
                if op.t_neg:
                    _metrics.registry.observe("residency_sec",
                                              now - op.t_neg)
            _metrics.registry.record_batch(len(bucket))
        if kind == "ag":
            op = bucket[0]
            pad = _bucket_len(int(op.dim0s.max()), minimum=1)
            rest = op.payload.shape[1:]
            block = np.zeros((pad,) + rest, op.payload.dtype)
            block[:op.payload.shape[0]] = op.payload
            fn = self._jit_for("ag", (pad,) + rest, op.payload.dtype)
            self._tl_phase(tl_lib, bucket, b"XLA_DISPATCH")
            batch = _Batch(self._traced_dispatch(fn, block, "ag", 1),
                           t_disp=time.perf_counter() if mx else 0.0)
            self._tl_phase(tl_lib, bucket, None)
            h = op.handle
            h._ag_pad = pad
            h._ag_dim0s = op.dim0s
            h._shape = (int(op.dim0s.sum()),) + rest
            h._set_result(batch, 0, 0, op.tick, op.seq)
        else:
            dtype = bucket[0].payload.dtype
            lens = [op.payload.size for op in bucket]
            total = int(sum(lens))
            length = _bucket_len(total)
            # The flat buffer also shards across this process's local
            # chips; keep it divisible so every chip holds an equal slice.
            chips = self._local_chips
            length = -(-length // chips) * chips
            flat = np.zeros(length, dtype)
            off = 0
            offs = []
            for op, n in zip(bucket, lens):
                flat[off:off + n] = op.payload.reshape(-1)
                offs.append(off)
                off += n
            # Wire compression: negotiated mode at this bucket's tick, on
            # f32 allreduce buckets past the min-bytes floor (the same
            # per-bucket-size-class decision the engine's coordinator
            # makes, from the same lockstep state — so the decision is
            # identical on every rank even though it is computed locally).
            bucket_bytes = sum(op.payload.nbytes for op in bucket)
            comp = 0
            if kind == "ar" and dtype == np.float32:
                comp = self._compression_for(bucket[0].tick)
                if (comp not in _WIRE_DTYPES
                        or bucket_bytes < self._comp_min_bytes):
                    comp = 0
            if comp:
                # Residual-map bound, checked ONCE before this bucket
                # touches the map (a mid-bucket clear would discard
                # residuals just stored for the bucket's earlier
                # tensors): never-repeating auto-named tensors gain
                # nothing from error feedback and must not grow this
                # forever.
                fresh = sum(1 for op in bucket
                            if op.name not in self._residuals)
                if fresh and len(self._residuals) + fresh > 4096:
                    self._residuals.clear()
                # Error feedback: fold each tensor's residual into its
                # segment, quantize the whole flat buffer once, and save
                # each segment's new rounding error for the next step.
                for op, o, n in zip(bucket, offs, lens):
                    r = self._residuals.get(op.name)
                    if r is not None and r.size == n:
                        flat[o:o + n] += r
                wire_flat, residual = quantize_error_feedback(flat, comp)
                for op, o, n in zip(bucket, offs, lens):
                    self._residuals[op.name] = residual[o:o + n].copy()
                flat = wire_flat
                fn = self._jit_for("arc", length, flat.dtype)
                mode_name = _WIRE_MODE_NAMES[comp]
            else:
                fn = self._jit_for(kind, length, dtype, bucket[0].root)
                mode_name = "none"
            if kind == "ar":
                # Ungated (like stalls): the wire-vs-payload ratio is the
                # compression acceptance number, assertable without full
                # metrics.  Payload counts at the CALLER-visible width,
                # wire at the dispatched buffer's dtype width (padding
                # excluded) — same semantics as the engine's counters.
                caller_bytes = sum(
                    int(np.prod(op.handle._shape))
                    * np.dtype(op.handle._dtype).itemsize for op in bucket)
                self.comp_stats["payload_bytes"] += caller_bytes
                self.comp_stats["wire_bytes"] += total * flat.dtype.itemsize
                self.comp_stats["ops"][mode_name] += 1
            if mx:
                _metrics.registry.observe(
                    "bucket_fill",
                    min(1.0, bucket_bytes
                        / max(self._threshold_for(bucket[0].tick), 1)))
            self._tl_phase(tl_lib, bucket, b"XLA_DISPATCH")
            batch = _Batch(self._traced_dispatch(fn, flat, kind,
                                                 len(bucket)),
                           t_disp=time.perf_counter() if mx else 0.0)
            self._tl_phase(tl_lib, bucket, None)
            for op, o, n in zip(bucket, offs, lens):
                op.handle._set_result(batch, o, n, op.tick, op.seq)
        self.stats["dispatches"] += 1
        self.stats["fused_tensors"] += len(bucket)
        if _postmortem.plane_ring.enabled:
            _postmortem.plane_ring.record("execute", bucket[0].name,
                                          len(bucket))

    def _tl_phase(self, tl_lib, bucket: List[_PlaneOp],
                  start: Optional[bytes]) -> None:
        """End the current timeline activity for every op in the bucket
        and (optionally) start the next one."""
        if tl_lib is None:
            return
        for op in bucket:
            tl_lib.hvd_tpu_timeline_activity_end(op.name.encode())
            if start is not None:
                tl_lib.hvd_tpu_timeline_activity_start(op.name.encode(),
                                                       start)

    def _traced_dispatch(self, fn, local: np.ndarray, kind: str, n_ops: int):
        """Launch the compiled collective, annotated for jax.profiler so
        plane dispatches are attributable inside an XProf/jax trace too
        (SURVEY §5.1's 'hooks into jax.profiler')."""
        import jax

        with jax.profiler.TraceAnnotation(
                f"hvd_plane_dispatch:{kind}:x{n_ops}"):
            return fn(self._global_array(local, replicated=(kind == "ag")))

    # -- public enqueue API ----------------------------------------------

    _OP_NAMES = {"ar": "allreduce", "bc": "broadcast", "ag": "allgather"}

    def _enqueue(self, kind: str, payload: np.ndarray, root: int,
                 handle: XlaHandle, name: str) -> XlaHandle:
        op = _PlaneOp(name, kind, payload, root, handle)
        # Flight recorder (postmortem plane): the XLA plane mirrors the
        # engine's ring so both data planes record their final seconds.
        if _postmortem.plane_ring.enabled:
            _postmortem.plane_ring.record("enqueue", name)
        if _metrics.registry.enabled:
            op.t_enq = time.perf_counter()
            # bytes.in/out are PAYLOAD bytes on both planes: the
            # caller-visible tensor at its own dtype's width (bf16/f16
            # pre-widening, f32 pre-compression).  On-wire bytes are
            # reported separately, in metrics_snapshot()["compression"]
            # (wire_bytes vs payload_bytes), so the two never mix.
            _metrics.registry.record_enqueue(
                "xla", self._OP_NAMES[kind],
                int(np.prod(handle._shape))
                * np.dtype(handle._dtype).itemsize)
        with self._mu:
            self._negotiate(op)
            self._pending.append(op)
        return handle

    def allreduce_async(self, array: np.ndarray, average: bool,
                        out: Optional[np.ndarray], name: str) -> XlaHandle:
        dtype = array.dtype
        # bf16/f16 sum in f32, like the engine's staging (engine.cc
        # HalfBufToFloat); bf16 from ml_dtypes reports kind "V".
        compute = array.astype(np.float32) if dtype.itemsize == 2 \
            and dtype.kind in ("f", "V") else array
        handle = XlaHandle(self, "ar", name, out, average, self._size,
                           dtype, array.shape)
        return self._enqueue("ar", compute, 0, handle, name)

    def broadcast_async(self, array: np.ndarray, root_rank: int,
                        out: Optional[np.ndarray], name: str) -> XlaHandle:
        handle = XlaHandle(self, "bc", name, out, False, self._size,
                           array.dtype, array.shape)
        return self._enqueue("bc", array, root_rank, handle, name)

    def allgather_async(self, array: np.ndarray, name: str) -> XlaHandle:
        # Final shape is known only after negotiation (ragged dim 0); the
        # handle's shape is patched at wait() from the negotiated dim0s.
        handle = XlaHandle(self, "ag", name, None, False, self._size,
                           array.dtype, array.shape)
        return self._enqueue("ag", array, 0, handle, name)


def _xla_coordinator(ps) -> Optional[str]:
    ep = os.environ.get("HVD_TPU_XLA_COORD")
    if ep:
        return ep
    if ps.coord_endpoint:
        # Default offset must clear the engine data ports, which occupy
        # port_base+1 .. port_base+local_size (runner/hosts.py); 500 matches
        # the launcher's own xla_coord allocation (hosts.py plan()).
        host, port = ps.coord_endpoint.rsplit(":", 1)
        offset = int(os.environ.get("HVD_TPU_XLA_COORD_PORT_OFFSET", "500"))
        return f"{host}:{int(port) + offset}"
    return None


def _ranks_by_process_index(ps, mesh_devices) -> np.ndarray:
    """The rank of the process at each JAX process index (the rows of
    ``mesh_devices``), asked of the fabric itself.

    Row r of the plane's mesh must hold rank r's devices: allgather blocks
    and the broadcast root are addressed by rank.  JAX's process index is
    not the rank: on a TPU host the runtime numbers the processes itself,
    whatever ``process_id`` said (four pinned v5e ranks 0..3 came up as
    processes 0, 2, 3, 1)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    by_index = Mesh(mesh_devices, ("hvd_proc", "hvd_local"))
    rank_at = np.asarray(jax.jit(
        lambda a: a, out_shardings=NamedSharding(by_index, P()))(
        jax.make_array_from_process_local_data(
            NamedSharding(by_index, P("hvd_proc")),
            np.asarray([ps.rank], np.int32), (ps.size,))))
    if sorted(rank_at.tolist()) != list(range(ps.size)):
        raise RuntimeError(
            f"ranks at JAX process indices are {rank_at.tolist()}, "
            f"expected a permutation of 0..{ps.size - 1}")
    return rank_at


def initialize(ps) -> XlaDataPlane:
    """Connect jax.distributed across the job and build the process mesh.
    Raises when the fabric cannot be initialized; the caller
    (``common._open_xla_plane``) decides what that means for the job.

    In a multi-rank job ``jax.distributed.initialize`` must be the first
    thing that touches JAX: the installed JAX refuses it once a backend
    exists, and on a TPU host a backend opened before it would claim the
    chip without the job's process grid."""
    global _plane
    with _lock:
        if _plane is not None:
            # Re-init in the same process: the engine's tick counter
            # and applied-parameter history restarted, so tick-keyed
            # fusion thresholds / compression modes memoized in the
            # previous lifetime are stale (and, being per-rank
            # wall-time artifacts, would split ranks into different
            # bucket plans).  Residuals reset with the engine's.
            _plane._tick_thresholds.clear()
            _plane._tick_comp.clear()
            _plane._residuals.clear()
            return _plane
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from horovod_tpu.common.config import Config

        if ps.size > 1 and not jax.distributed.is_initialized():
            coord = _xla_coordinator(ps)
            if coord is None:
                raise RuntimeError(
                    "no XLA coordinator endpoint (HVD_TPU_XLA_COORD)")
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=ps.size, process_id=ps.rank)
        devices = jax.devices()
        # A (process, local-chip) 2-D mesh: each process may own
        # several local devices (the reference ran several GPUs from
        # one process, test_tensorflow.py:189); with one device per
        # process this reduces to the 1-D per-process mesh.
        by_proc = {}
        for d in devices:
            by_proc.setdefault(d.process_index, []).append(d)
        if len(by_proc) != ps.size:
            raise RuntimeError(
                f"{len(by_proc)} processes visible to JAX, expected "
                f"{ps.size}")
        counts = {len(v) for v in by_proc.values()}
        if len(counts) != 1:
            raise RuntimeError(
                f"uneven device counts per process: "
                f"{ {k: len(v) for k, v in by_proc.items()} }")
        chips = counts.pop()
        mesh_devices = np.array(
            [sorted(by_proc[i], key=lambda d: d.id)
             for i in sorted(by_proc)])
        if ps.size > 1:
            mesh_devices = mesh_devices[np.argsort(
                _ranks_by_process_index(ps, mesh_devices))]
        mesh = Mesh(mesh_devices, ("hvd_proc", "hvd_local"))
        _plane = XlaDataPlane(
            mesh,
            NamedSharding(mesh, P("hvd_proc", "hvd_local")),
            NamedSharding(mesh, P()),
            ps.rank, ps.size,
            Config.from_env().fusion_threshold,
            spec_proc_only=NamedSharding(mesh, P("hvd_proc")),
            local_chips=chips)
        return _plane


def reset() -> None:
    """Testing hook: forget the cached plane (jax.distributed state is
    process-wide and cannot be re-initialized; use fresh processes)."""
    global _plane
    with _lock:
        _plane = None
