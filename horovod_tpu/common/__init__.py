"""Python seam to the native collective engine.

Counterpart of the reference's horovod/common/__init__.py (ctypes CDLL load,
init/shutdown/rank/size/local_rank/... wrappers raising ValueError when
uninitialized) plus the numpy-level async collective API that every framework
binding builds on (the role the torch cffi interface plays in the reference,
/root/reference/horovod/torch/interface.h).
"""

from __future__ import annotations

import atexit
import contextlib
import ctypes
import json
import os
import sys
import threading
import time
import weakref
from typing import Any, Optional, Sequence, Union

import numpy as np

from horovod_tpu.common import dtypes, metrics
from horovod_tpu.common.basics import (ProcessSet, chip_assigned,
                                       resolve_process_set)
from horovod_tpu.common.config import Config

# Op codes shared with the C++ engine (engine/cc/wire.h OpType).
OP_ALLREDUCE = 0
OP_ALLGATHER = 1
OP_BROADCAST = 2
# Negotiation-only (no data moves): the XLA plane's metadata-cache fast
# path replays a verified cross-rank agreement through this op to keep
# the global dispatch order without the metadata allreduce.
OP_NOOP = 3
# Point-to-point plane (docs/pipeline.md): negotiated pairwise transfers
# for pipeline parallelism.  A send and its matching recv announce under
# ONE wire name (``<name>.p2p.<src>-<dst>.t<tag>``) and execute when BOTH
# sides are ready (paired readiness).
OP_SEND = 4
OP_RECV = 5

# Status codes (engine/cc/wire.h StatusCode).
ST_OK = 0
ST_UNKNOWN = 1
ST_PRECONDITION = 2
ST_ABORTED = 3
ST_INVALID = 4
ST_PENDING = 5
ST_RANKS_DOWN = 6
ST_TIMEOUT = 7
ST_RESHAPE = 8


class HorovodInternalError(RuntimeError):
    """An unrecoverable engine error (transport failure, shutdown race)."""


class RanksDownError(HorovodInternalError):
    """A coordinated abort because one or more ranks died (control-socket
    EOF at the coordinator, or the coordinator itself went away).  The
    message names the missing ranks and the collectives they left pending;
    ``ranks`` carries them parsed (empty when unparsable).  The job cannot
    make progress — restart it (``hvdrun --max-restarts``) and resume from
    the latest checkpoint (docs/fault-tolerance.md)."""

    def __init__(self, message: str, ranks: Sequence[int] = ()):  # noqa: D107
        super().__init__(message)
        self.ranks = list(ranks)


class CollectiveTimeoutError(HorovodInternalError):
    """A coordinated abort because a collective stalled past
    ``HVD_TPU_COLLECTIVE_TIMEOUT_SEC``: a subset of ranks never submitted
    the matching op (rank-divergent control flow, or a wedged — not dead —
    peer).  The message names the stalled tensors and missing ranks."""


class MembershipChangedError(HorovodInternalError):
    """RETRYABLE (docs/fault-tolerance.md#elastic-membership): the elastic
    job reshaped — ranks died and the survivors re-negotiated size/rank at
    a tick boundary (or a standby was admitted) — and this collective was
    cancelled at the barrier.  No process relaunch or checkpoint reload is
    needed: re-enter agreement and resync state by root broadcast
    (``hvd.run_elastic`` does both).  ``lost_ranks`` names the dead ranks
    in the previous membership's numbering (empty on pure grows)."""

    def __init__(self, message: str, lost_ranks: Sequence[int] = ()):  # noqa: D107
        super().__init__(message)
        self.lost_ranks = list(lost_ranks)


class HorovodNotInitializedError(HorovodInternalError, ValueError):
    """An operation that needs a running engine was called before
    ``hvd.init()`` (or after ``hvd.shutdown()``).  Subclasses ValueError
    for compatibility with the reference's pre-init contract."""


_lib = None
_lib_lock = threading.Lock()
_process_set: Optional[ProcessSet] = None
# XLA data plane (compiled collectives over the accelerator fabric) when
# HVD_TPU_XLA_DATA_PLANE=1; None = disabled/unavailable -> TCP engine.
_xla_plane = None
# Dtypes the XLA plane accepts: jax's default (x64-disabled) world plus the
# half types it widens; everything else (f64, bool, ...) stays on the engine.
_XLA_PLANE_DTYPES = ("float32", "float16", "bfloat16", "int32", "int8",
                     "uint8")
# Metrics plumbing: per-rank JSON dump path (HVD_TPU_METRICS_FILE) and the
# count of engine stall/abort events already folded into the Python
# registry.
_metrics_file: Optional[str] = None
_engine_stalls_seen = 0
_engine_aborts_seen = 0
# Announce-order sync state (straggler attribution): events already folded
# into the Python registry, and the last cumulative per-rank
# last-to-announce counts read from the engine.
_engine_announces_seen = 0
_engine_last_announce_seen: list = []
# Response-cache sync state (docs/performance.md): engine-cumulative
# hit/miss/eviction counts already folded into the registry.
_engine_cache_seen = [0, 0, 0]
# Two-level topology sync state: per-bucket phase records already folded
# into the topology phase histograms (the engine log is bounded; the
# cumulative count keeps totals honest past it).
_engine_topo_seen = 0
# Deterministic fault injection (common/faults.py, HVD_TPU_FAULT_SPEC):
# the injector for this (rank, restart epoch), or None; and the per-process
# submission index of user-level collectives it is driven by.
_fault_injector = None
_collective_seq = 0
_fault_lock = threading.Lock()
# Serializes _sync_engine_stalls: the monitor thread and API callers may
# snapshot concurrently, and the ctypes stall-count read releases the GIL.
_stall_sync_lock = threading.Lock()


def _load_lib():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        from horovod_tpu.engine.build import build

        path = build()
        lib = ctypes.CDLL(path, mode=ctypes.RTLD_GLOBAL)
        lib.hvd_tpu_init.restype = ctypes.c_int
        lib.hvd_tpu_init.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_double,
            ctypes.c_longlong, ctypes.c_double, ctypes.c_char_p,
            ctypes.c_int, ctypes.c_double, ctypes.c_longlong,
            ctypes.c_int, ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_longlong, ctypes.c_double, ctypes.c_int,
            ctypes.c_longlong, ctypes.c_int, ctypes.c_int,
            ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_longlong, ctypes.c_int, ctypes.c_longlong,
            ctypes.c_longlong]
        lib.hvd_tpu_init_error.restype = ctypes.c_char_p
        lib.hvd_tpu_init_error.argtypes = []
        # Every export gets an explicit restype/argtypes — including the
        # void and no-arg ones ctypes would default correctly today —
        # so the hvdlint C-API parity checker can hold the seam to the
        # C signatures (docs/contributing.md#c-api-parity).
        lib.hvd_tpu_shutdown.restype = None
        lib.hvd_tpu_shutdown.argtypes = []
        lib.hvd_tpu_initialized.restype = ctypes.c_int
        lib.hvd_tpu_initialized.argtypes = []
        lib.hvd_tpu_rank.restype = ctypes.c_int
        lib.hvd_tpu_rank.argtypes = []
        lib.hvd_tpu_size.restype = ctypes.c_int
        lib.hvd_tpu_size.argtypes = []
        lib.hvd_tpu_local_rank.restype = ctypes.c_int
        lib.hvd_tpu_local_rank.argtypes = []
        lib.hvd_tpu_local_size.restype = ctypes.c_int
        lib.hvd_tpu_local_size.argtypes = []
        lib.hvd_tpu_enqueue.restype = ctypes.c_longlong
        lib.hvd_tpu_enqueue.argtypes = [
            ctypes.c_int, ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int]
        lib.hvd_tpu_enqueue_p2p.restype = ctypes.c_longlong
        lib.hvd_tpu_enqueue_p2p.argtypes = [
            ctypes.c_int, ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int]
        lib.hvd_tpu_enqueue_group.restype = ctypes.c_longlong
        lib.hvd_tpu_enqueue_group.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.POINTER(ctypes.c_longlong), ctypes.c_int]
        lib.hvd_tpu_p2p_info.restype = ctypes.c_char_p
        lib.hvd_tpu_p2p_info.argtypes = []
        lib.hvd_tpu_poll.restype = ctypes.c_int
        lib.hvd_tpu_poll.argtypes = [ctypes.c_longlong]
        lib.hvd_tpu_wait.restype = ctypes.c_int
        lib.hvd_tpu_wait.argtypes = [ctypes.c_longlong]
        lib.hvd_tpu_status.restype = ctypes.c_int
        lib.hvd_tpu_status.argtypes = [ctypes.c_longlong]
        lib.hvd_tpu_error.restype = ctypes.c_char_p
        lib.hvd_tpu_error.argtypes = [ctypes.c_longlong]
        lib.hvd_tpu_completion_seq.restype = ctypes.c_longlong
        lib.hvd_tpu_completion_seq.argtypes = [ctypes.c_longlong]
        lib.hvd_tpu_completion_tick.restype = ctypes.c_longlong
        lib.hvd_tpu_completion_tick.argtypes = [ctypes.c_longlong]
        lib.hvd_tpu_negotiation_us.restype = ctypes.c_longlong
        lib.hvd_tpu_negotiation_us.argtypes = [ctypes.c_longlong]
        lib.hvd_tpu_ticks_done.restype = ctypes.c_longlong
        lib.hvd_tpu_ticks_done.argtypes = []
        lib.hvd_tpu_result_nbytes.restype = ctypes.c_longlong
        lib.hvd_tpu_result_nbytes.argtypes = [ctypes.c_longlong]
        lib.hvd_tpu_result_dim0.restype = ctypes.c_longlong
        lib.hvd_tpu_result_dim0.argtypes = [ctypes.c_longlong]
        lib.hvd_tpu_result_ptr.restype = ctypes.c_void_p
        lib.hvd_tpu_result_ptr.argtypes = [ctypes.c_longlong]
        lib.hvd_tpu_copy_result.restype = ctypes.c_int
        lib.hvd_tpu_copy_result.argtypes = [
            ctypes.c_longlong, ctypes.c_void_p, ctypes.c_longlong]
        lib.hvd_tpu_release.restype = None
        lib.hvd_tpu_release.argtypes = [ctypes.c_longlong]
        lib.hvd_tpu_stall_count.restype = ctypes.c_longlong
        lib.hvd_tpu_stall_count.argtypes = []
        lib.hvd_tpu_stall_info.restype = ctypes.c_char_p
        lib.hvd_tpu_stall_info.argtypes = []
        lib.hvd_tpu_abort_code.restype = ctypes.c_int
        lib.hvd_tpu_abort_code.argtypes = []
        lib.hvd_tpu_abort_message.restype = ctypes.c_char_p
        lib.hvd_tpu_abort_message.argtypes = []
        lib.hvd_tpu_abort_count.restype = ctypes.c_longlong
        lib.hvd_tpu_abort_count.argtypes = []
        lib.hvd_tpu_clock_offset_us.restype = ctypes.c_longlong
        lib.hvd_tpu_clock_offset_us.argtypes = []
        lib.hvd_tpu_clock_rtt_us.restype = ctypes.c_longlong
        lib.hvd_tpu_clock_rtt_us.argtypes = []
        lib.hvd_tpu_liveness_info.restype = ctypes.c_char_p
        lib.hvd_tpu_liveness_info.argtypes = []
        lib.hvd_tpu_link_info.restype = ctypes.c_char_p
        lib.hvd_tpu_link_info.argtypes = []
        lib.hvd_tpu_anomaly_info.restype = ctypes.c_char_p
        lib.hvd_tpu_anomaly_info.argtypes = []
        lib.hvd_tpu_anomaly_log.restype = ctypes.c_char_p
        lib.hvd_tpu_anomaly_log.argtypes = []
        lib.hvd_tpu_announce_count.restype = ctypes.c_longlong
        lib.hvd_tpu_announce_count.argtypes = []
        lib.hvd_tpu_announce_log.restype = ctypes.c_char_p
        lib.hvd_tpu_announce_log.argtypes = []
        lib.hvd_tpu_last_announce_counts.restype = ctypes.c_char_p
        lib.hvd_tpu_last_announce_counts.argtypes = []
        lib.hvd_tpu_cache_hit_count.restype = ctypes.c_longlong
        lib.hvd_tpu_cache_hit_count.argtypes = []
        lib.hvd_tpu_cache_miss_count.restype = ctypes.c_longlong
        lib.hvd_tpu_cache_miss_count.argtypes = []
        lib.hvd_tpu_cache_eviction_count.restype = ctypes.c_longlong
        lib.hvd_tpu_cache_eviction_count.argtypes = []
        lib.hvd_tpu_cache_size.restype = ctypes.c_longlong
        lib.hvd_tpu_cache_size.argtypes = []
        lib.hvd_tpu_control_info.restype = ctypes.c_char_p
        lib.hvd_tpu_control_info.argtypes = []
        lib.hvd_tpu_steady_active.restype = ctypes.c_int
        lib.hvd_tpu_steady_active.argtypes = []
        lib.hvd_tpu_simscale_run.restype = ctypes.c_int
        lib.hvd_tpu_simscale_run.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_longlong, ctypes.c_int, ctypes.c_int,
            ctypes.c_double, ctypes.c_char_p, ctypes.c_longlong]
        lib.hvd_tpu_autotune_enabled.restype = ctypes.c_int
        lib.hvd_tpu_autotune_enabled.argtypes = []
        lib.hvd_tpu_autotune_frozen.restype = ctypes.c_int
        lib.hvd_tpu_autotune_frozen.argtypes = []
        lib.hvd_tpu_autotune_windows.restype = ctypes.c_longlong
        lib.hvd_tpu_autotune_windows.argtypes = []
        lib.hvd_tpu_autotune_fusion_threshold.restype = ctypes.c_longlong
        lib.hvd_tpu_autotune_fusion_threshold.argtypes = []
        lib.hvd_tpu_autotune_cycle_time_us.restype = ctypes.c_longlong
        lib.hvd_tpu_autotune_cycle_time_us.argtypes = []
        lib.hvd_tpu_autotune_best_score.restype = ctypes.c_double
        lib.hvd_tpu_autotune_best_score.argtypes = []
        lib.hvd_tpu_autotune_history.restype = ctypes.c_char_p
        lib.hvd_tpu_autotune_history.argtypes = []
        lib.hvd_tpu_autotune_applied.restype = ctypes.c_char_p
        lib.hvd_tpu_autotune_applied.argtypes = []
        lib.hvd_tpu_autotune_set.restype = ctypes.c_int
        lib.hvd_tpu_autotune_set.argtypes = [ctypes.c_longlong,
                                             ctypes.c_double,
                                             ctypes.c_longlong,
                                             ctypes.c_longlong]
        lib.hvd_tpu_autotune_cross_algo_threshold.restype = \
            ctypes.c_longlong
        lib.hvd_tpu_autotune_cross_algo_threshold.argtypes = []
        lib.hvd_tpu_topology_info.restype = ctypes.c_char_p
        lib.hvd_tpu_topology_info.argtypes = []
        lib.hvd_tpu_topology_log.restype = ctypes.c_char_p
        lib.hvd_tpu_topology_log.argtypes = []
        lib.hvd_tpu_fusion_threshold_at.restype = ctypes.c_longlong
        lib.hvd_tpu_fusion_threshold_at.argtypes = [ctypes.c_longlong]
        lib.hvd_tpu_compression_mode.restype = ctypes.c_int
        lib.hvd_tpu_compression_mode.argtypes = []
        lib.hvd_tpu_compression_mode_at.restype = ctypes.c_longlong
        lib.hvd_tpu_compression_mode_at.argtypes = [ctypes.c_longlong]
        lib.hvd_tpu_compression_info.restype = ctypes.c_char_p
        lib.hvd_tpu_compression_info.argtypes = []
        lib.hvd_tpu_compression_log.restype = ctypes.c_char_p
        lib.hvd_tpu_compression_log.argtypes = []
        lib.hvd_tpu_elastic_enabled.restype = ctypes.c_int
        lib.hvd_tpu_elastic_enabled.argtypes = []
        lib.hvd_tpu_membership_epoch.restype = ctypes.c_longlong
        lib.hvd_tpu_membership_epoch.argtypes = []
        lib.hvd_tpu_membership_reshapes.restype = ctypes.c_longlong
        lib.hvd_tpu_membership_reshapes.argtypes = []
        lib.hvd_tpu_membership_info.restype = ctypes.c_char_p
        lib.hvd_tpu_membership_info.argtypes = []
        lib.hvd_tpu_membership_ack_pending.restype = ctypes.c_int
        lib.hvd_tpu_membership_ack_pending.argtypes = []
        lib.hvd_tpu_membership_ack.restype = None
        lib.hvd_tpu_membership_ack.argtypes = []
        lib.hvd_tpu_timeline_enabled.restype = ctypes.c_int
        lib.hvd_tpu_timeline_enabled.argtypes = []
        lib.hvd_tpu_timeline_op_start.restype = None
        lib.hvd_tpu_timeline_op_start.argtypes = [ctypes.c_char_p,
                                                  ctypes.c_char_p]
        lib.hvd_tpu_timeline_activity_start.restype = None
        lib.hvd_tpu_timeline_activity_start.argtypes = [ctypes.c_char_p,
                                                        ctypes.c_char_p]
        lib.hvd_tpu_timeline_activity_end.restype = None
        lib.hvd_tpu_timeline_activity_end.argtypes = [ctypes.c_char_p]
        lib.hvd_tpu_timeline_op_end.restype = None
        lib.hvd_tpu_timeline_op_end.argtypes = [ctypes.c_char_p,
                                                ctypes.c_longlong]
        lib.hvd_tpu_timeline_instant.restype = None
        lib.hvd_tpu_timeline_instant.argtypes = [ctypes.c_char_p,
                                                 ctypes.c_char_p]
        lib.hvd_tpu_timeline_flush.restype = None
        lib.hvd_tpu_timeline_flush.argtypes = []
        lib.hvd_tpu_flight_count.restype = ctypes.c_longlong
        lib.hvd_tpu_flight_count.argtypes = []
        lib.hvd_tpu_flight_dump.restype = ctypes.c_char_p
        lib.hvd_tpu_flight_dump.argtypes = []
        lib.hvd_tpu_pending_info.restype = ctypes.c_char_p
        lib.hvd_tpu_pending_info.argtypes = []
        lib.hvd_tpu_coord_pending_info.restype = ctypes.c_char_p
        lib.hvd_tpu_coord_pending_info.argtypes = []
        lib.hvd_tpu_diagnosis.restype = ctypes.c_char_p
        lib.hvd_tpu_diagnosis.argtypes = []
        _lib = lib
        return lib


def _resolve_timeline_path(path: str, rank: int, epoch: int = 0) -> str:
    """Resolve ``HOROVOD_TIMELINE``'s forms (docs/timeline.md) to this
    rank's trace path: a ``%d`` template or a directory (existing, or a
    trailing-separator path) yield one Chrome-trace file PER RANK; a plain
    file path keeps the legacy rank-0-only single file.  A non-zero
    restart epoch (``hvdrun --max-restarts``) lands in the filename
    (``rank<N>.e<E>.json``) so a relaunch cannot truncate the crashed
    attempt's post-mortem traces."""
    if not path:
        return ""
    suffix = f".e{epoch}" if epoch else ""
    if "%d" in path:
        resolved = path.replace("%d", str(rank)) + suffix
        parent = os.path.dirname(resolved)
        if parent:
            os.makedirs(parent, exist_ok=True)
        return resolved
    if path.endswith(os.sep) or os.path.isdir(path):
        os.makedirs(path, exist_ok=True)
        return os.path.join(path, f"rank{rank}{suffix}.json")
    return path if rank == 0 else ""


def init(comm: Union[Sequence[int], Any, None] = None) -> None:
    """Initialize the engine.

    ``comm`` optionally restricts the job to a subset of launcher ranks —
    either a rank list or an mpi4py(-style) communicator, mirroring both
    forms the reference accepts
    (/root/reference/horovod/common/__init__.py:51-78; the communicator
    is duck-typed via ``Get_size``/``allgather``, see
    :func:`horovod_tpu.common.basics.comm_ranks` — no MPI dependency).
    """
    global _process_set
    lib = _load_lib()
    if lib.hvd_tpu_initialized():
        return
    if comm is not None and hasattr(comm, "Get_size"):
        from horovod_tpu.common.basics import comm_ranks

        comm = comm_ranks(comm, resolve_process_set(None).rank)
    ps = resolve_process_set(comm)
    cfg = Config.from_env()
    # A rejoining standby's rank is a placeholder until the coordinator
    # admits it, so a rank-keyed timeline path would collide with the live
    # rank that currently owns that number; standbys skip the timeline.
    timeline = ("" if cfg.rejoin else
                _resolve_timeline_path(cfg.timeline_path, ps.rank,
                                       cfg.restart_epoch))
    data = ",".join(ps.data_endpoints) if ps.data_endpoints else ""
    from horovod_tpu.common import autotune as _autotune

    # Pin-spec and compression-spec errors must surface at init, not be
    # silently dropped into a knob the user asked to hold
    # (common/autotune.py, common/config.py).
    fix_fusion, fix_cycle, fix_comp, fix_algo = _autotune.parse_fix(
        cfg.autotune_fix)
    compression_code = cfg.compression_code  # ValueError on a bad mode
    if fix_comp > 0 and compression_code == 0:
        # The engine pins the autotune axis at "none" whenever the job
        # did not opt into compression (a tuner must never make an exact
        # job lossy) — so a lossy pin here would be silently dropped,
        # the exact failure mode parse_fix exists to reject.  (A lossy
        # pin WITH the two-level topology is fine: the negotiated mode
        # narrows the cross-node/DCN hop there.)
        raise ValueError(
            "HVD_TPU_AUTOTUNE_FIX pins a lossy wire-compression mode but "
            "HVD_TPU_COMPRESSION is off; set HVD_TPU_COMPRESSION=bf16|fp8 "
            "(or drop the compression pin).")
    if fix_algo >= 0 and not cfg.hierarchical_allreduce:
        # The cross-algo axis only means anything on the two-level
        # topology; the flat ring pins it silently at the env value, so
        # an explicit pin there would be dropped — the same parse_fix
        # contract the compression pin enforces.
        raise ValueError(
            "HVD_TPU_AUTOTUNE_FIX pins cross_algo_threshold but the flat "
            "ring has no cross-node hop; set "
            "HVD_TPU_HIERARCHICAL_ALLREDUCE=1 (or drop the pin).")
    # XLA data plane selection.  Like the reference's NCCL path — which
    # auto-selected whenever NCCL was compiled in, no runtime flag
    # (/root/reference/horovod/common/operations.cc:861-914) — the plane
    # is AUTO-enabled on a rank that was given a chip of its own
    # (basics.chip_assigned: read from the environment, never by asking
    # JAX, which would open the chip); HVD_TPU_XLA_DATA_PLANE (or
    # HOROVOD_XLA_DATA_PLANE) forces it on (=1) or off (=0).  A rank
    # with no chip of its own never initialises a JAX backend here.
    auto = cfg.xla_data_plane is None
    wanted = chip_assigned() if auto else cfg.xla_data_plane
    # Elastic membership rides the TCP engine only: the XLA plane's
    # device mesh is fixed at init and cannot survive a reshape.
    elastic = cfg.elastic or cfg.rejoin
    # The fabric comes up BEFORE the engine starts.  Opening a chip
    # freezes the whole process for seconds (the TPU runtime's start-up),
    # and the engine's heartbeat detector, once it runs, takes a rank
    # that has been silent for one second for dead: on four v5e chips
    # the ranks aborted each other inside init with the order reversed.
    plane = plane_failure = None
    if wanted and not elastic:
        plane, plane_failure = _open_xla_plane(ps)
    rc = lib.hvd_tpu_init(
        ps.rank, ps.size, ps.local_rank, ps.local_size,
        (ps.coord_endpoint or "").encode(), data.encode(),
        cfg.cycle_time_ms, cfg.fusion_threshold, cfg.stall_warning_sec,
        timeline.encode(), int(cfg.hierarchical_allreduce),
        cfg.collective_timeout_sec, cfg.effective_cache_capacity,
        int(cfg.autotune), cfg.autotune_warmup, cfg.autotune_window,
        fix_fusion, fix_cycle, int(cfg.elastic or cfg.rejoin),
        cfg.min_np, int(cfg.rejoin), compression_code,
        cfg.compression_min_bytes, fix_comp, cfg.cross_algo_threshold,
        fix_algo, int(cfg.coord_tree), cfg.steady_threshold,
        cfg.steady_max_period)
    if rc != 0:
        raise HorovodInternalError(
            "engine initialization failed: "
            + lib.hvd_tpu_init_error().decode())
    _process_set = ps
    if cfg.restart_epoch:
        # Identify relaunched runs in metrics snapshots/dumps.
        metrics.registry.set_restart_epoch(cfg.restart_epoch)
    # Metrics: enabled by HVD_TPU_METRICS=1 or implied by a dump file /
    # monitor port (docs/metrics.md).  The monitor binds port+local_rank
    # so several ranks on one host coexist; rank 0's local_rank is 0, so
    # the scrape example `curl localhost:$HVD_TPU_MONITOR_PORT/metrics`
    # always hits rank 0.
    global _metrics_file
    if cfg.metrics_enabled:
        metrics.registry.enable()
    _metrics_file = (f"{cfg.metrics_file}.{ps.rank}"
                     if cfg.metrics_file else None)
    # Postmortem plane (docs/troubleshooting.md#reading-a-postmortem):
    # with a dump dir set, fatal uncaught exceptions leave a rank dump
    # too (typed aborts and injected crashes hook their own paths).
    if cfg.postmortem_dir:
        from horovod_tpu.common import postmortem as _postmortem

        _postmortem.install_excepthook()
    if cfg.monitor_port is not None:
        port = cfg.monitor_port + ps.local_rank if cfg.monitor_port else 0
        try:
            metrics.start_monitor(port, snapshot_fn=metrics_snapshot)
            # Job-level aggregation (docs/metrics.md#cluster): rank 0's
            # monitor additionally serves /cluster, one merged health view
            # scraped from every rank's /health.  Needs a fixed base port
            # (port 0 binds randomly — peers become unscrapable).
            if ps.rank == 0 and cfg.monitor_port:
                metrics.configure_cluster(
                    _cluster_targets(ps, cfg.monitor_port))
        except OSError as exc:
            import warnings

            # A busy port must not take down the training job; metrics
            # stay collectable through the API and the shutdown dump.
            warnings.warn(f"metrics monitor could not bind port {port}: "
                          f"{exc}; continuing without the HTTP endpoint.")
    global _xla_plane
    if elastic:
        # A standby must not enqueue the init-time plane agreement into a
        # job that is not running one.
        if wanted and not auto:
            import warnings

            warnings.warn(
                "elastic membership (HVD_TPU_ELASTIC/--min-np) does not "
                "support the XLA data plane; eager collectives will use "
                "the TCP engine.")
        _xla_plane = None
    elif wanted or auto:
        _xla_plane = _agree_on_xla_plane(ps, plane, plane_failure)
    # Deterministic fault injection (docs/fault-tolerance.md), armed LAST:
    # init()'s own internal collectives (the plane agreement above) must
    # not consume fault-spec op indices — op=N counts the caller's
    # collectives from 0.
    global _fault_injector, _collective_seq
    from horovod_tpu.common import faults as _faults

    with _fault_lock:
        _collective_seq = 0  # re-init after shutdown restarts the count
    _fault_injector = _faults.from_env(ps.rank)
    atexit.register(shutdown)


def _cluster_targets(ps: ProcessSet, base_port: int) -> list:
    """(rank, host, port) scrape targets for the /cluster aggregation:
    every rank's monitor binds ``base_port + local_rank``, and hvdrun
    places ranks in contiguous per-host blocks, so a rank's local index is
    the count of same-host ranks before it.  Falls back to localhost when
    the launcher provided no data endpoints (single-process init)."""
    targets = []
    seen: dict = {}
    for r in range(ps.size):
        if ps.data_endpoints and r < len(ps.data_endpoints):
            host = ps.data_endpoints[r].rsplit(":", 1)[0]
        else:
            host = "127.0.0.1"
        local_idx = seen.get(host, 0)
        seen[host] = local_idx + 1
        targets.append((r, host, base_port + local_idx))
    return targets


def _open_xla_plane(ps: ProcessSet):
    """Bring the XLA data plane up on this rank, which asked for it.
    Returns ``(plane, failure)``: the failure is kept, not raised, so that
    this rank still votes (:func:`_agree_on_xla_plane`) and every rank of
    the job fails together instead of waiting for one that left."""
    try:
        from horovod_tpu.jax import eager_mesh

        return eager_mesh.initialize(ps), None
    except Exception as exc:
        return None, exc


def _agree_on_xla_plane(ps: ProcessSet, plane, failure):
    """Agree job-wide on the plane this rank opened (or did not want);
    returns the plane or None (TCP engine).

    A plane that was asked for — by pinning or by
    ``HVD_TPU_XLA_DATA_PLANE=1`` — and cannot form raises: a job that
    silently rode TCP would report success without ever using the
    fabric.  Only a rank that did not ask (auto mode, no chip) follows
    the job onto the TCP engine with a warning."""
    if ps.size > 1:
        # Over the TCP engine (_xla_plane is still None, so this
        # allreduce cannot ride the plane): ranks split across two
        # transports would deadlock.  Every rank votes, even with a local
        # "no".
        total = allreduce(np.asarray(1 if plane else 0, np.int32),
                          average=False, name="__xla_plane_agreement__")
        if int(total) != ps.size:
            if plane is not None:
                failure = RuntimeError(
                    f"only {int(total)} of {ps.size} ranks formed it")
            elif failure is None and int(total):
                import warnings

                warnings.warn(
                    "XLA data plane disabled: this rank was given no chip "
                    "of its own; eager collectives use the TCP engine.")
            plane = None
    if failure is not None:
        shutdown()
        raise HorovodInternalError(
            "the XLA data plane was requested (HVD_TPU_XLA_DATA_PLANE=1 or "
            f"a pinned chip) but could not form: {failure}") from failure
    return plane


def _flush_metrics_file(clear: bool = True) -> None:
    """Write the per-rank ``HVD_TPU_METRICS_FILE`` dump now.  The clean
    ``shutdown()`` path clears the pending path afterwards; the abort /
    postmortem paths flush WITHOUT clearing (crashed ranks must leave
    metrics too, and a later clean shutdown simply overwrites the dump
    with fresher totals)."""
    global _metrics_file
    if _metrics_file is None:
        return
    path = _metrics_file
    if clear:
        _metrics_file = None
    try:
        with open(path, "w") as f:
            json.dump(metrics_snapshot(), f, indent=2)
            f.write("\n")
    except OSError as exc:
        import warnings

        warnings.warn(f"could not write metrics file {path}: {exc}")


def shutdown() -> None:
    """Shut the engine down.  Idempotent: safe to call twice, or without a
    prior ``init()`` (both are no-ops beyond flushing metrics plumbing)."""
    global _process_set, _xla_plane, _fault_injector
    _fault_injector = None
    # The state plane's lifetime is the engine's: disarm (close the
    # snapshot worker + peer listener) so a later init()+arm() starts
    # clean and a stale plane can never route a new job's resyncs.
    try:
        from horovod_tpu import state as _state_mod

        _state_mod.disarm()
    except Exception:
        pass
    if _lib is not None and int(_lib.hvd_tpu_abort_code()) != 0:
        # A typed abort the process never consumed through a Handle.wait
        # (e.g. the driver was between collectives when the coordinator
        # aborted, and atexit is the first code to look): leave the
        # postmortem artifact before the engine state goes away.
        from horovod_tpu.common import postmortem as _postmortem

        _postmortem.write_postmortem(
            _postmortem.reason_for_code(int(_lib.hvd_tpu_abort_code())))
    _flush_metrics_file(clear=True)
    metrics.stop_monitor()
    if _lib is not None and _lib.hvd_tpu_initialized():
        _lib.hvd_tpu_shutdown()
    _process_set = None
    _xla_plane = None


def _check_initialized(lib) -> None:
    if not lib.hvd_tpu_initialized():
        raise HorovodNotInitializedError(
            "Horovod-TPU has not been initialized; use hvd.init().")


def is_initialized() -> bool:
    """True between a successful ``init()`` and ``shutdown()``.  Never
    loads or builds the native engine as a side effect."""
    return _lib is not None and bool(_lib.hvd_tpu_initialized())


def restart_epoch() -> int:
    """The ``hvdrun --max-restarts`` relaunch counter for this process: 0
    on the first run, +1 per restart (``HVD_TPU_RESTART_EPOCH``).  Usable
    before ``init()`` — checkpoint-resume glue runs early."""
    return int(os.environ.get("HVD_TPU_RESTART_EPOCH") or 0)


def membership_epoch() -> int:
    """The elastic-membership epoch of this engine lifetime: 0 until the
    first reshape, +1 per reshape survived (shrink or grow).  After a
    reshape, ``hvd.rank()``/``hvd.size()`` re-resolve to the new dense
    membership; this counter is how drivers notice the change
    (docs/fault-tolerance.md#elastic-membership).  0 before ``init()``."""
    if _lib is None:
        return 0
    return int(_lib.hvd_tpu_membership_epoch())


def membership_ack() -> None:
    """Acknowledge the latest membership reshape: clears the engine's
    post-reshape enqueue poison so collectives negotiate again in the new
    membership.  Call only once every rank is about to re-enter agreement
    from a synchronized point — ``hvd.run_elastic`` does this (followed by
    the root-broadcast state resync) and is the normal way to consume
    reshapes."""
    if _lib is not None:
        _lib.hvd_tpu_membership_ack()


def rank() -> int:
    lib = _load_lib()
    _check_initialized(lib)
    return lib.hvd_tpu_rank()


def size() -> int:
    lib = _load_lib()
    _check_initialized(lib)
    return lib.hvd_tpu_size()


def local_rank() -> int:
    lib = _load_lib()
    _check_initialized(lib)
    return lib.hvd_tpu_local_rank()


def local_size() -> int:
    lib = _load_lib()
    _check_initialized(lib)
    return lib.hvd_tpu_local_size()


def mpi_threads_supported() -> bool:
    """Compatibility shim: there is no MPI; the engine is always
    thread-safe for concurrent enqueues (the property this reference API,
    /root/reference/horovod/common/__init__.py:142-153, reported)."""
    _check_initialized(_load_lib())
    return True


# ---------------------------------------------------------------------------
# Collective metrics (common/metrics.py; docs/metrics.md).
# ---------------------------------------------------------------------------


def _sync_engine_stalls() -> None:
    """Fold the engine's (C++, rank-0 sweep) stall warnings into the Python
    registry.  The C side reports a cumulative event count plus a bounded
    log of the most recent "name|seconds" records; we consume only the
    events not yet seen, so repeated snapshots never double-count and
    ``metrics_reset()`` keeps its clear-everything semantics."""
    global _engine_stalls_seen
    if _lib is None:
        return
    with _stall_sync_lock:
        count = int(_lib.hvd_tpu_stall_count())
        new = count - _engine_stalls_seen
        if new <= 0:
            return
        _engine_stalls_seen = count
        entries = [e for e in
                   _lib.hvd_tpu_stall_info().decode().split(";") if e]
        taken = entries[-new:]
        for entry in taken:
            name, _, sec = entry.partition("|")
            try:
                duration = float(sec)
            except ValueError:
                duration = 0.0
            metrics.registry.record_stall(name, duration)
        # The engine's log is bounded (64): events beyond it keep the
        # total honest even though their tensor names are gone.
        if new > len(taken):
            metrics.registry.record_stall_count(new - len(taken))


def _sync_engine_aborts() -> None:
    """Fold the engine's coordinated-abort events into the registry (kind
    from the latched status code: ranks_down / timeout).  Consumes only
    unseen events, like the stall sync."""
    global _engine_aborts_seen
    if _lib is None:
        return
    with _stall_sync_lock:
        count = int(_lib.hvd_tpu_abort_count())
        new = count - _engine_aborts_seen
        if new <= 0:
            return
        _engine_aborts_seen = count
        code = int(_lib.hvd_tpu_abort_code())
        kind = "timeout" if code == ST_TIMEOUT else "ranks_down"
        metrics.registry.record_abort(kind, new)


def _sync_engine_announces() -> None:
    """Fold the coordinator's announce-order accounting into the registry
    (straggler attribution, docs/troubleshooting.md).  Per-rank
    last-to-announce counts come from an exact cumulative C-side vector;
    the first->last skew histogram from a bounded event log — events that
    fell off the log keep the per-rank totals honest but contribute no
    skew sample.  Coordinator-side data: non-zero on rank 0 only."""
    global _engine_announces_seen, _engine_last_announce_seen
    if _lib is None:
        return
    with _stall_sync_lock:
        counts_raw = _lib.hvd_tpu_last_announce_counts().decode()
        counts = [int(tok) for tok in counts_raw.split(",") if tok]
        for r, c in enumerate(counts):
            prev = (_engine_last_announce_seen[r]
                    if r < len(_engine_last_announce_seen) else 0)
            if c > prev:
                metrics.registry.record_last_announce(r, c - prev)
        _engine_last_announce_seen = counts
        # One C call carries "cumulative_count:entries", serialized under
        # the engine's announce lock — pairing a separate count call with
        # the log would race concurrent negotiations and mis-window the
        # skew samples.
        head, _, tail = _lib.hvd_tpu_announce_log().decode().partition(":")
        try:
            count = int(head)
        except ValueError:
            return
        new = count - _engine_announces_seen
        if new <= 0:
            return
        _engine_announces_seen = count
        entries = [e for e in tail.split(";") if e]
        for entry in entries[-new:]:
            _, _, us = entry.partition("|")
            try:
                skew_sec = float(us) / 1e6
            except ValueError:
                continue
            metrics.registry.observe("announce_skew_sec", skew_sec)


def _sync_engine_cache() -> None:
    """Fold the engine's response-cache counters (C++, cumulative) into
    the registry's ``"cache"`` section.  Consumes only unseen events, like
    the stall sync, so snapshots never double-count and the cache size
    gauge always reflects the engine's current entry count."""
    if _lib is None:
        return
    with _stall_sync_lock:
        counts = (int(_lib.hvd_tpu_cache_hit_count()),
                  int(_lib.hvd_tpu_cache_miss_count()),
                  int(_lib.hvd_tpu_cache_eviction_count()))
        for kind, total, seen_idx in (("hits", counts[0], 0),
                                      ("misses", counts[1], 1),
                                      ("evictions", counts[2], 2)):
            new = total - _engine_cache_seen[seen_idx]
            if new > 0:
                metrics.registry.record_cache("engine", kind, new)
            _engine_cache_seen[seen_idx] = total
        metrics.registry.set_cache_size("engine",
                                        int(_lib.hvd_tpu_cache_size()))
        meta = getattr(_xla_plane, "_meta_cache", None)
        if meta is not None:
            metrics.registry.set_cache_size("xla", len(meta))


def _sync_engine_membership() -> None:
    """Mirror the engine's elastic-membership state into the registry's
    ungated ``"membership"`` section (epoch, current size, reshapes, ranks
    lost/joined).  A state copy like the autotune sync: overwriting is
    idempotent and ``metrics_reset()`` re-mirrors on the next snapshot."""
    if _lib is None:
        return
    with _stall_sync_lock:
        info = _lib.hvd_tpu_membership_info().decode()
        parts = (info.split("|") + ["", "", "", ""])[:4]
        try:
            epoch, size_now = int(parts[0]), int(parts[1])
        except ValueError:
            return
        metrics.registry.set_membership({
            "epoch": epoch,
            "size": size_now,
            "reshapes": int(_lib.hvd_tpu_membership_reshapes()),
            "ranks_lost": [int(tok) for tok in parts[2].split(",") if tok],
            "ranks_joined": [int(tok) for tok in parts[3].split(",") if tok],
        })


def _sync_engine_flight() -> None:
    """Mirror the flight recorders' cumulative event counts (engine C++
    ring + XLA-plane Python ring) into the registry's ungated ``"flight"``
    section.  A state copy, like the membership sync."""
    from horovod_tpu.common import postmortem as _postmortem

    with _stall_sync_lock:
        engine_events = (int(_lib.hvd_tpu_flight_count())
                         if _lib is not None else 0)
        metrics.registry.set_flight({
            "events": {"engine": engine_events,
                       "xla": _postmortem.plane_ring.total},
            "capacity": _postmortem.ring_capacity(),
        })


def _sync_engine_compression() -> None:
    """Mirror the wire-compression state of both data planes into the
    registry's ungated ``"compression"`` section (docs/performance.md
    #wire-compression): the applied mode and min-bytes floor, per-plane
    wire-vs-payload byte totals and per-mode bucket counts, and the
    error-feedback residual gauges.  A state copy like the membership
    sync — the C counters are cumulative, so overwriting is idempotent."""
    if _lib is None:
        return
    from horovod_tpu.common.config import COMPRESSION_NAMES

    with _stall_sync_lock:
        parts = _lib.hvd_tpu_compression_info().decode().split("|")
        try:
            (wire, payload, n_none, n_bf16, n_fp8, res_bytes, res_tensors,
             min_bytes) = (int(p) for p in parts[:8])
        except ValueError:
            return
        planes = {
            "engine": {"wire_bytes": wire, "payload_bytes": payload,
                       "ops": {"none": n_none, "bf16": n_bf16,
                               "fp8": n_fp8}},
            "xla": {"wire_bytes": 0, "payload_bytes": 0,
                    "ops": {"none": 0, "bf16": 0, "fp8": 0}},
        }
        plane_stats = getattr(_xla_plane, "comp_stats", None)
        plane_res = 0
        if plane_stats is not None:
            planes["xla"] = {
                "wire_bytes": int(plane_stats["wire_bytes"]),
                "payload_bytes": int(plane_stats["payload_bytes"]),
                "ops": dict(plane_stats["ops"]),
            }
            plane_res = sum(r.nbytes for r in
                            getattr(_xla_plane, "_residuals", {}).values())
        metrics.registry.set_compression({
            "mode": COMPRESSION_NAMES.get(
                int(_lib.hvd_tpu_compression_mode()), "off"),
            "min_bytes": min_bytes,
            "planes": planes,
            "residual_bytes": res_bytes + plane_res,
            "residual_tensors": res_tensors + len(
                getattr(_xla_plane, "_residuals", {}) or {}),
        })


def _sync_engine_topology() -> None:
    """Mirror the engine's two-level topology state into the registry's
    ungated ``"topology"`` section (docs/performance.md
    #two-level-topology) and fold the bounded per-bucket phase log into
    the ``topology_*_sec`` phase histograms.  The gauges/counters are a
    state copy like the compression sync; the log is delta-consumed like
    the stall sync so repeated snapshots never double-observe."""
    global _engine_topo_seen
    if _lib is None:
        return
    with _stall_sync_lock:
        parts = _lib.hvd_tpu_topology_info().decode().split("|")
        try:
            (hier, nodes, local_size, threshold, ops_ring, ops_tree,
             local_bytes, cross_bytes, log_total) = (
                int(p) for p in parts[:9])
        except ValueError:
            return
        # 10th field (PR-19): the node-local hop's transport ("shm" once
        # the segment armed, else "tcp"); tolerate 9-field engines.
        local_transport = parts[9] if len(parts) > 9 else "tcp"
        metrics.registry.set_topology({
            "hierarchical": bool(hier),
            "nodes": nodes,
            "local_size": local_size,
            "cross_algo_threshold": threshold,
            "cross_ops": {"ring": ops_ring, "tree": ops_tree},
            "bytes": {"local": local_bytes, "cross": cross_bytes},
            "local_transport": local_transport,
        })
        new = log_total - _engine_topo_seen
        if new <= 0:
            return
        _engine_topo_seen = log_total
        entries = [e for e in
                   _lib.hvd_tpu_topology_log().decode().split(";") if e]
        for entry in entries[-new:]:
            fields = entry.split("|")
            if len(fields) != 5:
                continue
            try:
                rs_us, cross_us, ag_us = (int(f) for f in fields[2:5])
            except ValueError:
                continue
            metrics.registry.observe("topology_local_rs_sec", rs_us / 1e6)
            if cross_us:
                metrics.registry.observe("topology_cross_sec",
                                         cross_us / 1e6)
            metrics.registry.observe("topology_local_ag_sec", ag_us / 1e6)


def _sync_engine_control() -> None:
    """Mirror the engine's control-plane state into the registry's
    ungated ``"control"`` section (docs/performance.md
    #control-plane-scaling): the coordinator-tree shape, the
    decentralized steady-state counters, and the control-frame totals.
    A state copy like the topology sync — the C counters are cumulative,
    so overwriting is idempotent."""
    if _lib is None:
        return
    with _stall_sync_lock:
        parts = _lib.hvd_tpu_control_info().decode().split("|")
        try:
            (tree, children, hosts, active, pattern_len, threshold,
             entries, exits, replays, cycles, negotiated, sent,
             received) = (int(p) for p in parts[:13])
        except ValueError:
            return
        metrics.registry.set_control({
            "tree": bool(tree),
            "depth": 2 if tree else 1,
            "children": children,
            "hosts": hosts,
            "steady": {"active": bool(active), "pattern_len": pattern_len,
                       "threshold": threshold, "entries": entries,
                       "exits": exits, "replays": replays,
                       "cycles": cycles},
            "negotiated_ticks": negotiated,
            "frames": {"sent": sent, "received": received},
        })


def _sync_engine_liveness() -> None:
    """Mirror the engine's data-plane heartbeat detector into the
    registry's ungated ``"liveness"`` section (docs/fault-tolerance.md
    #failure-detection): the configured cadence and miss limit, beacon
    frame totals, miss/eviction events, per-peer last-seen ages, and the
    init clock-sync fan-in.  A state copy like the control sync — the C
    counters are cumulative, so overwriting is idempotent."""
    if _lib is None:
        return
    with _stall_sync_lock:
        info = _lib.hvd_tpu_liveness_info().decode()
        parts = info.split("|")
        if len(parts) < 8:
            return
        try:
            (interval_ms, miss_limit, sent, recv, miss_events, evictions,
             fanin) = (int(p) for p in parts[:7])
        except ValueError:
            return
        peers = {}
        for tok in parts[7].split():
            fields = tok.split(":")
            if len(fields) != 3:
                continue
            try:
                peers[int(fields[0])] = {"age_us": int(fields[1]),
                                         "misses": int(fields[2])}
            except ValueError:
                continue
        metrics.registry.set_liveness({
            "interval_ms": interval_ms,
            "miss_limit": miss_limit,
            "frames": {"sent": sent, "received": recv},
            "miss_events": miss_events,
            "evictions": evictions,
            "clock_fanin": fanin,
            "peers": peers,
        })


def _sync_engine_links() -> None:
    """Mirror the engine's per-peer link telemetry into the registry's
    ungated ``"links"`` section (docs/metrics.md#links): transport byte /
    stall counters, the timed-send latency histogram, and the
    heartbeat-echo RTT estimate for every TCP link this rank holds.  A
    state copy — the net-layer counters are cumulative, so overwriting is
    idempotent."""
    if _lib is None:
        return
    with _stall_sync_lock:
        info = _lib.hvd_tpu_link_info().decode()
        parts = info.split("|")
        if len(parts) < 2:
            return
        peers = {}
        for tok in parts[1].split(";"):
            fields = tok.split(":")
            if len(fields) != 20:
                continue
            try:
                peers[int(fields[0])] = {
                    "bytes_out": int(fields[1]),
                    "bytes_in": int(fields[2]),
                    "sends": int(fields[3]),
                    "recvs": int(fields[4]),
                    "stalls": int(fields[5]),
                    "short_writes": int(fields[6]),
                    "send_us_sum": int(fields[7]),
                    "send_us_count": int(fields[8]),
                    "send_us_buckets": [int(b) for b in
                                        fields[9].split(",") if b],
                    "rtt_last_us": int(fields[10]),
                    "rtt_ewma_us": int(fields[11]),
                    "rtt_samples": int(fields[12]),
                    "shm_bytes_out": int(fields[13]),
                    "shm_bytes_in": int(fields[14]),
                    "shm_handoffs": int(fields[15]),
                    "shm_us_sum": int(fields[16]),
                    "shm_us_count": int(fields[17]),
                    "shm_us_buckets": [int(b) for b in
                                       fields[18].split(",") if b],
                    "transport": fields[19],
                }
            except ValueError:
                continue
        metrics.registry.set_links({"enabled": parts[0] == "1",
                                    "peers": peers})


def _sync_engine_anomalies() -> None:
    """Mirror the engine's online anomaly detector into the registry's
    ungated ``"anomalies"`` section (docs/metrics.md#anomalies): the
    configured sigma/interval, cumulative verdict counts per kind, and
    the bounded typed-verdict log.  A state copy — idempotent."""
    if _lib is None:
        return
    with _stall_sync_lock:
        info = _lib.hvd_tpu_anomaly_info().decode()
        parts = info.split("|")
        if len(parts) < 6:
            return
        try:
            sigma, interval_ms = int(parts[0]), int(parts[1])
            counts = [int(p) for p in parts[2:6]]
        except ValueError:
            return
        log = []
        for tok in _lib.hvd_tpu_anomaly_log().decode().split(";"):
            fields = tok.split("|")
            if len(fields) != 4:
                continue
            try:
                age_us = int(fields[3])
            except ValueError:
                continue
            log.append({"kind": fields[0], "subject": fields[1],
                        "detail": fields[2], "age_us": age_us})
        metrics.registry.set_anomalies({
            "sigma": sigma,
            "interval_ms": interval_ms,
            "verdicts": dict(zip(metrics.ANOMALY_KINDS, counts)),
            "log": log,
        })


def _sync_engine_autotune() -> None:
    """Mirror the engine's autotuning state into the registry's ungated
    ``"autotune"`` section (docs/performance.md#autotuning).  Unlike the
    event syncs above this is a state COPY, not a delta fold: the report
    is current-state plus bounded logs, so overwriting is idempotent and
    a ``metrics_reset()`` simply re-mirrors on the next snapshot."""
    if _lib is None:
        return
    from horovod_tpu.common import autotune as _autotune

    with _stall_sync_lock:
        metrics.registry.set_autotune(_autotune.report(_lib))


def _sync_engine_p2p() -> None:
    """Mirror the engine's point-to-point plane counters into the
    registry's ungated ``"p2p"`` section (docs/pipeline.md
    #observability): transfer/byte totals per direction, the matched
    counter against the unmatched gauge, stage-group ops, and the open
    dedicated-channel gauge.  A state copy — idempotent."""
    if _lib is None:
        return
    with _stall_sync_lock:
        info = _lib.hvd_tpu_p2p_info().decode()
        parts = info.split("|")
        if len(parts) != 8:
            return
        try:
            (sends, recvs, bytes_out, bytes_in, matched, unmatched,
             group_ops, channels) = (int(p) for p in parts)
        except ValueError:
            return
        metrics.registry.set_p2p({
            "sends": sends,
            "recvs": recvs,
            "bytes": {"out": bytes_out, "in": bytes_in},
            "matched": matched,
            "unmatched": unmatched,
            "group_ops": group_ops,
            "channels": channels,
        })


def _sync_step_completions() -> None:
    """Fold into ``step_sec`` the training steps whose loss is ready by
    now (jax/train.py's waiter would get to them a moment later), so that a
    snapshot taken after the caller waited for a loss counts that step.
    Nothing to do where the JAX binding was never imported."""
    # getattr: a monitor thread may ask while the module is being imported.
    completions = getattr(sys.modules.get("horovod_tpu.jax.train"),
                          "_completions", None)
    if completions is not None:
        completions.drain()


def metrics_snapshot() -> dict:
    """Plain nested dict of the collective metrics registry: op/byte
    counters per data plane, fusion-batch counters, latency/fill
    histograms, stall events (engine sweep + XLA-plane waits), the
    coordinator's announce-order skew accounting (``"skew"``, rank 0),
    and the online-autotuning state (``"autotune"``: applied params,
    freeze state, per-window search history).  Always callable; counters
    and histograms only accumulate while metrics are enabled
    (``HVD_TPU_METRICS=1``, a metrics file, or a monitor port); stall,
    fault, skew, and autotune records always do."""
    _sync_engine_stalls()
    _sync_engine_aborts()
    _sync_engine_announces()
    _sync_engine_cache()
    _sync_engine_autotune()
    _sync_engine_membership()
    _sync_engine_flight()
    _sync_engine_compression()
    _sync_engine_topology()
    _sync_engine_control()
    _sync_engine_liveness()
    _sync_engine_links()
    _sync_engine_anomalies()
    _sync_engine_p2p()
    _sync_step_completions()
    return metrics.registry.snapshot()


def metrics_reset() -> None:
    """Zero every counter, histogram, and stall record (the enabled flag
    is unaffected).  Outstanding engine stall events and training steps
    that have completed are consumed first so they cannot resurface in the
    next snapshot."""
    _sync_engine_stalls()
    _sync_engine_aborts()
    _sync_engine_announces()
    _sync_engine_cache()
    _sync_engine_topology()
    _sync_step_completions()
    metrics.registry.reset()


# ---------------------------------------------------------------------------
# Online autotuning API (common/autotune.py; docs/performance.md).
# ---------------------------------------------------------------------------


def autotune_report() -> dict:
    """The online-autotuning report: whether the tuner is enabled/frozen,
    the currently applied ``fusion_threshold`` / ``cycle_time_ms`` (set by
    lockstep broadcast — identical on every rank of a healthy job), the
    per-rank ``applied`` parameter log, and — on rank 0 — the per-window
    search ``history`` with scores.  Callable without ``init()`` (returns
    the empty shape) so post-shutdown tooling can read the last state."""
    from horovod_tpu.common import autotune as _autotune

    if _lib is None:
        return _autotune.empty_report()
    return _autotune.report(_lib)


def autotune_set(fusion_threshold: Optional[int] = None,
                 cycle_time_ms: Optional[float] = None,
                 compression: Optional[str] = None,
                 cross_algo_threshold: Optional[int] = None) -> None:
    """Inject engine parameters for lockstep broadcast at the next
    negotiation tick — the pluggable-policy seam: a custom tuning policy
    runs on rank 0, reads ``metrics_snapshot()``, and drives the same
    broadcast machinery the built-in search uses, so every rank applies
    the change at the same tick boundary.  Works with the built-in tuner
    disabled or frozen; while a search is live it resumes from the
    nearest grid point.  ``compression`` takes a wire mode name
    ("off"/"bf16"/"fp8"); ``cross_algo_threshold`` the two-level
    ring-vs-tree byte boundary (docs/performance.md#two-level-topology).
    Rank 0 only (``ValueError`` elsewhere)."""
    lib = _load_lib()
    _check_initialized(lib)
    from horovod_tpu.common import autotune as _autotune

    _autotune.set_params(lib, fusion_threshold, cycle_time_ms, compression,
                         cross_algo_threshold)


def compression_report() -> dict:
    """The wire-compression report (docs/performance.md#wire-compression):
    the applied mode and min-bytes floor (lockstep state — identical on
    every rank of a healthy job), per-plane wire-vs-payload byte totals
    and per-mode bucket counts, the error-feedback residual gauges, and
    the engine's bounded per-bucket decision ``log`` ([{"name", "mode"},
    ...] in execution order — identical across ranks; tests allgather and
    compare it).  Returns the empty shape before ``init()``."""
    from horovod_tpu.common.config import COMPRESSION_NAMES

    empty_ops = {"none": 0, "bf16": 0, "fp8": 0}
    rep = {
        "mode": "off", "min_bytes": 0,
        "engine": {"wire_bytes": 0, "payload_bytes": 0,
                   "ops": dict(empty_ops)},
        "xla": {"wire_bytes": 0, "payload_bytes": 0, "ops": dict(empty_ops)},
        "residual_bytes": 0, "residual_tensors": 0,
        "log": [],
    }
    if _lib is None:
        return rep
    parts = _lib.hvd_tpu_compression_info().decode().split("|")
    try:
        (wire, payload, n_none, n_bf16, n_fp8, res_bytes, res_tensors,
         min_bytes) = (int(p) for p in parts[:8])
    except ValueError:
        return rep
    rep.update({
        "mode": COMPRESSION_NAMES.get(
            int(_lib.hvd_tpu_compression_mode()), "off"),
        "min_bytes": min_bytes,
        "engine": {"wire_bytes": wire, "payload_bytes": payload,
                   "ops": {"none": n_none, "bf16": n_bf16, "fp8": n_fp8}},
        "residual_bytes": res_bytes,
        "residual_tensors": res_tensors,
    })
    plane_stats = getattr(_xla_plane, "comp_stats", None)
    if plane_stats is not None:
        rep["xla"] = {"wire_bytes": int(plane_stats["wire_bytes"]),
                      "payload_bytes": int(plane_stats["payload_bytes"]),
                      "ops": dict(plane_stats["ops"])}
        # Residual gauges cover BOTH planes, exactly like
        # metrics_snapshot()["compression"] — the two public surfaces
        # must agree on the same field names.
        plane_res = getattr(_xla_plane, "_residuals", {}) or {}
        rep["residual_bytes"] += sum(r.nbytes for r in plane_res.values())
        rep["residual_tensors"] += len(plane_res)
    for entry in _lib.hvd_tpu_compression_log().decode().split(";"):
        if not entry:
            continue
        name, _, mode = entry.rpartition("|")
        rep["log"].append({"name": name, "mode": mode})
    return rep


# ---------------------------------------------------------------------------
# Application span API (docs/timeline.md): land app events in this rank's
# trace next to the engine's collective rows.
# ---------------------------------------------------------------------------


def timeline_enabled() -> bool:
    """True when this rank is writing a timeline (``HOROVOD_TIMELINE`` /
    ``hvdrun --timeline``); span and marker calls are no-ops otherwise."""
    return _lib is not None and bool(_lib.hvd_tpu_timeline_enabled())


def _trace_begin(row: str, label: str) -> None:
    """Open a span labelled `label` on trace row `row` (internal: the
    framework hooks — keras callbacks, jax train steps — share it with
    :func:`trace_span`)."""
    if _lib is not None and _lib.hvd_tpu_timeline_enabled():
        _lib.hvd_tpu_timeline_op_start(row.encode(), label.encode())


def _trace_end(row: str) -> None:
    if _lib is not None and _lib.hvd_tpu_timeline_enabled():
        _lib.hvd_tpu_timeline_op_end(row.encode(), 0)


@contextlib.contextmanager
def trace_span(name: str, label: Optional[str] = None):
    """Context manager landing an application span in this rank's timeline::

        with hvd.trace_span("data_loading"):
            batch = next(loader)

    The span occupies the trace row ``name`` (same-row spans nest);
    ``label`` overrides the event label (default: the row name).  Where
    JAX is already imported the span is also a
    ``jax.profiler.TraceAnnotation(name)``, so a ``jax.profiler.trace``
    shows it beside the device's operations, on their clock (this module
    never imports JAX itself).  A no-op when the timeline is disabled and
    no profiler session runs — safe to leave in production code."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    timeline = _lib is not None and _lib.hvd_tpu_timeline_enabled()
    with (profiler.TraceAnnotation(name) if profiler is not None
          else contextlib.nullcontext()):
        if timeline:
            _lib.hvd_tpu_timeline_op_start(name.encode(),
                                           (label or name).encode())
        try:
            yield
        finally:
            if timeline:
                _lib.hvd_tpu_timeline_op_end(name.encode(), 0)


def trace_marker(name: str, row: str = "app.markers") -> None:
    """Drop an instant event named `name` on the ``app.markers`` trace row
    (or `row`).  A no-op when the timeline is disabled."""
    if _lib is not None and _lib.hvd_tpu_timeline_enabled():
        _lib.hvd_tpu_timeline_instant(row.encode(), name.encode())


# ---------------------------------------------------------------------------
# Async numpy collectives -- the substrate for all framework bindings.
# ---------------------------------------------------------------------------


class Handle:
    """An outstanding collective.  Poll with :meth:`done`, finish with
    :meth:`wait`.  Keeps input/output arrays alive while the engine may
    still touch their memory (the reference pins tensors in _handle_map,
    /root/reference/horovod/torch/mpi_ops.py:28-31)."""

    def __init__(self, raw: int, op: int, inp: np.ndarray,
                 out: Optional[np.ndarray], name: str):
        self._raw = raw
        self._op = op
        self._in = inp
        self._out = out
        self._name = name
        self._finished = False
        self._finish_lock = threading.Lock()
        # Metrics: end-to-end wait latency measured from enqueue.  One
        # enabled check; 0.0 doubles as the "metrics off" sentinel.
        self._t0 = time.perf_counter() if metrics.registry.enabled else 0.0
        # Engine (tick, seq) completion stamp, set by wait(): ops fused in
        # one negotiation cycle share a tick — observability for tests and
        # the timeline (the reference's cycle accounting).
        self.completion_tick: Optional[int] = None
        self.completion_seq: Optional[int] = None

    def done(self) -> bool:
        if self._finished:
            return True
        return _lib.hvd_tpu_poll(self._raw) != 0

    def wait(self) -> np.ndarray:
        # Atomic test-and-set: with the zero-copy allgather result a
        # double-wait would register two finalizers releasing the same
        # engine buffer (use-after-free), not just waste a copy.
        with self._finish_lock:
            if self._finished:
                raise ValueError(
                    f"handle for '{self._name}' already waited on")
            self._finished = True
        release = True
        code = _lib.hvd_tpu_wait(self._raw)
        try:
            if code != ST_OK:
                msg = _lib.hvd_tpu_error(self._raw).decode()
                code, msg = _promote_transport_failure(code, msg)
                raise _status_error(code, msg, self._name)
            self.completion_tick = int(
                _lib.hvd_tpu_completion_tick(self._raw))
            self.completion_seq = int(
                _lib.hvd_tpu_completion_seq(self._raw))
            if self._t0:
                # Engine-plane negotiation latency (enqueue -> agreed
                # response), stamped by the engine thread — the number the
                # response cache exists to shrink (docs/performance.md).
                neg_us = int(_lib.hvd_tpu_negotiation_us(self._raw))
                if neg_us >= 0:
                    metrics.registry.observe("negotiation_sec",
                                             neg_us / 1e6)
            if self._op == OP_ALLGATHER:
                nbytes = int(_lib.hvd_tpu_result_nbytes(self._raw))
                dim0 = _lib.hvd_tpu_result_dim0(self._raw)
                shape = (int(dim0),) + self._in.shape[1:]
                if self._t0:
                    metrics.registry.record_bytes_out("engine", nbytes)
                    metrics.registry.observe(
                        "wait_sec", time.perf_counter() - self._t0)
                if not nbytes:
                    return np.empty(shape, dtype=self._in.dtype)
                # Zero-copy: view the engine-owned result buffer directly
                # (the second full copy of the gathered payload the
                # round-3 host path paid).  The handle — and with it the
                # buffer — is released when the array is dropped; the
                # engine never touches a completed handle's buffer again,
                # and the (leaked) engine keeps released-less handles
                # valid across shutdown, so the view cannot dangle.
                itemsize = np.dtype(self._in.dtype).itemsize
                assert int(np.prod(shape)) * itemsize == nbytes, \
                    (shape, self._in.dtype, nbytes)
                ptr = _lib.hvd_tpu_result_ptr(self._raw)
                view = (ctypes.c_char * nbytes).from_address(ptr)
                # The finalizer hangs off the ctypes view — the bottom of
                # every derived ndarray's base chain (numpy collapses
                # view-of-view bases, so an intermediate array could be
                # collected while slices of it live on).
                weakref.finalize(view, _lib.hvd_tpu_release, self._raw)
                release = False
                return np.frombuffer(view,
                                     dtype=self._in.dtype).reshape(shape)
            if self._t0:
                metrics.registry.record_bytes_out("engine", self._out.nbytes)
                metrics.registry.observe(
                    "wait_sec", time.perf_counter() - self._t0)
            return self._out
        finally:
            if release:
                _lib.hvd_tpu_release(self._raw)


def _parse_down_ranks(msg: str) -> list:
    """Extract the rank list from an engine abort message of the form
    'ranks down: 0, 2 (...)'; empty when the shape is unexpected."""
    import re

    m = re.search(r"ranks down: ([0-9, ]+)", msg)
    if not m:
        return []
    return [int(tok) for tok in m.group(1).split(",") if tok.strip()]


def _promote_transport_failure(code: int, msg: str):
    """A mid-collective transport failure racing a coordinated abort:
    prefer the typed verdict.  Under the decentralized steady state
    (docs/performance.md#control-plane-scaling) survivors enter the data
    plane WITHOUT a negotiation round, so a peer's crash surfaces as a
    broken ring (ST_UNKNOWN) on them a beat before the coordinator's
    RanksDown broadcast lands — wait briefly for the control plane's
    verdict so the caller still gets the typed error naming the dead
    rank (the star had the same race with a much narrower window).
    ST_ABORTED drains check the latch once, without waiting: a clean
    shutdown also drains with that status and must not stall."""
    if _lib is None:
        return code, msg
    transport = code == ST_UNKNOWN and "failed" in msg
    deadline = time.monotonic() + (2.0 if transport else 0.0)
    while True:
        ac = int(_lib.hvd_tpu_abort_code())
        if ac in (ST_RANKS_DOWN, ST_TIMEOUT):
            return ac, _lib.hvd_tpu_abort_message().decode()
        if not transport or time.monotonic() >= deadline:
            return code, msg
        time.sleep(0.01)


def _status_error(code: int, msg: str, name: str) -> Exception:
    prefix = f"collective '{name}' failed: "
    if code == ST_PRECONDITION:
        return ValueError(prefix + msg)
    if code in (ST_RANKS_DOWN, ST_TIMEOUT):
        # Typed abort: leave the postmortem artifact NOW, while the
        # engine's flight ring and pending tables still describe the
        # moment of death (both planes route their abort statuses through
        # here).  Write-once and best-effort inside.
        from horovod_tpu.common import postmortem as _postmortem

        _postmortem.write_postmortem(_postmortem.reason_for_code(code))
    if code == ST_RANKS_DOWN:
        return RanksDownError(prefix + msg, ranks=_parse_down_ranks(msg))
    if code == ST_TIMEOUT:
        return CollectiveTimeoutError(prefix + msg)
    if code == ST_RESHAPE:
        return MembershipChangedError(prefix + msg,
                                      lost_ranks=_parse_down_ranks(msg))
    if code == ST_ABORTED:
        return HorovodInternalError(prefix + msg)
    return HorovodInternalError(prefix + (msg or f"status {code}"))


def _as_c_dims(shape) -> tuple:
    arr = (ctypes.c_longlong * len(shape))(*shape)
    return arr, len(shape)


_name_counter = [0]
_name_lock = threading.Lock()


def _auto_name(prefix: str) -> str:
    with _name_lock:
        _name_counter[0] += 1
        return f"{prefix}.noname.{_name_counter[0]}"


def _as_contig(array) -> np.ndarray:
    """C-contiguous ndarray view/copy that preserves 0-d shapes
    (`np.ascontiguousarray` would promote scalars to shape (1,))."""
    array = np.asarray(array)
    if not array.flags["C_CONTIGUOUS"]:
        array = np.ascontiguousarray(array)
    return array


def _check_out(out: np.ndarray, array: np.ndarray) -> None:
    if out.shape != array.shape or out.dtype != array.dtype:
        raise ValueError(
            f"output buffer mismatch: expected shape {array.shape} dtype "
            f"{array.dtype}, got shape {out.shape} dtype {out.dtype}")
    if not out.flags["C_CONTIGUOUS"] or not out.flags["WRITEABLE"]:
        raise ValueError("output buffer must be C-contiguous and writeable")


def _plane_eligible(array: np.ndarray) -> bool:
    return _xla_plane is not None and array.dtype.name in _XLA_PLANE_DTYPES


def _fault_hook(name: str) -> None:
    """Collective-boundary fault injection (common/faults.py).  Sits in
    the shared entry points, so it covers BOTH data planes — the XLA plane
    is dispatched from these same functions.  One None check when no spec
    is active; the submission index only advances while an injector is
    armed (it is the injector's coordinate system, nobody else's)."""
    if _fault_injector is None:
        return
    global _collective_seq
    with _fault_lock:
        idx = _collective_seq
        _collective_seq += 1
    _fault_injector.on_collective(idx, name)


def allreduce_async(array: np.ndarray, average: bool = True,
                    name: Optional[str] = None,
                    out: Optional[np.ndarray] = None,
                    group: Optional["StageGroup"] = None) -> Handle:
    lib = _load_lib()
    _check_initialized(lib)
    array = _as_contig(array)
    if out is None:
        out = np.empty_like(array)
    else:
        _check_out(out, array)
    if group is not None:
        # Scoped collective (docs/pipeline.md#stage-groups): reduces only
        # over the group's ranks — the data-parallel dimension inside one
        # pipeline stage.  Always the engine path: the XLA plane compiles
        # full-world collectives and knows nothing of membership subsets.
        name = name or _auto_name("group_allreduce")
        _fault_hook(name)
        dims, ndim = _as_c_dims(array.shape)
        members = (ctypes.c_longlong * len(group.ranks))(*group.ranks)
        raw = lib.hvd_tpu_enqueue_group(
            name.encode(),
            array.ctypes.data_as(ctypes.c_void_p),
            out.ctypes.data_as(ctypes.c_void_p),
            dims, ndim, dtypes.numpy_to_code(array.dtype), int(average),
            members, len(group.ranks))
        if raw < 0:
            raise HorovodInternalError("engine is shut down")
        if metrics.registry.enabled:
            metrics.registry.record_enqueue("engine", "allreduce",
                                            array.nbytes)
        return Handle(raw, OP_ALLREDUCE, array, out, name)
    name = name or _auto_name("allreduce")
    _fault_hook(name)
    if _plane_eligible(array):
        # Compiled XLA collective over the fabric; dispatch order and
        # shape/dtype consistency are negotiated over the control plane.
        return _xla_plane.allreduce_async(array, average, out, name)
    dims, ndim = _as_c_dims(array.shape)
    raw = lib.hvd_tpu_enqueue(
        OP_ALLREDUCE, name.encode(),
        array.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        dims, ndim, dtypes.numpy_to_code(array.dtype), -1, int(average))
    if raw < 0:
        raise HorovodInternalError("engine is shut down")
    if metrics.registry.enabled:
        metrics.registry.record_enqueue("engine", "allreduce", array.nbytes)
    return Handle(raw, OP_ALLREDUCE, array, out, name)


def allgather_async(array: np.ndarray, name: Optional[str] = None) -> Handle:
    lib = _load_lib()
    _check_initialized(lib)
    array = _as_contig(array)
    if array.ndim == 0:
        raise ValueError("allgather requires tensors of rank >= 1")
    name = name or _auto_name("allgather")
    _fault_hook(name)
    if _plane_eligible(array):
        # Compiled XLA all-gather over the fabric; ragged dim-0 geometry is
        # exchanged by the plane's metadata negotiation.
        return _xla_plane.allgather_async(array, name)
    dims, ndim = _as_c_dims(array.shape)
    raw = lib.hvd_tpu_enqueue(
        OP_ALLGATHER, name.encode(),
        array.ctypes.data_as(ctypes.c_void_p), None,
        dims, ndim, dtypes.numpy_to_code(array.dtype), -1, 0)
    if raw < 0:
        raise HorovodInternalError("engine is shut down")
    if metrics.registry.enabled:
        metrics.registry.record_enqueue("engine", "allgather", array.nbytes)
    return Handle(raw, OP_ALLGATHER, array, None, name)


def broadcast_async(array: np.ndarray, root_rank: int,
                    name: Optional[str] = None,
                    out: Optional[np.ndarray] = None) -> Handle:
    lib = _load_lib()
    _check_initialized(lib)
    array = _as_contig(array)
    if out is None:
        out = np.empty_like(array)
    else:
        _check_out(out, array)
    name = name or _auto_name("broadcast")
    _fault_hook(name)
    if _plane_eligible(array):
        if not (0 <= root_rank < (_process_set.size if _process_set else 1)):
            raise ValueError(f"broadcast root rank {root_rank} out of range")
        return _xla_plane.broadcast_async(array, root_rank, out, name)
    dims, ndim = _as_c_dims(array.shape)
    raw = lib.hvd_tpu_enqueue(
        OP_BROADCAST, name.encode(),
        array.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        dims, ndim, dtypes.numpy_to_code(array.dtype), root_rank, 0)
    if raw < 0:
        raise HorovodInternalError("engine is shut down")
    if metrics.registry.enabled:
        metrics.registry.record_enqueue("engine", "broadcast", array.nbytes)
    return Handle(raw, OP_BROADCAST, array, out, name)


class StageGroup:
    """Immutable membership subset for scoped collectives
    (docs/pipeline.md#stage-groups).  A pipeline job arranges its world
    as a stages x data-parallel grid: collectives scoped to one group
    reduce along the DP axis inside a stage, while the p2p plane
    (``send``/``recv``) crosses groups along the PP axis.  Membership is
    validated by the coordinator at negotiation time — every announcing
    rank must list an identical group, and every listed rank must
    announce — so a mismatched grid fails with a typed precondition
    error instead of a hang."""

    def __init__(self, ranks):
        members = sorted({int(r) for r in ranks})
        if not members:
            raise ValueError("stage group must contain at least one rank")
        if members[0] < 0:
            raise ValueError(f"stage group rank {members[0]} is negative")
        self.ranks = tuple(members)

    @property
    def size(self) -> int:
        return len(self.ranks)

    def __contains__(self, r) -> bool:
        return int(r) in self.ranks

    def __eq__(self, other) -> bool:
        return isinstance(other, StageGroup) and self.ranks == other.ranks

    def __hash__(self) -> int:
        return hash(self.ranks)

    def __repr__(self) -> str:
        return f"StageGroup(ranks={list(self.ranks)})"


def stage_group(ranks) -> StageGroup:
    """Build a :class:`StageGroup` from an iterable of global ranks."""
    return StageGroup(ranks)


def _p2p_wire_name(name: Optional[str], src: int, dst: int,
                   tag: int) -> str:
    """Canonical p2p wire name — the paired-readiness contract
    (docs/pipeline.md#wire-protocol) keys a send and its matching recv
    on ONE name, so both ends must construct it identically: the sender
    stamps (rank -> peer), the receiver (peer -> rank), and both arrive
    at the same ``<base>.p2p.<src>-<dst>.t<tag>``."""
    base = name or "p2p"
    return f"{base}.p2p.{src}-{dst}.t{tag}"


def _enqueue_p2p(op: int, kind: str, array: np.ndarray,
                 out: Optional[np.ndarray], peer: int, tag: int,
                 wire_name: str) -> Handle:
    lib = _load_lib()
    if not (0 <= peer < size()):
        raise ValueError(f"p2p peer rank {peer} out of range for world "
                         f"size {size()}")
    if peer == rank():
        raise ValueError("p2p peer must be a different rank")
    if tag < 0:
        raise ValueError(f"p2p tag {tag} must be non-negative")
    _fault_hook(wire_name)
    # Always the engine path: p2p rides the Channel transport seam
    # directly — there is no compiled-collective equivalent.
    dims, ndim = _as_c_dims(array.shape)
    raw = lib.hvd_tpu_enqueue_p2p(
        op, wire_name.encode(),
        array.ctypes.data_as(ctypes.c_void_p) if op == OP_SEND else None,
        out.ctypes.data_as(ctypes.c_void_p) if out is not None else None,
        dims, ndim, dtypes.numpy_to_code(array.dtype), peer, tag)
    if raw < 0:
        raise HorovodInternalError("engine is shut down")
    # No record_enqueue here: snap["ops"] is collectives-only (pinned by
    # test_snapshot_shape); the engine mirrors the canonical p2p counters
    # into snap["p2p"] via set_p2p, bytes included.
    # A send has no output buffer; hand the Handle the input so wait()'s
    # byte accounting and return value stay uniform.
    return Handle(raw, op, array, out if out is not None else array,
                  wire_name)


def send_async(array: np.ndarray, dest: int, tag: int = 0,
               name: Optional[str] = None) -> Handle:
    """Asynchronously send ``array`` to global rank ``dest``.  Completes
    only once the matching :func:`recv` has announced — an unmatched
    send surfaces as a collective-timeout naming this tensor and peer,
    never a silent hang (docs/pipeline.md#faults)."""
    lib = _load_lib()
    _check_initialized(lib)
    array = _as_contig(array)
    wire_name = _p2p_wire_name(name, rank(), dest, tag)
    return _enqueue_p2p(OP_SEND, "send", array, None, dest, tag, wire_name)


def recv_async(out: np.ndarray, source: int, tag: int = 0,
               name: Optional[str] = None) -> Handle:
    """Asynchronously receive into caller-allocated ``out`` from global
    rank ``source``.  The buffer is the shape/dtype contract: the
    coordinator cross-checks it against the sender's announcement and
    fails a mismatch with a typed precondition error.  Fixed-shape
    buffers keep repeated micro-batch cycles cacheable
    (docs/pipeline.md#steady-state)."""
    lib = _load_lib()
    _check_initialized(lib)
    out = np.asarray(out)
    if not out.flags["C_CONTIGUOUS"] or not out.flags["WRITEABLE"]:
        raise ValueError("recv buffer must be C-contiguous and writeable")
    wire_name = _p2p_wire_name(name, source, rank(), tag)
    return _enqueue_p2p(OP_RECV, "recv", out, out, source, tag, wire_name)


def send(array: np.ndarray, dest: int, tag: int = 0,
         name: Optional[str] = None) -> None:
    send_async(array, dest, tag, name).wait()


def recv(out: np.ndarray, source: int, tag: int = 0,
         name: Optional[str] = None) -> np.ndarray:
    return recv_async(out, source, tag, name).wait()


def allreduce(array: np.ndarray, average: bool = True,
              name: Optional[str] = None,
              group: Optional[StageGroup] = None) -> np.ndarray:
    return allreduce_async(array, average, name, group=group).wait()


def allgather(array: np.ndarray, name: Optional[str] = None) -> np.ndarray:
    return allgather_async(array, name).wait()


def broadcast(array: np.ndarray, root_rank: int,
              name: Optional[str] = None) -> np.ndarray:
    return broadcast_async(array, root_rank, name).wait()
