"""Runtime configuration knobs, read from the environment.

TPU-native counterpart of the env config block read in the reference's
background thread (/root/reference/horovod/common/operations.cc:1393-1420).
Both the reference's historical names (``HOROVOD_*``) and the new
``HVD_TPU_*`` names are honoured, new names winning, so reference scripts and
docs carry over unchanged.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

DEFAULT_FUSION_THRESHOLD = 64 * 1024 * 1024  # bytes, same default as reference
DEFAULT_CYCLE_TIME_MS = 5.0
DEFAULT_STALL_WARNING_SEC = 60.0


def _get(new: str, old: str) -> Optional[str]:
    return os.environ.get(new, os.environ.get(old))


_FALSY = ("", "0", "false", "no", "off")


def _flag(value: Optional[str]) -> bool:
    return value is not None and value.strip().lower() not in _FALSY


# Wire-compression mode spellings -> engine CompressionMode codes
# (engine/cc/wire.h; mirrored by the XLA plane's jnp casts).
COMPRESSION_CODES = {"off": 0, "none": 0, "0": 0, "": 0,
                     "bf16": 1, "bfloat16": 1,
                     "fp8": 2, "fp8_e4m3": 2, "float8_e4m3fn": 2}
COMPRESSION_NAMES = {0: "off", 1: "bf16", 2: "fp8"}


def parse_compression(value: Optional[str]) -> int:
    """``HVD_TPU_COMPRESSION`` spelling -> CompressionMode code; raises
    ``ValueError`` on an unknown mode."""
    key = (value or "off").strip().lower()
    if key not in COMPRESSION_CODES:
        raise ValueError(
            f"HVD_TPU_COMPRESSION: unknown wire-compression mode {value!r} "
            f"(want off, bf16, or fp8)")
    return COMPRESSION_CODES[key]


@dataclasses.dataclass(frozen=True)
class Config:
    fusion_threshold: int = DEFAULT_FUSION_THRESHOLD
    cycle_time_ms: float = DEFAULT_CYCLE_TIME_MS
    stall_warning_sec: float = DEFAULT_STALL_WARNING_SEC
    timeline_path: str = ""          # Chrome-tracing JSON output, rank 0
    # Two-level allreduce (docs/performance.md#two-level-topology):
    # node-local reduce-scatter, one cross-node (DCN) exchange per local
    # rank over its 1/local_size shard, node-local allgather — requires
    # the hvdrun contiguous-block rank layout.  The bandwidth-optimal
    # successor of the reference's HOROVOD_HIERARCHICAL_ALLREDUCE star
    # (operations.cc:1003-1048).
    hierarchical_allreduce: bool = False
    # Ring-vs-tree boundary for the two-level cross-node hop: buckets
    # under this many bytes take the recursive-doubling (tree) exchange
    # (log2(nodes) latency steps), the rest the bandwidth-optimal ring.
    # Autotuned as the fourth ParameterManager axis; 0 = ring always.
    cross_algo_threshold: int = 64 * 1024
    # Execute eager collectives as compiled XLA collectives over the
    # accelerator fabric (jax.distributed across the job) instead of the TCP
    # ring — the TPU mapping of the reference's NCCL data plane
    # (operations.cc:861-1100).  Tri-state, like the reference's NCCL path
    # which needed no runtime flag once compiled in (operations.cc:861-914):
    # None (env unset) = AUTO — enable on ranks given a chip of their own
    # (basics.chip_assigned; the environment is read, JAX is not asked);
    # True = forced on; False ("0"/"false"/"off") = explicit opt-out.
    # Unsupported dtypes stay on the TCP engine either way.
    xla_data_plane: Optional[bool] = None
    # Collective metrics registry (common/metrics.py, docs/metrics.md).
    # `metrics` force-enables collection; setting a metrics file or a
    # monitor port implies it (an empty registry serves nobody).
    metrics: bool = False
    metrics_file: str = ""           # JSON dump at shutdown, per rank
    monitor_port: Optional[int] = None  # HTTP /metrics server (+local_rank)
    # Fault tolerance (docs/fault-tolerance.md).  collective_timeout_sec:
    # hard deadline for a collective stuck in negotiation — past it the
    # coordinator escalates the stall warning to a coordinated abort
    # (CollectiveTimeoutError on every rank); <= 0 disables.  Applies to
    # both data planes (the engine's negotiation sweep and the XLA plane's
    # dispatch wait).
    collective_timeout_sec: float = 0.0
    # Deterministic fault injection spec (common/faults.py), e.g.
    # "rank=1:crash@op=12; rank=2:hang@op=5; rank=1:delay=3.0@op=7".
    fault_spec: str = ""
    # Restart counter exported by `hvdrun --max-restarts` (0 on the first
    # run, +1 per relaunch).  Read by checkpoint-resume glue and gates
    # fault clauses without an explicit epoch=N to the first run.
    restart_epoch: int = 0
    # Negotiation response cache (docs/performance.md): once a collective
    # has been fully negotiated, every rank replays the agreement from a
    # compact slot index instead of re-serializing string requests (and
    # the XLA plane skips its `__xp.*` metadata allreduce entirely).
    # HVD_TPU_RESPONSE_CACHE=0 is the kill switch; HVD_TPU_CACHE_CAPACITY
    # bounds the per-rank entry count (LRU eviction past it).
    response_cache: bool = True
    cache_capacity: int = 1024
    # Online autotuning (common/autotune.py, docs/performance.md
    # #autotuning): HVD_TPU_AUTOTUNE=1 lets the coordinator tune
    # fusion_threshold and cycle_time_ms online, broadcasting candidates
    # in the response list so every rank applies them in lockstep.  The
    # first `autotune_warmup` windows (of `autotune_window` negotiated
    # collectives each) are discarded; `autotune_fix` pins knobs
    # ("fusion_threshold=67108864,cycle_time_ms=5").
    autotune: bool = False
    autotune_warmup: int = 2
    autotune_window: int = 32
    autotune_fix: str = ""
    # Elastic membership (docs/fault-tolerance.md#elastic-membership).
    # HVD_TPU_ELASTIC=1 (set by `hvdrun --min-np/--max-np`): when a rank
    # dies, survivors re-negotiate size/rank at the next tick and keep
    # training (shrink-and-continue) instead of aborting, as long as at
    # least `min_np` ranks remain; `HVD_TPU_REJOIN=1` marks a standby
    # process that registers with a live coordinator and is admitted at
    # the next reshape barrier.
    elastic: bool = False
    min_np: int = 1
    rejoin: bool = False
    # Wire-level gradient compression (docs/performance.md
    # #wire-compression).  HVD_TPU_COMPRESSION=off|bf16|fp8: fp32
    # allreduce buckets of at least `compression_min_bytes` transfer as
    # bf16 (2x fewer wire bytes) or fp8-e4m3 (4x) with fp32 master copies
    # and per-tensor error-feedback residuals; reduction still
    # accumulates in f32 at every ring hop.  Agreed job-wide at init (a
    # mixed-env launch is a typed error), kill-switched by "off" (the
    # default — the fp32 wire stays bit-identical), and exposed to the
    # autotuner as a third axis (HVD_TPU_AUTOTUNE_FIX=compression=...
    # pins it).  f16/bf16 payloads ship at native width regardless.
    compression: str = "off"
    compression_min_bytes: int = 1024
    # Postmortem plane (docs/troubleshooting.md#reading-a-postmortem).
    # HVD_TPU_POSTMORTEM_DIR: directory each rank writes its
    # rank-<N>.json crash/hang dump into on typed aborts, injected
    # crashes, and fatal uncaught exceptions (hvdrun --postmortem-dir
    # sets it job-wide); empty disables.  HVD_TPU_FLIGHT_EVENTS sizes the
    # always-on flight-recorder rings (engine C++ ring and the XLA
    # plane's Python ring alike); 0 disables recording.
    postmortem_dir: str = ""
    flight_events: int = 512
    # Control-plane scaling (docs/performance.md#control-plane-scaling).
    # coord_tree (HVD_TPU_COORD_TREE, default on): multi-host jobs
    # restructure the rank-0 coordinator star into a two-level tree —
    # each host's local-rank-0 aggregates its node's announces into one
    # frame per tick and relays broadcasts back down, so rank 0 holds
    # O(hosts) sockets instead of O(ranks).  Single-host layouts keep the
    # degenerate one-level star either way.  steady_threshold
    # (HVD_TPU_STEADY_THRESHOLD): once a negotiation cycle's cache-hit
    # pattern repeats identically this many times, the coordinator
    # broadcasts a STEADY verdict and every rank self-clocks on an epoch
    # counter, replaying the cached responses with ZERO control-plane
    # messages per cycle (any miss falls back to full negotiation); 0
    # disables.  steady_max_period (HVD_TPU_STEADY_MAX_PERIOD) bounds the
    # detectable cycle length in collectives.
    coord_tree: bool = True
    steady_threshold: int = 32
    steady_max_period: int = 256
    # Data-plane heartbeat failure detector (docs/fault-tolerance.md
    # #failure-detection).  heartbeat_ms (HVD_TPU_HEARTBEAT_MS, default
    # 100): every rank's monitor thread beacons tiny typed frames to its
    # ring neighbours over dedicated data-plane sockets on this cadence,
    # entirely off the engine tick; 0 disables the detector.
    # heartbeat_miss (HVD_TPU_HEARTBEAT_MISS, default 10): consecutive
    # silent intervals before a neighbour is flagged frozen — elastic
    # jobs evict it through the reshape barrier, non-elastic jobs reach
    # a coordinated RanksDownError naming it, in O(heartbeat window)
    # instead of O(collective timeout).  net_fault_spec
    # (HVD_TPU_NET_FAULT_SPEC, common chaos grammar): deterministic
    # link-fault injection, e.g. "link=0-1:drop@after=2" or
    # "partition=0,1/2,3@after=1" or "link=1-2:delay=5|jitter=3" or
    # "link=0-3:flaky=0.05"; parsed by the engine at init (a bad spec is
    # a typed init error) and composable with HVD_TPU_FAULT_SPEC.
    heartbeat_ms: int = 100
    heartbeat_miss: int = 10
    net_fault_spec: str = ""
    # Perf-introspection plane (docs/metrics.md#links, #anomalies).
    # link_stats (HVD_TPU_LINK_STATS, default on): per-peer transport
    # telemetry — bytes, write stalls, timed-send latency histograms,
    # heartbeat-echo RTT — accounted at the net layer and exposed via
    # metrics_snapshot()["links"] / hvd_tpu_link_* families; 0 disables
    # the accounting (one relaxed atomic per transport call remains).
    # anomaly_sigma (HVD_TPU_ANOMALY_SIGMA, default 5): robust-excursion
    # threshold (median + sigma * MAD) of the online anomaly detector
    # that turns those baselines into typed verdicts — slow_link(A-B),
    # straggler(rank), cache_degraded, slow_phase(phase); 0 disables the
    # detector thread.  anomaly_interval_ms
    # (HVD_TPU_ANOMALY_INTERVAL_MS, default 500): detector sweep cadence,
    # floored at 10ms.
    link_stats: bool = True
    anomaly_sigma: int = 5
    anomaly_interval_ms: int = 500
    # Pluggable data-plane transport (docs/performance.md#transport).
    # shm (HVD_TPU_SHM=auto|off|force, default auto): the node-local
    # hops of the two-level allreduce hand fused-bucket segments through
    # mmap'd per-node shared-memory rings (no serialization, no syscall
    # per segment) when every rank of a node shares a host; "off" pins
    # every hop to TCP (kill switch — the data path is bit-identical
    # either way), "force" fails init with a typed error when shm
    # cannot arm.  Agreed job-wide at init like compression (a mixed-env
    # launch is a typed error).  shm_ring_bytes
    # (HVD_TPU_SHM_RING_BYTES, default 1 MiB, floor 64 KiB): payload
    # capacity of each direction's ring, rounded up to a power of two.
    shm: str = "auto"
    shm_ring_bytes: int = 1 << 20

    @property
    def compression_code(self) -> int:
        """The engine's CompressionMode code for ``compression``
        (engine/cc/wire.h).  Raises ``ValueError`` on an unknown
        spelling — a typo must not silently run uncompressed."""
        return parse_compression(self.compression)

    @property
    def effective_cache_capacity(self) -> int:
        """Slots the engine is told to keep: 0 (disabled) when the kill
        switch is thrown, else the configured capacity."""
        return self.cache_capacity if self.response_cache else 0

    @property
    def metrics_enabled(self) -> bool:
        return bool(self.metrics or self.metrics_file
                    or self.monitor_port is not None)

    @staticmethod
    def from_env() -> "Config":
        fusion = _get("HVD_TPU_FUSION_THRESHOLD", "HOROVOD_FUSION_THRESHOLD")
        # HVD_TPU_CYCLE_TIME_MS is the documented spelling (the idle-tick
        # floor of the adaptive engine loop, docs/performance.md); the
        # older unsuffixed names still work.
        cycle = os.environ.get(
            "HVD_TPU_CYCLE_TIME_MS",
            _get("HVD_TPU_CYCLE_TIME", "HOROVOD_CYCLE_TIME"))
        stall = _get("HVD_TPU_STALL_WARNING_SEC", "HOROVOD_STALL_WARNING_SEC")
        timeline = _get("HVD_TPU_TIMELINE", "HOROVOD_TIMELINE")
        return Config(
            fusion_threshold=int(fusion) if fusion else DEFAULT_FUSION_THRESHOLD,
            cycle_time_ms=float(cycle) if cycle else DEFAULT_CYCLE_TIME_MS,
            stall_warning_sec=float(stall) if stall else DEFAULT_STALL_WARNING_SEC,
            timeline_path=timeline or "",
            hierarchical_allreduce=_flag(
                _get("HVD_TPU_HIERARCHICAL_ALLREDUCE",
                     "HOROVOD_HIERARCHICAL_ALLREDUCE")),
            cross_algo_threshold=int(os.environ.get(
                "HVD_TPU_CROSS_ALGO_THRESHOLD") or 64 * 1024),
            xla_data_plane=(None if (plane := _get(
                "HVD_TPU_XLA_DATA_PLANE", "HOROVOD_XLA_DATA_PLANE")) is None
                else _flag(plane)),
            metrics=_flag(os.environ.get("HVD_TPU_METRICS")),
            metrics_file=os.environ.get("HVD_TPU_METRICS_FILE", ""),
            monitor_port=(int(port) if (port := os.environ.get(
                "HVD_TPU_MONITOR_PORT")) else None),
            collective_timeout_sec=float(os.environ.get(
                "HVD_TPU_COLLECTIVE_TIMEOUT_SEC") or 0.0),
            fault_spec=os.environ.get("HVD_TPU_FAULT_SPEC", ""),
            restart_epoch=int(os.environ.get(
                "HVD_TPU_RESTART_EPOCH") or 0),
            response_cache=_flag(os.environ.get(
                "HVD_TPU_RESPONSE_CACHE", "1")),
            cache_capacity=int(os.environ.get(
                "HVD_TPU_CACHE_CAPACITY") or 1024),
            autotune=_flag(os.environ.get("HVD_TPU_AUTOTUNE")),
            autotune_warmup=int(os.environ.get(
                "HVD_TPU_AUTOTUNE_WARMUP") or 2),
            autotune_window=int(os.environ.get(
                "HVD_TPU_AUTOTUNE_WINDOW") or 32),
            autotune_fix=os.environ.get("HVD_TPU_AUTOTUNE_FIX", ""),
            compression=os.environ.get("HVD_TPU_COMPRESSION", "off"),
            compression_min_bytes=int(os.environ.get(
                "HVD_TPU_COMPRESSION_MIN_BYTES") or 1024),
            elastic=_flag(os.environ.get("HVD_TPU_ELASTIC")),
            min_np=int(os.environ.get("HVD_TPU_MIN_NP") or 1),
            rejoin=_flag(os.environ.get("HVD_TPU_REJOIN")),
            postmortem_dir=os.environ.get("HVD_TPU_POSTMORTEM_DIR", ""),
            flight_events=int(os.environ.get(
                "HVD_TPU_FLIGHT_EVENTS") or 512),
            coord_tree=_flag(os.environ.get("HVD_TPU_COORD_TREE", "1")),
            steady_threshold=int(os.environ.get(
                "HVD_TPU_STEADY_THRESHOLD") or 32),
            steady_max_period=int(os.environ.get(
                "HVD_TPU_STEADY_MAX_PERIOD") or 256),
            heartbeat_ms=int(os.environ.get("HVD_TPU_HEARTBEAT_MS")
                             if os.environ.get("HVD_TPU_HEARTBEAT_MS")
                             not in (None, "") else 100),
            heartbeat_miss=int(os.environ.get(
                "HVD_TPU_HEARTBEAT_MISS") or 10),
            net_fault_spec=os.environ.get("HVD_TPU_NET_FAULT_SPEC", ""),
            link_stats=_flag(os.environ.get("HVD_TPU_LINK_STATS", "1")),
            anomaly_sigma=int(os.environ.get("HVD_TPU_ANOMALY_SIGMA")
                              if os.environ.get("HVD_TPU_ANOMALY_SIGMA")
                              not in (None, "") else 5),
            anomaly_interval_ms=int(os.environ.get(
                "HVD_TPU_ANOMALY_INTERVAL_MS") or 500),
            shm=os.environ.get("HVD_TPU_SHM", "auto") or "auto",
            shm_ring_bytes=int(os.environ.get(
                "HVD_TPU_SHM_RING_BYTES") or (1 << 20)),
        )
