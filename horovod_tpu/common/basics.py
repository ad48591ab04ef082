"""Process-model bootstrap: rank/size/local_rank resolution for TPU pod slices.

TPU-native counterpart of the reference's MPI process model
(/root/reference/horovod/common/operations.cc:1299-1428, where rank/size come
from MPI_COMM_WORLD).  Here they resolve, in priority order, from:

  1. Explicit arguments to :func:`resolve_process_set`.
  2. ``HVD_TPU_RANK`` / ``HVD_TPU_SIZE`` / ``HVD_TPU_LOCAL_RANK`` /
     ``HVD_TPU_LOCAL_SIZE`` — set by the ``hvdrun`` launcher
     (the mpirun replacement, see ``horovod_tpu/runner``).
  3. libtpu multi-process pinning env (``CLOUD_TPU_TASK_ID`` +
     ``TPU_PROCESS_ADDRESSES``) — one process per chip, local geometry
     from grouping the address list by host.
  4. TPU pod-slice metadata environment (``TPU_WORKER_ID`` +
     ``TPU_WORKER_HOSTNAMES``, or Cloud TPU ``CLOUD_TPU_TASK_ID``);
     one process per host by default, N per host when the process manager
     also exports ``HVD_TPU_LOCAL_RANK``/``HVD_TPU_LOCAL_SIZE``.
  5. An already-initialised JAX distributed runtime
     (``jax.process_index()`` / ``jax.process_count()``).
  6. Single-process defaults (rank 0 of 1).

No MPI anywhere.  The launcher also provides the control/data-plane endpoints
(``HVD_TPU_COORD``, ``HVD_TPU_DATA``) consumed by the C++ engine.
"""

from __future__ import annotations

import dataclasses
import os
import socket
from typing import Optional, Sequence


@dataclasses.dataclass(frozen=True)
class ProcessSet:
    """Resolved identity of this process within the job."""

    rank: int
    size: int
    local_rank: int
    local_size: int
    # Control-plane (rank-0 coordinator) endpoint, "host:port".
    coord_endpoint: Optional[str] = None
    # Data-plane endpoints for every rank, ["host:port", ...] (len == size).
    data_endpoints: Optional[Sequence[str]] = None

    def validate(self) -> "ProcessSet":
        if not (0 <= self.rank < self.size):
            raise ValueError(
                f"rank {self.rank} out of range for size {self.size}")
        if not (0 <= self.local_rank < self.local_size):
            raise ValueError(
                f"local_rank {self.local_rank} out of range for "
                f"local_size {self.local_size}")
        if self.size > 1:
            if not self.coord_endpoint:
                raise ValueError(
                    "size > 1 requires a coordinator endpoint "
                    "(set HVD_TPU_COORD or launch via hvdrun)")
            if not self.data_endpoints or len(self.data_endpoints) != self.size:
                raise ValueError(
                    "size > 1 requires one data endpoint per rank "
                    "(set HVD_TPU_DATA or launch via hvdrun)")
        return self


def _env_int(name: str, default: Optional[int] = None) -> Optional[int]:
    val = os.environ.get(name)
    if val is None or val == "":
        return default
    try:
        return int(val)
    except ValueError as exc:
        raise ValueError(f"environment variable {name}={val!r} is not an int") from exc


def _from_launcher_env() -> Optional[ProcessSet]:
    rank = _env_int("HVD_TPU_RANK")
    size = _env_int("HVD_TPU_SIZE")
    if rank is None or size is None:
        return None
    local_rank = _env_int("HVD_TPU_LOCAL_RANK", rank)
    local_size = _env_int("HVD_TPU_LOCAL_SIZE", size)
    coord = os.environ.get("HVD_TPU_COORD")
    data = os.environ.get("HVD_TPU_DATA")
    endpoints = data.split(",") if data else None
    return ProcessSet(rank, size, local_rank, local_size, coord, endpoints)


def _from_tpu_pinned_metadata() -> Optional[ProcessSet]:
    """Resolve from the libtpu multi-process pinning env (one process per
    chip: ``CLOUD_TPU_TASK_ID`` + ``TPU_PROCESS_ADDRESSES``, as set by the
    ``hvdrun --tpu-pin`` planner or a GKE-style process manager).  Local
    geometry comes from grouping the address list by host."""
    task_id = _env_int("CLOUD_TPU_TASK_ID")
    addresses = os.environ.get("TPU_PROCESS_ADDRESSES")
    if task_id is None or not addresses:
        return None
    addrs = [a.strip() for a in addresses.split(",") if a.strip()]
    size = len(addrs)
    if size <= 1:
        return ProcessSet(0, 1, 0, 1)
    hosts = [a.rsplit(":", 1)[0] for a in addrs]
    peers = [i for i, h in enumerate(hosts) if h == hosts[task_id]]
    coord_port = _env_int("HVD_TPU_COORD_PORT", 58930)
    data_port = _env_int("HVD_TPU_DATA_PORT", 58931)
    coord = f"{hosts[0]}:{coord_port}"
    # Per-rank data ports offset by local rank so co-hosted ranks don't
    # collide (the hvdrun planner uses the same layout, runner/hosts.py).
    local_ranks = {}
    seen: dict = {}
    for i, h in enumerate(hosts):
        local_ranks[i] = seen.get(h, 0)
        seen[h] = local_ranks[i] + 1
    endpoints = [f"{h}:{data_port + local_ranks[i]}"
                 for i, h in enumerate(hosts)]
    return ProcessSet(task_id, size, peers.index(task_id), len(peers),
                      coord, endpoints)


def _from_tpu_metadata() -> Optional[ProcessSet]:
    """Resolve from Cloud TPU pod-slice metadata env.  Default: one process
    per host (the classic Cloud TPU layout).  With N processes per host
    (chip pinning), the process manager additionally exports
    ``HVD_TPU_LOCAL_RANK``/``HVD_TPU_LOCAL_SIZE`` and the global identity
    is host-major: rank = worker_id * local_size + local_rank."""
    worker_id = _env_int("TPU_WORKER_ID", _env_int("CLOUD_TPU_TASK_ID"))
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES")
    if worker_id is None or not hostnames:
        return None
    hosts = [h.strip() for h in hostnames.split(",") if h.strip()]
    local_rank = _env_int("HVD_TPU_LOCAL_RANK", 0)
    local_size = _env_int("HVD_TPU_LOCAL_SIZE", 1)
    size = len(hosts) * local_size
    if size <= 1:
        return ProcessSet(0, 1, 0, 1)
    coord_port = _env_int("HVD_TPU_COORD_PORT", 58930)
    data_port = _env_int("HVD_TPU_DATA_PORT", 58931)
    coord = f"{hosts[0]}:{coord_port}"
    endpoints = [f"{h}:{data_port + lr}"
                 for h in hosts for lr in range(local_size)]
    return ProcessSet(worker_id * local_size + local_rank, size,
                      local_rank, local_size, coord, endpoints)


def _from_jax_distributed() -> Optional[ProcessSet]:
    try:
        import jax  # local import: keep basics importable without jax

        # Only meaningful when the distributed runtime was initialised.
        from jax._src import distributed  # type: ignore

        if distributed.global_state.client is None:
            return None
        return ProcessSet(
            jax.process_index(), jax.process_count(),
            _env_int("HVD_TPU_LOCAL_RANK", 0),
            _env_int("HVD_TPU_LOCAL_SIZE", 1))
    except Exception:  # pragma: no cover - jax absent or internal change
        return None


def chip_assigned() -> bool:
    """True when this process was given a TPU chip of its own: the pinning
    environment of ``hvdrun --tpu-pin`` (runner/tpu_pin.py) or of a process
    manager doing the same, or — outside the launcher — a multi-process
    identity resolved from pod-slice metadata.  Reads the environment only:
    asking JAX would open the chip, and an unpinned ``hvdrun -np N`` job
    shares one host's chips among N ranks that must not fight over them."""
    if os.environ.get("TPU_VISIBLE_CHIPS"):
        return True
    if _from_launcher_env() is not None:
        return False
    ps = _from_tpu_pinned_metadata() or _from_tpu_metadata()
    return ps is not None and ps.size > 1


def comm_ranks(comm, launcher_rank: int) -> list:
    """Map an mpi4py-style communicator to the launcher-rank subset the
    rank-list init path consumes.

    The reference accepts either a rank list or an mpi4py communicator in
    ``hvd.init(comm=...)`` (/root/reference/horovod/common/__init__.py:
    51-78, where the C side marshals the raw ``MPI_Comm``).  There is no
    MPI anywhere in this framework, so the shim is duck-typed instead of
    importing mpi4py: any object with ``Get_size`` and a pickle-based
    ``allgather`` works — each member contributes its own launcher rank
    and the gathered list IS the subset, with no world-group rank
    translation needed.  The list keeps the communicator's own rank
    order (allgather returns in comm-rank order), and
    :func:`resolve_process_set` numbers the subset by list position —
    so ``hvd.rank() == comm.Get_rank()`` even for reordered
    subcommunicators (root-only logic stays on the comm's root).
    """
    ranks = list(comm.allgather(launcher_rank))
    if len(ranks) != comm.Get_size():
        raise ValueError(
            f"communicator allgather returned {len(ranks)} ranks but "
            f"Get_size() says {comm.Get_size()}")
    return ranks


def resolve_process_set(ranks: Optional[Sequence[int]] = None) -> ProcessSet:
    """Resolve this process's identity.

    ``ranks`` mirrors the reference's ``hvd.init(comm=[...])`` rank-subset
    argument (/root/reference/horovod/common/__init__.py:51-78): when given,
    it must contain this process's launcher rank, and rank/size are re-mapped
    to the subset.
    """
    ps = (_from_launcher_env() or _from_tpu_pinned_metadata()
          or _from_tpu_metadata() or _from_jax_distributed()
          or ProcessSet(0, 1, 0, 1))
    if ranks is not None:
        ranks = list(ranks)
        if len(set(ranks)) != len(ranks):
            raise ValueError(f"duplicate ranks in subset {ranks}")
        if ps.rank not in ranks:
            raise ValueError(
                f"process rank {ps.rank} not in requested subset {ranks}")
        # LIST ORDER defines the new numbering — matching MPI Group.Incl
        # semantics, which is what the reference's comm forms resolve to:
        # subset rank i is launcher rank ranks[i], so a reordered
        # mpi4py subcommunicator keeps hvd.rank() == comm.Get_rank()
        # (root-only logic stays on the comm's root).
        new_rank = ranks.index(ps.rank)
        endpoints = None
        if ps.data_endpoints:
            endpoints = [ps.data_endpoints[r] for r in ranks]
        coord = None
        if endpoints:
            host = endpoints[0].rsplit(":", 1)[0]
            # Derive a subset coordinator endpoint from rank-0-of-subset's
            # data host with the configured coordinator port.
            port = _env_int("HVD_TPU_COORD_PORT")
            if port is None and ps.coord_endpoint:
                port = int(ps.coord_endpoint.rsplit(":", 1)[1])
            coord = f"{host}:{port}" if port else ps.coord_endpoint
        # Node-locality must be re-derived for the subset.  The data
        # endpoints carry each subset rank's host, so group by host and index
        # within the group; without endpoints (single-host jobs) the subset
        # rank itself is the local rank.
        if endpoints:
            hosts = [e.rsplit(":", 1)[0] for e in endpoints]
            my_host = hosts[new_rank]
            peers = [i for i, h in enumerate(hosts) if h == my_host]
            local_rank = peers.index(new_rank)
            local_size = len(peers)
        else:
            local_rank, local_size = new_rank, len(ranks)
        ps = ProcessSet(new_rank, len(ranks), local_rank, local_size,
                        coord, endpoints)
    return ps.validate()


def pick_free_port(host: str = "127.0.0.1") -> int:
    """Ask the OS for a currently-free TCP port (used by tests/launcher)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        return s.getsockname()[1]


def pick_free_ports(n: int, host: str = "127.0.0.1") -> list:
    """``n`` distinct currently-free TCP ports, all sockets held open
    until every port is picked.  Sequential :func:`pick_free_port` calls
    release each socket before the next bind, so the OS may hand the
    same port out twice within one launch — a rank then dies with
    EADDRINUSE (the bind/listen flake the suite used to see under
    port-churn load)."""
    socks = []
    try:
        for _ in range(n):
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.bind((host, 0))
            socks.append(s)
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()
