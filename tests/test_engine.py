"""Multi-process engine tests: the negotiation protocol, ring data plane,
tensor fusion, and the negative paths (cross-rank shape/dtype/op mismatch
must surface as typed Python errors, not hangs).

Mirrors the reference's TF/torch collective test matrix
(/root/reference/test/test_tensorflow.py:40-300,
 /root/reference/test/test_torch.py:60-260), rewritten against the engine's
numpy substrate and run over N real processes via the hvdrun launcher.
"""

import numpy as np
import pytest

from tests.distributed import distributed_test


def _init():
    import horovod_tpu as hvd

    hvd.init()
    return hvd


@distributed_test()
def test_allreduce_sum():
    hvd = _init()
    r, n = hvd.rank(), hvd.size()
    for dtype in (np.float32, np.float64, np.int32, np.int64):
        x = (np.arange(101) + r).astype(dtype)
        out = hvd.allreduce(x, average=False, name=f"sum.{np.dtype(dtype)}")
        want = sum((np.arange(101) + i).astype(dtype) for i in range(n))
        assert np.array_equal(out, want), (r, dtype)


@distributed_test()
def test_allreduce_average():
    hvd = _init()
    r, n = hvd.rank(), hvd.size()
    x = np.full((7, 3), float(r), np.float32)
    out = hvd.allreduce(x, average=True, name="avg")
    want = sum(range(n)) / n
    assert np.allclose(out, want), (r, out[0, 0], want)


@distributed_test()
def test_allreduce_half_precision():
    import ml_dtypes

    hvd = _init()
    r, n = hvd.rank(), hvd.size()
    for dtype, tag in ((np.float16, "f16"), (ml_dtypes.bfloat16, "bf16")):
        x = np.full(64, 0.5 + r, dtype)
        out = hvd.allreduce(x, average=False, name=f"half.{tag}")
        want = sum(0.5 + i for i in range(n))
        assert np.allclose(np.asarray(out, np.float32), want, rtol=1e-2), \
            (r, tag, out[0], want)


@distributed_test()
def test_allreduce_fusion_many_small():
    """100 outstanding named tensors in flight at once -- exercises the
    coordinator's fusion path and the async handle table (the reference's
    test_horovod_allreduce_async_fused, test_torch.py:132)."""
    hvd = _init()
    r, n = hvd.rank(), hvd.size()
    handles = [
        hvd.allreduce_async(np.full(17, float(i + r), np.float32),
                            average=False, name=f"fused.{i}")
        for i in range(100)
    ]
    assert all(isinstance(h.done(), bool) for h in handles)
    for i, h in enumerate(handles):
        out = h.wait()
        want = sum(float(i + j) for j in range(n))
        assert np.allclose(out, want), (r, i)


@distributed_test()
def test_allreduce_large_tensor():
    """Payload whose ring segments exceed kernel socket buffering: all ranks
    send simultaneously, so the data plane must keep draining its recv leg
    while its send leg backs up (full-duplex Exchange), or the ring
    deadlocks."""
    hvd = _init()
    r, n = hvd.rank(), hvd.size()
    x = np.random.RandomState(r).randn(1 << 23).astype(np.float32)  # 32 MiB
    out = hvd.allreduce(x, average=False, name="big")
    want = sum(np.random.RandomState(i).randn(1 << 23).astype(np.float32)
               for i in range(n))
    assert np.allclose(out, want, atol=1e-4), r


@distributed_test()
def test_allgather_variable_dim0():
    hvd = _init()
    r, n = hvd.rank(), hvd.size()
    x = np.full((r + 1, 4), r, np.int32)
    out = hvd.allgather(x, name="gather.var")
    assert out.shape == (sum(i + 1 for i in range(n)), 4)
    off = 0
    for i in range(n):
        assert np.all(out[off:off + i + 1] == i), (r, i)
        off += i + 1


@distributed_test()
def test_broadcast_from_each_root():
    hvd = _init()
    r, n = hvd.rank(), hvd.size()
    for root in range(n):
        x = np.full((5, 2), float(r * 10 + 7), np.float64)
        out = hvd.broadcast(x, root_rank=root, name=f"bcast.{root}")
        assert np.all(out == root * 10 + 7), (r, root)
        # Input of non-root ranks must be left untouched.
        assert np.all(x == r * 10 + 7)


@distributed_test()
def test_allreduce_shape_mismatch_error():
    hvd = _init()
    r = hvd.rank()
    shape = (17, 3) if r == 0 else (17, 2)
    with pytest.raises(ValueError, match="[Mm]ismatched"):
        hvd.allreduce(np.zeros(shape, np.float32), name="badshape")


@distributed_test()
def test_allreduce_dtype_mismatch_error():
    hvd = _init()
    dtype = np.float32 if hvd.rank() == 0 else np.float64
    with pytest.raises(ValueError, match="[Mm]ismatched data types"):
        hvd.allreduce(np.zeros(8, dtype), name="baddtype")


@distributed_test()
def test_mismatched_op_error():
    hvd = _init()
    x = np.zeros((4, 4), np.float32)
    with pytest.raises(ValueError, match="[Mm]ismatched collective"):
        if hvd.rank() == 0:
            hvd.allreduce(x, name="mixedop")
        else:
            hvd.allgather(x, name="mixedop")


@distributed_test()
def test_broadcast_root_mismatch_error():
    hvd = _init()
    with pytest.raises(ValueError, match="root rank"):
        hvd.broadcast(np.zeros(4, np.float32), root_rank=hvd.rank(),
                      name="badroot")


@distributed_test()
def test_allgather_trailing_dim_mismatch_error():
    hvd = _init()
    shape = (2, 3) if hvd.rank() == 0 else (2, 4)
    with pytest.raises(ValueError, match="[Mm]ismatched allgather"):
        hvd.allgather(np.zeros(shape, np.float32), name="badgather")


@distributed_test(np_=2)
def test_two_rank_ring():
    """Smallest nontrivial ring (left and right neighbour are the same
    process, distinct sockets)."""
    hvd = _init()
    r, n = hvd.rank(), hvd.size()
    assert n == 2
    out = hvd.allreduce(np.ones(10, np.float32) * (r + 1), average=False,
                        name="2rank")
    assert np.allclose(out, 3.0)


@distributed_test()
def test_interleaved_order_independent():
    """Ranks enqueue the same tensors in different orders; negotiation must
    still match them up by name without deadlock."""
    hvd = _init()
    r, n = hvd.rank(), hvd.size()
    names = [f"ooo.{i}" for i in range(10)]
    order = names if r % 2 == 0 else list(reversed(names))
    handles = {nm: hvd.allreduce_async(
        np.full(5, float(int(nm.split(".")[1])), np.float32),
        average=False, name=nm) for nm in order}
    for nm in names:
        out = handles[nm].wait()
        assert np.allclose(out, float(int(nm.split(".")[1])) * n), (r, nm)


def _hier_env(local_size):
    """Re-shape this rank's env into `local_size`-sized nodes and enable the
    two-level allreduce, before hvd.init() reads it."""
    import os

    rank = int(os.environ["HVD_TPU_RANK"])
    os.environ["HVD_TPU_LOCAL_SIZE"] = str(local_size)
    os.environ["HVD_TPU_LOCAL_RANK"] = str(rank % local_size)
    os.environ["HOROVOD_HIERARCHICAL_ALLREDUCE"] = "1"


@distributed_test(np_=4)
def test_hierarchical_allreduce_two_nodes():
    """4 ranks as 2 nodes x 2 local: local reduce-scatter -> per-shard
    cross-node exchange -> local allgather must equal the flat ring
    result (the two-level successor of the reference's
    HOROVOD_HIERARCHICAL_ALLREDUCE, operations.cc:1003-1048)."""
    _hier_env(local_size=2)
    hvd = _init()
    r, n = hvd.rank(), hvd.size()
    for i, count in enumerate((1, 7, 1000, 100003)):
        x = (np.arange(count) * 0.01 + r).astype(np.float32)
        out = hvd.allreduce(x, average=False, name=f"hier.{i}")
        want = sum((np.arange(count) * 0.01 + j).astype(np.float32)
                   for j in range(n))
        assert np.allclose(out, want, rtol=1e-5), (r, count)
    # Average + fusion path.
    handles = [hvd.allreduce_async(np.full(11, float(r), np.float32),
                                   average=True, name=f"hier.avg.{i}")
               for i in range(20)]
    for h in handles:
        assert np.allclose(h.wait(), sum(range(n)) / n)
    # Other collectives still ride the flat ring alongside.
    g = hvd.allgather(np.full((1, 2), float(r), np.float32), name="hier.g")
    assert g.shape == (n, 2)


@distributed_test(np_=4)
def test_hierarchical_bad_layout_falls_back():
    """An interleaved (non-contiguous) rank layout must not deadlock: the
    topology agreement makes every rank fall back to the flat ring."""
    import os

    rank = int(os.environ["HVD_TPU_RANK"])
    os.environ["HVD_TPU_LOCAL_SIZE"] = "2"
    # Wrong layout: local_rank = rank // 2 passes the modular check on some
    # ranks only -- exactly the divergence case.
    os.environ["HVD_TPU_LOCAL_RANK"] = str(rank // 2)
    os.environ["HOROVOD_HIERARCHICAL_ALLREDUCE"] = "1"
    hvd = _init()
    r, n = hvd.rank(), hvd.size()
    out = hvd.allreduce(np.full(33, float(r + 1), np.float32),
                        average=False, name="fallback")
    assert np.allclose(out, sum(range(1, n + 1)))


@distributed_test(np_=3)
def test_hierarchical_single_node():
    """All ranks on one node: the cross phase degenerates to nothing and
    the result is a pure local reduce-scatter + allgather."""
    _hier_env(local_size=3)
    hvd = _init()
    r, n = hvd.rank(), hvd.size()
    out = hvd.allreduce(np.full(257, 1.5 * (r + 1), np.float64),
                        average=False, name="hier1")
    assert np.allclose(out, 1.5 * sum(range(1, n + 1)))


def test_stall_warning_printed():
    """Coordinator stall sweep: when a subset of ranks never announces a
    tensor, rank 0 warns with the tensor name and the missing ranks
    (operations.cc:1231-1276 behavior; untested in the reference)."""
    import sys

    from horovod_tpu.runner import run_command

    code = (
        "import os, time, numpy as np, horovod_tpu as hvd\n"
        "hvd.init()\n"
        "if hvd.rank() == 0:\n"
        "    h = hvd.allreduce_async(np.ones(4, np.float32), name='lonely')\n"
        "    time.sleep(3.0)\n"  # > 2x the 1s stall window
        "else:\n"
        "    time.sleep(3.0)\n"
        "    h = hvd.allreduce_async(np.ones(4, np.float32), name='lonely')\n"
        "h.wait()\n"
    )
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, HVD_TPU_STALL_WARNING_SEC="1",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    results = run_command([sys.executable, "-c", code], 2, env=env,
                          timeout=120.0, capture=True)
    assert all(r.returncode == 0 for r in results), \
        [(r.rank, r.stderr[-300:]) for r in results]
    rank0_err = results[0].stderr
    assert "Stalled ops" in rank0_err, rank0_err[-500:]
    assert "lonely" in rank0_err and "missing ranks: 1" in rank0_err


@distributed_test(np_=3)
def test_init_comm_subset():
    """hvd.init(comm=[...]) restricts the job to a rank subset with dense
    renumbering (the reference's init(comm=...) rank-list mode,
    /root/reference/horovod/common/__init__.py:51-62)."""
    import os

    import horovod_tpu as hvd

    launcher_rank = int(os.environ["HVD_TPU_RANK"])
    if launcher_rank == 1:
        return  # not in the subset; must not join
    hvd.init(comm=[0, 2])
    assert hvd.size() == 2
    assert hvd.rank() == (0 if launcher_rank == 0 else 1)
    out = hvd.allreduce(np.full(5, float(launcher_rank), np.float32),
                        average=False, name="subset")
    assert np.allclose(out, 2.0), out  # 0 + 2


@distributed_test(np_=3)
def test_init_comm_mpi4py_style():
    """hvd.init(comm=<communicator>) accepts an mpi4py-style object (the
    reference's second init form, /root/reference/horovod/common/
    __init__.py:51-78): duck-typed Get_size/allgather, each member
    contributing its launcher rank.  The stub stands in for a REORDERED
    subcommunicator (comm rank 0 = launcher rank 2, as
    MPI.Group.Incl([2, 0]) would build): hvd.rank() must equal the
    comm's own rank, so root-only logic stays on the comm's root."""
    import os

    import horovod_tpu as hvd

    launcher_rank = int(os.environ["HVD_TPU_RANK"])
    if launcher_rank == 1:
        return  # not a member of the communicator; must not join
    comm_rank = 0 if launcher_rank == 2 else 1

    class SubComm:  # mpi4py allgather returns values in comm-rank order
        def Get_size(self):
            return 2

        def Get_rank(self):
            return comm_rank

        def allgather(self, value):
            assert value == launcher_rank
            return [2, 0]

    hvd.init(comm=SubComm())
    assert hvd.size() == 2
    assert hvd.rank() == comm_rank
    out = hvd.allreduce(np.full(4, float(launcher_rank), np.float32),
                        average=False, name="mpi4py_subset")
    assert np.allclose(out, 2.0), out  # 0 + 2


def test_timeline_written(tmp_path):
    """Timeline (Chrome tracing) is written on rank 0 when enabled --
    reference aux subsystem /root/reference/horovod/common/timeline.{h,cc}."""
    import json
    import os
    import subprocess
    import sys

    tl = tmp_path / "timeline.json"
    code = (
        "import numpy as np, horovod_tpu as hvd\n"
        "hvd.init()\n"
        "for i in range(3):\n"
        "    hvd.allreduce(np.ones(100, np.float32), name=f'tl.{i}')\n"
        "hvd.allgather(np.ones((2, 2), np.float32), name='tl.g')\n"
        "hvd.shutdown()\n"
    )
    # Pin the TCP engine transport: the XLA data plane would record
    # XLA_ALLREDUCE instead of the engine activities asserted below.
    env = dict(os.environ, HOROVOD_TIMELINE=str(tl), JAX_PLATFORMS="cpu",
               HVD_TPU_XLA_DATA_PLANE="0")
    for var in ("HVD_TPU_RANK", "HVD_TPU_SIZE"):
        env.pop(var, None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)
    text = tl.read_text()
    # Chrome-tracing array with trailing comma tolerated by the viewer;
    # complete it for json.loads.
    events = json.loads(text.rstrip().rstrip(",") + "]")
    names = {e.get("name") for e in events}
    assert "ALLREDUCE" in names
    assert "ALLGATHER" in names
    assert "RING_ALLREDUCE" in names or "MEMCPY_IN_FUSION_BUFFER" in names
    pids = {e.get("pid") for e in events}
    assert len(pids) >= 4  # one per tensor name


# ---------------------------------------------------------------------------
# Fault injection: rank death must surface as HorovodInternalError on the
# survivors, never a hang.  The reference's weakest area (SURVEY.md 5.3) --
# its coordinated-shutdown path (operations.cc:1446-1461) was never tested.
# ---------------------------------------------------------------------------


@distributed_test(np_=3, timeout=120.0)
def test_rank_death_before_collective_aborts_survivors():
    """A rank that exits without joining a collective tears the job down:
    the coordinator notices the dead control socket (engine.cc worker-death
    path) and survivors' pending collectives complete with
    HorovodInternalError well inside the stall window."""
    import os

    from horovod_tpu.common import HorovodInternalError

    hvd = _init()
    r = hvd.rank()
    if r == 1:
        os._exit(0)  # simulated crash: no shutdown handshake, sockets drop
    h = hvd.allreduce_async(np.full(64, float(r), np.float32),
                            average=False, name="orphaned")
    with pytest.raises(HorovodInternalError):
        h.wait()


@distributed_test(np_=3, timeout=120.0)
def test_rank_death_mid_allreduce_aborts_survivors():
    """A rank that dies while the ring is moving a large payload breaks the
    neighbour exchange mid-stream; survivors get HorovodInternalError (from
    the failed exchange or the coordinated shutdown, whichever trips
    first), and every LATER collective fails uniformly too instead of
    leaving a half-functional job."""
    import os
    import time

    from horovod_tpu.common import HorovodInternalError

    hvd = _init()
    r = hvd.rank()
    # 64 MB keeps the ring busy for hundreds of ms on loopback, so the
    # killed rank typically dies mid-exchange.
    payload = np.full(16 << 20, float(r), np.float32)
    h = hvd.allreduce_async(payload, average=False, name="doomed")
    if r == 1:
        time.sleep(0.3)  # negotiation (~5ms cycle) done; transfer underway
        os._exit(0)
    # On a fast host the 64 MB ring can outrun the 0.3 s fuse and this
    # first wait legitimately succeeds; the contract under test is that a
    # survivor ERRORS (on this op or the next) and never hangs.
    with pytest.raises(HorovodInternalError):
        h.wait()
        hvd.allreduce(np.zeros(4, np.float32), name="death_sweep")
    # Uniform failure: every subsequent collective must also raise, not
    # hang and not succeed (the job is dead, not degraded).
    with pytest.raises(HorovodInternalError):
        hvd.broadcast(np.zeros(4, np.float32), 0, name="after_death")


@distributed_test(np_=4, timeout=120.0)
def test_leader_death_mid_hierarchical_aborts_all():
    """Killing a rank mid-two-level-allreduce: its node peer's local-ring
    exchange and its cross-ring peers' exchanges fail, the failure
    cascades through the closed topology fds, and every survivor raises
    HorovodInternalError (never hangs); later collectives fail
    uniformly."""
    import os
    import time

    from horovod_tpu.common import HorovodInternalError

    _hier_env(local_size=2)
    hvd = _init()
    r = hvd.rank()
    payload = np.full(16 << 20, float(r), np.float32)
    h = hvd.allreduce_async(payload, average=False, name="hier_doomed")
    if r == 2:  # leader of node 1
        time.sleep(0.3)
        os._exit(0)
    # As above: if the collective outran the fuse, the next one must fail.
    with pytest.raises(HorovodInternalError):
        h.wait()
        hvd.allreduce(np.zeros(4, np.float32), name="hier_sweep")
    with pytest.raises(HorovodInternalError):
        hvd.allgather(np.zeros((1, 2), np.float32), name="hier_after")


@distributed_test(np_=2)
def test_reinit_races_previous_teardown():
    """Back-to-back shutdown -> init cycles with NO pause: a worker's
    reconnect can land in the PREVIOUS engine's listen backlog on rank 0
    (a running non-elastic coordinator never accepts on its control
    listener), where the hello buffers fine and dies with an RST only at
    teardown — while the new init on rank 0 waits for a hello that will
    never arrive.  The init handshake must retry whole (reconnect +
    hello + agreement) instead of failing the job; before that fix this
    loop deadlocked roughly every other run."""
    hvd = _init()
    for cycle in range(4):
        r, n = hvd.rank(), hvd.size()
        out = hvd.allreduce(np.full(64, float(r + 1), np.float32),
                            average=False, name=f"reinit.{cycle}")
        assert abs(out[0] - n * (n + 1) / 2.0) < 1e-5, out[0]
        hvd.shutdown()
        hvd.init()
    hvd.shutdown()
