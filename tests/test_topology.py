"""Two-level topology tests (docs/performance.md#two-level-topology).

The topology under test: node-local reduce-scatter -> one cross-node
(DCN) exchange per local rank over its 1/local_size shard (ring, or
recursive-doubling tree under the HVD_TPU_CROSS_ALGO_THRESHOLD boundary)
-> node-local allgather, chunk-pipelined, with the PR-9 wire compression
narrowing the cross hop.  Covered here:

* numerical identity against the flat ring for mixed fused buckets
  (sum + average neighbours) — bit-equal with compression off;
* per-phase failure injection: a member dying mid-collective fails every
  survivor with a typed error, fast, never a hang;
* DCN-hop compression lockstep (compression_report() decision log
  allgather-identical) and cross-hop byte reduction;
* native-width half payloads (wire == payload bytes in the metrics);
* the ring-vs-tree boundary crossing mid-run via hvd.autotune_set and
  converging as the autotuner's fourth axis;
* the ungated metrics_snapshot()["topology"] section, its Prometheus
  families, phase histograms, and timeline/flight events.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from distributed import distributed_test, run_ranks  # noqa: E402


def _hier_env(local_size, **extra):
    """Re-shape this rank's env into `local_size`-sized nodes and enable
    the two-level allreduce, before hvd.init() reads it."""
    rank = int(os.environ["HVD_TPU_RANK"])
    os.environ["HVD_TPU_LOCAL_SIZE"] = str(local_size)
    os.environ["HVD_TPU_LOCAL_RANK"] = str(rank % local_size)
    os.environ["HOROVOD_HIERARCHICAL_ALLREDUCE"] = "1"
    for k, v in extra.items():
        os.environ[k] = v


def _init():
    import horovod_tpu as hvd

    hvd.init()
    return hvd


def _assert_allgather_identical(hvd, text, name, width=4096):
    """Allgather `text` (padded) from every rank and assert equality —
    the lockstep-contract check used for decision/applied logs."""
    padded = text.ljust(width)[:width].encode()
    rows = hvd.allgather(
        np.frombuffer(padded, dtype=np.uint8).reshape(1, -1), name=name)
    base = bytes(rows[0])
    for r in range(rows.shape[0]):
        assert bytes(rows[r]) == base, (
            f"{name}: rank {r} diverged:\n{bytes(rows[r])!r}\nvs\n{base!r}")


# ---------------------------------------------------------------------------
# Numerical identity and phase coverage.
# ---------------------------------------------------------------------------


@distributed_test(np_=4)
def test_two_level_matches_flat_mixed_fused():
    """Flat-vs-hierarchical identity for mixed fused buckets: integer-
    valued f32 payloads (exact sums, so association order cannot change
    bits) reduced as a fused group mixing sum and average neighbours must
    BIT-compare equal between the flat ring and the two-level topology,
    with compression off — the kill-switch identity bar PR 9 set."""
    import horovod_tpu as hvd

    def run_suite(tag):
        n = hvd.size()
        handles = []
        for i in range(12):
            x = ((np.arange(64 + 17 * i) % 89) + hvd.rank() + i).astype(
                np.float32)
            handles.append(hvd.allreduce_async(
                x, average=(i % 2 == 1), name=f"{tag}.mix.{i}"))
        outs = [h.wait().copy() for h in handles]
        big = (np.arange(1 << 18) % 251 + hvd.rank()).astype(np.float32)
        outs.append(hvd.allreduce(big, average=False, name=f"{tag}.big"))
        del n
        return outs

    hvd.init()  # flat ring
    flat = run_suite("flat")
    hvd.shutdown()

    _hier_env(local_size=2)
    hvd.init()
    assert hvd.local_size() == 2
    hier = run_suite("hier")
    topo = hvd.metrics_snapshot()["topology"]
    assert topo["hierarchical"] and topo["nodes"] == 2, topo
    assert topo["bytes"]["local"] > 0 and topo["bytes"]["cross"] > 0, topo
    for a, b in zip(flat, hier):
        assert np.array_equal(a, b), (
            "flat vs two-level results differ bitwise")
    hvd.shutdown()


@distributed_test(np_=3)
def test_two_level_single_node_generic_dtypes():
    """One 3-rank node (no cross phase): the local RS+AG pair must be a
    complete allreduce for every dtype family — f32, f64 (generic native
    path), int64, and native-width bf16."""
    import ml_dtypes

    _hier_env(local_size=3)
    hvd = _init()
    r, n = hvd.rank(), hvd.size()
    out = hvd.allreduce(np.full(257, 1.5 * (r + 1), np.float64),
                        average=False, name="f64")
    assert np.allclose(out, 1.5 * sum(range(1, n + 1)))
    out = hvd.allreduce(np.arange(1001, dtype=np.int64) + r,
                        average=False, name="i64")
    assert np.array_equal(out, np.arange(1001, dtype=np.int64) * n
                          + sum(range(n)))
    xb = (np.arange(96) % 5).astype(ml_dtypes.bfloat16)
    out = hvd.allreduce(xb, average=False, name="bf16")
    assert np.array_equal(out.astype(np.float32),
                          (np.arange(96) % 5).astype(np.float32) * n)
    out = hvd.allreduce(np.full(7, float(r), np.float32), average=True,
                        name="f32avg")
    assert np.allclose(out, sum(range(n)) / n)


def _phase_death_rank_fn():
    """Rank body for the per-phase failure tests: the doomed rank (from
    TOPOTEST_DOOMED) exits mid-collective; every survivor must get a
    typed HorovodInternalError on this or the next collective, fast."""
    from horovod_tpu.common import HorovodInternalError

    _hier_env(local_size=2)
    if os.environ.get("TOPOTEST_TREE") == "1":
        os.environ["HVD_TPU_CROSS_ALGO_THRESHOLD"] = str(1 << 30)
    hvd = _init()
    doomed = int(os.environ["TOPOTEST_DOOMED"])
    r = hvd.rank()
    payload = np.full(16 << 20, float(r), np.float32)
    h = hvd.allreduce_async(payload, average=False, name="doomed")
    if r == doomed:
        time.sleep(float(os.environ.get("TOPOTEST_DELAY", "0.3")))
        os._exit(0)
    t0 = time.time()
    with pytest.raises(HorovodInternalError):
        h.wait()
        hvd.allreduce(np.zeros(4, np.float32), name="sweep")
    # Fast: the closed topology fds cascade the failure well inside the
    # 30s exchange silence timeout.
    assert time.time() - t0 < 25.0, "survivor stalled instead of failing"
    with pytest.raises(HorovodInternalError):
        hvd.allgather(np.zeros((1, 2), np.float32), name="after")


def test_two_level_phase_death_cross_peer():
    """Tier-1 representative of the per-phase failure matrix: rank 2
    (node 1, local 0 — rank 0's cross-ring peer AND rank 3's local peer)
    dies mid-two-level-allreduce; both failure directions cascade."""
    os.environ["TOPOTEST_DOOMED"] = "2"
    os.environ.pop("TOPOTEST_TREE", None)
    try:
        run_ranks(_phase_death_rank_fn, np_=4, timeout=120.0)
    finally:
        os.environ.pop("TOPOTEST_DOOMED", None)


@pytest.mark.slow
@pytest.mark.parametrize("doomed,tree", [(1, False), (3, False), (1, True)])
def test_two_level_phase_death_matrix(doomed, tree):
    """Slow sweep of the remaining death scenarios: a same-node local
    peer (rank 1), the far corner (rank 3), and a death under the TREE
    cross exchange.  Tier-1 keeps the cross-peer representative
    (test_two_level_phase_death_cross_peer)."""
    os.environ["TOPOTEST_DOOMED"] = str(doomed)
    if tree:
        os.environ["TOPOTEST_TREE"] = "1"
    try:
        run_ranks(_phase_death_rank_fn, np_=4, timeout=120.0)
    finally:
        os.environ.pop("TOPOTEST_DOOMED", None)
        os.environ.pop("TOPOTEST_TREE", None)


# ---------------------------------------------------------------------------
# DCN-hop compression.
# ---------------------------------------------------------------------------


@distributed_test(np_=4)
def test_two_level_dcn_compression_lockstep():
    """bf16 on the cross hop: every rank's per-bucket decision log is
    allgather-identical (the lockstep contract), the cross-hop bytes
    halve against the full-width local hop, error stays small, and the
    compressed result is identical across ranks."""
    _hier_env(local_size=2, HVD_TPU_COMPRESSION="bf16")
    hvd = _init()
    r, n = hvd.rank(), hvd.size()
    base = hvd.metrics_snapshot()["topology"]["bytes"]
    count = 1 << 19
    x = np.random.RandomState(r).rand(count).astype(np.float32) - 0.5
    want = np.zeros(count, np.float32)
    for j in range(n):
        want += np.random.RandomState(j).rand(count).astype(np.float32) - 0.5
    for i in range(3):
        out = hvd.allreduce(x, average=False, name="comp.big")
    rel = float(np.max(np.abs(out - want)) / np.max(np.abs(want)))
    assert rel < 0.05, rel
    # Every rank holds the SAME compressed result (owner-quantize rule).
    gathered = hvd.allgather(out[:1024].reshape(1, -1), name="comp.gather")
    for j in range(n):
        assert np.array_equal(gathered[j], gathered[0]), j
    after = hvd.metrics_snapshot()["topology"]["bytes"]
    local = after["local"] - base["local"]
    cross = after["cross"] - base["cross"]
    # L=2, M=2: full-width local moves 2 exchanges of count/2 f32 per op;
    # the bf16 cross ring moves count/2 elems at 2 bytes — a 4x
    # local-to-cross ratio (2x of it from compression; >= 1.8x is the
    # acceptance bar for the DCN-byte claim).
    assert cross > 0 and local / cross >= 3.5, (local, cross)
    rep = hvd.compression_report()
    assert rep["engine"]["ops"]["bf16"] >= 3, rep["engine"]["ops"]
    log_text = ";".join(f"{e['name']}|{e['mode']}" for e in rep["log"])
    _assert_allgather_identical(hvd, log_text, "comp.log")


@distributed_test(np_=3)
def test_single_node_two_level_never_compresses():
    """A single-NODE two-level job has no DCN hop — the only hop the
    verdict narrows — so a requested bf16 mode must stay inert: results
    exact, wire bytes == payload bytes, zero compressed buckets (no
    phantom compression win in the metrics)."""
    _hier_env(local_size=3, HVD_TPU_COMPRESSION="bf16")
    hvd = _init()
    r, n = hvd.rank(), hvd.size()
    before = hvd.compression_report()["engine"]
    # 257 (= 1 + 2^-8 scaled) needs 8 fraction bits — one more than bf16
    # stores — and small-integer sums are exact in f32, so any lossy wire
    # anywhere shows up bitwise.
    x = np.full(1 << 15, 257.0, np.float32) * (r + 1)
    out = hvd.allreduce(x, average=False, name="inert")
    want = 257.0 * sum(range(1, n + 1))
    assert np.array_equal(out, np.full(1 << 15, want, np.float32)), out[:3]
    after = hvd.compression_report()["engine"]
    assert after["ops"]["bf16"] == before["ops"]["bf16"], after["ops"]
    dw = after["wire_bytes"] - before["wire_bytes"]
    dp = after["payload_bytes"] - before["payload_bytes"]
    assert dw == dp, (dw, dp)


@distributed_test(np_=4)
def test_two_level_half_native_width():
    """f16/bf16 payloads cross BOTH two-level hops at native width: the
    compression metrics' wire bytes equal the payload bytes (the old
    star staged halves through f32 at 2x)."""
    import ml_dtypes

    _hier_env(local_size=2)
    hvd = _init()
    r, n = hvd.rank(), hvd.size()
    before = hvd.compression_report()["engine"]
    for dt, name in ((ml_dtypes.bfloat16, "nb"), (np.float16, "nh")):
        x = (np.arange(1 << 15) % 17).astype(dt)
        out = hvd.allreduce(x, average=False, name=name)
        assert np.array_equal(out.astype(np.float32),
                              (np.arange(1 << 15) % 17) * float(n))
    after = hvd.compression_report()["engine"]
    dw = after["wire_bytes"] - before["wire_bytes"]
    dp = after["payload_bytes"] - before["payload_bytes"]
    assert dw == dp and dp == 2 * (2 << 15), (dw, dp)
    del r


# ---------------------------------------------------------------------------
# Ring-vs-tree selection.
# ---------------------------------------------------------------------------


@distributed_test(np_=4)
def test_tree_ring_boundary_crosses_mid_run():
    """Small buckets take the recursive-doubling tree, big ones the
    ring; moving HVD_TPU_CROSS_ALGO_THRESHOLD mid-run via
    hvd.autotune_set flips the per-bucket decision at a lockstep tick on
    every rank, with correct results throughout and a flight-recorder
    event on the switch."""
    _hier_env(local_size=2)
    os.environ["HVD_TPU_CROSS_ALGO_THRESHOLD"] = str(64 << 10)
    hvd = _init()
    r, n = hvd.rank(), hvd.size()

    def sweep(tag):
        for i, count in enumerate((64, 1 << 10, 1 << 17)):
            x = (np.arange(count) % 31 + r).astype(np.float32)
            out = hvd.allreduce(x, average=False, name=f"{tag}.{i}")
            want = (np.arange(count) % 31).astype(np.float32) * n \
                + sum(range(n))
            assert np.array_equal(out, want), (tag, count)

    sweep("warm")
    snap = hvd.metrics_snapshot()["topology"]
    assert snap["cross_ops"]["tree"] > 0, snap   # 64/1K buckets < 64KiB
    assert snap["cross_ops"]["ring"] > 0, snap   # the 512KiB bucket
    assert snap["cross_algo_threshold"] == 64 << 10, snap
    # Every rank has read its snapshot before rank 0 moves the boundary:
    # the injection rides the next tick, idle ones too, and would reach a
    # rank still on its way to the lines above.
    hvd.allreduce(np.zeros(4, np.float32), name="snapshots.read")
    if r == 0:
        hvd.autotune_set(cross_algo_threshold=0)  # ring always
    # One collective flushes the broadcast; then the boundary is live
    # everywhere (applied at the same tick on every rank).
    hvd.allreduce(np.zeros(4, np.float32), name="flush")
    before = hvd.metrics_snapshot()["topology"]["cross_ops"]
    sweep("ringonly")
    after = hvd.metrics_snapshot()["topology"]["cross_ops"]
    assert after["tree"] == before["tree"], (before, after)
    assert after["ring"] >= before["ring"] + 3, (before, after)
    assert hvd.metrics_snapshot()["topology"]["cross_algo_threshold"] == 0
    # The applied log (tick|fusion|cycle|comp|cross_algo|frozen) is
    # lockstep-identical — the allgather-identity contract.
    applied = json.dumps(hvd.autotune_report()["applied"], sort_keys=True)
    _assert_allgather_identical(hvd, applied, "algo.applied")
    # The ring<->tree switch left a flight event.
    from horovod_tpu.common import _load_lib

    dump = _load_lib().hvd_tpu_flight_dump().decode()
    assert "|topology|" in dump, dump[-500:]


@pytest.mark.slow  # convergence-deadline test (150s internal budget) is
# load-sensitive on a saturated box; the other three autotune axes and
# the cross-algo grid unit tests stay tier-1
@distributed_test(np_=4, timeout=240.0)
def test_cross_algo_fourth_axis_converges():
    """The autotuner's FOURTH axis: with the other three knobs pinned,
    a two-level job's search walks the cross-algo grid and freezes, with
    the applied log allgather-identical across ranks (the acceptance
    contract)."""
    _hier_env(local_size=2)
    os.environ["HVD_TPU_AUTOTUNE"] = "1"
    os.environ["HVD_TPU_AUTOTUNE_WINDOW"] = "8"
    os.environ["HVD_TPU_AUTOTUNE_WARMUP"] = "1"
    os.environ["HVD_TPU_AUTOTUNE_FIX"] = (
        "fusion_threshold=1048576,cycle_time_ms=1,compression=off")
    hvd = _init()
    r = hvd.rank()
    x = (np.arange(2048) % 13 + r).astype(np.float32)
    deadline = time.time() + 150.0
    step = 0
    while not hvd.autotune_report()["frozen"]:
        assert time.time() < deadline, hvd.autotune_report()
        handles = [hvd.allreduce_async(x, average=False,
                                       name=f"tune.{step}.{i}")
                   for i in range(8)]
        for h in handles:
            h.wait()
        step += 1
    rep = hvd.autotune_report()
    assert rep["frozen"] and rep["windows"] >= 2, rep
    # The frozen boundary is a grid point, identical everywhere.
    from horovod_tpu.common.autotune import CROSS_ALGO_GRID

    assert rep["cross_algo_threshold"] in CROSS_ALGO_GRID, rep
    applied = json.dumps(rep["applied"], sort_keys=True)
    _assert_allgather_identical(hvd, applied, "tune.applied")
    # Pinned knobs never moved.
    for entry in rep["applied"]:
        assert entry["fusion_threshold"] == 1048576, entry
        assert entry["compression"] == "off", entry


# ---------------------------------------------------------------------------
# Observability units (single process, fast).
# ---------------------------------------------------------------------------


def test_hierarchical_mesh_mirrors_two_level_decomposition():
    """The XLA-compiled mirror of the engine's two-level topology
    (parallel/mesh.py): a psum over the (dcn, ici) hierarchical mesh
    equals the flat global sum — XLA lowers it to the same
    RS-on-inner / cross-on-outer / AG-on-inner decomposition the TCP
    engine runs by hand — and explicit inner-then-outer psums compose to
    the identical result."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map
    from horovod_tpu.parallel import hierarchical_mesh

    devices = jax.devices()[:8]
    mesh = hierarchical_mesh(devices, num_slices=2)
    assert isinstance(mesh, Mesh)
    assert mesh.devices.shape == (2, 4)
    assert mesh.axis_names == ("dcn", "ici")

    x = jnp.arange(8.0, dtype=jnp.float32).reshape(8, 1)

    def both(v):
        return jax.lax.psum(v, ("dcn", "ici"))

    def two_level(v):
        return jax.lax.psum(jax.lax.psum(v, "ici"), "dcn")

    spec = P(("dcn", "ici"), None)
    flat = shard_map(both, mesh=mesh, in_specs=(spec,), out_specs=P())(x)
    nested = shard_map(two_level, mesh=mesh, in_specs=(spec,),
                       out_specs=P())(x)
    assert float(flat[0, 0]) == float(np.arange(8.0).sum())
    assert np.array_equal(np.asarray(flat), np.asarray(nested))


def test_topology_section_is_ungated():
    from horovod_tpu.common import metrics

    reg = metrics.MetricsRegistry()  # never enabled
    snap = reg.snapshot()
    assert snap["topology"] == {
        "hierarchical": False, "nodes": 1, "local_size": 1,
        "cross_algo_threshold": 0,
        "local_transport": "tcp",
        "cross_ops": {"ring": 0, "tree": 0},
        "bytes": {"local": 0, "cross": 0},
    }
    reg.set_topology({"hierarchical": True, "nodes": 4, "local_size": 2,
                      "cross_algo_threshold": 1 << 16,
                      "cross_ops": {"ring": 5, "tree": 2},
                      "bytes": {"local": 100, "cross": 40}})
    snap = reg.snapshot()
    assert snap["topology"]["nodes"] == 4
    assert snap["topology"]["cross_ops"] == {"ring": 5, "tree": 2}
    reg.reset()
    assert reg.snapshot()["topology"]["nodes"] == 1


def test_topology_prometheus_families():
    from horovod_tpu.common import metrics

    reg = metrics.MetricsRegistry()
    reg.set_topology({"hierarchical": True, "nodes": 2, "local_size": 2,
                      "cross_algo_threshold": 64 << 10,
                      "cross_ops": {"ring": 3, "tree": 1},
                      "bytes": {"local": 4096, "cross": 1024}})
    reg.observe("topology_local_rs_sec", 0.002)
    reg.observe("topology_cross_sec", 0.004)
    reg.observe("topology_local_ag_sec", 0.001)
    text = metrics.prometheus_text(reg.snapshot())
    assert "hvd_tpu_topology_hierarchical 1" in text
    assert "hvd_tpu_topology_nodes 2" in text
    assert 'hvd_tpu_topology_cross_ops_total{algo="ring"} 3' in text
    assert 'hvd_tpu_topology_cross_ops_total{algo="tree"} 1' in text
    assert 'hvd_tpu_topology_bytes_total{hop="cross"} 1024' in text
    assert "hvd_tpu_topology_cross_algo_threshold_bytes 65536" in text
    assert "hvd_tpu_topology_local_rs_seconds_count 1" in text
    assert "hvd_tpu_topology_cross_seconds_count 1" in text


def test_metrics_dump_topology_line():
    from tools.metrics_dump import render

    snap = {
        "enabled": True,
        "ops": {"engine": {"allreduce": 1, "allgather": 0, "broadcast": 0},
                "xla": {"allreduce": 0, "allgather": 0, "broadcast": 0}},
        "bytes": {"engine": {"in": 10, "out": 10},
                  "xla": {"in": 0, "out": 0}},
        "batches": {"dispatched": 0, "fused_tensors": 0},
        "stalls": {"count": 0, "tensors": {}},
        "topology": {"hierarchical": True, "nodes": 2, "local_size": 2,
                     "cross_algo_threshold": 64 << 10,
                     "cross_ops": {"ring": 4, "tree": 2},
                     "bytes": {"local": 1 << 20, "cross": 1 << 19}},
        "histograms": {},
    }
    text = render(snap)
    assert "== topology ==" in text
    assert "ring 4 / tree 2" in text
    assert "2 node(s) x 2 local" in text
