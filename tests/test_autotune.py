"""Online autotuning tests (docs/performance.md#autotuning): lockstep
determinism (every rank applies the identical parameter sequence and the
identical frozen params), convergence from deliberately bad initial
params, interplay with the negotiation response cache across a
fusion-threshold change (no stale-fusion replay), HVD_TPU_AUTOTUNE_FIX
pinning, manual injection (hvd.autotune_set), and — the part that must
never regress — the tuner-off default leaves every existing contract
untouched.  Plus units for the env-spec parsing and the
snapshot/Prometheus surface.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tests.distributed import distributed_test

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _allgather_str(hvd, text: str, name: str, width: int = 8192):
    """Allgather a small per-rank string as fixed-width bytes; returns the
    list of per-rank strings."""
    buf = np.frombuffer(text.encode().ljust(width, b" ")[:width],
                        np.uint8).copy()
    rows = hvd.allgather(buf.reshape(1, width), name=name)
    return [bytes(rows[i]).decode().rstrip() for i in range(rows.shape[0])]


# ---------------------------------------------------------------------------
# The acceptance shape: 4 ranks, deliberately bad initial params, online
# search converges + freezes, every rank applied the identical sequence.
# ---------------------------------------------------------------------------


@distributed_test(np_=4)
def test_lockstep_convergence_from_bad_params():
    os.environ["HVD_TPU_AUTOTUNE"] = "1"
    os.environ["HVD_TPU_AUTOTUNE_WINDOW"] = "8"
    os.environ["HVD_TPU_AUTOTUNE_WARMUP"] = "1"
    os.environ["HVD_TPU_FUSION_THRESHOLD"] = "1024"
    os.environ["HVD_TPU_CYCLE_TIME_MS"] = "50"
    import horovod_tpu as hvd

    hvd.init()
    r, n = hvd.rank(), hvd.size()
    frozen_at = None
    for s in range(400):
        for k in range(6):
            out = hvd.allreduce(np.full(256, float(r + k + s), np.float32),
                                average=False, name=f"grad.{k}")
            want = sum(float(i + k + s) for i in range(n))
            assert np.allclose(out, want), (r, s, k, out[0], want)
        # Collective break: ranks observe the freeze broadcast at
        # different wall times; a rank-local break would leave the
        # slower ranks' next step unmatched.
        f = np.asarray([int(hvd.autotune_report()["frozen"])], np.int32)
        if int(hvd.allreduce(f, average=False, name="at.poll")[0]) == n:
            frozen_at = s
            break
    rep = hvd.autotune_report()
    assert rep["enabled"], r
    assert rep["frozen"], (r, rep["windows"], frozen_at)
    # The search must have climbed out of the bad initial point: the
    # first broadcast already snaps the 1 KB threshold to the grid.
    assert rep["fusion_threshold"] >= 64 * 1024, rep["fusion_threshold"]
    assert 0 < rep["cycle_time_ms"] <= 50.0, rep["cycle_time_ms"]
    assert rep["applied"], r

    # Lockstep determinism: the full applied-parameter sequence — ticks,
    # values, freeze flags — is identical on every rank, and so are the
    # final frozen params in the (ungated) snapshot section.
    applied = ";".join(
        f"{a['tick']}|{a['fusion_threshold']}|{a['cycle_time_ms']}|"
        f"{int(a['frozen'])}" for a in rep["applied"])
    for i, peer in enumerate(_allgather_str(hvd, applied, "at.applied")):
        assert peer == applied, (r, i)
    snap = hvd.metrics_snapshot()["autotune"]
    finals = hvd.allgather(np.asarray(
        [[snap["fusion_threshold"], int(snap["cycle_time_ms"] * 1000),
          int(snap["frozen"])]], np.int64), name="at.finals")
    for i in range(n):
        assert (finals[i] == finals[0]).all(), (r, finals)
    # Rank 0 (the coordinator) also carries the per-window history.
    if r == 0:
        assert len(rep["history"]) == rep["windows"] > 0
        assert rep["best_score"] > 0
        assert {"window", "fusion_threshold", "cycle_time_ms",
                "score"} <= set(rep["history"][0])
    hvd.shutdown()


# ---------------------------------------------------------------------------
# Autotune x response cache: a threshold change with a warm cache re-fuses
# replays at the new boundary in lockstep — never a stale-bucket replay,
# never a mismatch error, and completion ticks stay rank-identical.
# ---------------------------------------------------------------------------


@distributed_test(np_=3)
def test_cache_interplay_across_threshold_change():
    import horovod_tpu as hvd

    hvd.init()
    r, n = hvd.rank(), hvd.size()

    def step(s):
        hs = [hvd.allreduce_async(np.full(64, float(r + i + s), np.float32),
                                  average=False, name=f"cw.{i}")
              for i in range(16)]
        ticks = []
        for i, h in enumerate(hs):
            out = h.wait()
            want = sum(float(j + i + s) for j in range(n))
            assert np.allclose(out, want), (r, s, i)
            ticks.append(h.completion_tick)
        return ticks

    for s in range(3):  # warm: the cache holds every name
        step(s)
    warm = hvd.metrics_snapshot()["cache"]["engine"]
    # Rank 0 injects a threshold below a single tensor (64 floats =
    # 256 B): every replayed bucket must split to singletons, identically
    # on every rank, the moment the broadcast lands.
    if r == 0:
        hvd.autotune_set(fusion_threshold=64)
    for s in range(3, 6):
        ticks = step(s)
        rows = hvd.allgather(np.asarray([ticks], np.int64),
                             name=f"cw.ticks.{s}")
        for i in range(n):
            assert (rows[i] == rows[0]).all(), (r, s, rows)
    # And back up: replays re-fuse again.
    if r == 0:
        hvd.autotune_set(fusion_threshold=64 * 1024 * 1024)
    for s in range(6, 9):
        step(s)
    c = hvd.metrics_snapshot()["cache"]["engine"]
    hits = c["hits"] - warm["hits"]
    misses = c["misses"] - warm["misses"]
    # The threshold changes must not have invalidated the cache: the six
    # post-warm steps are pure hits (16 names x 6 steps).  The only
    # misses are this test's own tick-verification allgathers (three
    # fresh names).
    assert hits == 96, (r, warm, c)
    assert misses == 3, (r, warm, c)
    # Every rank observed both applications, identically.
    rep = hvd.autotune_report()
    applied = ";".join(
        f"{a['tick']}|{a['fusion_threshold']}" for a in rep["applied"])
    assert "|64" in applied and f"|{64 * 1024 * 1024}" in applied, \
        (r, applied)
    for peer in _allgather_str(hvd, applied, "cw.applied"):
        assert peer == applied, (r, applied, peer)
    hvd.shutdown()


# ---------------------------------------------------------------------------
# Pinning, manual injection, and the tuner-off default.
# ---------------------------------------------------------------------------


@distributed_test(np_=1)
def test_fix_pins_cycle_while_fusion_tunes():
    os.environ["HVD_TPU_AUTOTUNE"] = "1"
    os.environ["HVD_TPU_AUTOTUNE_WINDOW"] = "4"
    os.environ["HVD_TPU_AUTOTUNE_WARMUP"] = "1"
    os.environ["HVD_TPU_AUTOTUNE_FIX"] = "cycle_time_ms=5"
    import horovod_tpu as hvd
    from horovod_tpu.common.autotune import FUSION_GRID

    hvd.init()
    for s in range(600):
        for k in range(4):
            hvd.allreduce(np.ones(128, np.float32), average=False,
                          name=f"p{k}")
        if hvd.autotune_report()["frozen"]:
            break
    rep = hvd.autotune_report()
    assert rep["frozen"], rep["windows"]
    # The pinned knob never moved, through every applied broadcast; the
    # free knob walked the documented grid.
    for a in rep["applied"]:
        assert a["cycle_time_ms"] == 5.0, a
        assert a["fusion_threshold"] in FUSION_GRID, a
    assert rep["cycle_time_ms"] == 5.0
    hvd.shutdown()


@distributed_test(np_=1)
def test_fix_both_pinned_freezes_immediately():
    os.environ["HVD_TPU_AUTOTUNE"] = "1"
    os.environ["HVD_TPU_AUTOTUNE_WINDOW"] = "4"
    # Warmup 0 also covers the anchor-broadcast-on-first-window path.
    os.environ["HVD_TPU_AUTOTUNE_WARMUP"] = "0"
    os.environ["HVD_TPU_AUTOTUNE_FIX"] = \
        "fusion_threshold=123456,cycle_time_ms=2"
    import horovod_tpu as hvd

    hvd.init()
    for s in range(200):
        hvd.allreduce(np.ones(8, np.float32), average=False, name="bp")
        if hvd.autotune_report()["frozen"]:
            break
    rep = hvd.autotune_report()
    assert rep["frozen"]
    # Nothing to search: exactly one broadcast, carrying the pins.
    assert rep["fusion_threshold"] == 123456, rep
    assert rep["cycle_time_ms"] == 2.0, rep
    assert len(rep["applied"]) == 1, rep["applied"]
    assert rep["applied"][0]["frozen"], rep["applied"]
    hvd.shutdown()


@distributed_test(np_=1)
def test_default_off_and_manual_set():
    os.environ.pop("HVD_TPU_AUTOTUNE", None)
    import horovod_tpu as hvd
    from horovod_tpu.common.config import DEFAULT_FUSION_THRESHOLD

    hvd.init()
    for k in range(3):
        hvd.allreduce(np.ones(16, np.float32), average=False, name=f"d{k}")
    rep = hvd.autotune_report()
    assert not rep["enabled"] and not rep["frozen"], rep
    assert rep["applied"] == [] and rep["history"] == [], rep
    assert rep["fusion_threshold"] == DEFAULT_FUSION_THRESHOLD, rep
    snap = hvd.metrics_snapshot()["autotune"]
    assert snap["enabled"] is False, snap
    # Manual injection works with the tuner off (the pluggable-policy
    # seam) and an unset knob keeps the applied value.
    hvd.autotune_set(cycle_time_ms=2.0)
    for s in range(50):
        hvd.allreduce(np.ones(16, np.float32), average=False, name="d0")
        rep = hvd.autotune_report()
        if rep["applied"]:
            break
    assert rep["applied"], "injection never applied"
    assert rep["applied"][-1]["cycle_time_ms"] == 2.0, rep["applied"]
    assert rep["applied"][-1]["fusion_threshold"] == \
        DEFAULT_FUSION_THRESHOLD, rep["applied"]
    assert rep["cycle_time_ms"] == 2.0, rep
    # A manual injection is not a converged search.
    assert not rep["frozen"] and not rep["applied"][-1]["frozen"], rep
    with pytest.raises(ValueError):
        hvd.autotune_set()  # no knob given
    with pytest.raises(ValueError):
        hvd.autotune_set(fusion_threshold=-5)
    hvd.shutdown()


@distributed_test(np_=2)
def test_autotune_set_is_rank0_only():
    import horovod_tpu as hvd

    hvd.init()
    if hvd.rank() == 0:
        hvd.autotune_set(cycle_time_ms=5.0)
    else:
        with pytest.raises(ValueError, match="rank 0"):
            hvd.autotune_set(cycle_time_ms=5.0)
    # Keep the job collectively aligned before shutdown.
    hvd.allreduce(np.ones(4, np.float32), average=False, name="sync")
    hvd.shutdown()


# ---------------------------------------------------------------------------
# Units: env-spec parsing, report shape, metrics surface.
# ---------------------------------------------------------------------------


def test_parse_fix():
    from horovod_tpu.common.autotune import parse_fix

    assert parse_fix("") == (-1, -1.0, -1, -1)
    assert parse_fix("fusion_threshold=1024") == (1024, -1.0, -1, -1)
    assert parse_fix("cycle_time_ms=2.5") == (-1, 2.5, -1, -1)
    assert parse_fix("fusion_threshold=8192, cycle_time_ms=5") == \
        (8192, 5.0, -1, -1)
    # The wire-compression axis (docs/performance.md#wire-compression)
    # pins by mode name; "off" pins it disabled rather than tuning it.
    assert parse_fix("compression=bf16") == (-1, -1.0, 1, -1)
    assert parse_fix("compression=fp8") == (-1, -1.0, 2, -1)
    assert parse_fix("compression=off, cycle_time_ms=5") == (-1, 5.0, 0, -1)
    # The cross-algo axis (docs/performance.md#two-level-topology) pins
    # in bytes; 0 pins "ring always".
    assert parse_fix("cross_algo_threshold=65536") == (-1, -1.0, -1, 65536)
    assert parse_fix("cross_algo_threshold=0") == (-1, -1.0, -1, 0)
    with pytest.raises(ValueError, match="bad clause"):
        parse_fix("warmup=3")
    with pytest.raises(ValueError, match="bad value"):
        parse_fix("cycle_time_ms=fast")
    with pytest.raises(ValueError, match="bad value"):
        parse_fix("compression=int4")
    with pytest.raises(ValueError, match="negative"):
        parse_fix("fusion_threshold=-1")
    with pytest.raises(ValueError, match="negative"):
        parse_fix("cross_algo_threshold=-1")


def test_snapshot_has_ungated_autotune_section():
    from horovod_tpu.common import metrics
    from horovod_tpu.common.autotune import empty_report

    reg = metrics.MetricsRegistry()  # never enabled
    snap = reg.snapshot()
    assert snap["autotune"] == empty_report()
    report = dict(empty_report(), enabled=True, windows=2,
                  fusion_threshold=4096, cycle_time_ms=2.5,
                  history=[{"window": 1, "fusion_threshold": 4096,
                            "cycle_time_ms": 2.5, "score": 10.0}])
    reg.set_autotune(report)
    snap = reg.snapshot()
    assert snap["autotune"]["windows"] == 2
    assert snap["autotune"]["history"][0]["score"] == 10.0
    # reset() clears the mirror back to the empty shape (the next real
    # snapshot re-reads the engine).
    reg.reset()
    assert reg.snapshot()["autotune"] == empty_report()


def test_prometheus_autotune_families():
    from horovod_tpu.common import metrics
    from horovod_tpu.common.autotune import empty_report

    reg = metrics.MetricsRegistry()
    reg.set_autotune(dict(empty_report(), enabled=True, frozen=True,
                          windows=7, fusion_threshold=1 << 20,
                          cycle_time_ms=2.5, best_score=42.0))
    text = metrics.prometheus_text(reg.snapshot())
    assert "hvd_tpu_autotune_enabled 1" in text
    assert "hvd_tpu_autotune_frozen 1" in text
    assert "hvd_tpu_autotune_windows_total 7" in text
    assert f"hvd_tpu_autotune_fusion_threshold_bytes {1 << 20}" in text
    assert "hvd_tpu_autotune_cycle_time_seconds 0.0025" in text
    assert "hvd_tpu_autotune_best_score 42.0" in text


def test_fusion_grid_mirror_is_log_spaced():
    from horovod_tpu.common.autotune import CYCLE_GRID_MS, FUSION_GRID

    assert list(FUSION_GRID) == sorted(FUSION_GRID)
    assert list(CYCLE_GRID_MS) == sorted(CYCLE_GRID_MS)
    assert FUSION_GRID[0] == 64 * 1024
    assert FUSION_GRID[-1] == 256 * 1024 * 1024
    assert 64 * 1024 * 1024 in FUSION_GRID  # the engine default
    assert 5.0 in CYCLE_GRID_MS             # the engine default
