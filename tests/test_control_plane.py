"""Control-plane scaling tests (docs/performance.md#control-plane-scaling).

PR-13's tentpole: the rank-0 coordinator star becomes a two-level tree
(each host's local-rank-0 aggregates its node's announces into one frame
per tick and relays broadcasts back down), and the PR-4 cache-bit steady
state goes fully decentralized — once a negotiation cycle's hit pattern
repeats HVD_TPU_STEADY_THRESHOLD times, ranks self-clock on an epoch
counter and replay the cached responses with ZERO control-plane frames
per cycle, falling back to full negotiation on any miss.  Covered here:

* collective correctness with the tree enabled (multi-node layout on one
  machine, the test_topology simulation recipe) and the ungated
  metrics_snapshot()["control"] section's tree shape;
* fault typing through the tree: a leaf crash surfaces RanksDownError
  naming the TRUE rank (forwarded by its sub-coordinator), a Python-side
  hang still trips CollectiveTimeoutError with the diagnosis naming the
  hung rank behind the aggregation;
* steady state: entry after the threshold, ZERO frames per replay cycle
  (asserted via the control section's frame counters), correct results
  while self-clocked, miss -> clean fallback to negotiation, and a crash
  mid-steady-state still aborting typed;
* the in-process simulated-scale harness (hvd_tpu_simscale_run): steady
  cycles flat in ranks while the star grows, zero steady frames;
* the registry/Prometheus/metrics_dump plumbing for the new section.
"""

from __future__ import annotations

import ctypes
import json
import os
import random
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from distributed import distributed_test  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**overrides):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.setdefault("HVD_TPU_KILL_GRACE_SEC", "3")
    env.update({k: str(v) for k, v in overrides.items()})
    for var in ("HVD_TPU_RANK", "HVD_TPU_SIZE", "HVD_TPU_COORD",
                "HVD_TPU_DATA", "HVD_TPU_FAULT_SPEC"):
        env.setdefault(var, "")
        if not env[var]:
            env.pop(var, None)
    return env


def _tree_env(local_size=2):
    """Re-shape this rank's env into `local_size`-sized nodes (the
    test_topology recipe) so the control tree builds on one machine."""
    rank = int(os.environ["HVD_TPU_RANK"])
    os.environ["HVD_TPU_LOCAL_SIZE"] = str(local_size)
    os.environ["HVD_TPU_LOCAL_RANK"] = str(rank % local_size)


# The child code all tree fault tests share: a multi-node layout env
# reshape BEFORE hvd.init, as a string prefix for run_command children.
_TREE_PRELUDE = (
    "import os\n"
    "rank = int(os.environ['HVD_TPU_RANK'])\n"
    "os.environ['HVD_TPU_LOCAL_SIZE'] = '2'\n"
    "os.environ['HVD_TPU_LOCAL_RANK'] = str(rank % 2)\n"
    "import numpy as np, horovod_tpu as hvd\n"
)


# ---------------------------------------------------------------------------
# Tree shape + correctness.
# ---------------------------------------------------------------------------


@distributed_test(np_=4)
def test_tree_collectives_and_control_section():
    """A 4-rank, 2-node layout builds the two-level tree; allreduce /
    allgather / broadcast stay correct through it (fresh AND cache-hit
    negotiations), and metrics_snapshot()["control"] reports the tree
    shape per role: rank 0 reads its node's worker plus the other node's
    sub-coordinator, the sub-coordinator reads its own workers, leaves
    read nobody."""
    _tree_env(local_size=2)
    import horovod_tpu as hvd

    hvd.init()
    r, n = hvd.rank(), hvd.size()
    for step in range(4):  # repeats ride the cache-bit aggregate path
        out = hvd.allreduce(np.arange(64, dtype=np.float32) + r,
                            average=False, name="tree.sum")
        want = np.arange(64, dtype=np.float32) * n + sum(range(n))
        assert np.array_equal(out, want), (r, step)
        avg = hvd.allreduce(np.full(8, float(r), np.float32),
                            average=True, name="tree.avg")
        assert np.allclose(avg, sum(range(n)) / n), (r, step)
    rows = hvd.allgather(np.full((r + 1, 3), r, np.int32), name="tree.ag")
    assert rows.shape == (sum(range(n + 1)), 3), rows.shape
    src = (np.arange(5, dtype=np.int64) * 2 if r == 2
           else np.zeros(5, dtype=np.int64))
    b = hvd.broadcast(src, root_rank=2, name="tree.bc")
    assert np.array_equal(b, np.arange(5, dtype=np.int64) * 2), (r, b)

    ctrl = hvd.metrics_snapshot()["control"]
    assert ctrl["tree"] and ctrl["depth"] == 2, ctrl
    assert ctrl["hosts"] == 2, ctrl
    want_children = {0: 2, 1: 0, 2: 1, 3: 0}[r]
    assert ctrl["children"] == want_children, (r, ctrl)
    assert ctrl["frames"]["sent"] > 0, ctrl
    hvd.shutdown()


@distributed_test(np_=4)
def test_single_host_layout_keeps_star():
    """The hvdrun single-host layout (local_size == size) keeps the
    degenerate one-level star: no sub-coordinators, depth 1 — the
    acceptance criterion that the tree must not tax single-host jobs."""
    import horovod_tpu as hvd

    hvd.init()
    out = hvd.allreduce(np.ones(8, np.float32), average=False,
                        name="star.sum")
    assert np.array_equal(out, np.full(8, float(hvd.size()), np.float32))
    ctrl = hvd.metrics_snapshot()["control"]
    assert not ctrl["tree"] and ctrl["depth"] == 1, ctrl
    assert ctrl["hosts"] == 1, ctrl
    hvd.shutdown()


# ---------------------------------------------------------------------------
# Fault typing through the tree.
# ---------------------------------------------------------------------------


def test_tree_leaf_crash_names_true_rank():
    """rank 3 (a leaf under sub-coordinator 2) crashing surfaces
    RanksDownError on every survivor NAMING RANK 3 — its death is
    observed at the sub-coordinator and forwarded in the aggregate's
    dead_ranks, not blamed on the sub."""
    from horovod_tpu.common.faults import CRASH_EXIT_CODE
    from horovod_tpu.runner import run_command

    code = _TREE_PRELUDE + (
        "from horovod_tpu.common import RanksDownError\n"
        "hvd.init()\n"
        "try:\n"
        "    for s in range(12):\n"
        "        hvd.allreduce(np.ones(8, np.float32), average=False,\n"
        "                      name='tc.x')\n"
        "    raise SystemExit(9)\n"
        "except RanksDownError as e:\n"
        "    assert 3 in e.ranks, (e.ranks, str(e))\n"
        "    raise SystemExit(0)\n"
    )
    results = run_command(
        [sys.executable, "-c", code], 4,
        env=_env(HVD_TPU_FAULT_SPEC="rank=3:crash@op=5",
                 HVD_TPU_COLLECTIVE_TIMEOUT_SEC="20"),
        timeout=90.0, capture=True)
    by_rank = {r.rank: r for r in results}
    assert by_rank[3].returncode == CRASH_EXIT_CODE, by_rank[3]
    for r in (0, 1, 2):
        assert by_rank[r].returncode == 0, \
            (r, by_rank[r].returncode, by_rank[r].stderr[-800:])


@pytest.mark.slow
def test_tree_hang_diagnosis_names_hung_rank():
    """A Python-level hang on rank 3 (engine thread alive, frames keep
    flowing through the aggregates) still trips the collective-timeout
    sweep, and the cross-rank diagnosis names rank 3 — the per-rank
    announce bookkeeping survives the aggregation.  Slow tier: the
    grace-kill of the wedged rank costs ~18s of wall time (the tier-1
    budget keeps the star-path hang coverage in test_faults)."""
    from horovod_tpu.runner import run_command

    code = _TREE_PRELUDE + (
        "import os\n"
        "from horovod_tpu.common import CollectiveTimeoutError\n"
        "hvd.init()\n"
        "try:\n"
        "    for s in range(8):\n"
        "        hvd.allreduce(np.ones(8, np.float32), average=False,\n"
        "                      name='th.x')\n"
        "    os._exit(9)\n"
        "except CollectiveTimeoutError as e:\n"
        "    assert 'th.x' in str(e), str(e)\n"
        "    assert 'rank 3' in str(e), str(e)  # diagnosis names it\n"
        "    os._exit(7)  # nonzero: arms the launcher's grace-kill of\n"
        "                 # the wedged rank (the test_faults idiom)\n"
    )
    results = run_command(
        [sys.executable, "-c", code], 4,
        env=_env(HVD_TPU_FAULT_SPEC="rank=3:hang@op=3",
                 HVD_TPU_COLLECTIVE_TIMEOUT_SEC="2"),
        timeout=60.0, capture=True)
    by_rank = {r.rank: r for r in results}
    for r in (0, 1, 2):
        assert by_rank[r].returncode == 7, \
            (r, by_rank[r].returncode, by_rank[r].stderr[-800:])
    assert by_rank[3].returncode == -9  # grace-killed wedged rank


@distributed_test(np_=4)
def test_tree_straggler_attribution_two_hosts():
    """PR-3 skew satellite under the tree: with a deterministic delay on
    rank 3 (a leaf behind a sub-coordinator), rank 0's last-to-announce
    verdicts still name RANK 3, not sub-coordinator 2 — the aggregate
    frames forward per-rank announce timestamps."""
    _tree_env(local_size=2)
    import time

    import horovod_tpu as hvd

    hvd.init()
    for i in range(6):
        if hvd.rank() == 3 and 1 <= i <= 4:
            time.sleep(0.2)
        hvd.allreduce(np.ones(16, np.float32), name=f"skew.{i}")
    if hvd.rank() == 0:
        snap = hvd.metrics_snapshot()
        last = snap["skew"]["last_to_announce"]
        assert last, snap["skew"]
        assert max(last, key=last.get) == "3", last
        assert snap["histograms"]["announce_skew_sec"]["count"] > 0
    hvd.shutdown()


# ---------------------------------------------------------------------------
# Decentralized steady state.
# ---------------------------------------------------------------------------


@distributed_test(np_=3)
def test_steady_state_zero_frames_and_fallback():
    """The tentpole's steady-state contract end to end: after the
    threshold the job enters steady (control section reports it), replay
    cycles move ZERO control frames while results stay correct, and a
    new tensor (a pattern miss) falls back to full negotiation cleanly,
    counting an exit."""
    os.environ["HVD_TPU_STEADY_THRESHOLD"] = "4"
    import horovod_tpu as hvd

    n = None
    hvd.init()
    n = hvd.size()

    def step(tag, s):
        for k in range(3):
            out = hvd.allreduce(np.full(8, float(k + s), np.float32),
                                average=False, name=f"sd.{k}")
            assert np.array_equal(
                out, np.full(8, float((k + s) * n), np.float32)), (tag, s, k)

    for s in range(12):  # warm + detect + enter
        step("warm", s)
    snap = hvd.metrics_snapshot()["control"]
    assert snap["steady"]["entries"] >= 1, snap
    assert snap["steady"]["active"], snap
    frames_before = snap["frames"]["sent"]
    cycles_before = snap["steady"]["cycles"]
    for s in range(10):  # pure self-clocked replay
        step("steady", s)
    snap2 = hvd.metrics_snapshot()["control"]
    assert snap2["frames"]["sent"] == frames_before, (snap, snap2)
    assert snap2["steady"]["cycles"] >= cycles_before + 10, (snap, snap2)
    # Miss: a brand-new tensor exits steady and negotiates normally.
    out = hvd.allreduce(np.ones(4, np.float32), average=False,
                        name="sd.fresh")
    assert np.array_equal(out, np.full(4, float(n), np.float32))
    snap3 = hvd.metrics_snapshot()["control"]
    assert snap3["steady"]["exits"] >= 1, snap3
    assert snap3["frames"]["sent"] > frames_before, snap3
    # And the old loop still works (and may re-enter steady later).
    for s in range(3):
        step("post", s)
    hvd.shutdown()


def test_steady_crash_aborts_typed():
    """ISSUE acceptance: a crash MID-STEADY-STATE (the coordinator sees
    zero frames from anyone) still aborts typed within the timeout —
    socket EOF is the signal that survives a dark control plane."""
    from horovod_tpu.common.faults import CRASH_EXIT_CODE
    from horovod_tpu.runner import run_command

    code = (
        "import numpy as np, horovod_tpu as hvd\n"
        "from horovod_tpu.common import RanksDownError\n"
        "hvd.init()\n"
        "entered = False\n"
        "try:\n"
        "    for s in range(40):\n"
        "        hvd.allreduce(np.ones(8, np.float32), average=False,\n"
        "                      name='sc.x')\n"
        "        entered = entered or \\\n"
        "            hvd.metrics_snapshot()['control']['steady']['active']\n"
        "    raise SystemExit(9)\n"
        "except RanksDownError as e:\n"
        "    assert 1 in e.ranks, (e.ranks, str(e))\n"
        "    assert entered, 'crash landed before steady state armed'\n"
        "    raise SystemExit(0)\n"
    )
    results = run_command(
        [sys.executable, "-c", code], 3,
        env=_env(HVD_TPU_FAULT_SPEC="rank=1:crash@op=25",
                 HVD_TPU_STEADY_THRESHOLD="4",
                 HVD_TPU_COLLECTIVE_TIMEOUT_SEC="20"),
        timeout=90.0, capture=True)
    by_rank = {r.rank: r for r in results}
    assert by_rank[1].returncode == CRASH_EXIT_CODE, by_rank[1]
    for r in (0, 2):
        assert by_rank[r].returncode == 0, \
            (r, by_rank[r].returncode, by_rank[r].stderr[-800:])


# Elastic x steady: the revocation protocol (engine.cc
# MaybeRevokeSteadyForReshape, model-checked by tools/hvdmodel's
# quick-elastic / quick-revoke-only configs).  One re-enterable training
# script with a FIXED tensor name so every negotiation cycle is
# identical and the job actually arms steady state mid-run.
_STEADY_ELASTIC_TRAIN = """\
import os, sys, time
import numpy as np
import horovod_tpu as hvd

TOTAL = int(sys.argv[1])
PAUSE = float(os.environ.get("TEST_STEP_PAUSE") or 0)
hvd.init()
state = hvd.ElasticState(weights=np.zeros(8, np.float32), step=0)
saw_steady_epoch0 = False

def train(state):
    global saw_steady_epoch0
    while state.step < TOTAL:
        g = np.ones(8, np.float32)
        state.weights = state.weights + hvd.allreduce(
            g, average=True, name="se.g")
        state.step += 1
        snap = hvd.metrics_snapshot()
        if (snap["membership"]["epoch"] == 0
                and snap["control"]["steady"]["active"]):
            saw_steady_epoch0 = True
        if PAUSE:
            time.sleep(PAUSE)
    return state.weights

w = hvd.run_elastic(train, state)
assert np.allclose(w, float(TOTAL)), (hvd.rank(), w)
snap = hvd.metrics_snapshot()
c, m = snap["control"]["steady"], snap["membership"]
print("STEADYX", hvd.rank(), hvd.size(), m["epoch"], c["entries"],
      c["exits"], int(saw_steady_epoch0), int(w[0]), flush=True)
"""


def _steadyx(results):
    """[(rank, size, epoch, entries, exits, saw_steady_epoch0, w0)] from
    every clean rank's STEADYX line."""
    out = []
    for r in results:
        if r.returncode != 0:
            continue
        for line in r.stdout.splitlines():
            if line.startswith("STEADYX "):
                out.append(tuple(int(t) for t in line.split()[1:]))
    return out


def test_steady_elastic_crash_revokes_and_renegotiates(tmp_path):
    """A crash MID-STEADY on an elastic 4-rank job: rank 0 revokes the
    armed pattern (bare broadcast, no waiting on the dark control
    plane), every survivor exits steady and falls back to negotiation,
    the reshape admits the 3-survivor membership, and steady re-arms
    from tick one under the new membership — the job completes instead
    of aborting, which is the whole point of steady x elastic."""
    from horovod_tpu.common.faults import CRASH_EXIT_CODE
    from horovod_tpu.runner import membership_succeeded, run_membership

    script = tmp_path / "train.py"
    script.write_text(_STEADY_ELASTIC_TRAIN)
    results = run_membership(
        [sys.executable, str(script), "48"], 4, min_np=2, max_np=4,
        max_rejoins=0,
        env=_env(HVD_TPU_FAULT_SPEC="rank=2:crash@op=16",
                 HVD_TPU_STEADY_THRESHOLD="3",
                 HVD_TPU_COLLECTIVE_TIMEOUT_SEC="20"),
        timeout=120.0, capture=True, report=lambda msg: None)
    by_slot = {r.rank: r for r in results}
    assert by_slot[2].returncode == CRASH_EXIT_CODE, by_slot[2]
    for slot in (0, 1, 3):
        assert by_slot[slot].returncode == 0, \
            (slot, by_slot[slot].returncode, by_slot[slot].stderr[-800:])
    assert membership_succeeded(results, 2)
    members = _steadyx(results)
    assert len(members) == 3, (members, results)
    for rank, size, epoch, entries, exits, saw0, w0 in members:
        assert size == 3 and epoch >= 1, members
        assert w0 == 48, members
        # Pattern armed before the crash (epoch 0) on every survivor...
        assert saw0 == 1, members
        # ...then revoked (an exit) and re-negotiated from scratch under
        # the new membership (a second entry: the history reset means it
        # took `threshold` fresh identical cycles to re-arm).
        assert exits >= 1, members
        assert entries >= 2, members


@pytest.mark.slow
def test_steady_elastic_standby_grow_mid_steady(tmp_path):
    """Standby admission MID-STEADY: after the shrink the lone survivor
    re-arms its pattern; the standby's registration is a join pending
    against a dark control plane, so rank 0 revokes, negotiates the grow
    barrier, and both members finish with identical weights.  Exercises
    the join arm of MaybeRevokeSteadyForReshape (the crash test above
    exercises the death arm; the join arm's model-level twin runs every
    tier-1 pass inside `python -m tools.hvdmodel --quick`)."""
    from horovod_tpu.runner import membership_succeeded, run_membership

    script = tmp_path / "train.py"
    script.write_text(_STEADY_ELASTIC_TRAIN)
    results = run_membership(
        [sys.executable, str(script), "60"], 2, min_np=1, max_np=2,
        rejoin_delay=0.3,
        env=_env(HVD_TPU_FAULT_SPEC="rank=1:crash@op=10",
                 HVD_TPU_STEADY_THRESHOLD="2",
                 HVD_TPU_COLLECTIVE_TIMEOUT_SEC="20",
                 TEST_STEP_PAUSE="0.05"),
        timeout=120.0, capture=True, report=lambda msg: None)
    assert membership_succeeded(results, 1), \
        [(r.rank, r.returncode, r.stderr[-400:]) for r in results]
    by_slot = {r.rank: r for r in results}
    assert 2 in by_slot and by_slot[2].returncode == 0, \
        by_slot.get(2) and by_slot[2].stderr[-800:]
    members = _steadyx(results)
    assert len(members) == 2, (members, results)
    survivor = next(m for m in members if m[0] == 0)
    rank, size, epoch, entries, exits, saw0, w0 = survivor
    assert size == 2, members
    assert epoch == 2, members          # shrink, then grow
    # The survivor armed steady at least once and every arm that a
    # reshape interrupted was revoked cleanly (exits pair with entries
    # except a final still-active pattern).
    assert entries >= 1 and exits >= 1, members
    for m in members:
        assert m[6] == 60, members      # both trained to the end


@distributed_test(np_=4)
def test_steady_under_tree_with_flight_events():
    """Tree + steady compose: a 2-node layout enters steady, replays
    correctly, and the flight recorder holds the FL_STEADY enter record
    that explains a silent control plane to postmortems."""
    _tree_env(local_size=2)
    os.environ["HVD_TPU_STEADY_THRESHOLD"] = "4"
    import horovod_tpu as hvd

    hvd.init()
    n = hvd.size()
    for s in range(14):
        out = hvd.allreduce(np.full(8, 1.0, np.float32), average=False,
                            name="ts.x")
        assert np.array_equal(out, np.full(8, float(n), np.float32)), s
    ctrl = hvd.metrics_snapshot()["control"]
    assert ctrl["tree"] and ctrl["steady"]["entries"] >= 1, ctrl
    assert ctrl["steady"]["cycles"] > 0, ctrl
    from horovod_tpu.common import _load_lib, postmortem

    raw = _load_lib().hvd_tpu_flight_dump().decode()
    kinds = {e["event"] for e in postmortem.parse_engine_ring(raw)}
    assert "steady" in kinds, sorted(kinds)
    hvd.shutdown()


# ---------------------------------------------------------------------------
# Simulated-scale harness (the in-process C++ fleet).
# ---------------------------------------------------------------------------


def _simscale(size, local, ops, warm, steady, threshold, tree, timeout=60.0):
    from horovod_tpu.common import _load_lib

    lib = _load_lib()
    buf = ctypes.create_string_buffer(2048)
    for attempt in range(3):
        port = random.randint(30000, 58000)
        rc = lib.hvd_tpu_simscale_run(size, local, ops, warm, steady,
                                      threshold, int(tree), port, timeout,
                                      buf, 2048)
        rep = json.loads(buf.value.decode() or "{}")
        if rc == 0 and rep.get("ok"):
            return rep
    raise AssertionError(f"simscale failed after retries: {rep}")


# The simulated engines read their switches from the real environment at
# Init, as launched ranks do; os.environ writes reach the in-process C++
# getenv through putenv.
_SIMSCALE_SWITCHES = {
    "on": {"HVD_TPU_HEARTBEAT_MS": "20"},
    "heartbeat_off": {"HVD_TPU_HEARTBEAT_MS": "0"},
    "introspection_off": {"HVD_TPU_HEARTBEAT_MS": "20",
                          "HVD_TPU_LINK_STATS": "0",
                          "HVD_TPU_ANOMALY_SIGMA": "0"},
}


@pytest.mark.parametrize("switches", sorted(_SIMSCALE_SWITCHES))
def test_simscale_smoke_tree_steady(switches, monkeypatch):
    """8 in-process ranks, 2 per simulated host: the tree builds (rank 0
    reads 4 children: 1 node-0 worker + 3 sub-coordinators), steady
    arms, the steady window moves ZERO control frames, and rank 0's init
    clock sync probes its direct children only (O(hosts), never the
    star's every-rank probe).  Each liveness/introspection switch turned
    off really is off: no heartbeat frame is sent with the detector
    disabled, and link accounting (process-cumulative) does not grow
    over a fleet run with HVD_TPU_LINK_STATS=0."""
    for key, value in _SIMSCALE_SWITCHES[switches].items():
        monkeypatch.setenv(key, value)
    before = None
    if switches == "introspection_off":
        # A short fleet under the same switches reads the counter as it
        # stands; the measured run must leave it there.
        before = _simscale(4, 2, ops=1, warm=2, steady=1, threshold=0,
                           tree=True)["link_sends"]
    rep = _simscale(8, 2, ops=2, warm=25, steady=30, threshold=4, tree=True)
    assert rep["steady_entered"] == 1, rep
    assert rep["steady_frames_delta"] == 0, rep
    assert rep["coord_children"] == 4, rep
    assert rep["steady_cycles"] > 0, rep
    assert 0 < rep["clock_fanin"] <= 4 + 2, rep  # hosts + local ranks
    if switches == "heartbeat_off":
        assert rep["hb_frames_sent"] == 0, rep
    else:
        assert rep["hb_frames_sent"] > 0, rep
    if switches == "introspection_off":
        assert rep["link_sends"] == before, (before, rep)
    else:
        assert rep["link_sends"] > 0, rep


def test_simscale_star_baseline_negotiates_every_cycle():
    """The same fleet with the tree and steady disabled keeps the star:
    rank 0 reads every worker, probes every worker's clock, and every
    cycle moves frames."""
    rep = _simscale(8, 2, ops=2, warm=15, steady=8, threshold=0, tree=False)
    assert rep["steady_entered"] == 0, rep
    assert rep["coord_children"] == 7, rep
    assert rep["clock_fanin"] == 7, rep
    assert rep["steady_frames_delta"] > 0, rep


@pytest.mark.slow
def test_simscale_steady_flat_in_ranks():
    """Scale acceptance shape (a budget-friendly pair of sizes):
    steady-cycle p50 at 64 simulated ranks within 1.5x of 16 ranks, while
    the star's negotiated cycles grow several-fold over the same span."""
    small = _simscale(16, 4, ops=2, warm=30, steady=25, threshold=6,
                      tree=True, timeout=90.0)
    large = _simscale(64, 8, ops=2, warm=30, steady=25, threshold=6,
                      tree=True, timeout=120.0)
    assert small["steady_entered"] and large["steady_entered"], (small,
                                                                 large)
    assert large["steady_frames_delta"] == 0, large
    # Flat in ranks: 1.5x plus an additive allowance for the co-located
    # simulation's thread-wake quantum (hundreds of rank fleets share
    # this one machine; the real signal is µs-scale local replay, and
    # the star's per-cycle cost below is 10-100x this and GROWS).
    assert large["steady_p50_us"] <= \
        max(1.5 * small["steady_p50_us"],
            small["steady_p50_us"] + 500.0), (small, large)
    star_small = _simscale(16, 4, ops=2, warm=10, steady=15, threshold=0,
                           tree=False, timeout=90.0)
    star_large = _simscale(64, 8, ops=2, warm=10, steady=15, threshold=0,
                           tree=False, timeout=120.0)
    assert star_large["steady_p50_us"] > 2.0 * star_small["steady_p50_us"], \
        (star_small, star_large)
    assert large["steady_p50_us"] < star_large["steady_p50_us"] / 4.0, \
        (large, star_large)


# ---------------------------------------------------------------------------
# Registry / Prometheus / dump plumbing (in-process, no engine).
# ---------------------------------------------------------------------------


def test_control_section_registry_and_prometheus():
    from horovod_tpu.common import metrics

    reg = metrics.MetricsRegistry()
    snap = reg.snapshot()
    assert snap["control"] == {
        "tree": False, "depth": 1, "children": 0, "hosts": 1,
        "steady": {"active": False, "pattern_len": 0, "threshold": 0,
                   "entries": 0, "exits": 0, "replays": 0, "cycles": 0},
        "negotiated_ticks": 0, "frames": {"sent": 0, "received": 0}}
    reg.set_control({"tree": True, "depth": 2, "children": 3, "hosts": 4,
                     "steady": {"active": True, "pattern_len": 6,
                                "threshold": 32, "entries": 2, "exits": 1,
                                "replays": 600, "cycles": 100},
                     "negotiated_ticks": 40,
                     "frames": {"sent": 123, "received": 121}})
    snap = reg.snapshot()
    assert snap["control"]["steady"]["cycles"] == 100, snap["control"]
    text = metrics.prometheus_text(snap)
    assert "hvd_tpu_control_tree_depth 2" in text
    assert "hvd_tpu_control_children 3" in text
    assert "hvd_tpu_control_steady_active 1" in text
    assert "hvd_tpu_control_steady_cycles_total 100" in text
    assert ('hvd_tpu_control_steady_transitions_total{kind="entries"} 2'
            in text)
    assert 'hvd_tpu_control_frames_total{dir="sent"} 123' in text
    assert "hvd_tpu_control_negotiated_ticks_total 40" in text
    reg.reset()
    assert not reg.snapshot()["control"]["tree"]


def test_metrics_dump_renders_control_section(tmp_path):
    from horovod_tpu.common import metrics
    from tools import metrics_dump

    reg = metrics.MetricsRegistry()
    reg.set_control({"tree": True, "depth": 2, "children": 5, "hosts": 4,
                     "steady": {"active": True, "pattern_len": 6,
                                "threshold": 32, "entries": 1, "exits": 0,
                                "replays": 60, "cycles": 10},
                     "negotiated_ticks": 12,
                     "frames": {"sent": 48, "received": 47}})
    out = metrics_dump.render(reg.snapshot())
    assert "== control ==" in out, out
    assert "tree depth 2" in out and "fan-in 5" in out, out
    assert "steady ACTIVE" in out, out
    assert "10 steady / 12 negotiated" in out, out


def test_config_control_knobs(monkeypatch):
    from horovod_tpu.common.config import Config

    cfg = Config.from_env()
    assert cfg.coord_tree and cfg.steady_threshold == 32
    assert cfg.steady_max_period == 256
    monkeypatch.setenv("HVD_TPU_COORD_TREE", "0")
    monkeypatch.setenv("HVD_TPU_STEADY_THRESHOLD", "0")
    monkeypatch.setenv("HVD_TPU_STEADY_MAX_PERIOD", "64")
    cfg = Config.from_env()
    assert not cfg.coord_tree and cfg.steady_threshold == 0
    assert cfg.steady_max_period == 64
