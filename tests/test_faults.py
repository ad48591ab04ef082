"""Fault-tolerance tests: deterministic fault injection (HVD_TPU_FAULT_SPEC)
driving the coordinated-abort machinery (docs/fault-tolerance.md) — peer
EOF -> RanksDownError, stall -> CollectiveTimeoutError, the XLA plane's
bounded dispatch wait, and `hvdrun --max-restarts` checkpoint-resume — all
CPU-only, with tight per-test timeouts so the tier-1 budget holds.

The reference had NO coverage here (SURVEY.md 5.3): its coordinated
shutdown was never exercised, and a wedged rank hung jobs until an outer
timeout.  Every path below is reproducible on demand via the fault spec.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**overrides):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    # Fault tests deliberately wedge/kill ranks; a short kill grace keeps
    # the launcher's cleanup out of the tier-1 budget.
    env.setdefault("HVD_TPU_KILL_GRACE_SEC", "3")
    env.update({k: str(v) for k, v in overrides.items()})
    for var in ("HVD_TPU_RANK", "HVD_TPU_SIZE", "HVD_TPU_COORD",
                "HVD_TPU_DATA", "HVD_TPU_FAULT_SPEC",
                "HVD_TPU_RESTART_EPOCH", "HVD_TPU_NET_FAULT_SPEC",
                "HVD_TPU_HEARTBEAT_MS", "HVD_TPU_HEARTBEAT_MISS",
                "HVD_TPU_ANOMALY_SIGMA", "HVD_TPU_ANOMALY_INTERVAL_MS",
                "HVD_TPU_LINK_STATS", "HVD_TPU_MONITOR_PORT"):
        env.setdefault(var, "")
        if not env[var]:
            env.pop(var, None)
    return env


# ---------------------------------------------------------------------------
# Fault spec parsing (pure, in-process).
# ---------------------------------------------------------------------------


def test_fault_spec_parsing():
    from horovod_tpu.common import faults

    spec = "rank=1:crash@op=12; rank=2:hang@op=5, rank=1:delay=3.0@op=7@epoch=1"
    parsed = faults.parse_spec(spec)
    assert parsed == [
        faults.Fault(rank=1, action="crash", op=12),
        faults.Fault(rank=2, action="hang", op=5),
        faults.Fault(rank=1, action="delay", op=7, delay_sec=3.0, epoch=1),
    ]
    # Epoch gating: clauses without epoch= fire only on the first run.
    inj0 = faults.FaultInjector(parsed, rank=1, epoch=0)
    inj1 = faults.FaultInjector(parsed, rank=1, epoch=1)
    assert bool(inj0) and bool(inj1)
    assert 12 in inj0._by_op and 12 not in inj1._by_op
    assert 7 in inj1._by_op
    assert not faults.FaultInjector(parsed, rank=0, epoch=0)


@pytest.mark.parametrize("bad", [
    "rank=1:frobnicate@op=2",     # unknown action
    "rank=1:crash",               # missing op
    "node=1:crash@op=2",          # wrong key
    "rank=1:delay@op=2",          # delay without duration
    "rank=1:crash@op=2@when=now", # unknown term
])
def test_fault_spec_rejects_bad_clauses(bad):
    from horovod_tpu.common import faults

    with pytest.raises(ValueError, match="HVD_TPU_FAULT_SPEC"):
        faults.parse_spec(bad)


# ---------------------------------------------------------------------------
# Idempotency / pre-init guards (satellite).
# ---------------------------------------------------------------------------


def test_not_initialized_error_and_double_shutdown(single_process_hvd):
    hvd = single_process_hvd
    assert hvd.is_initialized()
    assert hvd.restart_epoch() == 0
    hvd.shutdown()
    assert not hvd.is_initialized()
    hvd.shutdown()  # double shutdown: no-op, no error
    from horovod_tpu.common import HorovodNotInitializedError

    with pytest.raises(HorovodNotInitializedError):
        hvd.rank()
    with pytest.raises(ValueError):  # the pre-existing contract still holds
        hvd.size()
    with pytest.raises(HorovodNotInitializedError):
        hvd.allreduce(np.ones(3, np.float32), name="preinit")
    hvd.init()  # reinit after shutdown works


# ---------------------------------------------------------------------------
# Peer EOF -> coordinated abort -> RanksDownError on every survivor.
# ---------------------------------------------------------------------------


def test_crash_fault_surfaces_ranks_down_error():
    """The ISSUE acceptance path: with rank=1:crash@op=<n> on a 4-rank CPU
    job, every survivor raises RanksDownError naming rank 1 (and recording
    the abort in the metrics registry) — fast, via control-socket EOF, not
    the stall timeout."""
    from horovod_tpu.runner import run_command

    code = (
        "import numpy as np, horovod_tpu as hvd\n"
        "from horovod_tpu.common import RanksDownError\n"
        "hvd.init()\n"
        "r = hvd.rank()\n"
        "try:\n"
        "    for i in range(6):\n"
        "        hvd.allreduce(np.ones(8, np.float32), name=f'step.{i}')\n"
        "    raise SystemExit(9)  # survivors must NOT complete\n"
        "except RanksDownError as e:\n"
        "    assert 1 in e.ranks, (e.ranks, str(e))\n"
        "    assert 'ranks down' in str(e) and '1' in str(e), str(e)\n"
        "    snap = hvd.metrics_snapshot()\n"
        "    assert snap['faults']['aborts'].get('ranks_down'), snap\n"
        "    raise SystemExit(0)\n"
    )
    results = run_command(
        [sys.executable, "-c", code], 4,
        env=_env(HVD_TPU_FAULT_SPEC="rank=1:crash@op=3",
                 HVD_TPU_COLLECTIVE_TIMEOUT_SEC="20"),
        timeout=90.0, capture=True)
    by_rank = {r.rank: r for r in results}
    from horovod_tpu.common.faults import CRASH_EXIT_CODE

    assert by_rank[1].returncode == CRASH_EXIT_CODE, by_rank[1]
    for r in (0, 2, 3):
        assert by_rank[r].returncode == 0, \
            (r, by_rank[r].returncode, by_rank[r].stderr[-800:])


# ---------------------------------------------------------------------------
# Stall past the hard deadline -> CollectiveTimeoutError (wedged, not dead).
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ~18s; the collective-timeout sweep + typed error stay
# tier-1 in test_pipeline.py::test_unmatched_send_times_out_naming_tensor
# _and_peer (same HVD_TPU_COLLECTIVE_TIMEOUT_SEC backstop, p2p plane)
def test_hang_fault_surfaces_collective_timeout_error():
    """A hung rank keeps its engine ticking (liveness looks healthy), so
    only the HVD_TPU_COLLECTIVE_TIMEOUT_SEC deadline can catch it: the
    survivor gets CollectiveTimeoutError naming the tensor and the missing
    rank, well inside the test timeout (no hang)."""
    import time

    from horovod_tpu.runner import run_command

    code = (
        "import numpy as np, os, time, horovod_tpu as hvd\n"
        "from horovod_tpu.common import CollectiveTimeoutError\n"
        "hvd.init()\n"
        "t0 = time.monotonic()\n"
        "try:\n"
        "    hvd.allreduce(np.ones(8, np.float32), name='wedge')\n"
        "    os._exit(9)\n"
        "except CollectiveTimeoutError as e:\n"
        "    assert 'wedge' in str(e), str(e)\n"
        "    assert 'missing ranks: 1' in str(e), str(e)\n"
        "    assert time.monotonic() - t0 < 15.0\n"
        "    snap = hvd.metrics_snapshot()\n"
        "    assert snap['faults']['aborts'].get('timeout'), snap\n"
        "    os._exit(7)  # nonzero: arms the launcher's grace-kill of the\n"
        "                 # wedged rank (rc 0 would wait out the timeout)\n"
    )
    t0 = time.monotonic()
    results = run_command(
        [sys.executable, "-c", code], 2,
        env=_env(HVD_TPU_FAULT_SPEC="rank=1:hang@op=0",
                 HVD_TPU_COLLECTIVE_TIMEOUT_SEC="2"),
        timeout=60.0, capture=True)
    assert time.monotonic() - t0 < 30.0  # detection + grace, not the timeout
    by_rank = {r.rank: r for r in results}
    assert by_rank[0].returncode == 7, \
        (by_rank[0].returncode, by_rank[0].stderr[-800:])
    assert by_rank[1].returncode == -9  # grace-killed wedged rank


@pytest.mark.slow  # ~17s; the freeze->RanksDownError contract stays
# tier-1 in test_nonelastic_freeze_detected_in_heartbeat_time (4 ranks,
# stricter: exact accusation set + O(heartbeat) detection bound)
def test_freeze_fault_surfaces_ranks_down_error():
    """A SIGSTOP'd process keeps its sockets open but silent — EOF never
    fires.  The data-plane heartbeat detector (docs/fault-tolerance.md
    #failure-detection) catches the silence in O(miss window); with the
    detector off, the coordinator's control-plane liveness deadline still
    does.  Either way the survivor gets RanksDownError naming the frozen
    rank."""
    import time

    from horovod_tpu.runner import run_command

    code = (
        "import numpy as np, os, horovod_tpu as hvd\n"
        "from horovod_tpu.common import RanksDownError\n"
        "hvd.init()\n"
        "try:\n"
        "    hvd.allreduce(np.ones(8, np.float32), name='iceberg')\n"
        "    os._exit(9)\n"
        "except RanksDownError as e:\n"
        "    assert 1 in e.ranks, (e.ranks, str(e))\n"
        "    assert ('no data-plane heartbeats' in str(e)\n"
        "            or 'no control-plane traffic' in str(e)), str(e)\n"
        "    os._exit(7)  # nonzero: arm the grace-kill of the frozen rank\n"
    )
    t0 = time.monotonic()
    results = run_command(
        [sys.executable, "-c", code], 2,
        env=_env(HVD_TPU_FAULT_SPEC="rank=1:freeze@op=0",
                 HVD_TPU_COLLECTIVE_TIMEOUT_SEC="2"),
        timeout=60.0, capture=True)
    assert time.monotonic() - t0 < 30.0
    by_rank = {r.rank: r for r in results}
    assert by_rank[0].returncode == 7, \
        (by_rank[0].returncode, by_rank[0].stderr[-800:])
    assert by_rank[1].returncode == -9  # SIGKILL works on stopped procs


def test_delay_fault_is_transparent():
    """delay=: the op completes correctly, just late — the knob for racing
    skew-sensitive paths without killing anything."""
    from horovod_tpu.runner import run_command

    code = (
        "import numpy as np, time, horovod_tpu as hvd\n"
        "hvd.init()\n"
        "t0 = time.monotonic()\n"
        "out = hvd.allreduce(np.ones(4, np.float32), average=False,\n"
        "                    name='slow')\n"
        "assert np.allclose(out, 2.0), out\n"
        "if hvd.rank() == 1:\n"
        "    assert time.monotonic() - t0 >= 0.5\n"
        "    snap = hvd.metrics_snapshot()\n"
        "    assert snap['faults']['injected'].get('delay') == 1, snap\n"
    )
    results = run_command(
        [sys.executable, "-c", code], 2,
        env=_env(HVD_TPU_FAULT_SPEC="rank=1:delay=0.5@op=0"),
        timeout=60.0, capture=True)
    assert all(r.returncode == 0 for r in results), \
        [(r.rank, r.returncode, r.stderr[-400:]) for r in results]


# ---------------------------------------------------------------------------
# XLA-plane parity: the dispatch wait is bounded too.
# ---------------------------------------------------------------------------


def test_xla_plane_wait_deadline(monkeypatch):
    """A plane op whose negotiation never completes (the cross-rank hang
    case) must fail its handle with CollectiveTimeoutError at the deadline
    instead of polling forever.  In-process: a fabricated 2-rank plane
    with a never-negotiated op — the multi-process plane path is exercised
    by test_xla_plane.py."""
    monkeypatch.setenv("HVD_TPU_COLLECTIVE_TIMEOUT_SEC", "1")
    from horovod_tpu import common
    from horovod_tpu.common import CollectiveTimeoutError
    from horovod_tpu.jax import eager_mesh

    common._load_lib()  # flush() reads ticks_done from the engine lib
    plane = eager_mesh.XlaDataPlane(
        mesh=None, spec_sharded=None, spec_replicated=None,
        rank=0, size=2, fusion_threshold=1 << 20)
    payload = np.ones(8, np.float32)
    handle = eager_mesh.XlaHandle(plane, "ar", "stuck", None, False, 2,
                                  payload.dtype, payload.shape)
    op = eager_mesh._PlaneOp("stuck", "ar", payload, 0, handle)
    plane._pending.append(op)  # neg_raw = -1: negotiation never completes
    import time

    t0 = time.monotonic()
    with pytest.raises(CollectiveTimeoutError, match="stuck"):
        handle.wait()
    assert time.monotonic() - t0 < 10.0
    assert not plane._pending  # withdrawn, not left to dispatch later
    snap = common.metrics_snapshot()
    assert snap["faults"]["aborts"].get("timeout"), snap["faults"]
    assert "stuck" in snap["stalls"]["tensors"], snap["stalls"]


# ---------------------------------------------------------------------------
# Job-level restart: hvdrun --max-restarts + checkpoint resume.
# ---------------------------------------------------------------------------

_RESTART_SCRIPT = """\
import os, sys
import numpy as np
import horovod_tpu as hvd
from horovod_tpu.jax.train import save_checkpoint, load_latest_checkpoint

ckpt_dir = sys.argv[1]
hvd.init()
r = hvd.rank()
step, state = load_latest_checkpoint(ckpt_dir)
w = np.asarray(state if state is not None else np.zeros(4), np.float32)
# Resume point agreed via rank 0 (checkpoints are written by rank 0 only).
w = hvd.broadcast(w, 0, name="resume.w")
step = int(hvd.broadcast(np.asarray(step, np.int32), 0, name="resume.step"))
TOTAL = 8
for s in range(step, TOTAL):
    g = hvd.allreduce(np.ones(4, np.float32), average=True, name=f"grad.{s}")
    w = w + g
    if r == 0:
        save_checkpoint(ckpt_dir, s + 1, w)
assert np.allclose(w, float(TOTAL)), (r, w)
if r == 0:
    with open(os.path.join(ckpt_dir, "done.txt"), "w") as f:
        f.write(f"epoch={hvd.restart_epoch()} start_step={step}\\n")
"""


@pytest.mark.slow  # ~8s; the relaunch loop stays tier-1 in
# test_transport.py::test_max_restarts_relaunch_rebuilds_shm and the
# checkpoint-restore path in test_elastic.py::test_shrink_to_one_smoke
def test_max_restarts_resumes_from_checkpoint(tmp_path):
    """The end-to-end restart contract: rank 1 crashes mid-run (epoch 0
    only — unepoched clauses are first-run-gated), hvdrun kills the
    survivors and relaunches with HVD_TPU_RESTART_EPOCH=1, and the job
    resumes from the latest checkpoint instead of step 0."""
    from horovod_tpu.runner import run_elastic

    script = tmp_path / "train.py"
    script.write_text(_RESTART_SCRIPT)
    ckpt = tmp_path / "ckpt"
    # Ops on rank 1: 2 broadcasts + grads -> op 6 = grad.4 (mid-training).
    results, restarts = run_elastic(
        [sys.executable, str(script), str(ckpt)], 4, max_restarts=1,
        env=_env(HVD_TPU_FAULT_SPEC="rank=1:crash@op=6",
                 HVD_TPU_COLLECTIVE_TIMEOUT_SEC="20"),
        timeout=90.0, capture=True, report=lambda msg: None)
    assert restarts == 1
    assert all(r.returncode == 0 for r in results), \
        [(r.rank, r.returncode, r.stderr[-400:]) for r in results]
    done = (ckpt / "done.txt").read_text()
    assert "epoch=1" in done, done
    # Resumed mid-run: the relaunch started past step 0 (the checkpoint
    # from before the crash), not from scratch.
    start = int(done.split("start_step=")[1])
    assert start >= 1, done


def test_hvdrun_cli_max_restarts(tmp_path):
    """The CLI flag end-to-end through hvdrun's main(): exit code 0 after
    one restart, and the relaunch notice on stderr."""
    import subprocess

    script = tmp_path / "train.py"
    script.write_text(_RESTART_SCRIPT)
    ckpt = tmp_path / "ckpt"
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         "--max-restarts", "1", "--timeout", "80", "--",
         sys.executable, str(script), str(ckpt)],
        env=_env(HVD_TPU_FAULT_SPEC="rank=1:crash@op=4",
                 HVD_TPU_COLLECTIVE_TIMEOUT_SEC="20"),
        capture_output=True, text=True, timeout=110)
    assert proc.returncode == 0, proc.stderr[-1200:]
    assert "restarting (1/1)" in proc.stderr, proc.stderr[-1200:]
    assert "succeeded after 1 restart(s)" in proc.stderr, proc.stderr[-800:]
    assert "epoch=1" in (ckpt / "done.txt").read_text()


# ---------------------------------------------------------------------------
# Launcher exit reporting (satellite).
# ---------------------------------------------------------------------------


def test_failure_report_labels_signals_and_tails_first_failure():
    from horovod_tpu.runner import RankResult, failure_report, signal_name

    assert signal_name(-9) == "SIGKILL (signal 9)"
    assert signal_name(-15) == "SIGTERM (signal 15)"
    assert signal_name(3) == "3"
    results = [
        RankResult(0, -9, "", "killed in the cascade"),
        RankResult(1, 1, "", "Traceback: the real error\nlast line",
                   first_failure=True),
        RankResult(2, 0, "", ""),
    ]
    report = failure_report(results)
    assert "rank 0 exited with SIGKILL (signal 9)" in report
    assert "rank 1 exited with 1  <- first failure" in report
    # The first-failing rank's stderr tail, not the kill cascade's.
    assert "the real error" in report and "killed in the cascade" not in report


def test_hvdrun_reports_signal_death(tmp_path):
    """A rank dying on a signal is labeled with the signal name in
    hvdrun's stderr report (not a bare negative number)."""
    import subprocess

    script = tmp_path / "sig.py"
    script.write_text(
        "import os, signal, horovod_tpu as hvd\n"
        "hvd.init()\n"
        "if hvd.rank() == 1:\n"
        "    os.kill(os.getpid(), signal.SIGKILL)\n"
        "import numpy as np\n"
        "try:\n"
        "    hvd.allreduce(np.ones(2, np.float32), name='x')\n"
        "except Exception:\n"
        "    pass\n"
    )
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         "--timeout", "60", "--", sys.executable, str(script)],
        env=_env(), capture_output=True, text=True, timeout=90)
    assert proc.returncode != 0
    assert "SIGKILL (signal 9)" in proc.stderr, proc.stderr[-800:]


# ---------------------------------------------------------------------------
# Elastic membership interplay (docs/fault-tolerance.md#elastic-membership):
# the checkpoint-restart path is the FALLBACK when shrinking cannot help.
# ---------------------------------------------------------------------------


def test_below_min_np_falls_back_to_checkpoint_restart(tmp_path):
    """A 2-rank elastic job with --min-np 2: losing a rank leaves too few
    survivors to shrink around, so the engine aborts fatally (naming the
    elastic minimum), run_membership gives up on elastic continuation, and
    the outer --max-restarts relaunch + checkpoint-resume fallback kicks
    in exactly as in the non-elastic case."""
    from horovod_tpu.runner import run_elastic

    script = tmp_path / "train.py"
    script.write_text(_RESTART_SCRIPT)
    ckpt = tmp_path / "ckpt"
    msgs = []
    results, restarts = run_elastic(
        [sys.executable, str(script), str(ckpt)], 2, max_restarts=1,
        min_np=2, max_np=2,
        env=_env(HVD_TPU_FAULT_SPEC="rank=1:crash@op=4",
                 HVD_TPU_COLLECTIVE_TIMEOUT_SEC="20"),
        timeout=120.0, capture=True, report=msgs.append)
    assert restarts == 1
    assert all(r.returncode == 0 for r in results), \
        [(r.rank, r.returncode, r.stderr[-400:]) for r in results]
    # The relaunch resumed from the checkpoint, not step 0.
    done = (ckpt / "done.txt").read_text()
    assert "epoch=1" in done, done
    assert int(done.split("start_step=")[1]) >= 1, done
    # The launcher explained why elastic continuation was abandoned.
    assert any("min-np" in m or "coordinator" in m for m in msgs), msgs


def test_clean_early_exit_counts_against_restarts_fast(tmp_path, monkeypatch):
    """Restart accounting (ISSUE 6 satellite): a rank that dies CLEANLY
    (rc 0) during the relaunch window — before init() completes — used to
    park its peers in their connect retries until the TOTAL --timeout
    budget burned.  The zero-exit straggler deadline
    (HVD_TPU_EXIT_STRAGGLER_SEC) kills the stragglers instead, so the
    attempt fails fast, counts against --max-restarts, and carries the
    failure_report stderr tail."""
    import time

    from horovod_tpu.runner import failure_report, run_elastic

    script = tmp_path / "early_exit.py"
    script.write_text(
        "import os, sys\n"
        "if os.environ.get('HVD_TPU_RANK') == '1':\n"
        "    sys.exit(0)  # clean death before init\n"
        "import horovod_tpu as hvd\n"
        "hvd.init()  # rank 0 blocks here waiting for rank 1\n"
    )
    # The deadline is read by the LAUNCHER (like HVD_TPU_KILL_GRACE_SEC),
    # not the ranks; 2s keeps the two attempts inside the test budget.
    monkeypatch.setenv("HVD_TPU_EXIT_STRAGGLER_SEC", "2")
    msgs = []
    t0 = time.monotonic()
    results, restarts = run_elastic(
        [sys.executable, str(script)], 2, max_restarts=1,
        env=_env(), timeout=300.0, capture=True, report=msgs.append)
    elapsed = time.monotonic() - t0
    # Two attempts at ~(straggler deadline + cleanup) each — nowhere near
    # the 300s total budget the old behavior would have burned.
    assert elapsed < 90.0, elapsed
    assert restarts == 1  # the relaunch was attempted and counted
    by_rank = {r.rank: r for r in results}
    assert by_rank[1].returncode == 0           # the clean early exit
    assert by_rank[0].returncode != 0           # straggler, killed
    assert any("restarting (1/1)" in m for m in msgs), msgs
    # The stderr tail reaches the report (rank 0 was killed waiting).
    assert failure_report(results), results


# ---------------------------------------------------------------------------
# Network chaos (HVD_TPU_NET_FAULT_SPEC) + the data-plane heartbeat
# failure detector (docs/fault-tolerance.md#failure-detection).
# ---------------------------------------------------------------------------


def test_net_fault_spec_rejects_bad_clause():
    """A malformed HVD_TPU_NET_FAULT_SPEC must fail init() with a typed
    message naming the bad clause — never arm a half-parsed table."""
    from horovod_tpu.runner import run_command

    code = (
        "import horovod_tpu as hvd\n"
        "from horovod_tpu.common import HorovodInternalError\n"
        "try:\n"
        "    hvd.init()\n"
        "except HorovodInternalError as e:\n"
        "    assert 'bad HVD_TPU_NET_FAULT_SPEC' in str(e), str(e)\n"
        "    assert 'frobnicate' in str(e), str(e)\n"
        "    raise SystemExit(0)\n"
        "raise SystemExit(9)\n"
    )
    results = run_command(
        [sys.executable, "-c", code], 1,
        env=_env(HVD_TPU_NET_FAULT_SPEC="link=0-1:frobnicate"),
        timeout=60.0, capture=True)
    assert results[0].returncode == 0, \
        (results[0].returncode, results[0].stderr[-800:])


def test_nonelastic_freeze_detected_in_heartbeat_time():
    """The ISSUE acceptance path, non-elastic arm: on a 4-rank job a
    SIGSTOP'd rank 2 is silent but never EOFs, so only the data-plane
    heartbeat detector can catch it quickly.  With the collective timeout
    pushed way out (30s) every survivor must still get RanksDownError
    naming exactly rank 2 in O(miss window) — proving detection is
    O(heartbeat), not O(collective-timeout)."""
    import time

    from horovod_tpu.runner import run_command

    code = (
        "import numpy as np, os, time, horovod_tpu as hvd\n"
        "from horovod_tpu.common import RanksDownError\n"
        "hvd.init()\n"
        "t0 = time.monotonic()\n"
        "try:\n"
        "    for i in range(200):\n"
        "        hvd.allreduce(np.ones(8, np.float32), name=f'hb.{i}')\n"
        "        time.sleep(0.02)\n"
        "    os._exit(9)  # survivors must NOT complete\n"
        "except RanksDownError as e:\n"
        "    assert set(e.ranks) == {2}, (e.ranks, str(e))\n"
        "    assert 'data-plane heartbeats' in str(e), str(e)\n"
        "    # Detection window is miss*interval = 1s; promote poll adds\n"
        "    # <=2s.  10s is generous slack yet far below the 30s timeout.\n"
        "    assert time.monotonic() - t0 < 10.0, time.monotonic() - t0\n"
        "    os._exit(7)  # nonzero: arm the grace-kill of the frozen rank\n"
    )
    t0 = time.monotonic()
    results = run_command(
        [sys.executable, "-c", code], 4,
        env=_env(HVD_TPU_FAULT_SPEC="rank=2:freeze@op=2",
                 HVD_TPU_HEARTBEAT_MS="100", HVD_TPU_HEARTBEAT_MISS="10",
                 HVD_TPU_COLLECTIVE_TIMEOUT_SEC="30"),
        timeout=90.0, capture=True)
    assert time.monotonic() - t0 < 45.0
    by_rank = {r.rank: r for r in results}
    for r in (0, 1, 3):
        assert by_rank[r].returncode == 7, \
            (r, by_rank[r].returncode, by_rank[r].stderr[-800:])
    assert by_rank[2].returncode == -9  # SIGKILL works on stopped procs


def test_partition_aborts_both_sides():
    """partition=0,1/2,3 mid-run: the fault layer silently swallows every
    byte across the cut (no EOF — exactly what a switch partition looks
    like), so BOTH sides must abort typed via heartbeats: the coordinator
    side (0,1) through rank 0's sweep, the minority side (2,3) through
    the local grace-expiry abort — the coordinator is unreachable from
    there.  Each side names only unreachable ranks, within ~2x the
    detection window (the 30s collective timeout never enters play).
    @after=4 (not 2): the clause arms per-process from engine start, so
    it must outlast the process-startup skew of 4 interpreter launches
    on a loaded box or the cut lands mid-init on the last rank."""
    import time

    from horovod_tpu.runner import run_command

    code = (
        "import numpy as np, os, time, horovod_tpu as hvd\n"
        "from horovod_tpu.common import RanksDownError\n"
        "hvd.init()\n"
        "t0 = time.monotonic()\n"
        "me = hvd.rank()\n"
        "far = {2, 3} if me in (0, 1) else {0, 1}\n"
        "try:\n"
        "    for i in range(400):\n"
        "        hvd.allreduce(np.ones(8, np.float32), name=f'p.{i}')\n"
        "        time.sleep(0.02)\n"
        "    os._exit(9)  # nobody trains through a partition\n"
        "except RanksDownError as e:\n"
        "    assert e.ranks and set(e.ranks) <= far, (me, e.ranks, str(e))\n"
        "    # @after=4 arming + 1s detection + grace + promote poll.\n"
        "    assert time.monotonic() - t0 < 15.0, time.monotonic() - t0\n"
        "    os._exit(7)\n"
    )
    t0 = time.monotonic()
    results = run_command(
        [sys.executable, "-c", code], 4,
        env=_env(HVD_TPU_NET_FAULT_SPEC="partition=0,1/2,3@after=4",
                 HVD_TPU_HEARTBEAT_MS="100", HVD_TPU_HEARTBEAT_MISS="10",
                 HVD_TPU_COLLECTIVE_TIMEOUT_SEC="30"),
        timeout=90.0, capture=True)
    assert time.monotonic() - t0 < 60.0
    for r in results:
        assert r.returncode == 7, \
            (r.rank, r.returncode, r.stderr[-800:])


def test_flaky_link_degrades_transparently():
    """link=0-1:flaky=0.05 chops ~5% of sends into partial writes plus a
    stall — the retry paths must absorb it with NO numeric or liveness
    consequence: every step's averaged allreduce is exactly right (the
    integer-valued float32 sums are bit-exact when nothing is lost), no
    rank is evicted, and the liveness section shows the detector ran."""
    from horovod_tpu.runner import run_command

    code = (
        "import numpy as np, horovod_tpu as hvd\n"
        "hvd.init()\n"
        "for i in range(30):\n"
        "    x = (np.arange(512, dtype=np.float32) + hvd.rank())\n"
        "    out = hvd.allreduce(x, average=False, name=f'fl.{i}')\n"
        "    want = 2.0 * np.arange(512, dtype=np.float32) + 1.0\n"
        "    assert np.array_equal(out, want), (i, out[:4], want[:4])\n"
        # Thirty tiny allreduces can finish inside the first 100 ms beat
        # interval now that hvd.init() no longer spends a second asking
        # JAX for devices: let a few beats pass before counting them.
        "import time; time.sleep(0.5)\n"
        "snap = hvd.metrics_snapshot()\n"
        "lv = snap['liveness']\n"
        "assert lv['interval_ms'] == 100 and lv['miss_limit'] == 10, lv\n"
        "assert lv['frames']['sent'] > 0, lv\n"
        "assert lv['frames']['received'] > 0, lv\n"
        "assert lv['evictions'] == 0, lv\n"
        "assert lv['peers'], lv\n"
        "from horovod_tpu.common import metrics\n"
        "text = metrics.prometheus_text(snap)\n"
        "assert 'hvd_tpu_liveness_frames_total' in text\n"
        "assert 'hvd_tpu_liveness_peer_age_us' in text\n"
    )
    results = run_command(
        [sys.executable, "-c", code], 2,
        env=_env(HVD_TPU_NET_FAULT_SPEC="link=0-1:flaky=0.05",
                 HVD_TPU_HEARTBEAT_MS="100", HVD_TPU_HEARTBEAT_MISS="10"),
        timeout=90.0, capture=True)
    assert all(r.returncode == 0 for r in results), \
        [(r.rank, r.returncode, r.stderr[-600:]) for r in results]


# ---------------------------------------------------------------------------
# Anomaly localization: the online detector must NAME the chaos-injected
# slow link — the ISSUE 18 closed-loop acceptance path.
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ~11s; anomaly verdict plumbing stays tier-1 in
# test_metrics.py::test_links_and_anomalies_sections and the chaos
# transport demotion in test_transport.py
def test_chaos_localization_names_the_slow_link():
    """link=0-2:delay=5 on a 4-rank job: the endpoints of the degraded
    link (ranks 0 and 2) must each emit a ``slow_link`` verdict whose
    subject is exactly "0-2" — visible in metrics_snapshot()'s anomalies
    log, as a flight event, in rank 0's /cluster aggregation, and in
    ``hvdtop --once`` — while the clean ranks (1 and 3) emit NO verdicts
    of any kind.  That last part is the hard half: localization is only
    useful if healthy links stay quiet.

    Timing: a 5ms injected delay against a sub-ms loopback baseline is a
    >100-sigma excursion; at ANOMALY_INTERVAL_MS=50 the sustain window
    (3 hot sweeps) lands well inside the post-step settle sleep."""
    from horovod_tpu.common.basics import pick_free_port
    from horovod_tpu.runner import run_command

    base_port = pick_free_port("127.0.0.1")
    code = (
        "import json, subprocess, sys, time, urllib.request\n"
        "import numpy as np, horovod_tpu as hvd\n"
        "hvd.init()\n"
        "r = hvd.rank()\n"
        "for i in range(250):\n"
        "    hvd.allreduce(np.ones(64, np.float32), name=f'ln.{i}')\n"
        "time.sleep(1.5)  # verdicts land on idle sweeps post-stepping\n"
        "snap = hvd.metrics_snapshot()\n"
        "links = snap['links']\n"
        "assert links['enabled'] and links['peers'], links\n"
        "assert any(v['send_us_count'] > 0\n"
        "           for v in links['peers'].values()), links\n"
        "an = snap['anomalies']\n"
        "assert an['sigma'] == 5 and an['interval_ms'] == 50, an\n"
        "if r in (0, 2):\n"
        "    assert an['verdicts']['slow_link'] >= 1, an\n"
        "    subs = set(e['subject'] for e in an['log']\n"
        "               if e['kind'] == 'slow_link')\n"
        "    assert subs == {'0-2'}, an['log']\n"
        "    from horovod_tpu.common import _load_lib\n"
        "    dump = _load_lib().hvd_tpu_flight_dump().decode()\n"
        "    assert '|anomaly|' in dump, dump[-500:]\n"
        "else:\n"
        "    assert sum(an['verdicts'].values()) == 0, an\n"
        "if r == 0:\n"
        f"    url = 'http://127.0.0.1:{base_port}/cluster'\n"
        "    doc = json.load(urllib.request.urlopen(url, timeout=10))\n"
        "    ca = doc['anomalies']\n"
        "    assert ca['total'] >= 2, ca  # one per endpoint, minimum\n"
        "    assert ca['verdicts'].get('slow_link', 0) >= 2, ca\n"
        "    feed = ca['recent']\n"
        "    assert feed, ca\n"
        "    assert all(e['subject'] == '0-2' for e in feed\n"
        "               if e['kind'] == 'slow_link'), feed\n"
        "    assert {int(e['rank']) for e in feed} <= {0, 2}, feed\n"
        f"    top = subprocess.run([sys.executable, {REPO + '/tools/hvdtop.py'!r},\n"
        f"                          '--port', '{base_port}', '--once'],\n"
        "                         capture_output=True, text=True, timeout=30)\n"
        "    assert top.returncode == 0, top.stderr[-800:]\n"
        "    assert 'slow_link(0-2)' in top.stdout, top.stdout\n"
        "    assert '<< slow_link' in top.stdout, top.stdout\n"
        "# Barrier: workers keep their monitors up until rank 0 scraped.\n"
        "hvd.allreduce(np.ones(1, np.float32), name='loc.barrier')\n"
        "hvd.shutdown()\n"
    )
    results = run_command(
        [sys.executable, "-c", code], 4,
        env=_env(HVD_TPU_NET_FAULT_SPEC="link=0-2:delay=5",
                 HVD_TPU_ANOMALY_INTERVAL_MS="50",
                 HVD_TPU_HEARTBEAT_MS="50",
                 # A verdict may land mid-stepping; a deeper ring keeps
                 # the anomaly event from being evicted by step events.
                 HVD_TPU_FLIGHT_EVENTS="8192",
                 HVD_TPU_MONITOR_PORT=str(base_port)),
        timeout=120.0, capture=True)
    for r in results:
        assert r.returncode == 0, (r.rank, r.stderr[-1500:])
