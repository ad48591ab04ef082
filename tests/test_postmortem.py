"""Postmortem-plane tests (docs/troubleshooting.md#reading-a-postmortem):
the always-on flight recorder, crash/hang dump files, the coordinator's
cross-rank stall diagnosis, the rank-0 /cluster aggregation, serving
request traces, and the rendering/lint tooling — the ISSUE-8 acceptance
paths, CPU-only with tight timeouts so the tier-1 budget holds.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**overrides):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.setdefault("HVD_TPU_KILL_GRACE_SEC", "3")
    env.update({k: str(v) for k, v in overrides.items()})
    for var in ("HVD_TPU_RANK", "HVD_TPU_SIZE", "HVD_TPU_COORD",
                "HVD_TPU_DATA", "HVD_TPU_FAULT_SPEC",
                "HVD_TPU_RESTART_EPOCH", "HVD_TPU_POSTMORTEM_DIR",
                "HVD_TPU_MONITOR_PORT"):
        env.setdefault(var, "")
        if not env[var]:
            env.pop(var, None)
    return env


# ---------------------------------------------------------------------------
# Flight recorder (in-process units + single-process engine ring).
# ---------------------------------------------------------------------------


def test_flight_ring_bounded_and_ordered():
    from horovod_tpu.common.postmortem import FlightRing

    ring = FlightRing(capacity=4)
    for i in range(10):
        ring.record("enqueue", f"t{i}", i)
    events = ring.drain()
    assert len(events) == 4          # bounded
    assert ring.total == 10          # cumulative survives the wrap
    assert [e["name"] for e in events] == ["t6", "t7", "t8", "t9"]
    assert [e["seq"] for e in events] == [6, 7, 8, 9]  # oldest first
    # ts_us is epoch-anchored and monotone.
    ts = [e["ts_us"] for e in events]
    assert ts == sorted(ts)
    disabled = FlightRing(capacity=0)
    disabled.record("enqueue", "x")
    assert not disabled.enabled and disabled.drain() == []


def test_parse_engine_ring():
    from horovod_tpu.common.postmortem import parse_engine_ring

    raw = "0|100|enqueue|grad_37|5;1|200|execute|grad_37|2;bad;x|y"
    events = parse_engine_ring(raw)
    assert events == [
        {"seq": 0, "ts_us": 100, "event": "enqueue", "name": "grad_37",
         "arg": 5},
        {"seq": 1, "ts_us": 200, "event": "execute", "name": "grad_37",
         "arg": 2},
    ]
    assert parse_engine_ring("") == []


def test_engine_flight_recorder_records(single_process_hvd):
    """The C++ ring records the control-plane story of a collective
    (enqueue -> announce -> execute -> tick) and the metrics snapshot's
    `flight` section mirrors the cumulative counts."""
    hvd = single_process_hvd
    from horovod_tpu import common
    from horovod_tpu.common import postmortem

    for i in range(3):
        hvd.allreduce(np.ones(4, np.float32), name=f"fl.{i}")
    events = postmortem.parse_engine_ring(
        common._lib.hvd_tpu_flight_dump().decode())
    kinds = [e["event"] for e in events]
    for expected in ("enqueue", "announce", "execute", "tick"):
        assert expected in kinds, kinds
    names = {e["name"] for e in events if e["event"] == "enqueue"}
    assert {"fl.0", "fl.1", "fl.2"} <= names, names
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs)
    snap = hvd.metrics_snapshot()
    assert snap["flight"]["events"]["engine"] >= len(events)
    assert snap["flight"]["capacity"] == 512


# ---------------------------------------------------------------------------
# Crash postmortems: every rank (crasher included) leaves a parseable dump
# whose ring / pending table / membership epoch agree across survivors.
# ---------------------------------------------------------------------------


def test_crash_postmortem_dumps(tmp_path):
    from horovod_tpu.runner import run_command

    pm = str(tmp_path / "pm")
    code = (
        "import numpy as np, horovod_tpu as hvd\n"
        "from horovod_tpu.common import RanksDownError\n"
        "hvd.init()\n"
        "try:\n"
        "    for i in range(6):\n"
        "        hvd.allreduce(np.ones(8, np.float32), name=f'step.{i}')\n"
        "    raise SystemExit(9)\n"
        "except RanksDownError:\n"
        "    raise SystemExit(0)\n"
    )
    metrics_file = str(tmp_path / "m.json")
    results = run_command(
        [sys.executable, "-c", code], 4,
        env=_env(HVD_TPU_FAULT_SPEC="rank=1:crash@op=3",
                 HVD_TPU_COLLECTIVE_TIMEOUT_SEC="20",
                 HVD_TPU_POSTMORTEM_DIR=pm,
                 HVD_TPU_METRICS_FILE=metrics_file),
        timeout=90.0, capture=True)
    by_rank = {r.rank: r for r in results}
    for r in (0, 2, 3):
        assert by_rank[r].returncode == 0, \
            (r, by_rank[r].returncode, by_rank[r].stderr[-800:])
    dumps = {}
    for r in range(4):
        path = os.path.join(pm, f"rank-{r}.json")
        assert os.path.exists(path), (r, os.listdir(pm))
        with open(path) as f:
            dumps[r] = json.load(f)  # must parse
    # The crasher dumped through the fault hook, before its hard exit.
    assert dumps[1]["reason"] == "fault_crash"
    crasher_ring = [e["name"] for e in dumps[1]["ring"]["engine"]]
    assert "step.2" in crasher_ring, crasher_ring[-10:]
    for r in (0, 2, 3):
        d = dumps[r]
        assert d["reason"] == "ranks_down", d["reason"]
        assert d["rank"] == r and d["size"] == 4
        assert d["membership_epoch"] == dumps[0]["membership_epoch"]
        # The pending table names the collective the dead rank stranded.
        pending = [p["name"] for p in d["pending"]["local"]]
        assert "step.3" in pending, (r, d["pending"])
        ring_names = [e["name"] for e in d["ring"]["engine"]]
        assert "step.3" in ring_names
        assert d["abort"]["code"] == 6  # ST_RANKS_DOWN
        # The diagnosis (broadcast in the abort message) names rank 1.
        assert d["diagnosis"] and "rank 1" in d["diagnosis"], d["diagnosis"]
    # Rank 0's dump carries the coordinator's waiting-on view.
    coord = dumps[0]["pending"]["coordinator"]
    assert any(p["name"] == "step.3" and 1 in p["missing_ranks"]
               for p in coord), coord
    # Satellite: crashed ranks leave their HVD_TPU_METRICS_FILE dump too
    # (os._exit skips atexit — the fault hook flushes it explicitly).
    for r in range(4):
        path = f"{metrics_file}.{r}"
        assert os.path.exists(path), (r, os.listdir(str(tmp_path)))
        with open(path) as f:
            snap = json.load(f)
        assert "flight" in snap and "ops" in snap


# ---------------------------------------------------------------------------
# Hang postmortems: the coordinator's cross-rank diagnosis names the
# stalled tensor and the wedged rank (the ISSUE acceptance path:
# rank=2:hang@op=12 on a 4-rank job).
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ~20s; postmortem dump + diagnosis coverage stays
# tier-1 in test_crash_postmortem_dumps and
# test_postmortem_dump_tool_renders_story
def test_hang_postmortem_cross_rank_diagnosis(tmp_path):
    from horovod_tpu.runner import run_command

    pm = str(tmp_path / "pm")
    code = (
        "import numpy as np, os, horovod_tpu as hvd\n"
        "from horovod_tpu.common import CollectiveTimeoutError\n"
        "hvd.init()\n"
        "try:\n"
        "    for i in range(13):\n"
        "        hvd.allreduce(np.ones(8, np.float32), name=f'step.{i}')\n"
        "    os._exit(9)\n"
        "except CollectiveTimeoutError as e:\n"
        "    assert 'step.12' in str(e), str(e)\n"
        "    assert 'missing ranks: 2' in str(e), str(e)\n"
        "    os._exit(7)  # nonzero: arm the grace-kill of the wedged rank\n"
    )
    results = run_command(
        [sys.executable, "-c", code], 4,
        env=_env(HVD_TPU_FAULT_SPEC="rank=2:hang@op=12",
                 HVD_TPU_COLLECTIVE_TIMEOUT_SEC="2",
                 HVD_TPU_POSTMORTEM_DIR=pm),
        timeout=90.0, capture=True)
    by_rank = {r.rank: r for r in results}
    for r in (0, 1, 3):
        assert by_rank[r].returncode == 7, \
            (r, by_rank[r].returncode, by_rank[r].stderr[-800:])
    assert by_rank[2].returncode == -9  # grace-killed wedged rank
    # The coordinator printed the one-paragraph diagnosis on stderr.
    assert "cross-rank diagnosis" in by_rank[0].stderr, \
        by_rank[0].stderr[-1500:]
    # Survivors' dumps: timeout reason, the diagnosis naming tensor+rank.
    for r in (0, 1, 3):
        path = os.path.join(pm, f"rank-{r}.json")
        assert os.path.exists(path), (r, os.listdir(pm))
        with open(path) as f:
            d = json.load(f)
        assert d["reason"] == "timeout"
        assert d["abort"]["code"] == 7  # ST_TIMEOUT
        diag = d["diagnosis"]
        assert diag and "rank 2" in diag, diag
        # The wedged rank DID announce earlier steps; the diagnosis says
        # where it stopped.
        assert "last announced" in diag, diag
        assert "step.12" in d["abort"]["message"], d["abort"]["message"]
    with open(os.path.join(pm, "rank-0.json")) as f:
        coord = json.load(f)["pending"]["coordinator"]
    assert any(p["name"] == "step.12" and p["missing_ranks"] == [2]
               for p in coord), coord
    # The failure report points at the dump and repeats the diagnosis.
    from horovod_tpu.runner.launch import failure_report

    report = failure_report(results, postmortem_dir=pm)
    assert "rank-2.json" not in report  # the wedged rank never dumped
    assert "postmortem: " in report and "rank-" in report, report
    assert "cross-rank diagnosis: " in report, report


# ---------------------------------------------------------------------------
# /cluster aggregation: one merged job document from rank 0's monitor.
# ---------------------------------------------------------------------------


def test_cluster_endpoint_merges_all_ranks():
    from horovod_tpu.common.basics import pick_free_port
    from horovod_tpu.runner import run_command

    base_port = pick_free_port("127.0.0.1")
    code = (
        "import json, urllib.request, numpy as np, horovod_tpu as hvd\n"
        "hvd.init()\n"
        "for i in range(3):\n"
        "    hvd.allreduce(np.ones(8, np.float32), name=f'step.{i}')\n"
        "if hvd.rank() == 0:\n"
        f"    url = 'http://127.0.0.1:{base_port}/cluster'\n"
        "    doc = json.load(urllib.request.urlopen(url, timeout=10))\n"
        "    assert doc['launched'] == 4 and doc['live'] == 4, doc\n"
        "    assert sorted(doc['ranks']) == ['0', '1', '2', '3'], doc\n"
        "    epochs = {r['membership_epoch']\n"
        "              for r in doc['ranks'].values()}\n"
        "    assert doc['membership_epochs_agree'] and epochs == {0}, doc\n"
        "    assert all(r['live'] for r in doc['ranks'].values()), doc\n"
        "    prom = urllib.request.urlopen(\n"
        f"        'http://127.0.0.1:{base_port}/cluster.prom',\n"
        "        timeout=10).read().decode()\n"
        "    assert 'hvd_tpu_cluster_ranks_live 4' in prom, prom\n"
        "# Barrier: workers keep their monitors up until rank 0 scraped.\n"
        "hvd.allreduce(np.ones(1, np.float32), name='cluster.barrier')\n"
        "hvd.shutdown()\n"
    )
    results = run_command(
        [sys.executable, "-c", code], 4,
        env=_env(HVD_TPU_MONITOR_PORT=str(base_port)),
        timeout=90.0, capture=True)
    for r in results:
        assert r.returncode == 0, (r.rank, r.stderr[-1200:])


# ---------------------------------------------------------------------------
# Serving request traces: ordered spans via the scheduler and the HTTP
# /v1/trace route.
# ---------------------------------------------------------------------------


def _drive_to_done(sch, req, max_batch, sampled_token=7, max_steps=64):
    steps = 0
    while req.state not in ("done", "failed") and steps < max_steps:
        plan = sch.step_plan()
        assert plan is not None, req.state
        sch.complete_step(plan, [sampled_token] * max_batch)
        steps += 1
    assert req.state == "done", req.state


def test_serving_trace_ordered_spans():
    from horovod_tpu.serving.scheduler import Scheduler, ServeConfig

    cfg = ServeConfig(max_batch=2, prefill_chunk=4, block_tokens=4,
                      num_blocks=16, max_blocks_per_seq=4, eos_id=-1)
    sch = Scheduler(cfg)
    req = sch.submit("acme", [1, 2, 3, 4, 5, 6], max_new_tokens=3)
    _drive_to_done(sch, req, cfg.max_batch)
    trace = sch.trace(req.id)
    assert trace is not None and trace["state"] == "done"
    events = [s["event"] for s in trace["spans"]]
    assert events[0] == "submitted" and events[-1] == "retired"
    # Lifecycle order: admitted before activated before the first
    # prefill chunk before the first decode step.
    for earlier, later in (("submitted", "admitted"),
                           ("admitted", "activated"),
                           ("activated", "prefill_chunk"),
                           ("prefill_chunk", "decode_step"),
                           ("decode_step", "retired")):
        assert events.index(earlier) < events.index(later), events
    t_ms = [s["t_ms"] for s in trace["spans"]]
    assert t_ms == sorted(t_ms)
    assert trace["spans"][-1]["generated"] == 3
    # Unknown ids are None (the route 404s).
    assert sch.trace(99999) is None


def test_serving_trace_http_route():
    from horovod_tpu.serving import server as _server
    from horovod_tpu.serving.scheduler import Scheduler, ServeConfig
    import urllib.error
    import urllib.request

    cfg = ServeConfig(max_batch=2, prefill_chunk=4, block_tokens=4,
                      num_blocks=16, max_blocks_per_seq=4, eos_id=-1,
                      port=0)
    sch = Scheduler(cfg)
    _server.stop_server()  # isolate from any earlier test's singleton
    port = _server.start_server(sch, cfg)
    try:
        req = sch.submit("acme", [1, 2, 3], max_new_tokens=2)
        _drive_to_done(sch, req, cfg.max_batch)
        url = f"http://127.0.0.1:{port}/v1/trace?id={req.id}"
        doc = json.load(urllib.request.urlopen(url, timeout=10))
        assert doc["id"] == req.id
        events = [s["event"] for s in doc["spans"]]
        assert events[0] == "submitted" and events[-1] == "retired"
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/trace?id=424242", timeout=10)
        assert err.value.code == 404
    finally:
        _server.stop_server()


def test_failed_requests_keep_their_trace():
    from horovod_tpu.serving.scheduler import Scheduler, ServeConfig

    cfg = ServeConfig(max_batch=2, prefill_chunk=4, block_tokens=4,
                      num_blocks=16, max_blocks_per_seq=4)
    sch = Scheduler(cfg)
    req = sch.submit("acme", [1, 2, 3], max_new_tokens=2)
    sch.fail_all(RuntimeError("boom"))
    trace = sch.trace(req.id)
    assert trace is not None and trace["state"] == "failed"
    assert trace["spans"][-1]["event"] == "failed"
    assert "boom" in trace["spans"][-1]["error"]


# ---------------------------------------------------------------------------
# Tooling: postmortem_dump.py rendering, failure_report pointers, and the
# extended metric-name section lint.
# ---------------------------------------------------------------------------


def _fake_dump(rank, reason, diagnosis=None, epoch=0, size=3):
    return {
        "schema": 1, "rank": rank, "size": size, "restart_epoch": 0,
        "membership_epoch": epoch, "reason": reason,
        "abort": {"code": 7, "message": "collective timeout ..."},
        "diagnosis": diagnosis,
        "ring": {"engine": [
            {"seq": 0, "ts_us": 1000, "event": "enqueue",
             "name": "grad_37", "arg": 0},
            {"seq": 1, "ts_us": 2000, "event": "announce",
             "name": "grad_37", "arg": 0},
        ], "xla": []},
        "pending": {
            "local": [{"name": "grad_37", "op": "allreduce",
                       "age_sec": 2.5}],
            "coordinator": ([{"name": "grad_37", "age_sec": 2.5,
                              "missing_ranks": [2]}] if rank == 0 else []),
        },
        "autotune": {}, "metrics": {}, "written_unix": time.time(),
    }


def test_postmortem_dump_tool_renders_story(tmp_path, capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import postmortem_dump

    d = str(tmp_path)
    diag = ("the coordinator is at tick 1841; rank 2 last announced "
            "'step.11' at tick 1803 and stopped announcing after that")
    for rank in (0, 1):
        with open(os.path.join(d, f"rank-{rank}.json"), "w") as f:
            json.dump(_fake_dump(rank, "timeout", diagnosis=diag), f)
    assert postmortem_dump.main([d]) == 0
    out = capsys.readouterr().out
    assert "2 dump(s)" in out
    assert "cross-rank diagnosis:" in out and "rank 2" in out
    assert "'grad_37' stalled 2.5s, waiting on ranks [2]" in out
    assert "no dump from rank(s) [2]" in out
    assert "grad_37" in out and "enqueue" in out
    # Empty dir: distinct failure.
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    assert postmortem_dump.main([empty]) == 1


def test_failure_report_postmortem_pointers(tmp_path):
    from horovod_tpu.runner.launch import RankResult, failure_report

    d = str(tmp_path)
    diag = "rank 1 never announced any collective"
    with open(os.path.join(d, "rank-0.json"), "w") as f:
        json.dump(_fake_dump(0, "ranks_down", diagnosis=diag), f)
    results = [RankResult(0, 1, "", "boom", first_failure=True),
               RankResult(1, -9, "", "")]
    report = failure_report(results, postmortem_dir=d)
    assert os.path.join(d, "rank-0.json") in report, report
    assert f"cross-rank diagnosis: {diag}" in report, report
    # Without a dir (and no env), no postmortem lines appear.
    plain = failure_report(results, postmortem_dir="")
    if "HVD_TPU_POSTMORTEM_DIR" not in os.environ:
        assert "postmortem" not in plain


def test_check_metric_names_section_lint():
    from tools.hvdlint import metrics_check as lint_tool
    from horovod_tpu.common import metrics

    snapshot = lint_tool.populated_registry().snapshot()
    text = metrics.prometheus_text(snapshot)
    doc = lint_tool._metrics_doc_text()
    assert lint_tool.lint(text) == []
    assert lint_tool.lint_sections(snapshot, text, doc) == []
    # A new snapshot section with no declared family is caught ...
    bad = dict(snapshot, mystery={"x": 1})
    errors = lint_tool.lint_sections(bad, text, doc)
    assert any("mystery" in e for e in errors), errors
    # ... and so is a declared family missing from the exposition.
    pruned = "\n".join(l for l in text.splitlines()
                       if "hvd_tpu_flight" not in l)
    errors = lint_tool.lint_sections(snapshot, pruned, doc)
    assert any("hvd_tpu_flight_events_total" in e for e in errors), errors


def test_postmortem_written_on_fatal_exception(tmp_path):
    """The excepthook path: a fatal uncaught exception on an initialized
    rank leaves a dump with reason 'exception'."""
    import subprocess

    pm = str(tmp_path / "pm")
    code = (
        "import horovod_tpu as hvd\n"
        "hvd.init()\n"
        "raise RuntimeError('driver blew up')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=_env(HVD_TPU_POSTMORTEM_DIR=pm), capture_output=True,
        text=True, timeout=60)
    assert proc.returncode != 0
    path = os.path.join(pm, "rank-0.json")
    assert os.path.exists(path), (proc.stderr[-800:], os.listdir(pm)
                                  if os.path.isdir(pm) else "no dir")
    with open(path) as f:
        d = json.load(f)
    assert d["reason"] == "exception"
    assert d["exception"]["type"] == "RuntimeError"
    assert "driver blew up" in d["exception"]["message"]
