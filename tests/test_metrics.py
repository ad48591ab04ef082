"""Collective metrics registry (horovod_tpu/common/metrics.py): snapshot
shape, counter monotonicity, histogram accounting, reset semantics,
thread-safety under concurrent collectives, stall surfacing, and the
Prometheus/JSON monitor endpoints.  Tier-1, CPU-only, in-process (size-1
engine); the multi-rank stall path is covered by the distributed test at
the bottom."""

import json
import os
import re
import threading
import urllib.request

import numpy as np
import pytest

from tests.distributed import distributed_test


@pytest.fixture
def hvd_metrics():
    """hvd.init() at size 1 with metrics collection enabled, registry
    cleared before and after (it is process-global)."""
    for var in ("HVD_TPU_RANK", "HVD_TPU_SIZE", "HVD_TPU_COORD",
                "HVD_TPU_DATA", "HVD_TPU_METRICS_FILE",
                "HVD_TPU_MONITOR_PORT"):
        os.environ.pop(var, None)
    os.environ["HVD_TPU_METRICS"] = "1"
    import horovod_tpu as hvd

    hvd.init()
    hvd.metrics_reset()
    yield hvd
    hvd.metrics_reset()
    hvd.shutdown()
    os.environ.pop("HVD_TPU_METRICS", None)
    from horovod_tpu.common import metrics

    metrics.registry.disable()


def test_snapshot_shape(hvd_metrics):
    hvd = hvd_metrics
    hvd.allreduce(np.ones(100, np.float32), name="m.ar")
    snap = hvd.metrics_snapshot()
    assert snap["enabled"] is True
    for plane in ("engine", "xla"):
        assert set(snap["ops"][plane]) == {"allreduce", "allgather",
                                           "broadcast"}
        assert set(snap["bytes"][plane]) == {"in", "out"}
    assert set(snap["batches"]) == {"dispatched", "fused_tensors"}
    assert set(snap["stalls"]) == {"count", "tensors"}
    for hist in snap["histograms"].values():
        assert set(hist) == {"buckets", "counts", "sum", "count"}
        assert len(hist["counts"]) == len(hist["buckets"]) + 1
    # The whole snapshot is plain data: JSON round-trips.
    assert json.loads(json.dumps(snap)) == snap


def test_counters_and_monotonicity(hvd_metrics):
    hvd = hvd_metrics
    x = np.ones(256, np.float32)
    hvd.allreduce(x, name="m.a")
    hvd.broadcast(x, 0, name="m.b")
    s1 = hvd.metrics_snapshot()
    assert s1["ops"]["engine"]["allreduce"] == 1
    assert s1["ops"]["engine"]["broadcast"] == 1
    assert s1["bytes"]["engine"]["in"] == 2 * x.nbytes
    assert s1["bytes"]["engine"]["out"] == 2 * x.nbytes
    hvd.allgather(np.ones((4, 8), np.float32), name="m.g")
    s2 = hvd.metrics_snapshot()
    for plane in ("engine", "xla"):
        for op in ("allreduce", "allgather", "broadcast"):
            assert s2["ops"][plane][op] >= s1["ops"][plane][op]
    assert s2["ops"]["engine"]["allgather"] == 1
    assert s2["bytes"]["engine"]["in"] == s1["bytes"]["engine"]["in"] + 128


def test_histogram_bucket_sums(hvd_metrics):
    hvd = hvd_metrics
    n = 7
    for i in range(n):
        hvd.allreduce(np.ones(32, np.float32), name=f"m.h{i}")
    hist = hvd.metrics_snapshot()["histograms"]["wait_sec"]
    assert hist["count"] == n
    assert sum(hist["counts"]) == n  # bucket counts account for every obs
    assert hist["sum"] > 0.0
    # Buckets are sorted upper bounds.
    assert hist["buckets"] == sorted(hist["buckets"])


def test_reset_semantics(hvd_metrics):
    hvd = hvd_metrics
    hvd.allreduce(np.ones(8, np.float32), name="m.r")
    assert hvd.metrics_snapshot()["ops"]["engine"]["allreduce"] == 1
    hvd.metrics_reset()
    snap = hvd.metrics_snapshot()
    assert snap["ops"]["engine"]["allreduce"] == 0
    assert snap["bytes"]["engine"]["in"] == 0
    assert snap["stalls"] == {"count": 0, "tensors": {}}
    assert all(h["count"] == 0 for h in snap["histograms"].values())
    assert snap["enabled"] is True  # reset clears data, not the gate
    # The registry keeps recording after a reset.
    hvd.allreduce(np.ones(8, np.float32), name="m.r2")
    assert hvd.metrics_snapshot()["ops"]["engine"]["allreduce"] == 1


def test_thread_safety_smoke(hvd_metrics):
    """Concurrent allreduces from several threads: every op and byte is
    accounted exactly once (the engine supports concurrent enqueues; the
    registry must too)."""
    hvd = hvd_metrics
    threads, per_thread, nbytes = 4, 8, 64 * 4
    errors = []

    def work(t):
        try:
            for i in range(per_thread):
                out = hvd.allreduce(np.full(64, float(t), np.float32),
                                    name=f"m.t{t}.{i}")
                assert np.allclose(out, float(t))
        except Exception as exc:  # pragma: no cover - failure path
            errors.append(exc)

    ts = [threading.Thread(target=work, args=(t,)) for t in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors, errors
    snap = hvd.metrics_snapshot()
    total = threads * per_thread
    assert snap["ops"]["engine"]["allreduce"] == total
    assert snap["bytes"]["engine"]["in"] == total * nbytes
    assert snap["bytes"]["engine"]["out"] == total * nbytes
    assert snap["histograms"]["wait_sec"]["count"] == total


def test_prometheus_endpoint_and_json(hvd_metrics):
    from horovod_tpu.common import metrics

    hvd = hvd_metrics
    hvd.allreduce(np.ones(128, np.float32), name="m.p")
    port = metrics.start_monitor(0, snapshot_fn=hvd.metrics_snapshot)
    try:
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        # Every non-comment line is "name{labels} value" or "name value".
        sample = re.compile(
            r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [0-9.+\-einfa]+$")
        lines = [l for l in text.splitlines() if l]
        assert lines, text
        for line in lines:
            if line.startswith("#"):
                assert line.startswith(("# HELP ", "# TYPE ")), line
            else:
                assert sample.match(line), line
        assert 'hvd_tpu_ops_total{plane="engine",op="allreduce"} 1' in lines
        # Histogram families expose cumulative buckets + +Inf + sum/count.
        assert any(l.startswith('hvd_tpu_wait_seconds_bucket{le="+Inf"}')
                   for l in lines)
        assert "hvd_tpu_wait_seconds_count 1" in lines
        raw = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json",
            timeout=10).read().decode()
        snap = json.loads(raw)
        # The JSON endpoint serves the same registry the API reads.
        assert snap["ops"] == hvd.metrics_snapshot()["ops"]
        assert urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).status == 200
    finally:
        metrics.stop_monitor()
    from horovod_tpu.common.metrics import monitor_port

    assert monitor_port() is None


def test_monitor_env_and_metrics_file(tmp_path):
    """HVD_TPU_MONITOR_PORT starts the monitor at init();
    HVD_TPU_METRICS_FILE writes a per-rank JSON dump at shutdown()."""
    for var in ("HVD_TPU_RANK", "HVD_TPU_SIZE", "HVD_TPU_COORD",
                "HVD_TPU_DATA"):
        os.environ.pop(var, None)
    path = str(tmp_path / "metrics.json")
    os.environ["HVD_TPU_METRICS_FILE"] = path
    os.environ["HVD_TPU_MONITOR_PORT"] = "0"  # ephemeral: avoids collisions
    import horovod_tpu as hvd
    from horovod_tpu.common import metrics

    hvd.init()
    try:
        hvd.metrics_reset()
        assert metrics.registry.enabled  # implied by file/port
        port = metrics.monitor_port()
        assert port
        hvd.allreduce(np.ones(16, np.float32), name="mf.a")
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert 'hvd_tpu_ops_total{plane="engine",op="allreduce"} 1' in text
    finally:
        hvd.shutdown()
        os.environ.pop("HVD_TPU_METRICS_FILE", None)
        os.environ.pop("HVD_TPU_MONITOR_PORT", None)
        metrics.registry.disable()
        metrics.registry.reset()
    dump = json.load(open(path + ".0"))  # rank-suffixed
    assert dump["ops"]["engine"]["allreduce"] == 1
    assert metrics.monitor_port() is None  # shutdown stops the monitor


def test_plane_stall_recorded_in_registry(monkeypatch):
    """Satellite: stall warnings are programmatic, not just stderr — the
    XLA plane's wait loop records (tensor, duration) into the registry
    even with metrics collection disabled."""
    import time as _time

    import horovod_tpu.common as common
    from horovod_tpu.common import metrics
    from horovod_tpu.jax.eager_mesh import XlaDataPlane, XlaHandle, _PlaneOp

    metrics.registry.disable()
    metrics.registry.reset()
    monkeypatch.setenv("HVD_TPU_STALL_WARNING_SEC", "0.05")
    plane = XlaDataPlane(mesh=None, spec_sharded=None, spec_replicated=None,
                         rank=0, size=2, fusion_threshold=1 << 20)
    handle = XlaHandle(plane, "ar", "stalled_metric", None, True, 2,
                       np.float32, (2,))
    op = _PlaneOp("stalled_metric", "ar", np.zeros(2, np.float32), 0, handle)
    plane._pending.append(op)
    monkeypatch.setattr(plane, "flush", lambda: None)

    def unblock():
        _time.sleep(0.3)
        handle._error = RuntimeError("unblocked")

    t = threading.Thread(target=unblock)
    t.start()
    plane._wait_dispatch(handle)
    t.join()
    snap = common.metrics_snapshot()
    assert snap["stalls"]["count"] >= 1
    assert "stalled_metric" in snap["stalls"]["tensors"]
    entry = snap["stalls"]["tensors"]["stalled_metric"]
    assert entry["count"] >= 1 and entry["last_duration_sec"] > 0
    metrics.registry.reset()


@distributed_test(np_=2, timeout=300.0)
def test_engine_stall_surfaced_to_snapshot():
    """Satellite (engine side): when a rank submits a collective its peer
    does not, the coordinator's stall sweep is visible on rank 0 through
    metrics_snapshot()["stalls"] — tensor name included — instead of only
    a stderr line.  Metrics collection stays at its default (disabled):
    stall records are ungated."""
    import time

    import horovod_tpu as hvd
    import horovod_tpu.common as common

    os.environ["HVD_TPU_STALL_WARNING_SEC"] = "0.3"
    hvd.init()
    if hvd.rank() == 0:
        h = common.allreduce_async(np.ones(4, np.float32), average=False,
                                   name="lonely")
        snap = {}
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            snap = hvd.metrics_snapshot()
            if snap["stalls"]["count"] >= 1:
                break
            time.sleep(0.1)
        assert snap["stalls"]["count"] >= 1, snap["stalls"]
        assert "lonely" in snap["stalls"]["tensors"], snap["stalls"]
        assert snap["stalls"]["tensors"]["lonely"]["last_duration_sec"] > 0
    else:
        time.sleep(2.0)  # let rank 0's sweep fire before unblocking it
        h = common.allreduce_async(np.ones(4, np.float32), average=False,
                                   name="lonely")
    out = h.wait()
    assert np.allclose(out, 2.0), out


@distributed_test(np_=2, timeout=300.0)
def test_monitor_scrape_during_two_process_job():
    """Acceptance: with HVD_TPU_MONITOR_PORT set, scraping /metrics during
    a 2-process hvdrun CPU job returns Prometheus text whose allreduce op
    count and byte totals match metrics_snapshot() on that rank."""
    os.environ["HVD_TPU_MONITOR_PORT"] = "0"  # ephemeral: collision-proof
    import horovod_tpu as hvd
    from horovod_tpu.common import metrics

    hvd.init()
    hvd.metrics_reset()
    r, n = hvd.rank(), hvd.size()
    x = np.full(500, float(r), np.float32)
    for i in range(3):
        out = hvd.allreduce(x, average=False, name=f"scrape.{i}")
        assert np.allclose(out, sum(range(n)))
    port = metrics.monitor_port()
    assert port, "monitor did not start from HVD_TPU_MONITOR_PORT"
    text = urllib.request.urlopen(
        f"http://localhost:{port}/metrics", timeout=10).read().decode()
    snap = hvd.metrics_snapshot()
    ar = snap["ops"]["engine"]["allreduce"]
    bin_ = snap["bytes"]["engine"]["in"]
    assert ar == 3 and bin_ == 3 * x.nbytes, snap
    assert f'hvd_tpu_ops_total{{plane="engine",op="allreduce"}} {ar}' \
        in text, text[:800]
    assert f'hvd_tpu_bytes_total{{plane="engine",direction="in"}} {bin_}' \
        in text, text[:800]
    hvd.shutdown()


def test_monitor_port_offsets_by_local_rank(monkeypatch):
    """A non-zero HVD_TPU_MONITOR_PORT binds port+local_rank so several
    ranks on one host coexist (rank 0 stays at the base port)."""
    for var in ("HVD_TPU_RANK", "HVD_TPU_SIZE", "HVD_TPU_COORD",
                "HVD_TPU_DATA"):
        os.environ.pop(var, None)
    import horovod_tpu as hvd
    from horovod_tpu.common import metrics

    calls = []
    monkeypatch.setenv("HVD_TPU_MONITOR_PORT", "19123")
    monkeypatch.setattr(metrics, "start_monitor",
                        lambda port, **kw: calls.append(port) or port)
    hvd.init()
    try:
        assert calls == [19123]  # size-1: local_rank 0 -> base port
    finally:
        hvd.shutdown()
        metrics.registry.disable()
        metrics.registry.reset()


def test_keras_metrics_logging_callback(hvd_metrics):
    """MetricsLoggingCallback logs per-epoch deltas of the registry."""
    keras = pytest.importorskip("keras")  # noqa: F841
    from horovod_tpu.keras.callbacks import MetricsLoggingCallback

    hvd = hvd_metrics
    lines = []
    cb = MetricsLoggingCallback(log_fn=lines.append)
    hvd.allreduce(np.ones(64, np.float32), name="cb.0")
    cb.on_epoch_end(0)
    hvd.allreduce(np.ones(64, np.float32), name="cb.1")
    hvd.allreduce(np.ones(64, np.float32), name="cb.2")
    cb.on_epoch_end(1)
    assert len(lines) == 2, lines
    assert "ops engine=1" in lines[0], lines[0]
    assert "ops engine=2" in lines[1], lines[1]  # delta, not cumulative
    assert "stalls 0" in lines[1]


def test_jax_train_step_feeds_step_histogram(hvd_metrics):
    """Steps built by build_train_step record into the step_sec histogram
    when metrics are enabled (and stay zero-overhead pass-throughs when
    not — the proxy consults the gate per call)."""
    jax = pytest.importorskip("jax")
    optax = pytest.importorskip("optax")
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P  # noqa: F401

    from horovod_tpu.jax.train import build_train_step

    hvd = hvd_metrics
    mesh = Mesh(np.array(jax.devices()[:2]), ("hvd",))

    def loss_fn(params, batch):
        return jnp.mean((batch @ params) ** 2)

    tx = optax.sgd(0.1)
    step = build_train_step(loss_fn, tx, mesh, axis_name="hvd")
    params = jnp.ones((4,))
    opt_state = tx.init(params)
    batch = jnp.ones((2, 4))
    before = hvd.metrics_snapshot()["histograms"]["step_sec"]["count"]
    params, opt_state, loss = step(params, opt_state, batch)
    float(loss)
    after = hvd.metrics_snapshot()["histograms"]["step_sec"]["count"]
    assert after == before + 1


def _toy_step(loss_fn, mesh_devices=2):
    jax = pytest.importorskip("jax")
    optax = pytest.importorskip("optax")
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from horovod_tpu.jax.train import build_train_step

    mesh = Mesh(np.array(jax.devices()[:mesh_devices]), ("hvd",))
    tx = optax.sgd(0.1)
    step = build_train_step(loss_fn, tx, mesh, axis_name="hvd")
    params = jnp.ones((4,))
    return step, (params, tx.init(params)), jnp.ones((mesh_devices, 4))


def _under(hist, bound):
    """Observations of a snapshot's histogram at or under `bound`."""
    return sum(c for b, c in zip(hist["buckets"], hist["counts"])
               if b <= bound)


def test_step_histograms_count_every_step(hvd_metrics):
    """n steps are n observations of the enqueue (step_dispatch_sec) and,
    once the caller has waited for the last loss, n completed steps
    (step_sec), whichever of the waiter and the snapshot saw them first."""
    import jax.numpy as jnp

    hvd = hvd_metrics
    step, (params, opt_state), batch = _toy_step(
        lambda p, b: jnp.mean((b @ p) ** 2))
    for _ in range(7):
        params, opt_state, loss = step(params, opt_state, batch)
    float(loss)
    hists = hvd.metrics_snapshot()["histograms"]
    assert hists["step_dispatch_sec"]["count"] == 7
    assert hists["step_sec"]["count"] == 7


def test_step_sec_is_the_step_not_the_enqueue(hvd_metrics):
    """A loop that runs ahead of a step slowed to 20 ms: step_sec reports
    the 20 ms, step_dispatch_sec the enqueue."""
    import time

    import jax

    hvd = hvd_metrics

    def nap(x):
        time.sleep(0.02)
        return x

    def loss_fn(params, batch):
        loss = ((batch @ params) ** 2).mean()
        # Outside what is differentiated: a callback has no JVP.
        napped = jax.pure_callback(
            nap, jax.ShapeDtypeStruct((), loss.dtype,
                                      vma=jax.typeof(loss).vma),
            jax.lax.stop_gradient(loss))
        return loss + 0.0 * napped

    step, (params, opt_state), batch = _toy_step(loss_fn)
    params, opt_state, loss = step(params, opt_state, batch)    # compiles
    float(loss)
    hvd.metrics_reset()
    for _ in range(6):
        params, opt_state, loss = step(params, opt_state, batch)
    float(loss)
    hists = hvd.metrics_snapshot()["histograms"]
    assert hists["step_sec"]["count"] == 6
    assert _under(hists["step_sec"], 0.01) == 0        # no step under 20 ms
    assert hists["step_sec"]["sum"] >= 6 * 0.02
    assert hists["step_dispatch_sec"]["count"] == 6
    assert _under(hists["step_dispatch_sec"], 0.01) >= 3


def test_step_completions_idle_while_metrics_are_off(monkeypatch):
    """With the registry off a step call starts no thread and queues
    nothing: the proxy reads two flags and enters a disabled annotation."""
    import jax.numpy as jnp

    from horovod_tpu.common import metrics
    from horovod_tpu.jax import train

    assert not metrics.registry.enabled
    fresh = train._StepCompletions()
    monkeypatch.setattr(train, "_completions", fresh)
    step, (params, opt_state), batch = _toy_step(
        lambda p, b: jnp.mean((b @ p) ** 2))
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, batch)
    float(loss)
    assert fresh._thread is None and not fresh._pending
    assert metrics.registry.snapshot()["histograms"]["step_sec"][
        "count"] == 0


def test_skew_section_and_prometheus_families():
    """Straggler attribution plumbing: record_last_announce feeds the
    snapshot's ungated "skew" section and the last_to_announce /
    announce_total Prometheus families; reset clears it."""
    from horovod_tpu.common.metrics import MetricsRegistry, prometheus_text

    reg = MetricsRegistry()
    reg.record_last_announce(2, 3)
    reg.record_last_announce(0)
    reg.observe("announce_skew_sec", 0.2)
    snap = reg.snapshot()
    assert snap["skew"] == {"count": 4,
                            "last_to_announce": {"2": 3, "0": 1}}
    assert snap["histograms"]["announce_skew_sec"]["count"] == 1
    text = prometheus_text(snap)
    assert 'hvd_tpu_last_to_announce_total{rank="2"} 3' in text
    assert "hvd_tpu_announce_total 4" in text
    assert "hvd_tpu_announce_skew_seconds_count 1" in text
    reg.reset()
    assert reg.snapshot()["skew"] == {"count": 0, "last_to_announce": {}}


def _fully_populated_registry():
    """One of everything, so every exposition family renders (shared by
    the conformance test and mirroring tools/hvdlint/metrics_check.py)."""
    from horovod_tpu.common import metrics

    reg = metrics.MetricsRegistry()
    reg.record_enqueue("engine", "allreduce", 1024)
    reg.record_enqueue("xla", "broadcast", 64)
    reg.record_bytes_out("engine", 1024)
    reg.record_batch(3)
    reg.record_stall("conf.tensor", 1.0)
    reg.record_fault("crash")
    reg.record_abort("ranks_down")
    reg.record_last_announce(1, 2)
    reg.set_restart_epoch(1)
    for name in metrics.HISTOGRAMS:
        reg.observe(name, 0.001)
    reg.set_links({"enabled": True, "peers": {1: {
        "bytes_out": 4096, "bytes_in": 2048, "sends": 7, "recvs": 5,
        "stalls": 1, "short_writes": 2, "send_us_sum": 900,
        "send_us_count": 7,
        "send_us_buckets": [3, 2, 1, 1, 0, 0, 0, 0, 0, 0],
        "rtt_last_us": 180, "rtt_ewma_us": 150, "rtt_samples": 4}}})
    reg.set_anomalies({"sigma": 5, "interval_ms": 500,
                       "verdicts": {"slow_link": 1},
                       "log": [{"kind": "slow_link", "subject": "0-1",
                                "detail": "timed-send level 9000us",
                                "age_us": 1000}]})
    return reg


def test_prometheus_exposition_conformance():
    """Satellite: scrape /metrics and check exposition-format conformance
    — # HELP/# TYPE pairing per family, metric-name charset, samples only
    under declared families — and that every registry section
    (ops/bytes/batches/stalls/faults/skew + every histogram) is
    exposed."""
    from horovod_tpu.common import metrics

    reg = _fully_populated_registry()
    port = metrics.start_monitor(0, snapshot_fn=reg.snapshot)
    try:
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    finally:
        metrics.stop_monitor()
        metrics.registry.disable()  # start_monitor enables the global one

    name_re = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
    helps, types, order = {}, {}, []
    for i, line in enumerate(text.splitlines()):
        if line.startswith("# HELP "):
            name = line.split()[2]
            assert name not in helps, f"duplicate HELP for {name}"
            helps[name] = i
        elif line.startswith("# TYPE "):
            parts = line.split()
            name, kind = parts[2], parts[3]
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = i
            order.append(name)
            assert kind in ("counter", "gauge", "histogram"), line
    # Pairing: every TYPE has a HELP immediately before it, and vice versa.
    assert set(helps) == set(types), (set(helps) ^ set(types))
    for name in order:
        assert types[name] == helps[name] + 1, f"HELP/TYPE split for {name}"
        assert name_re.match(name), name
    # Samples belong to a declared family (histograms via their suffixes).
    declared = set(types)
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        sample = line.split("{")[0].split(" ")[0]
        assert name_re.match(sample), line
        base = sample
        for suffix in ("_bucket", "_sum", "_count"):
            if sample.endswith(suffix) and sample[:-len(suffix)] in declared:
                base = sample[:-len(suffix)]
                break
        assert base in declared, f"undeclared sample {sample}"
    # Every registry section is exposed, PR-2 faults and skew included.
    expected = {"hvd_tpu_ops_total", "hvd_tpu_bytes_total",
                "hvd_tpu_batches_dispatched_total",
                "hvd_tpu_fused_tensors_total",
                "hvd_tpu_stall_events_total",
                "hvd_tpu_stalled_tensor_total",
                "hvd_tpu_faults_injected_total", "hvd_tpu_aborts_total",
                "hvd_tpu_restart_epoch", "hvd_tpu_announce_total",
                "hvd_tpu_last_to_announce_total"}
    expected |= {metrics._prom_hist_name(h) for h in metrics.HISTOGRAMS}
    # ISSUE 18: the per-link and anomaly families must pass the same
    # exposition conformance as every older section.
    expected |= {"hvd_tpu_link_stats_enabled", "hvd_tpu_link_bytes_total",
                 "hvd_tpu_link_sends_total",
                 "hvd_tpu_link_stall_events_total",
                 "hvd_tpu_link_send_latency_us", "hvd_tpu_link_rtt_us",
                 "hvd_tpu_link_rtt_samples_total", "hvd_tpu_anomaly_sigma",
                 "hvd_tpu_anomaly_verdicts_total"}
    assert expected <= declared, expected - declared
    assert 'hvd_tpu_last_to_announce_total{rank="1"} 2' in text


def test_check_metric_names_lint():
    """Satellite: the metric-name lint (snake_case, hvd_tpu_ prefix, no
    duplicate families) passes — run from tier-1 so drift fails CI."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.hvdlint", "metrics"],
        capture_output=True, text=True, cwd=repo, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout, proc.stdout


def test_check_metric_names_lint_detects_violations():
    """The lint rejects camelCase, missing prefixes, and duplicates (a
    lint that passes everything would let names drift silently)."""
    from tools.hvdlint import metrics_check as mod

    bad = ("# HELP hvd_tpu_camelCase_total x\n"
           "# TYPE hvd_tpu_camelCase_total counter\n"
           "hvd_tpu_camelCase_total 1\n"
           "# HELP wrong_prefix_total x\n"
           "# TYPE wrong_prefix_total counter\n"
           "wrong_prefix_total 1\n"
           "# HELP hvd_tpu_dup_total x\n"
           "# TYPE hvd_tpu_dup_total counter\n"
           "# HELP hvd_tpu_dup_total x\n"
           "# TYPE hvd_tpu_dup_total counter\n"
           "hvd_tpu_orphan_total 1\n")
    errors = "\n".join(mod.lint(bad))
    assert "camelCase" in errors
    assert "wrong_prefix_total" in errors
    assert "duplicate metric family 'hvd_tpu_dup_total'" in errors
    assert "orphan" in errors


def test_metrics_dump_stragglers_view(tmp_path):
    """Satellite: `metrics_dump.py --stragglers` ranks ranks by
    last_to_announce share and prints the skew histogram's p50/p99."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "metrics_dump", os.path.join(repo, "tools", "metrics_dump.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    reg = _fully_populated_registry()
    reg.record_last_announce(3, 7)
    for _ in range(5):
        reg.observe("announce_skew_sec", 0.2)
    path = tmp_path / "dump.json.0"
    path.write_text(json.dumps(reg.snapshot()))
    out = mod.render_stragglers(json.loads(path.read_text()))
    assert "dominant straggler: rank 3" in out, out
    assert "p50=" in out and "p99=" in out, out
    # And via the CLI flag.
    import subprocess
    import sys as _sys

    proc = subprocess.run(
        [_sys.executable, os.path.join(repo, "tools", "metrics_dump.py"),
         "--stragglers", str(path)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "dominant straggler: rank 3" in proc.stdout, proc.stdout


def test_links_and_anomalies_sections():
    """ISSUE 18 tentpole plumbing, engine-free: set_links/set_anomalies
    mirror into the ungated snapshot sections, the Prometheus families
    render with CUMULATIVE histogram buckets, and health_summary /
    cluster_document carry the per-rank link rows and the merged,
    rank-attributed anomaly feed."""
    from horovod_tpu.common import metrics

    reg = _fully_populated_registry()
    snap = reg.snapshot()
    # Snapshot shape: str-keyed peers (JSON round-trip safe), full
    # counter set, verdict log.
    assert snap["links"]["enabled"] is True
    peer = snap["links"]["peers"]["1"]
    assert peer["sends"] == 7 and peer["send_us_sum"] == 900
    assert len(peer["send_us_buckets"]) == \
        len(metrics.LINK_SEND_BUCKETS_US) + 1  # +Inf overflow bucket
    assert snap["anomalies"]["verdicts"]["slow_link"] == 1
    assert snap["anomalies"]["verdicts"]["straggler"] == 0  # zero-filled
    assert snap["anomalies"]["log"][0]["subject"] == "0-1"

    text = metrics.prometheus_text(snap)
    assert 'hvd_tpu_link_bytes_total{peer="1",dir="out"} 4096' in text
    assert 'hvd_tpu_link_sends_total{peer="1"} 7' in text
    assert ('hvd_tpu_link_stall_events_total{peer="1",kind="short_write"} 2'
            in text)
    # Buckets 3,2,1,1 at bounds 50,100,250,500 render cumulatively.
    assert 'hvd_tpu_link_send_latency_us_bucket{peer="1",le="50"} 3' in text
    assert 'hvd_tpu_link_send_latency_us_bucket{peer="1",le="100"} 5' in text
    assert 'hvd_tpu_link_send_latency_us_bucket{peer="1",le="500"} 7' in text
    assert ('hvd_tpu_link_send_latency_us_bucket{peer="1",le="+Inf"} 7'
            in text)
    assert 'hvd_tpu_link_rtt_us{peer="1",stat="ewma"} 150' in text
    assert "hvd_tpu_anomaly_sigma 5" in text
    assert 'hvd_tpu_anomaly_verdicts_total{kind="slow_link"} 1' in text

    # RTT gauges are omitted (not zero-valued) before the first echo.
    reg2 = metrics.MetricsRegistry()
    reg2.set_links({"enabled": True, "peers": {2: {
        "sends": 1, "send_us_sum": 10, "send_us_count": 1,
        "send_us_buckets": [1] + [0] * 9, "rtt_samples": 0}}})
    assert "hvd_tpu_link_rtt_us{" not in \
        metrics.prometheus_text(reg2.snapshot())

    # /health rows: merged stalls, summed bytes, -1 RTT sentinel handling.
    hs = metrics.health_summary(snap)
    row = hs["links"]["1"]
    assert row["bytes"] == 4096 + 2048
    assert row["stalls"] == 1 + 2
    assert row["send_mean_us"] == 900 // 7
    assert row["rtt_ewma_us"] == 150
    assert hs["anomalies"]["verdicts"]["slow_link"] == 1
    assert hs["anomalies"]["log"][-1]["kind"] == "slow_link"

    # /cluster rollup, through the real scrape path: rank 0 computed
    # locally, "rank 2" scraped from a live monitor serving a registry
    # with a fresher (smaller age_us) verdict.  Totals sum across ranks;
    # the merged feed is rank-attributed and age-sorted freshest-first.
    remote = metrics.MetricsRegistry()
    remote.set_anomalies({"sigma": 5, "interval_ms": 500,
                          "verdicts": {"slow_link": 2},
                          "log": [{"kind": "slow_link", "subject": "0-2",
                                   "detail": "x", "age_us": 50}]})
    port = metrics.start_monitor(0, snapshot_fn=remote.snapshot)
    try:
        metrics.configure_cluster([(0, "127.0.0.1", 0),
                                   (2, "127.0.0.1", port)])
        doc = metrics.cluster_document(reg.snapshot)
    finally:
        metrics.stop_monitor()
        metrics.registry.disable()  # start_monitor enables the global one
    assert doc["anomalies"]["total"] == 3, doc["anomalies"]
    assert doc["anomalies"]["verdicts"]["slow_link"] == 3
    feed = doc["anomalies"]["recent"]
    assert feed[0]["rank"] == "2" and feed[0]["age_us"] == 50, feed


def test_metrics_dump_links_view(tmp_path):
    """Satellite: `metrics_dump.py --links` renders the per-peer link
    table (mean/p99 send latency, RTT, backpressure) and the default
    render grows an anomalies section when verdicts exist."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "metrics_dump", os.path.join(repo, "tools", "metrics_dump.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    reg = _fully_populated_registry()
    snap = reg.snapshot()
    out = mod.render_links(snap)
    assert "peer" in out and "p99" in out, out
    assert "129us" in out, out  # peer 1's mean, round(900/7)
    out_default = mod.render(snap)
    assert "anomalies" in out_default, out_default
    assert "slow_link" in out_default, out_default
    # And via the CLI flag.
    import subprocess
    import sys as _sys

    path = tmp_path / "dump.json.0"
    path.write_text(json.dumps(snap))
    proc = subprocess.run(
        [_sys.executable, os.path.join(repo, "tools", "metrics_dump.py"),
         "--links", str(path)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "p99" in proc.stdout, proc.stdout


def test_prometheus_text_pure():
    """prometheus_text renders a synthetic snapshot without an engine."""
    from horovod_tpu.common.metrics import (MetricsRegistry,
                                            prometheus_text)

    reg = MetricsRegistry()
    reg.record_enqueue("xla", "allreduce", 1024)
    reg.record_bytes_out("xla", 1024)
    reg.record_batch(3)
    reg.observe("bucket_fill", 0.42)
    reg.observe("negotiation_sec", 0.003)
    reg.record_stall('we"ird\nname', 1.5)
    text = prometheus_text(reg.snapshot())
    assert 'hvd_tpu_ops_total{plane="xla",op="allreduce"} 1' in text
    assert 'hvd_tpu_bytes_total{plane="xla",direction="out"} 1024' in text
    assert "hvd_tpu_fused_tensors_total 3" in text
    assert "hvd_tpu_stall_events_total 1" in text
    assert '\\"' in text and "\\n" in text  # label escaping
    assert "hvd_tpu_bucket_fill_ratio_count 1" in text
    assert "hvd_tpu_negotiation_seconds_count 1" in text
