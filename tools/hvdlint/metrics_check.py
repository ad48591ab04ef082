"""Checker 6: Prometheus metric-name and section-coverage lint.

Run it alone with ``python -m tools.hvdlint metrics``.  Renders a
registry with one of everything recorded and verifies: every family is
snake_case with the ``hvd_tpu_`` prefix, pairs ``# HELP`` with ``# TYPE``, is unique
across sections; and every ``metrics_snapshot()`` top-level section maps
to at least one rendered family (SECTION_FAMILIES) and is documented in
docs/metrics.md.  Unlike the text-parsing checkers this one imports the
live registry, so it lints what the code actually renders.
"""

from __future__ import annotations

import os
import re
from collections import Counter
from typing import List

from tools.hvdlint import Violation

NAME_RE = re.compile(r"^hvd_tpu_[a-z0-9]+(_[a-z0-9]+)*$")
HIST_SUFFIXES = ("_bucket", "_sum", "_count")

# Section-coverage contract: every metrics_snapshot() top-level section
# must export at least one Prometheus family AND be documented in
# docs/metrics.md — a new section missing from this map, a mapped family
# missing from the exposition, or an undocumented section all fail the
# lint (this drifted silently in past PRs).  "enabled" is the gate flag,
# not a section; "histograms" is special-cased (one family per histogram).
SECTION_FAMILIES = {
    "ops": ("hvd_tpu_ops_total",),
    "bytes": ("hvd_tpu_bytes_total",),
    "batches": ("hvd_tpu_batches_dispatched_total",
                "hvd_tpu_fused_tensors_total"),
    "stalls": ("hvd_tpu_stall_events_total", "hvd_tpu_stalled_tensor_total"),
    "faults": ("hvd_tpu_faults_injected_total", "hvd_tpu_aborts_total",
               "hvd_tpu_restart_epoch"),
    "skew": ("hvd_tpu_announce_total", "hvd_tpu_last_to_announce_total"),
    "cache": ("hvd_tpu_response_cache_events_total",
              "hvd_tpu_response_cache_size"),
    "membership": ("hvd_tpu_membership_epoch", "hvd_tpu_membership_size",
                   "hvd_tpu_membership_reshapes_total"),
    "autotune": ("hvd_tpu_autotune_enabled",
                 "hvd_tpu_autotune_windows_total"),
    "serving": ("hvd_tpu_serving_requests_total",
                "hvd_tpu_serving_steps_total"),
    "flight": ("hvd_tpu_flight_events_total",
               "hvd_tpu_flight_ring_capacity"),
    "moe": ("hvd_tpu_moe_expert_rows",
            "hvd_tpu_moe_rows_over_bound_total",
            "hvd_tpu_moe_rows_walked",
            "hvd_tpu_moe_way_back"),
    "attention": ("hvd_tpu_attention_blocks",),
    "delta": ("hvd_tpu_delta_steps",),
    "ssm": ("hvd_tpu_ssm_chunks",),
    "mtp": ("hvd_tpu_mtp_loss",),
    "train_step": ("hvd_tpu_train_step_all_reduces",),
    "compression": ("hvd_tpu_compression_mode",
                    "hvd_tpu_compression_wire_bytes_total",
                    "hvd_tpu_compression_payload_bytes_total",
                    "hvd_tpu_compression_ops_total",
                    "hvd_tpu_compression_residual_bytes"),
    "topology": ("hvd_tpu_topology_hierarchical",
                 "hvd_tpu_topology_nodes",
                 "hvd_tpu_topology_local_size",
                 "hvd_tpu_topology_cross_algo_threshold_bytes",
                 "hvd_tpu_topology_cross_ops_total",
                 "hvd_tpu_topology_bytes_total"),
    "liveness": ("hvd_tpu_liveness_interval_ms",
                 "hvd_tpu_liveness_miss_limit",
                 "hvd_tpu_liveness_frames_total",
                 "hvd_tpu_liveness_miss_events_total",
                 "hvd_tpu_liveness_evictions_total",
                 "hvd_tpu_liveness_clock_fanin",
                 "hvd_tpu_liveness_peer_age_us"),
    "p2p": ("hvd_tpu_p2p_transfers_total",
            "hvd_tpu_p2p_bytes_total",
            "hvd_tpu_p2p_matched_total",
            "hvd_tpu_p2p_unmatched",
            "hvd_tpu_p2p_group_ops_total",
            "hvd_tpu_p2p_channels"),
    "links": ("hvd_tpu_link_stats_enabled",
              "hvd_tpu_link_bytes_total",
              "hvd_tpu_link_sends_total",
              "hvd_tpu_link_stall_events_total",
              "hvd_tpu_link_send_latency_us",
              "hvd_tpu_link_rtt_us",
              "hvd_tpu_link_rtt_samples_total"),
    "anomalies": ("hvd_tpu_anomaly_sigma",
                  "hvd_tpu_anomaly_verdicts_total"),
    "control": ("hvd_tpu_control_tree_depth",
                "hvd_tpu_control_children",
                "hvd_tpu_control_steady_active",
                "hvd_tpu_control_steady_cycles_total",
                "hvd_tpu_control_steady_transitions_total",
                "hvd_tpu_control_negotiated_ticks_total",
                "hvd_tpu_control_frames_total"),
    "state": ("hvd_tpu_state_armed",
              "hvd_tpu_state_snapshots_total",
              "hvd_tpu_state_snapshot_bytes_total",
              "hvd_tpu_state_last_snapshot_step",
              "hvd_tpu_state_overlap_ratio",
              "hvd_tpu_state_peer_copies_total",
              "hvd_tpu_state_peer_last_step",
              "hvd_tpu_state_restores_total",
              "hvd_tpu_state_checkpoint_events_total",
              "hvd_tpu_state_checkpoint_shard_bytes_total"),
    "histograms": (),
}


def populated_registry():
    """A registry with at least one sample in every section, so the
    exposition renders every family the code can produce."""
    from horovod_tpu.common import metrics

    reg = metrics.MetricsRegistry()
    reg.record_enqueue("engine", "allreduce", 1024)
    reg.record_bytes_out("engine", 1024)
    reg.record_batch(2)
    reg.record_stall("lint.tensor", 1.0)
    reg.record_fault("crash")
    reg.record_abort("ranks_down")
    reg.record_last_announce(1, 2)
    reg.set_restart_epoch(1)
    reg.record_cache("engine", "hits")
    reg.record_cache("xla", "misses")
    reg.set_cache_size("engine", 1)
    reg.set_membership({"epoch": 1, "size": 3, "reshapes": 1,
                        "ranks_lost": [1], "ranks_joined": [3]})
    reg.record_serving("requests", "lint-tenant")
    reg.record_serving("admitted", "lint-tenant")
    reg.record_serving("rejected", "lint-tenant")
    reg.record_serving("retired", "lint-tenant")
    reg.record_serving_tokens("lint-tenant", "prompt", 8)
    reg.record_serving_tokens("lint-tenant", "generated", 4)
    reg.record_serving_step(2, 4)
    reg.set_serving_gauges(queue_depth=1, active=2, kv_blocks_in_use=3,
                           kv_blocks_total=8)
    reg.set_flight({"events": {"engine": 5, "xla": 2}, "capacity": 512})
    reg.set_moe_rows([[3, 5], [4, 4]], 1, [8, 8], ["rows", "row_slabs"])
    reg.set_state_armed(True)
    reg.record_state_snapshot(7, 4096)
    reg.set_state_overlap(0.01, 0.4)
    reg.record_state_peer(sent_bytes=4096)
    reg.record_state_peer(received_step=7)
    reg.record_state_restore("peer")
    reg.record_state_restore("local")
    reg.record_state_restore("root_broadcast")
    reg.record_state_ckpt("sharded_saves", nbytes=4096)
    reg.record_state_ckpt("legacy_saves", nbytes=8192)
    reg.record_state_ckpt("loads")
    reg.record_state_ckpt("pruned")
    reg.set_topology({"hierarchical": True, "nodes": 2, "local_size": 2,
                      "cross_algo_threshold": 64 << 10,
                      "cross_ops": {"ring": 3, "tree": 1},
                      "bytes": {"local": 4096, "cross": 1024}})
    reg.set_control({"tree": True, "depth": 2, "children": 3, "hosts": 2,
                     "steady": {"active": True, "pattern_len": 4,
                                "threshold": 32, "entries": 1, "exits": 0,
                                "replays": 40, "cycles": 10},
                     "negotiated_ticks": 12,
                     "frames": {"sent": 24, "received": 24}})
    reg.set_liveness({"interval_ms": 100, "miss_limit": 10,
                      "frames": {"sent": 120, "received": 118},
                      "miss_events": 1, "evictions": 1, "clock_fanin": 2,
                      "peers": {1: {"age_us": 900, "misses": 0}}})
    reg.set_links({"enabled": True, "peers": {
        1: {"bytes_out": 4096, "bytes_in": 2048, "sends": 32,
            "recvs": 30, "stalls": 1, "short_writes": 0,
            "send_us_sum": 640, "send_us_count": 32,
            "send_us_buckets": [30, 2, 0, 0, 0, 0, 0, 0, 0, 0],
            "rtt_last_us": 210, "rtt_ewma_us": 200, "rtt_samples": 5}}})
    reg.set_anomalies({"sigma": 5, "interval_ms": 500,
                       "verdicts": {"slow_link": 1, "straggler": 0,
                                    "cache_degraded": 0, "slow_phase": 0},
                       "log": [{"kind": "slow_link", "subject": "0-1",
                                "detail": "lint", "age_us": 1000}]})
    reg.set_compression({
        "mode": "bf16", "min_bytes": 1024,
        "planes": {"engine": {"wire_bytes": 512, "payload_bytes": 1024,
                              "ops": {"none": 1, "bf16": 2, "fp8": 0}},
                   "xla": {"wire_bytes": 0, "payload_bytes": 0,
                           "ops": {"none": 0, "bf16": 0, "fp8": 0}}},
        "residual_bytes": 4096, "residual_tensors": 2,
    })
    reg.set_autotune({
        "enabled": True, "frozen": True, "windows": 3,
        "fusion_threshold": 1 << 20, "cycle_time_ms": 2.5,
        "best_score": 123.4,
        "history": [{"window": 1, "fusion_threshold": 1 << 20,
                     "cycle_time_ms": 2.5, "score": 123.4}],
        "applied": [{"tick": 7, "fusion_threshold": 1 << 20,
                     "cycle_time_ms": 2.5, "frozen": True}],
    })
    for name in metrics.HISTOGRAMS:
        reg.observe(name, 0.001)
    return reg


def lint(text: str) -> list:
    """Return the list of naming-convention violations in a Prometheus
    text exposition (empty = clean)."""
    errors = []
    helps = []
    families = []
    for line in text.splitlines():
        if line.startswith("# HELP "):
            helps.append(line.split()[2])
        elif line.startswith("# TYPE "):
            families.append(line.split()[2])
        elif line.startswith("#"):
            errors.append(f"unexpected comment line: {line!r}")
    for name in families:
        if not NAME_RE.match(name):
            errors.append(
                f"metric family '{name}' violates the naming convention "
                f"(snake_case with hvd_tpu_ prefix)")
        if name not in helps:
            errors.append(f"metric family '{name}' has # TYPE but no "
                          f"# HELP")
    for name in helps:
        if name not in families:
            errors.append(f"metric family '{name}' has # HELP but no "
                          f"# TYPE")
    for name, n in Counter(families).items():
        if n > 1:
            errors.append(
                f"duplicate metric family '{name}': two registry sections "
                f"export the same name")
    declared = set(families)
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        sample = line.split("{")[0].split(" ")[0]
        base = sample
        for suffix in HIST_SUFFIXES:
            if sample.endswith(suffix) and sample[:-len(suffix)] in declared:
                base = sample[:-len(suffix)]
                break
        if base not in declared:
            errors.append(f"sample '{sample}' has no # TYPE declaration")
    return errors


def _metrics_doc_text(root: str = None) -> str:
    if root is None:
        from tools.hvdlint import repo_root

        root = repo_root()
    try:
        with open(os.path.join(root, "docs", "metrics.md")) as f:
            return f.read().lower()
    except OSError:
        return ""


def lint_sections(snapshot: dict, text: str, doc_text: str) -> list:
    """Section-coverage violations: every snapshot top-level section must
    map to at least one rendered Prometheus family (SECTION_FAMILIES) and
    appear in docs/metrics.md."""
    errors = []
    families = {line.split()[2] for line in text.splitlines()
                if line.startswith("# TYPE ")}
    for section, value in snapshot.items():
        if section == "enabled":
            continue  # the collection gate, not a metrics section
        if section not in SECTION_FAMILIES:
            errors.append(
                f"snapshot section '{section}' has no SECTION_FAMILIES "
                f"entry (tools/hvdlint/metrics_check.py): declare its "
                f"Prometheus families so the exposition cannot silently "
                f"drop it")
            continue
        expected = SECTION_FAMILIES[section]
        if section == "histograms":
            from horovod_tpu.common.metrics import _prom_hist_name

            expected = tuple(_prom_hist_name(name) for name in value)
        if not expected:
            errors.append(
                f"snapshot section '{section}' declares no Prometheus "
                f"family at all")
        for family in expected:
            if family not in families:
                errors.append(
                    f"snapshot section '{section}': declared family "
                    f"'{family}' is missing from the exposition")
        if section.lower() not in doc_text:
            errors.append(
                f"snapshot section '{section}' is not documented in "
                f"docs/metrics.md")
    return errors


def lint_errors(root: str) -> List[str]:
    """All metric-lint error strings against the live registry."""
    from horovod_tpu.common import metrics

    snapshot = populated_registry().snapshot()
    text = metrics.prometheus_text(snapshot)
    return lint(text) + lint_sections(snapshot, text,
                                      _metrics_doc_text(root))


def check(root: str) -> List[Violation]:
    rel = os.path.join("horovod_tpu", "common", "metrics.py")
    from tools.hvdlint import repo_root

    if os.path.realpath(root) == os.path.realpath(repo_root()):
        try:
            errors = lint_errors(root)
        except Exception as exc:  # import/registry drift is a finding
            return [Violation("metrics", rel, 0,
                              f"metric lint could not run: {exc!r}")]
        return [Violation("metrics", rel, 0, err) for err in errors]
    # A foreign --root: this checker lints the LIVE registry, so the
    # import must resolve horovod_tpu (and this module) from the target
    # tree, not the invoker's checkout — run it in a subprocess with the
    # target tree at the head of sys.path.
    import subprocess
    import sys as _sys

    driver = ("import sys\n"
              f"sys.path.insert(0, {root!r})\n"
              "from tools.hvdlint.metrics_check import lint_errors\n"
              f"for e in lint_errors({root!r}):\n"
              "    print(e)\n")
    try:
        proc = subprocess.run([_sys.executable, "-c", driver],
                              capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as exc:
        return [Violation("metrics", rel, 0,
                          f"metric lint could not run on {root}: "
                          f"{exc!r}")]
    if proc.returncode != 0:
        return [Violation("metrics", rel, 0,
                          f"metric lint could not run on {root}: "
                          f"{proc.stderr.strip()[-500:]}")]
    return [Violation("metrics", rel, 0, line)
            for line in proc.stdout.splitlines() if line.strip()]

