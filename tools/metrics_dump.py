#!/usr/bin/env python
"""Pretty-print / diff HVD_TPU_METRICS_FILE dumps (docs/metrics.md).

A dump is the JSON written at shutdown() when HVD_TPU_METRICS_FILE is set
(one file per rank: <path>.<rank>) — the same nested dict
hvd.metrics_snapshot() returns.

    python tools/metrics_dump.py run.json.0            # one dump
    python tools/metrics_dump.py before.json.0 after.json.0   # diff (B - A)
    python tools/metrics_dump.py --stragglers run.json.0      # skew view
    python tools/metrics_dump.py --tenants run.json.0  # serving tenants
    python tools/metrics_dump.py --links run.json.0    # per-link table

Prints the per-op table (ops and bytes per data plane), fusion-batch
counters, stall events, response-cache hit rates (docs/performance.md),
and per-histogram count/mean/p50/p99 estimated
from the fixed buckets (linear interpolation inside the bucket, the
standard Prometheus histogram_quantile estimate).

``--stragglers`` renders the straggler view instead: ranks ordered by
their share of ``last_to_announce`` (the coordinator's announce-order
accounting — use rank 0's dump) plus the announce-skew histogram's
estimated p50/p99 (docs/troubleshooting.md "Diagnosing stragglers").
"""

from __future__ import annotations

import json
import sys
from typing import Optional


def quantile(hist: dict, q: float) -> Optional[float]:
    """Estimate the q-quantile from fixed-bucket counts (linear
    interpolation within the bucket; the overflow bucket clamps to the
    last finite bound).  None for an empty histogram."""
    total = hist["count"]
    if not total:
        return None
    target = q * total
    cumulative = 0
    lo = 0.0
    for bound, n in zip(hist["buckets"], hist["counts"]):
        if cumulative + n >= target and n:
            return lo + (bound - lo) * (target - cumulative) / n
        cumulative += n
        lo = bound
    return hist["buckets"][-1]  # landed in the +Inf overflow bucket


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024 or unit == "TB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024
    return f"{n}B"


def _fmt_sec(v: Optional[float]) -> str:
    if v is None:
        return "-"
    if v < 1e-3:
        return f"{v * 1e6:.0f}us"
    if v < 1.0:
        return f"{v * 1e3:.2f}ms"
    return f"{v:.3f}s"


def _delta(b, a):
    return b - a


def render(snap: dict, base: Optional[dict] = None) -> str:
    """Render one dump, or the difference ``snap - base``."""
    lines = []
    tag = " (delta: B - A)" if base else ""
    lines.append(f"== collective ops{tag} ==")
    lines.append(f"{'plane':<8}{'op':<12}{'count':>10}")
    for plane, per_op in snap["ops"].items():
        for op, n in per_op.items():
            if base:
                n = _delta(n, base["ops"][plane][op])
            if n:
                lines.append(f"{plane:<8}{op:<12}{n:>10}")
    if len(lines) == 2:
        lines.append("(no ops)")

    lines.append("== bytes ==")
    for plane, per_dir in snap["bytes"].items():
        for direction, n in per_dir.items():
            if base:
                n = _delta(n, base["bytes"][plane][direction])
            lines.append(f"{plane:<8}{direction:<12}{_fmt_bytes(n):>12}")

    batches = dict(snap["batches"])
    stalls = snap["stalls"]["count"]
    if base:
        batches = {k: _delta(v, base["batches"][k])
                   for k, v in batches.items()}
        stalls = _delta(stalls, base["stalls"]["count"])
    lines.append("== fusion ==")
    lines.append(f"batches dispatched {batches['dispatched']}, "
                 f"tensors carried {batches['fused_tensors']}")
    lines.append(f"== stalls == {stalls}")
    for name, entry in snap["stalls"]["tensors"].items():
        count = entry["count"]
        if base and name in base["stalls"]["tensors"]:
            count = _delta(count, base["stalls"]["tensors"][name]["count"])
        if count:
            lines.append(f"  {name}: x{count} "
                         f"(last {entry['last_duration_sec']:.1f}s)")

    # Fault-tolerance counters (docs/fault-tolerance.md); .get() keeps
    # pre-fault-tolerance dumps readable.
    faults = snap.get("faults", {})
    base_faults = (base or {}).get("faults", {})
    injected = dict(faults.get("injected", {}))
    aborts = dict(faults.get("aborts", {}))
    if base:
        for k, v in base_faults.get("injected", {}).items():
            injected[k] = injected.get(k, 0) - v
        for k, v in base_faults.get("aborts", {}).items():
            aborts[k] = aborts.get(k, 0) - v
    lines.append("== faults ==")
    epoch = faults.get("restart_epoch", 0)
    parts = [f"restart epoch {epoch}"]
    parts.append("injected " + (
        ", ".join(f"{k}x{v}" for k, v in sorted(injected.items()) if v)
        or "none"))
    parts.append("aborts " + (
        ", ".join(f"{k}x{v}" for k, v in sorted(aborts.items()) if v)
        or "none"))
    lines.append("; ".join(parts))

    # Announce-order skew (coordinator dumps; .get keeps older dumps
    # readable).  Full detail lives behind --stragglers.
    skew = snap.get("skew", {})
    counts = dict(skew.get("last_to_announce", {}))
    if base:
        for k, v in (base or {}).get("skew", {}).get(
                "last_to_announce", {}).items():
            counts[k] = counts.get(k, 0) - v
    lines.append("== skew ==")
    nonzero = {k: v for k, v in counts.items() if v}
    if nonzero:
        worst = max(nonzero, key=nonzero.get)
        lines.append(f"negotiations {sum(nonzero.values())}; "
                     f"last_to_announce " +
                     ", ".join(f"rank{k}x{v}"
                               for k, v in sorted(nonzero.items())) +
                     f"; dominant rank {worst}")
    else:
        lines.append("(no negotiations recorded — single rank, or not the "
                     "coordinator's dump)")

    # Response cache (docs/performance.md); .get keeps pre-cache dumps
    # readable.  The hit-rate line is the first thing to look at when a
    # job's negotiation_sec p50 is higher than expected.
    cache = snap.get("cache", {})
    base_cache = (base or {}).get("cache", {})
    lines.append("== response cache ==")
    printed = False
    for plane in sorted(cache):
        c = {k: cache[plane].get(k, 0)
             for k in ("hits", "misses", "evictions")}
        if base:
            for k in c:
                c[k] -= base_cache.get(plane, {}).get(k, 0)
        total = c["hits"] + c["misses"]
        if not total and not c["evictions"]:
            continue
        printed = True
        rate = 100.0 * c["hits"] / total if total else 0.0
        size = "" if base else f", size {cache[plane].get('size', 0)}"
        lines.append(f"{plane:<8}hits {c['hits']}, misses {c['misses']}, "
                     f"evictions {c['evictions']}, "
                     f"hit-rate {rate:.1f}%{size}")
    if not printed:
        lines.append("(no cache traffic — disabled, single step, or a "
                     "pre-cache dump)")

    # Wire compression (docs/performance.md#wire-compression); .get keeps
    # pre-compression dumps readable.  Counters diff in two-file mode;
    # mode/min-bytes/residual gauges stay absolute (the B dump's state).
    comp = snap.get("compression", {})
    comp_planes = comp.get("planes", {})
    totals = {"wire": 0, "payload": 0, "compressed": 0}
    base_planes = (base or {}).get("compression", {}).get("planes", {})
    for plane, entry in comp_planes.items():
        wire, payload = entry.get("wire_bytes", 0), entry.get(
            "payload_bytes", 0)
        compressed = sum(n for m, n in entry.get("ops", {}).items()
                         if m != "none")
        if base:
            b = base_planes.get(plane, {})
            wire -= b.get("wire_bytes", 0)
            payload -= b.get("payload_bytes", 0)
            compressed -= sum(n for m, n in b.get("ops", {}).items()
                              if m != "none")
        totals["wire"] += wire
        totals["payload"] += payload
        totals["compressed"] += compressed
    if totals["payload"] or comp.get("mode", "off") != "off":
        ratio = (totals["payload"] / totals["wire"]
                 if totals["wire"] else 0.0)
        lines.append("== compression ==")
        lines.append(
            f"mode {comp.get('mode', 'off')} "
            f"(min {_fmt_bytes(comp.get('min_bytes', 0))}); wire "
            f"{_fmt_bytes(totals['wire'])} for "
            f"{_fmt_bytes(totals['payload'])} payload "
            f"({ratio:.2f}x); compressed buckets {totals['compressed']}; "
            f"residuals {_fmt_bytes(comp.get('residual_bytes', 0))} over "
            f"{comp.get('residual_tensors', 0)} tensor(s)")

    # Two-level topology (docs/performance.md#two-level-topology); only
    # rendered when the job ran hierarchical, so flat-ring dumps stay
    # unchanged.  Byte/op counters diff in two-file mode; the shape and
    # threshold stay absolute.
    topo = snap.get("topology", {})
    if topo.get("hierarchical"):
        ops = dict(topo.get("cross_ops", {}))
        tbytes = dict(topo.get("bytes", {}))
        if base:
            b = base.get("topology", {})
            for a in ops:
                ops[a] -= b.get("cross_ops", {}).get(a, 0)
            for h in tbytes:
                tbytes[h] -= b.get("bytes", {}).get(h, 0)
        lines.append("== topology ==")
        lines.append(
            f"two-level, {topo.get('nodes', 1)} node(s) x "
            f"{topo.get('local_size', 1)} local; cross algo ring "
            f"{ops.get('ring', 0)} / tree {ops.get('tree', 0)} "
            f"(boundary {_fmt_bytes(topo.get('cross_algo_threshold', 0))}); "
            f"wire local {_fmt_bytes(tbytes.get('local', 0))}, cross "
            f"{_fmt_bytes(tbytes.get('cross', 0))}")

    # Control plane (docs/performance.md#control-plane-scaling); only
    # rendered when the job ran the coordinator tree or entered the
    # decentralized steady state, so plain star dumps stay unchanged.
    # Frame/cycle counters diff in two-file mode; the shape stays
    # absolute.
    ctrl = snap.get("control", {})
    steady = ctrl.get("steady", {})
    if ctrl.get("tree") or steady.get("entries") or steady.get("cycles"):
        frames = dict(ctrl.get("frames", {}))
        cycles = steady.get("cycles", 0)
        negotiated = ctrl.get("negotiated_ticks", 0)
        if base:
            b = base.get("control", {})
            for d in frames:
                frames[d] -= b.get("frames", {}).get(d, 0)
            cycles -= b.get("steady", {}).get("cycles", 0)
            negotiated -= b.get("negotiated_ticks", 0)
        lines.append("== control ==")
        lines.append(
            f"{'tree depth 2' if ctrl.get('tree') else 'star'}, "
            f"{ctrl.get('hosts', 1)} host(s), fan-in "
            f"{ctrl.get('children', 0)}; steady "
            f"{'ACTIVE' if steady.get('active') else 'off'} "
            f"(pattern {steady.get('pattern_len', 0)}, threshold "
            f"{steady.get('threshold', 0)}), cycles {cycles} steady / "
            f"{negotiated} negotiated, entries "
            f"{steady.get('entries', 0)} / exits {steady.get('exits', 0)}; "
            f"frames sent {frames.get('sent', 0)}, received "
            f"{frames.get('received', 0)}")

    # Heartbeat failure detector (docs/fault-tolerance.md
    # #failure-detection); only rendered when the detector is armed
    # (HVD_TPU_HEARTBEAT_MS > 0), so detector-off dumps stay unchanged.
    live = snap.get("liveness", {})
    if live.get("interval_ms"):
        frames = live.get("frames", {})
        peers = live.get("peers", {})
        worst = max((p.get("misses", 0) for p in peers.values()),
                    default=0)
        lines.append("== liveness ==")
        lines.append(
            f"heartbeat every {live.get('interval_ms', 0)} ms, miss limit "
            f"{live.get('miss_limit', 0)}; beacons sent "
            f"{frames.get('sent', 0)}, received "
            f"{frames.get('received', 0)}; {len(peers)} peer(s), worst "
            f"miss streak {worst}; miss events "
            f"{live.get('miss_events', 0)}, evictions "
            f"{live.get('evictions', 0)}; clock fan-in "
            f"{live.get('clock_fanin', 0)}")

    # Anomaly verdicts (docs/metrics.md#anomalies); only rendered when
    # the detector saw something (or is explicitly disabled), so clean
    # dumps stay unchanged.  Full per-link detail lives behind --links.
    anomalies = snap.get("anomalies", {})
    verdicts = {k: v for k, v in anomalies.get("verdicts", {}).items()
                if v}
    if base:
        for k, v in (base or {}).get("anomalies", {}).get(
                "verdicts", {}).items():
            if k in verdicts:
                verdicts[k] -= v
        verdicts = {k: v for k, v in verdicts.items() if v}
    if verdicts:
        lines.append("== anomalies ==")
        lines.append(
            "verdicts " + ", ".join(f"{k}x{v}" for k, v in
                                    sorted(verdicts.items()))
            + f" (sigma {anomalies.get('sigma', 0)})")
        for e in anomalies.get("log", [])[-4:]:
            subject = f"({e.get('subject')})" if e.get("subject") else ""
            lines.append(f"  {e.get('kind')}{subject}: "
                         f"{e.get('detail', '')} "
                         f"[{e.get('age_us', 0) / 1e6:.1f}s ago]")

    # Point-to-point plane (docs/pipeline.md#observability); only
    # rendered when the rank moved p2p traffic, so pure data-parallel
    # dumps stay unchanged.  Counters diff in two-file mode; the
    # unmatched / open-channel gauges stay absolute — the B dump's live
    # state.
    p2p = dict(snap.get("p2p", {}))
    pbytes = dict(p2p.get("bytes", {}))
    if base:
        b = base.get("p2p", {})
        for k in ("sends", "recvs", "matched", "group_ops"):
            p2p[k] = p2p.get(k, 0) - b.get(k, 0)
        for d in pbytes:
            pbytes[d] = pbytes.get(d, 0) - b.get("bytes", {}).get(d, 0)
    if p2p.get("sends") or p2p.get("recvs") or p2p.get("group_ops"):
        lines.append("== p2p ==")
        lines.append(
            f"sends {p2p.get('sends', 0)} "
            f"({_fmt_bytes(pbytes.get('out', 0))}), recvs "
            f"{p2p.get('recvs', 0)} ({_fmt_bytes(pbytes.get('in', 0))}); "
            f"matched {p2p.get('matched', 0)}, unmatched in flight "
            f"{p2p.get('unmatched', 0)}; stage-group ops "
            f"{p2p.get('group_ops', 0)}; dedicated channels "
            f"{p2p.get('channels', 0)}")

    # Elastic membership (docs/fault-tolerance.md#elastic-membership);
    # only rendered once the job reshaped, so pre-elastic dumps stay
    # unchanged.
    member = snap.get("membership", {})
    if member.get("epoch") or member.get("reshapes"):
        lines.append("== membership ==")
        lost = member.get("ranks_lost", [])
        joined = member.get("ranks_joined", [])
        lines.append(
            f"epoch {member.get('epoch', 0)}, size {member.get('size', 0)}, "
            f"reshapes {member.get('reshapes', 0)}; lost "
            + (", ".join(f"rank{r}" for r in lost) or "none")
            + "; joined "
            + (", ".join(f"rank{r}" for r in joined) or "none"))

    # State plane (docs/fault-tolerance.md#state-plane); only rendered
    # once a rank armed it (or a checkpoint moved), so pre-state dumps
    # stay unchanged.  Counters diff in two-file mode; the last-step /
    # overlap gauges stay absolute — the B dump's live state.
    st = dict(snap.get("state", {}))
    if st.get("armed") or st.get("snapshots") \
            or any(st.get("ckpt", {}).values()):
        counters = ("snapshots", "snapshot_bytes", "peer_copies_sent",
                    "peer_copies_received", "restores", "peer_restores",
                    "root_broadcast_fallbacks")
        if base:
            b = base.get("state", {})
            for k in counters:
                st[k] = st.get(k, 0) - b.get(k, 0)
        ck = dict(st.get("ckpt", {}))
        if base:
            bck = base.get("state", {}).get("ckpt", {})
            ck = {k: v - bck.get(k, 0) for k, v in ck.items()}
        lines.append("== state plane ==")
        lines.append(
            f"snapshots {st.get('snapshots', 0)} "
            f"({_fmt_bytes(st.get('snapshot_bytes', 0))}, last step "
            f"{st.get('last_snapshot_step', -1)}, overlap "
            f"{100.0 * st.get('overlap_ratio', 1.0):.1f}%); peer copies "
            f"sent {st.get('peer_copies_sent', 0)} / received "
            f"{st.get('peer_copies_received', 0)} (peer last step "
            f"{st.get('peer_last_step', -1)})")
        lines.append(
            f"restores {st.get('restores', 0)} "
            f"(peer {st.get('peer_restores', 0)}, root-broadcast "
            f"fallbacks {st.get('root_broadcast_fallbacks', 0)}); ckpt "
            f"saves sharded {ck.get('sharded_saves', 0)} / legacy "
            f"{ck.get('legacy_saves', 0)}, loads {ck.get('loads', 0)}, "
            f"pruned {ck.get('pruned', 0)}")

    # Serving plane (docs/inference.md); only rendered when the rank
    # served traffic, so training dumps stay unchanged.  Per-tenant
    # detail lives behind --tenants.  Counters diff in two-file mode
    # like every other section; gauges (queue, kv blocks, occupancy)
    # stay absolute — the B dump's live state.
    serving = dict(snap.get("serving", {}))
    if base:
        base_serving = base.get("serving", {})
        for k in ("requests", "admitted", "rejected", "retired", "failed",
                  "preempted", "reformed", "steps"):
            serving[k] = serving.get(k, 0) - base_serving.get(k, 0)
    if serving.get("requests") or serving.get("steps"):
        lines.append("== serving ==")
        lines.append(
            f"requests {serving.get('requests', 0)} "
            f"(admitted {serving.get('admitted', 0)}, "
            f"rejected {serving.get('rejected', 0)}, "
            f"retired {serving.get('retired', 0)}, "
            f"failed {serving.get('failed', 0)}, "
            f"preempted {serving.get('preempted', 0)})")
        lines.append(
            f"steps {serving.get('steps', 0)}, occupancy "
            f"{100.0 * serving.get('occupancy', 0.0):.1f}%, queue "
            f"{serving.get('queue_depth', 0)}, kv blocks "
            f"{serving.get('kv_blocks_in_use', 0)}/"
            f"{serving.get('kv_blocks_total', 0)}, reshapes ridden "
            f"{serving.get('reformed', 0)}")

    # Online autotuning (docs/performance.md#autotuning); only rendered
    # when the job opted in, so pre-autotune dumps stay unchanged.
    tune = snap.get("autotune", {})
    if tune.get("enabled"):
        lines.append("== autotune ==")
        state = "frozen" if tune.get("frozen") else "searching"
        lines.append(
            f"{state} after {tune.get('windows', 0)} window(s): "
            f"fusion {_fmt_bytes(tune.get('fusion_threshold', 0))}, "
            f"cycle {tune.get('cycle_time_ms', 0.0):g} ms, "
            f"best score {tune.get('best_score', 0.0):.0f}")

    lines.append("== histograms ==")
    lines.append(f"{'name':<18}{'count':>8}{'mean':>10}{'p50':>10}"
                 f"{'p99':>10}")
    for name, hist in snap["histograms"].items():
        if base:
            b = base["histograms"][name]
            hist = {"buckets": hist["buckets"],
                    "counts": [x - y for x, y in zip(hist["counts"],
                                                     b["counts"])],
                    "sum": hist["sum"] - b["sum"],
                    "count": hist["count"] - b["count"]}
        mean = hist["sum"] / hist["count"] if hist["count"] else None
        fmt = _fmt_sec if name.endswith("_sec") else (
            lambda v: "-" if v is None else f"{v:.2f}")
        lines.append(f"{name:<18}{hist['count']:>8}{fmt(mean):>10}"
                     f"{fmt(quantile(hist, 0.5)):>10}"
                     f"{fmt(quantile(hist, 0.99)):>10}")
    return "\n".join(lines)


def render_tenants(snap: dict) -> str:
    """The --tenants view: per-tenant request/token/reject breakdown from
    the serving section (docs/inference.md; use rank 0's dump — the
    scheduler lives there)."""
    lines = ["== tenants (serving plane, rank-0 scheduler view) =="]
    tenants = snap.get("serving", {}).get("tenants", {})
    if not tenants:
        lines.append("(no serving traffic recorded — not a serving rank, "
                     "or not the scheduler's dump; use rank 0's file)")
        return "\n".join(lines)
    lines.append(f"{'tenant':<16}{'admitted':>9}{'rejected':>9}"
                 f"{'retired':>8}{'failed':>7}{'prompt':>8}{'gen':>8}")
    for name in sorted(tenants,
                       key=lambda t: -tenants[t].get("admitted", 0)):
        e = tenants[name]
        lines.append(f"{name[:15]:<16}{e.get('admitted', 0):>9}"
                     f"{e.get('rejected', 0):>9}{e.get('retired', 0):>8}"
                     f"{e.get('failed', 0):>7}"
                     f"{e.get('prompt_tokens', 0):>8}"
                     f"{e.get('generated_tokens', 0):>8}")
    total_rej = sum(e.get("rejected", 0) for e in tenants.values())
    total_req = sum(e.get("requests", 0) for e in tenants.values())
    lines.append(f"shed rate: {total_rej}/{total_req} requests rejected "
                 f"({100.0 * total_rej / max(total_req, 1):.1f}%)")
    return "\n".join(lines)


def render_stragglers(snap: dict) -> str:
    """The --stragglers view: ranks by last_to_announce share plus the
    announce-skew histogram's estimated p50/p99."""
    lines = ["== stragglers (last_to_announce share, coordinator view) =="]
    counts = {int(k): v for k, v in
              snap.get("skew", {}).get("last_to_announce", {}).items()}
    total = sum(counts.values())
    if not total:
        lines.append("(no negotiations recorded — single rank, or not the "
                     "coordinator's dump; use rank 0's file)")
    else:
        lines.append(f"{'rank':<6}{'last':>8}{'share':>9}")
        for r, n in sorted(counts.items(), key=lambda kv: (-kv[1], kv[0])):
            lines.append(f"{r:<6}{n:>8}{100.0 * n / total:>8.1f}%")
        worst = max(counts, key=counts.get)
        lines.append(f"dominant straggler: rank {worst} "
                     f"({100.0 * counts[worst] / total:.1f}% of "
                     f"{total} negotiations)")
    hist = snap.get("histograms", {}).get("announce_skew_sec")
    if hist and hist.get("count"):
        lines.append(f"announce skew: n={hist['count']} "
                     f"p50={_fmt_sec(quantile(hist, 0.5))} "
                     f"p99={_fmt_sec(quantile(hist, 0.99))}")
    else:
        lines.append("announce skew: (empty histogram)")
    return "\n".join(lines)


def render_links(snap: dict) -> str:
    """The --links view: one row per peer link — bytes each way, timed
    sends with mean/p99 latency estimated from the fixed buckets,
    heartbeat-echo RTT, and transport backpressure
    (docs/metrics.md#links)."""
    lines = ["== links (per-peer transport telemetry) =="]
    links = snap.get("links", {})
    peers = links.get("peers", {})
    if not links.get("enabled", False):
        lines.append("(link telemetry disabled — HVD_TPU_LINK_STATS=0, "
                     "or a pre-telemetry dump)")
        return "\n".join(lines)
    if not peers:
        lines.append("(no links — single rank)")
        return "\n".join(lines)
    # Bucket bounds mirror LINK_SEND_BUCKETS_US (common/metrics.py) so
    # the tool stays importable without the package on scrape hosts.
    bounds = [50, 100, 250, 500, 1000, 2500, 5000, 10000, 50000]
    lines.append(f"{'peer':<6}{'out':>10}{'in':>10}{'sends':>8}"
                 f"{'mean':>9}{'p99':>9}{'rtt':>9}{'stalls':>8}")
    for r in sorted(peers, key=int):
        v = peers[r]
        count = v.get("send_us_count", 0)
        mean = (f"{v.get('send_us_sum', 0) / count:.0f}us"
                if count else "-")
        hist = {"buckets": bounds,
                "counts": v.get("send_us_buckets", [])[:len(bounds)],
                "count": count}
        p99 = quantile(hist, 0.99) if count else None
        rtt = (f"{v.get('rtt_ewma_us', 0)}us"
               if v.get("rtt_samples", 0) else "-")
        stalls = v.get("stalls", 0) + v.get("short_writes", 0)
        lines.append(
            f"{r:<6}{_fmt_bytes(v.get('bytes_out', 0)):>10}"
            f"{_fmt_bytes(v.get('bytes_in', 0)):>10}"
            f"{v.get('sends', 0):>8}{mean:>9}"
            f"{'-' if p99 is None else f'{p99:.0f}us':>9}"
            f"{rtt:>9}{stalls:>8}")
    verdicts = snap.get("anomalies", {}).get("verdicts", {})
    slow = [e for e in snap.get("anomalies", {}).get("log", [])
            if e.get("kind") == "slow_link"]
    if verdicts.get("slow_link"):
        lines.append("slow-link verdicts: " + "; ".join(
            f"{e.get('subject')} ({e.get('detail', '')})"
            for e in slow[-4:]))
    return "\n".join(lines)


def main(argv) -> int:
    argv = list(argv)
    stragglers = "--stragglers" in argv
    if stragglers:
        argv.remove("--stragglers")
    tenants = "--tenants" in argv
    if tenants:
        argv.remove("--tenants")
    links = "--links" in argv
    if links:
        argv.remove("--links")
    if len(argv) not in (2, 3) or argv[1] in ("-h", "--help"):
        print(__doc__)
        return 2
    if (stragglers or tenants or links) and len(argv) != 2:
        print("--stragglers/--tenants/--links take a single dump",
              file=sys.stderr)
        return 2
    with open(argv[1]) as f:
        a = json.load(f)
    if stragglers:
        print(render_stragglers(a))
        return 0
    if tenants:
        print(render_tenants(a))
        return 0
    if links:
        print(render_links(a))
        return 0
    if len(argv) == 3:
        with open(argv[2]) as f:
            b = json.load(f)
        print(f"A: {argv[1]}\nB: {argv[2]}")
        print(render(b, base=a))
    else:
        print(render(a))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
