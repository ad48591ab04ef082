#!/usr/bin/env python
"""Diff two bench records and fail on throughput regressions.

Compares the headline ``value`` (and, with ``--extras``, every shared
numeric ``extra_metrics`` entry) of two bench results and exits non-zero
when the new run regressed by more than the threshold — usable locally
("did my change cost throughput?") and as a CI gate between rounds:

    python bench.py > /tmp/new.json
    python tools/bench_compare.py /tmp/old.json /tmp/new.json --threshold 5

``--history`` renders the round-over-round trajectory instead of a gate:

    python tools/bench_compare.py --history r01.json r02.json ...

(the round files of rounds 1-5, BENCH_r01-r05.json, left the tree with the
set-up they were taken on; they remain in git history)

one line per round — headline value, vs_baseline ratio, and the delta
against the previous parseable round.  Rounds whose record failed to
parse (a driver crash leaves ``parsed`` empty) render as a gap line
rather than aborting the view.

Accepted file shapes (all produced in this repo):

* raw ``bench.py`` output — one or more JSON lines; the LAST line carrying
  a ``metric`` key wins (bench.py re-prints the headline enriched with
  extras, so the last parseable line is the most complete record);
* a driver round record (``BENCH_r*.json``) — a JSON object whose
  ``parsed`` field holds the bench record.

Headline metrics are throughputs (higher is better).  Extras ending in a
latency unit suffix (``_ms``/``_us``/``_sec`` — the serving bench's TTFT
and per-token latencies) are gated in the opposite direction: growth past
the threshold is the regression.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Optional, Tuple


def load_record(path: str) -> dict:
    """The bench record in `path` (see module docstring); raises
    ValueError when none is found."""
    with open(path) as f:
        text = f.read()
    record = None
    try:
        obj = json.loads(text)
        if isinstance(obj, dict):
            record = obj.get("parsed") if isinstance(obj.get("parsed"),
                                                     dict) else obj
    except json.JSONDecodeError:
        for line in text.splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(obj, dict) and "metric" in obj:
                record = obj
    if not isinstance(record, dict) or "metric" not in record:
        raise ValueError(f"{path}: no bench record found (want a JSON "
                         f"object with a 'metric' key, a driver record "
                         f"with 'parsed', or JSON lines)")
    return record


def _numeric(value) -> Optional[float]:
    # bool is an int subclass, but True/False extras are flags, not rates.
    if isinstance(value, (int, float)) and not isinstance(value, bool):
        return float(value)
    return None


# Sign convention for extras: every headline metric in this repo is a
# throughput (higher is better), but some extras are the opposite — a
# time-unit token marks latencies (`ttft_p99_ms`,
# `negotiation_p50_us_cached`), a `bytes` / `inflation` token marks
# wire-byte counters (`bf16_wire_bytes`, `half_wire_inflation` — the
# compression bench, docs/performance.md#wire-compression), and a
# `frames` token marks control-plane frame counts
# (`steady_frames_delta` — the negotiation_scale bench's
# zero-frames-per-steady-cycle contract,
# docs/performance.md#control-plane-scaling): growth past the threshold
# is the regression, not shrinkage.  The scale bench's `_inflation`
# ratios (`steady_scale_inflation` — the flat-in-ranks acceptance bar)
# gate the same way.  A unit preceded by "per" is a rate (`ops_per_sec`,
# `bytes_per_sec`), which stays higher-is-better.
LOWER_IS_BETTER_TOKENS = frozenset(
    ("ms", "us", "sec", "seconds", "bytes", "inflation", "frames"))


def lower_is_better(name: str) -> bool:
    # A token adjacent to "per" on either side is part of a rate
    # ("ops_per_sec", "bytes_per_sec") — rates stay higher-is-better.
    tokens = name.split("_")
    return any(t in LOWER_IS_BETTER_TOKENS
               and (i == 0 or tokens[i - 1] != "per")
               and (i + 1 >= len(tokens) or tokens[i + 1] != "per")
               for i, t in enumerate(tokens))


def compare(old: dict, new: dict, threshold_pct: float,
            extras: bool) -> Tuple[list, list]:
    """(regressions, report_lines) between two bench records.  Only pairs
    present in BOTH records compare; the headline compares only when the
    metric names match (diffing a resnet record against a transformer
    record is a usage error surfaced in the report)."""
    regressions = []
    lines = []

    def check(name: str, ov: float, nv: float) -> None:
        if ov <= 0:
            lines.append(f"  {name}: old={ov:g} (not comparable)")
            return
        delta_pct = (nv - ov) / ov * 100.0
        worse_pct = -delta_pct if lower_is_better(name) else delta_pct
        flag = ""
        if worse_pct < -threshold_pct:
            regressions.append((name, ov, nv, delta_pct))
            flag = "  << REGRESSION"
        lines.append(f"  {name}: {ov:g} -> {nv:g} "
                     f"({delta_pct:+.1f}%){flag}")

    if old["metric"] == new["metric"]:
        ov, nv = _numeric(old.get("value")), _numeric(new.get("value"))
        if ov is not None and nv is not None:
            check(old["metric"], ov, nv)
    else:
        lines.append(f"  headline metrics differ: {old['metric']} vs "
                     f"{new['metric']} (not compared)")
    if extras:
        oe = old.get("extra_metrics") or {}
        ne = new.get("extra_metrics") or {}
        for key in sorted(set(oe) & set(ne)):
            ov, nv = _numeric(oe[key]), _numeric(ne[key])
            if ov is not None and nv is not None:
                check(key, ov, nv)
    return regressions, lines


def render_history(paths: list) -> Tuple[list, int]:
    """(report lines, parseable-round count) for the --history view: one
    line per round file, in the order given (BENCH_r0*.json globs sort
    chronologically).  A round whose record cannot be parsed — e.g. a
    driver crash left ``parsed`` null — renders as a gap line; the
    trajectory deltas skip over it."""
    import os

    lines = [f"{'round':<18}{'value':>12}  {'unit':<18}"
             f"{'vs_baseline':>12}{'delta':>9}"]
    prev = None
    parsed_rounds = 0
    for path in paths:
        label = os.path.basename(path)[:17]
        try:
            with open(path) as f:
                raw = json.load(f)
        except (OSError, json.JSONDecodeError) as exc:
            lines.append(f"{label:<18}(unreadable: "
                         f"{type(exc).__name__})")
            continue
        record = raw.get("parsed") if isinstance(raw, dict) else None
        if not isinstance(record, dict) or \
                _numeric(record.get("value")) is None:
            rc = raw.get("rc") if isinstance(raw, dict) else None
            lines.append(f"{label:<18}(no parsed record"
                         f"{f', rc {rc}' if rc is not None else ''})")
            continue
        parsed_rounds += 1
        value = _numeric(record["value"])
        vs_base = _numeric(record.get("vs_baseline"))
        delta = (f"{(value - prev) / prev * 100.0:+.1f}%"
                 if prev else "-")
        lines.append(
            f"{label:<18}{value:>12g}  {record.get('unit', ''):<18}"
            f"{vs_base:>11.2f}x{delta:>9}" if vs_base is not None else
            f"{label:<18}{value:>12g}  {record.get('unit', ''):<18}"
            f"{'-':>12}{delta:>9}")
        prev = value
    return lines, parsed_rounds


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--history" in argv:
        argv.remove("--history")
        paths = [a for a in argv if not a.startswith("-")]
        if not paths:
            print("bench_compare: --history wants one or more round "
                  "files (BENCH_r0*.json)", file=sys.stderr)
            return 2
        lines, parsed_rounds = render_history(paths)
        print(f"bench_compare: history over {len(paths)} round(s)")
        for line in lines:
            print(line)
        return 0 if parsed_rounds else 2

    parser = argparse.ArgumentParser(
        description="diff two bench records; exit 1 on a >threshold% "
                    "throughput regression")
    parser.add_argument("old", help="baseline bench/driver JSON file")
    parser.add_argument("new", help="candidate bench/driver JSON file")
    parser.add_argument("--threshold", type=float, default=10.0,
                        metavar="PCT",
                        help="regression tolerance in percent (default 10)")
    parser.add_argument("--extras", action="store_true",
                        help="also gate shared numeric extra_metrics")
    args = parser.parse_args(argv)
    try:
        old = load_record(args.old)
        new = load_record(args.new)
    except (OSError, ValueError) as exc:
        print(f"bench_compare: {exc}", file=sys.stderr)
        return 2
    regressions, lines = compare(old, new, args.threshold, args.extras)
    print(f"bench_compare: {args.old} -> {args.new} "
          f"(threshold {args.threshold:g}%)")
    for line in lines:
        print(line)
    if regressions:
        print(f"bench_compare: FAIL — {len(regressions)} metric(s) "
              f"regressed more than {args.threshold:g}%", file=sys.stderr)
        return 1
    print("bench_compare: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
