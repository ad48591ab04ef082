#!/usr/bin/env python3
"""Run one cell several times the way the driver does and say how far the
runs spread: for each set the median of every end-to-end metric and its
spread (distance between the quartiles over the median), each run a process
of its own with a seed of its own.  A bound is about five times the widest
spread over the cells (PERF.md section 2).

    python benchmark/measure_sets.py --workload <cell> [--sets 2] [--runs 6]
        [--seed0 100] [--seconds <run_seconds>] [--traced 1] [--out DIR]

This parent never imports JAX (a chip belongs to one process at a time).
Every result line goes to DIR/<cell>.jsonl (default chiprun_out/), the
summary is the last line of standard output.  `--traced 1` adds one traced
run after the sets.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def quartile_spread(values):
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4, method="inclusive")
    return (q[2] - q[0]) / statistics.median(values)


def one_run(workload, seed, seconds, trace, log):
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    t0 = time.monotonic()
    proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE, text=True)
    lines = proc.stdout.strip().splitlines()
    record = {"seed": seed, "trace": trace, "exit_code": proc.returncode,
              "process_seconds": round(time.monotonic() - t0, 1),
              "notes": [], "result": None}
    for line in lines:
        try:
            record["notes"].append(json.loads(line))
        except ValueError:
            record["notes"].append(line)
    if proc.returncode == 0 and record["notes"]:
        record["result"] = record["notes"].pop()
    log.write(json.dumps(record) + "\n")
    log.flush()
    return record


def main() -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--seed0", type=int, default=100)
    parser.add_argument("--seconds", type=int,
                        default=manifest["run_seconds"])
    parser.add_argument("--traced", type=int, choices=(0, 1), default=0)
    parser.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = parser.parse_args()
    os.makedirs(args.out, exist_ok=True)
    summary = {"workload": args.workload, "seconds": args.seconds, "sets": []}
    with open(os.path.join(args.out, args.workload + ".jsonl"), "a") as log:
        seed = args.seed0
        for _ in range(args.sets):
            values, seeds, ok = {}, [], True
            for _ in range(args.runs):
                record = one_run(args.workload, seed, args.seconds, 0, log)
                seeds.append(seed)
                seed += 1
                result = record["result"]
                ok = ok and bool(result and result["correct"])
                for name, metric in ((result or {}).get("metrics")
                                     or {}).items():
                    values.setdefault(name, []).append(metric["value"])
            summary["sets"].append({
                "seeds": seeds, "all_correct": ok,
                "metrics": {name: {"values": v,
                                   "median": statistics.median(v),
                                   "median_without_first": statistics.median(
                                       v[1:]) if len(v) > 1 else None,
                                   "spread": quartile_spread(v)}
                            for name, v in values.items()}})
        if args.traced:
            record = one_run(args.workload, seed, args.seconds, 1, log)
            summary["traced"] = record["result"]
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
