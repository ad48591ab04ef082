"""Model FLOP/s utilisation: operations the forward and backward passes
require per sample (benchmark/ops_count.py: 2 per multiply-add, nothing
recomputed) x samples per second per chip / the chip's published peak
(benchmark/peaks.json).  The same number as the throughput, comparable
across cells.  Source: the benchmark's count and clock."""

from benchmark.end_to_end_metrics.throughput import \
    samples_per_second_per_chip


def read(run: dict):
    per_chip = samples_per_second_per_chip(run)
    if not run["peak"] or per_chip is None:
        return None
    return 100.0 * per_chip * run["ops_per_sample"]["total"] \
        / run["peak"]["bf16_flops_per_s"]
