"""Time a collective operation runs on a chip while no compute operation
runs there, over the traced window, mean over chips
(benchmark/trace_reduce.py `exposed_collective`).  Source: device trace."""

from benchmark import trace_reduce


def read(run: dict):
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    start, end = trace_reduce.window(trace)
    return 100.0 * trace_reduce.exposed_collective(trace) / (end - start)
