"""1 - (time in which an operation ran on the chip) / traced window, mean
over chips: the window is about sixteen steady steps of the same loop the
end-to-end window runs.  Source: device trace."""

import statistics

from benchmark import trace_reduce


def read(run: dict):
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    start, end = trace_reduce.window(trace)
    busy = statistics.fmean(trace_reduce.busy(trace).values())
    return 100.0 * (1.0 - busy / (end - start))
