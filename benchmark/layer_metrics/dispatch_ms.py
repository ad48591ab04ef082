"""Median host time for one step(...) call to return, over the window.
Source: the benchmark's own clock around each call (host_clock in the
program's sense: the program has no span of its own here yet)."""

import statistics


def read(run: dict):
    if not run["dispatch_s"]:
        return None
    return 1e3 * statistics.median(run["dispatch_s"])
