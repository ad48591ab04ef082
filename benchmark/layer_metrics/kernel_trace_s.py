"""Host seconds Python spent tracing the bodies of the `pallas_call`s of
`ops/` before the window began, over every program of the process: the
step's and the reference comparison's one-device pass (the process table's
`kernel` entries, `common/metrics.py` `kernel_trace`).  0 in a cell that runs
no kernel of ours.  Source: program span."""

from benchmark.layer_metrics import _setup


def read(run: dict):
    return _setup.before_the_window(run, "kernel")
