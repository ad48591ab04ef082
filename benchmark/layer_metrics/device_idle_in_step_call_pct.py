"""The part of the traced window in which the first chip ran nothing while
the host was inside the library's call (an `hvd.train_step` span was open):
`device_idle_pct` less this is idle while the host was in the loop around
the call.  Host and device stamps agree to about half a millisecond
(PERF.md section 3), which is the figure's uncertainty per gap.  Source:
device trace and program span."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.idle_in_step_call_pct(run)
