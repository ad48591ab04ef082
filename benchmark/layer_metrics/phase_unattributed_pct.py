"""Device time of the operations no scope of the program claims (no
op_name, or one outside `hvd_loss` and `hvd_optimizer`) over the time of all
operations: how far the optimizer and backward shares can be trusted.
Source: device trace, sorted by the compiled step's op_name."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.phase_share_pct(run, "unattributed")
