"""Device time of the operations that autodiff transposed
(`transpose(jvp(hvd_loss))` in the scope path) over the time of all
operations; forward is the rest less optimizer and unattributed.  Source:
device trace, sorted by the compiled step's op_name."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.phase_share_pct(run, "backward")
