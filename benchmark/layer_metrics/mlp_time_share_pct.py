"""Device time of the dense MLPs — `Block`'s up-gelu-down and `GatedMLP`,
forward and backward (`hvd_mlp`) — over the time of all operations.  A cell
whose configuration has no dense MLP (every block sparse experts) is not
listed for it.  Source: device trace, sorted by the compiled step's op_name
(`_layers.column_of`)."""

from benchmark.layer_metrics import _layers


def read(run: dict):
    return _layers.share_pct(run, "mlp")
