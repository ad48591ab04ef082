"""Device time of the operations inside `hvd_loss` (forward or backward; not
`hvd_loss_report`) that no layer of the model claims — norms, residual adds,
the loss's mean, and any layer added without a scope — over the time of all
operations.  With the families of `_layers.FAMILIES`, `optimizer_time_share_
pct` and what is left of `phase_unattributed_pct` once the kernels known by
name are taken out, it sums to 100.  Source: device trace, sorted by the
compiled step's op_name (`_layers.column_of`)."""

from benchmark.layer_metrics import _layers


def read(run: dict):
    return _layers.share_pct(run, "unscoped")
