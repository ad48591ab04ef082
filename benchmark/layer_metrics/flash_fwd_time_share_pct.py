"""Device time in the custom calls named `hvd_flash_fwd`
(ops/attention.py `_flash_forward`) over the time of all operations.  With
`flash_bwd_time_share_pct` it sums to `flash_time_share_pct`.  Source:
device trace."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.kernel_share_pct(run, "hvd_flash_fwd")
