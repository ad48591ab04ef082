"""Device time in the custom calls named `hvd_flash_bwd`,
`hvd_flash_bwd_dkdv` and `hvd_flash_bwd_dq` (ops/attention.py's combined and
split backward plans) over the time of all operations.  Source: device
trace."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.kernel_share_pct(run, "hvd_flash_bwd")
