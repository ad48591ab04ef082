"""Device time in the selected flash kernels — the custom calls named
`hvd_flash_fwd_selected`, `hvd_flash_bwd_selected`,
`hvd_flash_bwd_dkdv_selected`, `hvd_flash_bwd_dq_selected` (ops/attention.py
under a `Selected` mask) — over the time of all operations: the part of
`flash_fwd_time_share_pct` + `flash_bwd_time_share_pct` that runs under a mask
of data.  Part of `dsa_time_share_pct`.  Source: device trace."""

from benchmark.layer_metrics import _keye


def read(run: dict):
    return _keye.flash_time_share_pct(run)
