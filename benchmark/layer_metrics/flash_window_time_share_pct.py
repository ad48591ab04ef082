"""Device time in the banded flash kernels — the custom calls named
`hvd_flash_fwd_window`, `hvd_flash_bwd_window`, `hvd_flash_bwd_dkdv_window`,
`hvd_flash_bwd_dq_window` (ops/attention.py under `window=`) — over the time
of all operations: the windowed layers' part of `flash_fwd_time_share_pct` +
`flash_bwd_time_share_pct`.  Source: device trace."""

from benchmark.layer_metrics import _trinity


def read(run: dict):
    timed = _trinity.kernel_time(run, _trinity.WINDOW.values())
    return timed and 100.0 * timed[0] / timed[1]
