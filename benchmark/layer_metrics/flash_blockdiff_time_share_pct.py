"""Device time in the block-diffusion flash kernels — the custom calls named
`hvd_flash_fwd_blockdiff`, `hvd_flash_bwd_blockdiff`,
`hvd_flash_bwd_dkdv_blockdiff`, `hvd_flash_bwd_dq_blockdiff` (ops/attention.py
under `block_diffusion=`) — over the time of all operations: the part of
`flash_fwd_time_share_pct` + `flash_bwd_time_share_pct` that is the block
mask's.  Source: device trace."""

from benchmark.layer_metrics import _sdar


def read(run: dict):
    return _sdar.time_share_pct(run)
