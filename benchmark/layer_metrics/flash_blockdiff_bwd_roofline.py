"""The block-diffusion backward flash kernels' share of their roofline: the
mask's exact pairs, `L^2 + L B` a head over the two copies of L positions (2.5
times the forward's operations; q, k, v, o, do read and dq, dk, dv written
once: benchmark/ops_count_sdar.py) over the time in the custom calls named
`hvd_flash_bwd*_blockdiff`.  The blocks the mask cuts are the kernels' own
waste and count against it.  Operations bound it at head 128.  Source: device
trace."""

from benchmark.layer_metrics import _sdar


def read(run: dict):
    return _sdar.roofline_pct(run, "bwd")
