"""The block-diffusion forward flash kernels' share of their roofline: the
mask's exact pairs, `L^2 + L B` a head over the two copies of L positions
(QK^T and PV, 4 x head_dim operations a pair; q, k, v read and o written once
at both copies' rows: benchmark/ops_count_sdar.py) over the time in the custom
calls named `hvd_flash_fwd_blockdiff`.  The blocks the mask cuts are the
kernels' own waste and count against it.  Operations bound it at head 128.
Source: device trace."""

from benchmark.layer_metrics import _sdar


def read(run: dict):
    return _sdar.roofline_pct(run, "fwd")
