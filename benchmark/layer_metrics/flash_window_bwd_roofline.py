"""The banded backward flash kernels' share of their roofline: the band's exact
pairs, `W (W + 1) / 2 + (S - W) W` a head (2.5 times the forward's operations; q, k, v, o, do read and dq, dk, dv written once:
benchmark/ops_count_trinity.py) over the time in the custom calls named
`hvd_flash_bwd*_window`.  The blocks the band's edges cut are the kernels' own
waste and count against it.  Operations bound it at head 128.  Source: device
trace."""

from benchmark.layer_metrics import _trinity


def read(run: dict):
    return _trinity.roofline_pct(run, "flash_window", _trinity.WINDOW, "bwd")
