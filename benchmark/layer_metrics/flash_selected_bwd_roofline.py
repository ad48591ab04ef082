"""The selected backward flash kernels' share of their roofline: 2.5 times the
forward's operations over the causal pairs; q, k, v, o, do read and dq, dk, dv
written once (benchmark/ops_count_keye.py `flash_kernel`), over the time in the
custom calls named `hvd_flash_bwd_selected`, `hvd_flash_bwd_dkdv_selected`,
`hvd_flash_bwd_dq_selected`.  Source: device trace."""

from benchmark.layer_metrics import _keye


def read(run: dict):
    return _keye.flash_roofline_pct(run, "bwd")
