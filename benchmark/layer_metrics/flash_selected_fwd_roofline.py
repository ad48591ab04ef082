"""The selected forward flash kernels' share of their roofline: what the kernel
executes, the causal pairs a head (QK^T and PV, 4 x head_dim operations a pair;
q, k, v read and o written once: benchmark/ops_count_keye.py `flash_kernel`),
over the time in the custom calls named `hvd_flash_fwd_selected`.  The mask is
data and no tile is skipped for it, so the unselected pairs of a tile are
work done, counted here, and waste only to `mfu_pct`; the int8 selection tiles
every head reads again count against it.  Source: device trace."""

from benchmark.layer_metrics import _keye


def read(run: dict):
    return _keye.flash_roofline_pct(run, "fwd")
