"""The causal forward flash kernels' share of their roofline in a model that
also has banded ones: `flash_fwd_roofline`'s causal count (the half with its
diagonal; benchmark/ops_count_trinity.py) for the full-attention layers alone,
over the time in the custom calls named `hvd_flash_fwd*` WITHOUT the `_window`
suffix.  Operations bound it at head 128.  Source: device trace."""

from benchmark.layer_metrics import _trinity


def read(run: dict):
    return _trinity.roofline_pct(run, "flash_full", _trinity.FULL, "fwd")
