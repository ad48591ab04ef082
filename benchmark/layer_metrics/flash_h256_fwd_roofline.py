"""The forward flash kernel's share of its roofline at a head of 256: QK^T
and PV over the causal pairs, q in and o out a query head, k and v in once a
key/value head (benchmark/ops_count_qwen3next.py), over the time in the custom
calls named `hvd_flash_fwd`.  Operations bound it.  Source: device trace."""

from benchmark.layer_metrics import _qwen3next


def read(run: dict):
    return _qwen3next.flash_roofline_pct(run, "hvd_flash_fwd", "fwd")
