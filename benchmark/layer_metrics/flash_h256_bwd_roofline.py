"""The backward flash kernels' share of their roofline at a head of 256: the
probabilities again and four gradient products, 2.5 times the forward's
operations (benchmark/ops_count_qwen3next.py), over the time in the custom
calls named `hvd_flash_bwd...` (at this width the split pair,
`hvd_flash_bwd_dkdv` and `hvd_flash_bwd_dq`, which recomputes the
probabilities twice: that second recompute is not counted as work, so the pair
cannot read over five sevenths).  Source: device trace."""

from benchmark.layer_metrics import _qwen3next


def read(run: dict):
    return _qwen3next.flash_roofline_pct(run, "hvd_flash_bwd", "bwd")
