"""The backward flash kernels' share of their roofline at latent attention's
two widths: the probabilities again and four gradient products, 3 x 192 + 2 x
128 against the forward's 192 + 128 (benchmark/ops_count_ling.py), over the
time in the custom calls named `hvd_flash_bwd...` (at these widths the split
pair, `hvd_flash_bwd_dkdv` and `hvd_flash_bwd_dq`, which recomputes the
probabilities twice: that second recompute is not counted as work, so the pair
cannot read over five sevenths).  Source: device trace."""

from benchmark.layer_metrics import _ling


def read(run: dict):
    return _ling.flash_roofline_pct(run, "hvd_flash_bwd", "bwd")
