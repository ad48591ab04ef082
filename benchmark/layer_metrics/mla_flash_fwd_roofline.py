"""The forward flash kernel's share of its roofline at latent attention's two
widths: QK^T at 192 and PV at 128 over the causal half, q, k, v in and o out
(benchmark/ops_count_ling.py), over the time in the custom calls named
`hvd_flash_fwd`.  `flash_fwd_roofline`'s one-width count would read 4 % off.
Operations bound it.  Source: device trace."""

from benchmark.layer_metrics import _ling


def read(run: dict):
    return _ling.flash_roofline_pct(run, "hvd_flash_fwd", "fwd")
