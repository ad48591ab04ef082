"""The forward flash kernel's share of its roofline: `flash_roofline`'s
arithmetic over the custom calls named `hvd_flash_fwd` and the forward's
part of the kernels' work.  Source: device trace."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.flash_roofline_pct(run, "hvd_flash_fwd", backward=False)
