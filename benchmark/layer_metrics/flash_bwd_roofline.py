"""The backward flash kernels' share of their roofline: `flash_roofline`'s
arithmetic over the custom calls named `hvd_flash_bwd*` and the backward's
part of the kernels' work (the recompute counted: the kernel does it).
Source: device trace."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.flash_roofline_pct(run, "hvd_flash_bwd", backward=True)
