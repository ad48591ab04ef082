"""The latent experts' grouped matmuls' share of their roofline: the least
time the chip could take for up and down, forward and both gradients (six a
layer), on the rows really routed to this chip's experts, over the time under
`hvd_moe_experts` (the kernels, the weights' casts and the relu squared
between them).  Reading the weights bounds it at these shapes (0.40 against
0.24 ms a layer).  Source: device trace; rows from `moe_load_max_over_mean`'s
probe."""

from benchmark.layer_metrics import _hybrid


def read(run: dict):
    return _hybrid.experts_roofline_pct(run)
