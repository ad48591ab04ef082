"""The grouped expert matmuls' share of their roofline: the least time the
chip could take for gate, up and down, forward and both gradients, on the
rows really routed to this chip's experts, over the time under
`hvd_moe_experts` (the kernels, the weights' casts and the silu between
them).  Operations bound it at these shapes (3.1 against 1.8 ms a layer).
Source: device trace; rows from `moe_load_max_over_mean`'s probe."""

from benchmark.layer_metrics import _moe


def read(run: dict):
    return _moe.experts_roofline_pct(run)
