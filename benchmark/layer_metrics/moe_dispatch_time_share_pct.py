"""Device time of everything around the expert matmuls — `hvd_moe_router`
(softmax, top-k, the losses' statistics), `hvd_moe_dispatch` (sort, gather
of rows) and `hvd_moe_combine` (weights, scatter-add back to tokens),
forward and backward — over the time of all operations.  Source: device
trace, sorted by the compiled step's op_name."""

from benchmark.layer_metrics import _moe


def read(run: dict):
    return _moe.share_pct(run, [s for s in _moe.SCOPES
                                if s != "hvd_moe_experts"])
