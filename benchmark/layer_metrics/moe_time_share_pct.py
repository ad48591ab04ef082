"""Device time of the operations of the sparse-expert layer — scope path
holding `hvd_moe_` (router, dispatch, experts, combine), forward and
backward, libtpu's grouped-matmul kernels among them — over the time of all
operations.  Source: device trace, sorted by the compiled step's op_name."""

from benchmark.layer_metrics import _moe


def read(run: dict):
    return _moe.share_pct(run, _moe.SCOPES)
