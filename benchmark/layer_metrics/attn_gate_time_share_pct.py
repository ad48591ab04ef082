"""Device time of the attention output gate — `hvd_attn_gate`: the gate's
projection (as wide as the query's), its sigmoid and the multiply into the
kernels' output, forward and backward — over the time of all operations.  It
is part of `attn_proj_time_share_pct`.  Source: device trace, sorted by the
compiled step's op_name."""

from benchmark.layer_metrics import _hybrid


def read(run: dict):
    return _hybrid.share_pct(run, ["hvd_attn_gate"])
