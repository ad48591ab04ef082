"""Device time of the rotary embedding — `hvd_attn_rotate`: each rotation
`Attention` makes of q and of k (a grouped layer's k in front of its repeat),
forward and backward — over the time of all operations.  It is part of
`attn_proj_time_share_pct`.  Source: device trace, sorted by the compiled
step's op_name."""

from benchmark.layer_metrics import _hybrid


def read(run: dict):
    return _hybrid.share_pct(run, ["hvd_attn_rotate"])
