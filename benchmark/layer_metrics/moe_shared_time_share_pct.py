"""Device time of the shared expert — `hvd_moe_shared`: up, relu squared,
down, on every token, forward and backward — over the time of all operations.
`moe_time_share_pct` reads the four routed scopes and leaves this one and
`hvd_moe_latent` out.  Source: device trace, sorted by the compiled step's
op_name."""

from benchmark.layer_metrics import _hybrid


def read(run: dict):
    return _hybrid.share_pct(run, ["hvd_moe_shared"])
