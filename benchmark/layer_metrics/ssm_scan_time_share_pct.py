"""Device time of the Mamba-2 mixers' memory-bound middle — `hvd_ssm_scan`
(the chunked scan) and `hvd_ssm_conv` (the causal convolution and its silu),
forward and backward — over the time of all operations: what is left of
`ssm_time_share_pct` is the two projections and the gated norm.  Source:
device trace, sorted by the compiled step's op_name."""

from benchmark.layer_metrics import _hybrid


def read(run: dict):
    return _hybrid.share_pct(run, ["hvd_ssm_scan", "hvd_ssm_conv"])
