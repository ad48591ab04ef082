"""Device time of the Mamba-2 mixers' opening — `hvd_ssm_conv`: the causal
depthwise convolution over x, B and C (4,096 + 2 x 128 channels whole in the
Granite cell), its bias and its SiLU, forward and the written-out backward —
over the time of all operations.  Source: device trace, sorted by the compiled
step's op_name."""

from benchmark.layer_metrics import _hybrid


def read(run: dict):
    return _hybrid.share_pct(run, ["hvd_ssm_conv"])
