"""Device time of the Mamba-2 mixers' gated norm — `hvd_ssm_gate_norm`:
`y * silu(z)` and the RMSNorm within each group (ONE group in the Granite
cell: all 4,096 channels), float32, forward and backward — over the time of
all operations.  Source: device trace, sorted by the compiled step's
op_name."""

from benchmark.layer_metrics import _hybrid


def read(run: dict):
    return _hybrid.share_pct(run, ["hvd_ssm_gate_norm"])
