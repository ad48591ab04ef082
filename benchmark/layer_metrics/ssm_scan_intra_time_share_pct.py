"""Device time of the chunked scan's `intra` stage — `dt x`, `scores = C B^T`,
the `mixed` operand, the within-chunk product and the skip `D x` — under
`hvd_ssm_scan_intra`, forward and backward, over the time of all operations:
one of the four parts of the time under `hvd_ssm_scan`.  Source: device
trace, sorted by the compiled step's op_name."""

from benchmark.layer_metrics import _granite


def read(run: dict):
    return _granite.stage_share_pct(run, "intra")
