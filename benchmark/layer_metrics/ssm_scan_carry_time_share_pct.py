"""Device time of the chunked scan's `carry` stage — the chunk-by-chunk decay
matrix, the states `entering` each chunk, and `from_start`, what they add to
every token — under `hvd_ssm_scan_carry`, forward and backward, over the
time of all operations: one of the four parts of the time under
`hvd_ssm_scan`.  Source: device trace, sorted by the compiled step's
op_name."""

from benchmark.layer_metrics import _granite


def read(run: dict):
    return _granite.stage_share_pct(run, "carry")
