"""Device time of the chunked scan's `decay` stage — the cumulative sums of the
log-decays, the masked decay matrix `L` (heads x chunk float32 a token),
`to_end` and their exponentials — under `hvd_ssm_scan_decay`, forward and
backward, over the time of all operations: one of the four parts of the time
under `hvd_ssm_scan`.  Source: device trace, sorted by the compiled step's
op_name."""

from benchmark.layer_metrics import _granite


def read(run: dict):
    return _granite.stage_share_pct(run, "decay")
