"""Device time of one stage of the chunked delta rule — the unit-lower-
triangular solve with its written-out backward, and T applied to beta K G and
beta V (`hvd_kda_scan_solve`), forward and backward — over the time of all
operations.  The four stages partition `hvd_kda_scan`: their shares sum to
`kda_scan_time_share_pct`.  Source: device trace, sorted by the compiled
step's op_name (`_layers.stage_of`)."""

from benchmark.layer_metrics import _layers


def read(run: dict):
    return _layers.stage_share_pct(run, "solve")
