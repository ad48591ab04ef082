"""Device time of the chunked delta rule — `hvd_kda_scan`: the decays' sums,
the products inside a chunk, the substitution, the recurrence between chunks,
forward and backward — over the time of all operations: what is left of
`kda_time_share_pct` is the projections, the convolution, the gates and the
norm.  Source: device trace, sorted by the compiled step's op_name."""

from benchmark.layer_metrics import _hybrid


def read(run: dict):
    return _hybrid.share_pct(run, ["hvd_kda_scan"])
