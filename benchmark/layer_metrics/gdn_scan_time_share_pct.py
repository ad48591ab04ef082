"""Device time of the chunked delta rule under a decay a head —
`hvd_gdn_scan`: the decays' sums and the (chunk, chunk) matrix of their
exponentials, the products inside a chunk (K K^T and Q K^T once a key head),
the substitution, the recurrence between chunks, forward and backward — over
the time of all operations: what is left of `gdn_time_share_pct` is the
projections, the convolution, the gates and the norm.  Source: device trace,
sorted by the compiled step's op_name."""

from benchmark.layer_metrics import _hybrid


def read(run: dict):
    return _hybrid.share_pct(run, ["hvd_gdn_scan"])
