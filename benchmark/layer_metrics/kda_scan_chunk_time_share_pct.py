"""Device time of one stage of the chunked delta rule — the products inside a
chunk: K K^T and Q K^T through the sub-blocks, the decayed Q and K the
recurrence reads (`hvd_kda_scan_chunk`), forward and backward — over the time
of all operations.  The four stages partition `hvd_kda_scan`: their shares sum
to `kda_scan_time_share_pct`.  Source: device trace, sorted by the compiled
step's op_name (`_layers.stage_of`)."""

from benchmark.layer_metrics import _layers


def read(run: dict):
    return _layers.stage_share_pct(run, "chunk")
