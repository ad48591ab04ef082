"""Device time of the embedding lookup and of its gradient, the scatter-add
of a step's token rows into the table (`hvd_embed`), over the time of all
operations.  Source: device trace, sorted by the compiled step's op_name
(`_layers.column_of`)."""

from benchmark.layer_metrics import _layers


def read(run: dict):
    return _layers.share_pct(run, "embed")
