"""Device time of the vocabulary's far end — the head's matmul, forward and its
two backward products (`hvd_lm_head`; the weight gradient's fusion carries the
AdamW update XLA fused into it: a fusion's op_name is its matmul's), and the
cross-entropy's own passes over the logits (`hvd_token_xent`) — over the time
of all operations.  `final_norm` is outside; the update of the head's weights
that no matmul absorbed is the optimizer's.  Source: device trace, sorted by
the compiled step's op_name (`_layers.column_of`)."""

from benchmark.layer_metrics import _layers


def read(run: dict):
    return _layers.share_pct(run, "head")
