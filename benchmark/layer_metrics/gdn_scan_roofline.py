"""The chunked delta rule's share of its roofline under a decay a head over
grouped heads: the least time the chip could take for every Gated DeltaNet
layer's delta rule, forward and backward — the larger of its products'
operations over peak FLOP/s (K K^T and Q K^T once a KEY head) and the bytes it
cannot avoid (q, k a key head, v, the log-decay and beta a value head in, o
out, and their cotangents) over peak bytes/s, benchmark/ops_count_qwen3next.py
— over the time under `hvd_gdn_scan`.  What the form writes between its
products (a decay matrix, the solve, the decayed Q and K a value head, a state
a chunk) and a loop of 64 small steps are why it reads low.  Source: device
trace."""

from benchmark.layer_metrics import _qwen3next


def read(run: dict):
    return _qwen3next.scan_roofline_pct(run)
