"""The chunked delta rule's share of its roofline: the least time the chip
could take for every Kimi-delta layer's delta rule, forward and backward — the
larger of its products' operations over peak FLOP/s and the bytes it cannot
avoid (q, k, v, the log-decays and beta in, o out, and their cotangents) over
peak bytes/s, benchmark/ops_count_ling.py — over the time under
`hvd_kda_scan`.  Bytes bound it at these shapes (0.20 against 0.09 ms a
layer); what the form writes between its products (decay ratios a sub-block,
the solve, a state a chunk) and a loop of 128 small steps are why it reads
low.  Source: device trace."""

from benchmark.layer_metrics import _ling


def read(run: dict):
    return _ling.scan_roofline_pct(run)
