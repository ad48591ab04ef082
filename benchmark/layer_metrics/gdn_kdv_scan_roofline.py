"""The chunked delta rule's share of its roofline where a head's keys and
values have widths of their own (Olmo-Hybrid: 96 and 192, 15 heads here, three
layers): the least time the chip could take for every Gated DeltaNet layer's
delta rule, forward and backward — the larger of its products' operations over
peak FLOP/s and the bytes it cannot avoid over peak bytes/s at the TRUE widths,
benchmark/ops_count_olmohybrid.py — over the time under `hvd_gdn_scan`.  A key
of 96 fills three quarters of a 128-lane tile and a value of 192 one and a
half: what Mosaic pads them to is in the time and not in the least time, so
the padding reads as distance from 100 like everything else the form writes
between its products.  `gdn_scan_roofline` is the same quantity at one width
of 128 (Qwen3-Next's cell).  Source: device trace."""

from benchmark.layer_metrics import _olmohybrid


def read(run: dict):
    return _olmohybrid.scan_roofline_pct(run)
