"""The chunked scan's share of its roofline: the least time the chip could
take for every Mamba-2 layer's scan, forward and backward — the larger of the
four products' operations over peak FLOP/s and the bytes the scan cannot avoid
(x, B, C, dt in, y out, and their cotangents) over peak bytes/s — over the
time under `hvd_ssm_scan`.  Bytes bound it at these shapes (73 against 51 us a
layer); what the form writes between its products (decay matrices, states)
is why it reads low.  Source: device trace."""

from benchmark.layer_metrics import _hybrid


def read(run: dict):
    return _hybrid.scan_roofline_pct(run)
