"""The share of a chip's interconnect the step's all-reduces reach while they
are in flight: 2 (n - 1) / n times their bytes over `peaks.json`'s
`ici_bits_per_s`, over the union of their in-flight intervals (a start's
beginning to its done's end; a synchronous one's own event), mean over chips.
Over 100 is a wrong interval, not a fast chip.  Source: device trace."""

from benchmark.layer_metrics import _exchange


def read(run: dict):
    return _exchange.ici_pct(run)
