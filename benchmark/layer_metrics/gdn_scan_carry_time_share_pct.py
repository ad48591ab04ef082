"""Device time of one stage of the chunked delta rule under a decay a head —
the recurrence between chunks (`hvd_gdn_scan_carry`): whatever walks the
chunks with the state (two `while`s a layer, their bodies and the products
over all chunks around them; or the custom calls `hvd_gdn_scan_carry_fwd` and
`hvd_gdn_scan_carry_bwd`, which hold the state on the chip), the moves of its
operands and the move of `o` back to tokens, forward and backward — over the
time of all operations.  A part of `gdn_scan_time_share_pct`; a `while`'s own
event spans its body and is counted beside it, in numerator and denominator
alike.  A program without the scope gives None.  Source: device trace, sorted
by the compiled step's op_name."""

from benchmark.layer_metrics import _hybrid


def read(run: dict):
    return _hybrid.share_pct(run, ["hvd_gdn_scan_carry"])
