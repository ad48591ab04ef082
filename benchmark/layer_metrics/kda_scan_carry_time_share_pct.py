"""Device time of one stage of the chunked delta rule — the `lax.scan` between
chunks (`hvd_kda_scan_carry`): the moves of its operands and of its output,
the operations of the loop's body, AND the `while`s' own events, which span
their bodies (an operations line counts a loop with what runs inside it, in
every share's numerator and denominator alike), forward and backward — over
the time of all operations.  The four stages partition `hvd_kda_scan`: their
shares sum to `kda_scan_time_share_pct`.  Source: device trace, sorted by the
compiled step's op_name (`_layers.stage_of`)."""

from benchmark.layer_metrics import _layers


def read(run: dict):
    return _layers.stage_share_pct(run, "carry")
