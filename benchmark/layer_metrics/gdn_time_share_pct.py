"""Device time of the operations of the Gated DeltaNet mixers — scope path
holding `hvd_gdn_` (in_proj, conv, gate, scan, gate_norm, out_proj), forward
and backward — over the time of all operations.  Source: device trace, sorted
by the compiled step's op_name."""

from benchmark.layer_metrics import _hybrid


def read(run: dict):
    return _hybrid.share_pct(run, ["hvd_gdn_"])
