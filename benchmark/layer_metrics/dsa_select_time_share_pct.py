"""Device time under `hvd_dsa_select` — each row's threshold, the `topk`-th
largest score by 32 compare-and-count passes over the scores' ordered bits, the
selection as int8 and its counts — over the time of all operations.  Part of
`dsa_time_share_pct`.  Source: device trace, sorted by the compiled step's
op_name."""

from benchmark.layer_metrics import _keye, _program

probe = _program.op_names_probe


def read(run: dict):
    return _keye.share_pct(run, "select")
