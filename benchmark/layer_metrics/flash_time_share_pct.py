"""Device time in `custom_call` operations (benchmark/trace_reduce.py's categories)
over the time of all operations, mean over chips.  Source: device trace."""

from benchmark.layer_metrics._share import category_share_pct


def read(run: dict):
    return category_share_pct(run, "custom_call")
