"""Device time in `matmul_conv` operations (benchmark/trace_reduce.py's categories)
over the time of all operations, mean over chips.  On a TPU a convolution
with its fused epilogue (batch-norm statistics, ReLU) is one `kOutput` fusion.  Source: device trace."""

from benchmark.layer_metrics._share import category_share_pct


def read(run: dict):
    return category_share_pct(run, "matmul_conv")
