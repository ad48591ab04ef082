"""Device time in `matmul_conv` operations (benchmark/trace_reduce.py's categories)
over the time of all operations, mean over chips.  On a TPU every dot is a
convolution, and with its fused epilogue one `kOutput` fusion: the projections,
the MLP and the 50k-row head with its loss.  Source: device trace."""

from benchmark.layer_metrics._share import category_share_pct


def read(run: dict):
    return category_share_pct(run, "matmul_conv")
