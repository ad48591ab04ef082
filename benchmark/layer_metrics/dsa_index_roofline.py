"""The score product's share of its roofline: one product a causal pair and
indexer head forward, and in each of the two backward kernels the same
recomputed and one gradient product, five in all; the float32 scores written
once and their cotangent read twice (benchmark/ops_count_keye.py
`index_kernel`), over the time in the custom calls named `hvd_dsa_index`,
`hvd_dsa_index_bwd_dq`, `hvd_dsa_index_bwd_dk`.  Source: device trace."""

from benchmark.layer_metrics import _keye


def read(run: dict):
    return _keye.index_roofline_pct(run)
