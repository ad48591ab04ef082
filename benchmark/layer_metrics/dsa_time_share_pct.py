"""Device time of the learned selection's machinery — the operations under
`hvd_dsa_index` (the indexer's projections, norm, rotation and the score
product, forward and backward), `hvd_dsa_select` (thresholds and selection)
and `hvd_dsa_kl` (target pass, KL terms, their gradient), the `hvd_dsa_*`
kernels among them, and the `_selected` flash kernels — over the time of all
operations: what a layer with an indexer costs, attention included.  Source:
device trace, sorted by the compiled step's op_name and by instruction name."""

from benchmark.layer_metrics import _keye, _program

probe = _program.op_names_probe


def read(run: dict):
    return _keye.share_pct(run, "index", "select", "kl", "flash")
