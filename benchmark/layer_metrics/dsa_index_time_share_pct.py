"""Device time under `hvd_dsa_index` — the indexer's three projections, its
key's LayerNorm, both rotations and the score product `sum_j w_j relu(q_j .
k)`, forward (`hvd_dsa_index`) and backward (`hvd_dsa_index_bwd_dq`,
`hvd_dsa_index_bwd_dk`, which run where the KL's gradient is made) — over the
time of all operations.  Part of `dsa_time_share_pct`.  Source: device trace,
sorted by the compiled step's op_name."""

from benchmark.layer_metrics import _keye, _program

probe = _program.op_names_probe


def read(run: dict):
    return _keye.share_pct(run, "index")
