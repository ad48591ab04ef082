"""Device time under `hvd_dsa_kl` — the target pass `hvd_dsa_probs` (the
attention's probabilities summed over the heads, recomputed from q, k and the
rows' log-sum-exp), the KL terms and their gradient by the scores; the score
product's backward, which runs inside this scope, is `dsa_index_time_share_
pct`'s — over the time of all operations.  Part of `dsa_time_share_pct`.
Source: device trace, sorted by the compiled step's op_name."""

from benchmark.layer_metrics import _keye, _program

probe = _program.op_names_probe


def read(run: dict):
    return _keye.share_pct(run, "kl")
