"""Device time of latent attention's query latent — `hvd_mla_q_latent`: the
product with `W_qa` (2,048 x 1,536 in the JoyAI cell) and the latent's RMSNorm
in float32, forward and backward, in every block (the module's too) — over the
time of all operations.  `hvd_mla_q_proj` keeps `W_qb` and the rotation.
Source: device trace, sorted by the compiled step's op_name."""

from benchmark.layer_metrics import _hybrid


def read(run: dict):
    return _hybrid.share_pct(run, ["hvd_mla_q_latent"])
