"""Device time of the latent-attention layers — every operation under an
`hvd_mla_` scope (q_proj, kv_latent, attend, out_proj) and the flash kernels
beneath `hvd_mla_attend`, forward and backward — over the time of all
operations.  Source: device trace, sorted by the compiled step's op_name and,
for the kernels, by instruction name."""

from benchmark.layer_metrics import _ling


def read(run: dict):
    return _ling.latent_attention_share_pct(run)
