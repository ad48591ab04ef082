"""Device time of `Attention` outside its kernels — the q/k/v projections with
the q/k norm (`hvd_attn_qkv`), the rotation and what surrounds the kernels
(`hvd_attn_attend`), the output projection (`hvd_attn_out`), forward and
backward — over the time of all operations.  The flash custom calls beneath
`hvd_attn_attend` are `flash_time_share_pct`'s, by instruction name, and are
not in it.  Source: device trace, sorted by the compiled step's op_name
(`_layers.column_of`)."""

from benchmark.layer_metrics import _layers


def read(run: dict):
    return _layers.share_pct(run, "attn_proj")
