"""Device time of everything the multi-token-prediction module adds — every
operation under `hvd_mtp`: the second lookup in the table, the two norms and
`W_eh` (`hvd_mtp_proj`), the module's whole layer (latent attention with its
flash kernels, the router, the experts, the shared expert) and the second
application of the head with its loss's pass, forward and backward; a kernel
that keeps no scope is counted by its layer's path (`_joyai`) — over the time
of all operations.  A fifth of the JoyAI cell's operations by count.  Source:
device trace, sorted by the compiled step's op_name."""

from benchmark.layer_metrics import _joyai


def read(run: dict):
    return _joyai.module_share_pct(run)
