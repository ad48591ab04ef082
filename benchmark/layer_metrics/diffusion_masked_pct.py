"""The share of the data tokens of one batch of the pool that the noise has
masked — the tokens the loss is taken on: about half under a linear schedule
with one level a block (the mean of `eps + (1 - eps) u`).  It says the traffic
is what its file says; a step's attention and experts do not depend on it, the
loss's weights do.  Source: the program's own counter (`masked_tokens` of the
builder's counter pass), read by a probe outside the window."""

from benchmark.layer_metrics import _sdar

probe = _sdar.counters_probe


def read(run: dict):
    seen = run["probes"].get("diffusion_masked_pct")
    return seen and seen["masked_pct"]
