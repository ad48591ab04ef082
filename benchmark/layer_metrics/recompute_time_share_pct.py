"""Device time of what the layers compute a second time — the operations
whose op_name holds `rematted_computation`, the marker `jax.checkpoint` leaves
in the name stack of what it computes again in the backward pass
(`models.MixerLayer(recompute=True)`: norms, projections, rotations, the
router, the rows' movement; not the flash forward kernels nor the grouped
expert products, whose outputs such a layer keeps) — over the time of all
operations.  Part of `backward_time_share_pct` (the marker stands inside
`transpose(...)`).  It would read LOW by any grouped expert product computed
again: libtpu's custom calls drop the scope path (`_moe.scope_of`), the marker
with it.  A program that recomputes nothing (any other cell, a parent without the switch) gives None.
Source: device trace, sorted by the compiled step's op_name."""

from benchmark.layer_metrics import _hybrid, _program

probe = _program.op_names_probe
MARKER = "rematted_computation"


def read(run: dict):
    return _hybrid.share_pct(run, [MARKER])
