"""What the seven readers of Granite-4.0-H's Mamba-2 mixers share.

The chunked scan (`horovod_tpu.ops.ssm.chunked_scan`) runs its four stages
under `jax.named_scope`s beneath the mixer's `hvd_ssm_scan` —
`hvd_ssm_scan_decay` (the cumulative sums, the masked decay matrix, `to_end`,
their exponentials), `hvd_ssm_scan_intra` (`scores`, `mixed`, the within-chunk
product), `hvd_ssm_scan_ends` (each chunk's end state), `hvd_ssm_scan_carry`
(the chunk-by-chunk matrix, `entering`, `from_start`) — which reach an
operation's `op_name` forward and backward alike (`_hybrid.scope_time` has how
the device trace's events find it); `hvd_ssm_conv` and `hvd_ssm_gate_norm` are
the mixer's two element-wise stages.  The mixer sows `ssm_chunks_carried` and
`ssm_chunks` into `intermediates`; benchmark/builders/granite_lm.py's counter
pass reads them a layer.  Any caller of the scan carries the scopes (the
Nemotron cell's program too); only this builder carries the counter pass.

A program with no such scope or counter (any other cell, a parent without the
scopes) gives None from every function here: nothing raises.
"""

from benchmark.layer_metrics import _hybrid

STAGES = ("decay", "intra", "ends", "carry")


def stage_share_pct(run: dict, stage: str):
    """Device time under one stage's scope over the time of all operations
    (the four sum to the time under `hvd_ssm_scan`)."""
    return _hybrid.share_pct(run, ["hvd_ssm_scan_" + stage])


def carry_probe(context: dict):
    """{"carried": [(sequence, chunk, head) triples that pass state on, per
    Mamba-2 layer], "chunks": [all of them per layer]} from one forward pass
    outside the window (the builder's counter pass); None where the builder's
    model sows no such counter."""
    carry_of = getattr(context["built"], "ssm_carry", None)
    if carry_of is None:
        return None
    from benchmark.reference import compare

    out = carry_of(compare.first_device_copy(context["state"][0]),
                   compare.first_device_copy(context["pool"][0]))
    context["note"](ssm_carry_probe=out)
    return out
