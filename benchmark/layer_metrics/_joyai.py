"""What the three readers of JoyAI-LLM-Flash's additions share.

`models.TransformerLM(mtp=)` runs everything its multi-token-prediction module
adds under the `jax.named_scope` `hvd_mtp` — the second lookup in the table
(`hvd_embed` beneath it), the two norms and `W_eh` (`hvd_mtp_proj`), the
module's block (its `hvd_mla_*` and `hvd_moe_*` scopes beneath it) and the
second application of the head (`hvd_lm_head`) — and latent attention with a
query latent runs `W_qa` and its norm under `hvd_mla_q_latent`.  Both reach an
operation's `op_name` in the compiled step's text, forward and backward alike
(`_hybrid.scope_time` has how the device trace's events find it); the Pallas
kernels beneath them keep the path too.  libtpu's own grouped-matmul kernels
(`ragged-dot-*`) keep NO path, the layer's flax name neither (described-chip
compile, PR 66): such a kernel is filed under the layer of the operation that
ran just before it on the same chip — its rows' gather, its gate's fusion or
the kernel of the same layer before it — which is its layer's path as far as
the trace can tell.  The model sows the two mean losses of a pass
under `targets=` as `mtp_losses`; benchmark/builders/joyai_lm.py's counter
pass reads them.

A program with no such scope or counter (any other cell, a parent without the
module) gives None from every function here: nothing raises.
"""

from benchmark import program_trace
from benchmark.layer_metrics import _moe
from benchmark.layer_metrics._program import OP_NAMES_PROBE

SCOPE = "hvd_mtp"


def module_share_pct(run: dict):
    """Device time of every operation under `hvd_mtp`, and of every pathless
    grouped-matmul kernel that ran behind one, over the time of all
    operations; None where no operation ran under the scope."""
    program = program_trace.of_run(run)
    names = run["probes"].get(OP_NAMES_PROBE)
    if not program or not names:
        return None
    inside = everything = 0.0
    for events in program["devices"].values():
        before = ""                  # the path of the last operation with one
        for short, _, duration in events:           # in order of their start
            instruction = program_trace.instruction(short)
            path = names["op_names"].get(instruction) or ""
            if _moe._GROUPED_MATMUL.match(instruction):
                path = before
            elif "/" in path:
                before = path
            everything += duration
            if SCOPE in path:
                inside += duration
    return 100.0 * inside / everything if inside else None


def losses_probe(context: dict):
    """{"main": L_main, "modules": [L_mtp]} of one forward pass outside the
    window, on the first batch of the pool with the weights as the window
    left them (the builder's counter pass); None where the builder's model
    sows no such counter."""
    losses_of = getattr(context["built"], "mtp_losses", None)
    if losses_of is None:
        return None
    from benchmark.reference import compare

    out = losses_of(compare.first_device_copy(context["state"][0]),
                    compare.first_device_copy(context["pool"][0]))
    context["note"](mtp_losses_probe=out)
    return out
