"""What the two readers of Olmo-Hybrid's mixers share.

Its Gated DeltaNet mixer is Qwen3-Next's (`models.delta.DeltaMixer(gate=
"head")`, the scopes `hvd_gdn_*`, the delta rule's four stages beneath
`hvd_gdn_scan`, its recurrence the custom calls `hvd_gdn_scan_carry_fwd` and
`_bwd`), with a key width and a value width of their own
(`DeltaConfig(value_head_dim=)`) and a step that may pass 1
(`DeltaConfig(beta_scale=)`).  Such a mixer sows `gdn_beta_over_one` and
`gdn_beta_steps` into `intermediates`; benchmark/builders/olmohybrid_lm.py's
counter pass reads them a layer.

A program with no such scope, kernel shape or counter (any other cell, a
parent without the two fields) gives None from every function here: nothing
raises.
"""

from benchmark import ops_count_olmohybrid
from benchmark.layer_metrics import _hybrid

KERNEL = "gdn_kdv_scan"                       # in `Built.kernels`


def scan_roofline_pct(run: dict):
    """The least time the chip could take for every Gated DeltaNet layer's
    delta rule at its true key and value widths, forward and backward, over
    the time under `hvd_gdn_scan`."""
    timed, shape = _hybrid.scope_time(run, ["hvd_gdn_scan"]), \
        run["kernels"].get(KERNEL)
    if not timed or not shape or not run["peak"] \
            or not run.get("profiled_steps"):
        return None
    least = ops_count_olmohybrid.scan_least_seconds(
        shape, _hybrid._tokens_profiled(run), run["peak"])
    return 100.0 * least / (timed[0] / 1e9)


def steps_probe(context: dict):
    """{"over_one": [steps with beta > 1 per Gated DeltaNet layer], "steps":
    [all of them per layer]} from one forward pass outside the window (the
    builder's counter pass); None where the builder's model sows no such
    counter."""
    steps_of = getattr(context["built"], "delta_steps", None)
    if steps_of is None:
        return None
    from benchmark.reference import compare

    out = steps_of(compare.first_device_copy(context["state"][0]),
                   compare.first_device_copy(context["pool"][0]))
    context["note"](delta_steps_probe=out)
    return out
