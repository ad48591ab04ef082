"""What the nine readers of Keye's learned selection share.

A `models.Attention(indexer=)` runs the indexer under three `jax.named_scope`s
— `hvd_dsa_index` (its projections, norm, rotation and the score product,
forward and backward), `hvd_dsa_select` (the thresholds and the selection),
`hvd_dsa_kl` (the target pass, the KL terms and their gradient by the scores)
— which reach an operation's `op_name` in the compiled step's text; the
attention itself stays under `hvd_attn_attend`, its kernels named after the
causal ones with `_selected` behind (`hvd_flash_fwd_selected`,
`hvd_flash_bwd_selected`, `hvd_flash_bwd_dkdv_selected`,
`hvd_flash_bwd_dq_selected`), so the prefix readers (`flash_fwd_time_share_
pct`, `flash_bwd_time_share_pct`) count them and these tell them apart by the
whole name.  `ops/dsa.py`'s own kernels are `hvd_dsa_index`,
`hvd_dsa_index_bwd_dq`, `hvd_dsa_index_bwd_dk` and `hvd_dsa_probs`.  The layer
sows `dsa_keys_selected`, `dsa_keys_causal`, `dsa_threshold_ties`,
`dsa_tiles_live`, `dsa_tiles_causal` into `intermediates`;
benchmark/builders/keye_lm.py's counter pass stacks them a layer.

An operation is filed under the INNERMOST of the three scopes of its path (the
score product's backward runs where the KL's gradient is made, under
`hvd_dsa_kl/.../hvd_dsa_index`: it is the index's).

A program with no such scope, kernel or counter (any other cell, a parent
without the layer) gives None from every function here: nothing raises.
"""

import re

from benchmark import program_trace
from benchmark.layer_metrics import _hybrid, _trinity
from benchmark.layer_metrics._program import OP_NAMES_PROBE

SCOPES = ("index", "select", "kl")
# direction -> the instruction names of that direction's kernels
SELECTED = {
    "fwd": re.compile(r"^hvd_flash_fwd_selected(?:\.\d+)?$"),
    "bwd": re.compile(r"^hvd_flash_bwd(?:_dkdv|_dq)?_selected(?:\.\d+)?$")}
INDEX = re.compile(r"^hvd_dsa_index(?:_bwd_dq|_bwd_dk)?(?:\.\d+)?$")
KERNEL, INDEX_KERNEL = "flash_selected", "dsa_index"   # in `Built.kernels`
_SCOPE = re.compile(r"(?:^|/)hvd_dsa_(%s)(?=/|$)" % "|".join(SCOPES))


def scope_of(path):
    """The innermost of the three scopes in an op_name, or None."""
    found = None
    for found in _SCOPE.finditer(path or ""):
        pass
    return found and found.group(1)


def sorted_time(run: dict):
    """({"index", "select", "kl", "flash": nanoseconds}, nanoseconds of all
    operations), mean over chips — the three scopes by op_name, the selected
    flash kernels by instruction name; None where there is no trace, no
    compiled text, or none of them ran."""
    program = program_trace.of_run(run)
    names = run["probes"].get(OP_NAMES_PROBE)
    if not program or not names:
        return None
    parts, everything = dict.fromkeys(SCOPES + ("flash",), 0.0), 0.0
    chips = max(len(program["devices"]), 1)
    for events in program["devices"].values():
        for short, _, duration in events:
            everything += duration / chips
            instruction = program_trace.instruction(short)
            if any(p.match(instruction) for p in SELECTED.values()):
                parts["flash"] += duration / chips
                continue
            scope = scope_of(names["op_names"].get(instruction))
            if scope:
                parts[scope] += duration / chips
    return (parts, everything) if any(parts.values()) else None


def share_pct(run: dict, *parts):
    timed = sorted_time(run)
    inside = timed and sum(timed[0][part] for part in parts)
    return 100.0 * inside / timed[1] if inside else None


def flash_time_share_pct(run: dict):
    timed = _trinity.kernel_time(run, SELECTED.values())
    return timed and 100.0 * timed[0] / timed[1]


def flash_roofline_pct(run: dict, direction: str):
    """`_trinity.roofline_pct`'s arithmetic over benchmark/ops_count_keye.py's
    count (what the kernels execute: the causal pairs) and the kernels named
    above."""
    return _trinity.roofline_pct(run, KERNEL, SELECTED, direction)


def index_roofline_pct(run: dict):
    """The least time the chip could take for the score product's three
    kernels (benchmark/ops_count_keye.py `index_kernel`: the larger of
    operations over peak FLOP/s and bytes over peak bytes/s) over the time in
    the custom calls named `hvd_dsa_index*`."""
    timed, work = _trinity.kernel_time(run, [INDEX]), \
        run["kernels"].get(INDEX_KERNEL)
    if not timed or not work or not run["peak"] \
            or not run.get("profiled_steps"):
        return None
    tokens = _hybrid._tokens_profiled(run)
    least = max(work["ops"] * tokens / run["peak"]["bf16_flops_per_s"],
                work["bytes"] * tokens / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / (timed[0] / 1e9)


def counters_probe(context: dict):
    """{"counts": [[selected, causal, ties, tiles live, tiles causal] per
    selecting layer]} from one forward pass outside the window (the builder's
    counter pass); None where the builder's model sows no such counter."""
    rows_of = getattr(context["built"], "expert_rows", None)
    if rows_of is None:
        return None
    from benchmark.reference import compare

    seen = rows_of(compare.first_device_copy(context["state"][0]),
                   compare.first_device_copy(context["pool"][0]))
    if "selection_counts" not in seen:
        return None
    out = {"counts": [[int(n) for n in layer]
                      for layer in seen["selection_counts"]]}
    context["note"](selection_probe=out)
    return out
