"""What the five readers of SDAR's block-diffusion step share.

`ops/attention.py` names the kernels of a `block_diffusion=` call after the
causal ones with `_blockdiff` behind — `hvd_flash_fwd_blockdiff`,
`hvd_flash_bwd_blockdiff`, `hvd_flash_bwd_dkdv_blockdiff`,
`hvd_flash_bwd_dq_blockdiff` — so the prefix readers (`flash_fwd_time_share_
pct`, `flash_bwd_time_share_pct`) count them and these tell them apart by the
whole name.  A `models.Attention(block_diffusion=)` sows `attn_blocks_visited`
and `attn_blocks_causal` into `intermediates`, the second what a causal kernel
would visit over both copies' rows; benchmark/builders/sdar_lm.py's counter
pass adds `masked_tokens`, the data tokens its batch has masked.

A program with no such kernel or counter (any other cell, a parent without the
mask) gives None from every function here: nothing raises.
"""

import re

from benchmark.layer_metrics import _trinity

# direction -> the instruction names of that direction's kernels
BLOCKDIFF = {
    "fwd": re.compile(r"^hvd_flash_fwd_blockdiff(?:\.\d+)?$"),
    "bwd": re.compile(r"^hvd_flash_bwd(?:_dkdv|_dq)?_blockdiff(?:\.\d+)?$")}
KERNEL = "flash_blockdiff"          # its key in `Built.kernels`


def time_share_pct(run: dict):
    timed = _trinity.kernel_time(run, BLOCKDIFF.values())
    return timed and 100.0 * timed[0] / timed[1]


def roofline_pct(run: dict, direction: str):
    """`_trinity.roofline_pct`'s arithmetic over benchmark/ops_count_sdar.py's
    count (the mask's exact pairs) and the kernels named above."""
    return _trinity.roofline_pct(run, KERNEL, BLOCKDIFF, direction)


def counters_probe(context: dict):
    """{"blocks": [[visited, causal over both copies] per attention layer],
    "masked_pct": the share of the probed batch's data tokens that are
    masked} from one forward pass outside the window (the builder's counter
    pass); None where the builder's model sows no such counter."""
    rows_of = getattr(context["built"], "expert_rows", None)
    if rows_of is None:
        return None
    from benchmark.reference import compare

    batch = compare.first_device_copy(context["pool"][0])
    seen = rows_of(compare.first_device_copy(context["state"][0]), batch)
    if "attn_blocks" not in seen or "masked_tokens" not in seen:
        return None
    out = {"blocks": [[int(n) for n in layer]
                      for layer in seen["attn_blocks"]],
           "masked_pct": 100.0 * int(seen["masked_tokens"]) / batch[0].size}
    context["note"](blockdiff_probe=out)
    return out
