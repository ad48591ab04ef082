"""The (query block, key block) pairs a head's block-diffusion forward kernel
visits over what a causal kernel would visit over the same 2 L rows under the
same blocks, the worst layer's: the share of a causal walk's blocks that touch
the block mask and no more (24 of 36 in 1,024-blocks at L = 4,096, 80 of 136 in
512-blocks); a kernel that stops skipping reads 100 or more.  Source: the
program's own counters (`attn_blocks_visited`, `attn_blocks_causal` in the
`intermediates` collection), read by a probe outside the window."""

from benchmark.layer_metrics import _sdar

probe = _sdar.counters_probe


def read(run: dict):
    seen = run["probes"].get("attn_blockdiff_blocks_visited_pct")
    if not seen or not seen["blocks"]:
        return None
    return max(100.0 * visited / causal for visited, causal in seen["blocks"])
