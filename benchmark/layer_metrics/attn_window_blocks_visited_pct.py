"""The (query block, key block) pairs a head's banded forward kernel visits
over what the causal kernel visits under the same blocks, the worst windowed
layer's: the share of the plan's blocks that touch the band and no more (21 of
36 in 1,024-blocks at 8,192 tokens under a window of 2,048, 70 of 136 in
512-blocks); a kernel that stops skipping reads 100.  Source: the program's own
counters (`attn_blocks_visited`, `attn_blocks_causal` in the `intermediates`
collection), read by a probe outside the window."""

from benchmark.layer_metrics import _trinity

probe = _trinity.blocks_probe


def read(run: dict):
    seen = run["probes"].get(_trinity.BLOCKS_PROBE)
    if not seen or not seen["blocks"]:
        return None
    return max(100.0 * visited / causal for visited, causal in seen["blocks"])
