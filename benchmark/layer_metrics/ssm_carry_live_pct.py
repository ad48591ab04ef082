"""Of the (sequence, chunk, head) triples of the Mamba-2 layers, all layers
together, the share that passes on more than a thousandth of the state that
entered the chunk (`exp` of the chunk's summed `dt A` over 1e-3): whether the
scan's `carry` stage moves anything in what the cell runs, or every chunk is
on its own.  Source: the program's own counters (`ssm_chunks_carried`,
`ssm_chunks` in the `intermediates` collection), read by a probe outside the
window."""

from benchmark.layer_metrics import _granite

probe = _granite.carry_probe


def read(run: dict):
    seen = run["probes"].get("ssm_carry_live_pct")
    if not seen or not sum(seen["chunks"]):
        return None
    return 100.0 * sum(seen["carried"]) / sum(seen["chunks"])
