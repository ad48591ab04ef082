"""How unevenly the router loads this chip's experts: the largest over the
mean of rows per local expert, the worst layer's, for one batch of the pool
with the weights as the window left them (1 = balanced; the expert with the
most rows sets the tail of a grouped matmul's groups, and the sum against
the buffer's bound decides whether a row is ever left out).  Source: the
program's own counter (`rows_per_local_expert` in the `intermediates`
collection), read by a probe outside the window."""

from benchmark.layer_metrics import _moe

probe = _moe.load_probe


def read(run: dict):
    load = run["probes"].get(_moe.LOAD_PROBE)
    if not load:
        return None
    return max(max(layer) * len(layer) / max(sum(layer), 1)
               for layer in load["rows_per_local_expert"])
