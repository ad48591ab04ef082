"""The share of the bytes a chip hands to collectives a step that travel in
asynchronous pairs, beside compute (`step.exchange_overlap`'s counts, as
bytes).  Source: program counter."""

from benchmark.layer_metrics import _exchange


def read(run: dict):
    return _exchange.async_pct(run)
