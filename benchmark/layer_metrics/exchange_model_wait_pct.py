"""`exchange_wait_pct` over the table's `model` entries alone: the
collectives the model makes in its own right (sync batch norm's statistics,
forward and backward) apart from the gradients'.  Source: device trace."""

from benchmark.layer_metrics import _exchange


def read(run: dict):
    return _exchange.wait_pct(run, role="model")
