"""Core time in the starts, dones and synchronous collectives of the compiled
step's table of collectives (`step.collectives()`, the executable that ran)
over the traced window, mean over chips: what the exchange still costs the
core, asynchronous pairs included — `collective_exposed_pct` reads the
synchronous remainder alone and can never read over this.  Source: device
trace (joined to the table by instruction name)."""

from benchmark.layer_metrics import _exchange

probe = _exchange.probe


def read(run: dict):
    return _exchange.wait_pct(run)
