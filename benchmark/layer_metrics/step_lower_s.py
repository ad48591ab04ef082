"""Seconds from the step's jaxpr to its StableHLO module — the Mosaic
lowering of every kernel body is in here — summed over the programs this run
lowered: `step.setup["lower_s"]`.  Source: program span."""

from benchmark.layer_metrics import _setup


def read(run: dict):
    return _setup.step_setup(run, "lower_s")
