"""Seconds in the backend's compile or the persistent cache's retrieval,
with the load onto the device, summed over the step's programs this run
loaded: `step.setup["load_s"]`.  Source: program span."""

from benchmark.layer_metrics import _setup


def read(run: dict):
    return _setup.step_setup(run, "load_s")
