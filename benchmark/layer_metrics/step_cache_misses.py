"""Loads of the step that the persistent compile cache did not answer
(`step.setup["cache_misses"]`): 0 says the run was warm, and the set-up
seconds beside it compare with another warm run's.  Source: program counter."""

from benchmark.layer_metrics import _setup


def read(run: dict):
    return _setup.step_setup(run, "cache_misses")
