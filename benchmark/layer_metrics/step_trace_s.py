"""Seconds Python spent tracing the step to a jaxpr, summed over the programs
of the step that this run traced: `step.setup["trace_s"]`, JAX's own
`jaxpr_trace_duration` events filed by the program (`_TimedStep`).  Owns the
probe the seven set-up readers share.  Source: program span."""

from benchmark.layer_metrics import _setup

probe = _setup.probe


def read(run: dict):
    return _setup.step_setup(run, "trace_s")
