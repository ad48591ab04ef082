"""How many programs of the step this run loaded onto the device
(`step.setup["programs"]["loaded"]`): one where the warm-up's first call finds
the executable `run.py` compiled — on one chip and on four (my chip runs,
PR 51) — and more where something loads the step again.  Source: program
counter."""

from benchmark.layer_metrics import _setup


def read(run: dict):
    programs = _setup.step_setup(run, "programs")
    return programs and programs["loaded"]
