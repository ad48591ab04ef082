"""Generated code of the executable the step held, in MB (1e6):
`memory_analysis().generated_code_size_in_bytes` as `step.setup["code_bytes"]`
keeps it — `run.py`'s compiled step.  Source: program counter."""

from benchmark.layer_metrics import _setup


def read(run: dict):
    code_bytes = _setup.step_setup(run, "code_bytes")
    return None if code_bytes is None else code_bytes / 1e6
