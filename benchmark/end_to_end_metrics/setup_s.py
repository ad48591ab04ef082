"""Process start (run.py's first statement) to the first timed step: the
runtime's start, weights, pool, compiling or loading the step, warm-up and
the comparison with the reference.  Host clock."""


def read(run: dict):
    return run["setup_s"]
