"""What the seven readers of the program's own account of its set-up share.

`horovod_tpu/jax/train.py` `_TimedStep.setup` says what building the step's
programs cost, by whichever route they were built (`run.py`'s
`.lower().compile()`, the warm-up's first call): seconds traced, lowered and
loaded — JAX's own events — the programs counted, the persistent cache's
verdicts, the code size of the executable the step held.
`horovod_tpu/common/metrics.py` `setup_table` keeps the same for the whole
process, each entry with the `time.perf_counter()` it ended at, and the
seconds Python spent tracing each kernel body of `ops/` (`kernel_trace`): the
reference comparison's one-device pass builds no step of the library's and is
in the table alone.

One probe (`step_trace_s` owns it) copies both after the window.  The step's
account needs no cut: the probes that ran before built steps of their own.
The table is cut at the window's start — `run["setup_s"]` after `run.py`'s
`_PROCESS_START`, on the same clock — because those probes traced and
compiled too.

A program without the account (a parent of PR 51) gives None from every
function here: nothing raises.
"""

import sys

PROBE = "step_trace_s"


def probe(context: dict):
    step = context["built"].step
    try:
        from horovod_tpu.common.metrics import setup_table
        setup = dict(step.setup)
    except (ImportError, AttributeError):
        return None
    process = setup_table.process()
    out = {"step": setup, "entries": process.pop("entries"),
           "process_start": getattr(sys.modules["__main__"],
                                    "_PROCESS_START", None)}
    # The table's entries of 50 ms or more, by the process's own clock, for
    # whoever reads the run's output by hand.
    since = out["process_start"] or 0.0
    context["note"](step_setup=setup, process_setup=process, setup_entries=[
        [round(ended - since, 2), stage, name, round(seconds, 3)]
        for ended, stage, name, seconds in out["entries"] if seconds >= 0.05])
    return out


def step_setup(run: dict, key: str):
    """`step.setup[key]`, or None."""
    probed = run["probes"].get(PROBE)
    return probed and probed["step"].get(key)


def before_the_window(run: dict, stage: str):
    """Seconds of the table's `stage` entries that ended before the window
    began."""
    probed = run["probes"].get(PROBE)
    if not probed or probed["process_start"] is None:
        return None
    cut = probed["process_start"] + run["setup_s"]
    return sum((seconds for ended, kind, _, seconds in probed["entries"]
                if kind == stage and ended < cut), 0.0)
