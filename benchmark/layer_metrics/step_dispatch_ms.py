"""Median duration of the program's own `hvd.train_step` spans in the traced
window: the library's call as `_TimedStep` (horovod_tpu/jax/train.py) writes
it on the profiler's clock, inside the benchmark's `dispatch` span that
`dispatch_ms` times from outside.  Source: program span."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.step_dispatch_ms(run)
