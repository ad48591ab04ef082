"""Bytes a chip hands to collectives a step: the sum over the compiled step's
table of collectives (each operand in the dtype it is summed in), in MB.
Source: program counter."""

from benchmark.layer_metrics import _exchange


def read(run: dict):
    return _exchange.megabytes(run)
