"""One builder per architecture or entry point.  A configuration file names
its builder (`"builder": "dense_lm"`); `run.py` imports
`benchmark.builders.<builder>` and calls

    build(config, traffic, devices, seed) -> Built

with the two JSON files as dictionaries and the JAX devices the cell runs on.
A new architecture, or another entry point of the program (`run_pipeline`,
`ServingEngine`), is one new file here and no edit to `run.py`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional


@dataclasses.dataclass
class Built:
    """Everything `run.py` needs of one cell, and nothing it has to guess."""

    mesh: Any
    # The system under test: the jitted step build_train_step returned,
    # called as step(params, opt_state, batch + aux) like the examples do.
    step: Callable
    # () -> (params, opt_state[, aux...]) made on the device, under jit, from
    # the seed, replicated over the mesh.
    init_state: Callable[[], tuple]
    # What the traffic generator draws for one batch: a list of
    # {"name", "shape" (per sample), "dtype", "high" (integers)}.
    fields: List[Dict[str, Any]]
    # {field name: array} -> the batch tuple loss_fn takes (without aux).
    make_batch: Callable[[Dict[str, Any]], tuple]
    # Samples (tokens, images) in one global step, and what a sample is.
    samples_per_step: int
    sample_unit: str
    # benchmark/ops_count.py's numbers: {"total", "visible_to_compiler", ...}
    # per sample, and per kernel {"ops", "bytes"} per sample.
    ops_per_sample: Dict[str, float]
    kernels: Dict[str, Dict[str, float]]
    # What the compiled step must hold on a TPU: exact counts by name, and
    # names of which at least one must be there.
    program_exactly: Dict[str, int]
    program_at_least_one: List[str]
    # The pieces a plain jax.jit step is made of (framework_overhead_pct):
    # the same loss without the mesh axis, the optimizer, whether loss_fn
    # returns (loss, aux).
    plain_loss_fn: Callable
    optimizer: Any
    has_aux: bool
    # (state, pool) -> [{"name", "value", "limit"}...]: the comparison with
    # the plain float32 reference, run outside the window.
    reference_checks: Callable[[tuple, list], List[Dict[str, Any]]]
    notes: Optional[Dict[str, Any]] = None


def dtype_of(name: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def collectives_expected(devices):
    """(exact counts, names of which one must be there) for the compiled
    step's collectives: none on one chip, an all-reduce across several."""
    if len(devices) == 1:
        return {"all_reduce": 0}, []
    return {}, ["all_reduce"]
