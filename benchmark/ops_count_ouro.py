"""Operations of the Ouro looped decoder's training step (causal full
attention and a gated MLP a layer; `layers` layers run `passes` times over one
set of weights, the head and an exit gate on every pass's state), from shapes
alone, by benchmark/ops_count.py's rules: a multiply-add is TWO operations, a
training step is three passes of every matmul, NOTHING RECOMPUTED IS COUNTED
for utilisation.

A parameter is used `passes` times a token: `total` counts every layer's
products, the head's and the gate's `passes` times, and attention at the
sequence's causal half `passes` times.  That a recomputing layer, and every
pass's head, runs its products a fourth time (`TransformerLM(recompute=True)`;
the head under `jax.checkpoint`) is the program's business and not the model's
work, so `mfu_pct` does not know of it.

One count does know: `visible_to_compiler`, which `run.py` compares with what
libtpu says the compiled step holds.  The passes are a ROLLED loop, and
libtpu's cost analysis counts a `while` body ONCE, whatever its trip count: so
the forward loop's body (a pass's products, forward) once, the backward loop's
body (the same products computed again, and their two gradient products) once
— ONE pass of `passes`, at four passes of each product where it is recomputed.
Four unrolled passes would read `passes` times that and fall out of `run.py`'s
band: the agreement is also the check that the program holds the layers' bodies
once.  The Pallas attention kernels are invisible to it, as everywhere.

The flash kernels' own work (`kernels["flash"]`) is benchmark/ops_count.py's
count at `layers x passes` calls: a recomputing layer keeps its forward
kernel's outputs and does not call it again.
"""

from __future__ import annotations

from benchmark.ops_count import (OPS_PER_MAC, TRAIN_PASSES,
                                 causal_attention_forward_ops_per_token)


def layer_macs_per_token(hidden: int, intermediate: int) -> int:
    """q, k, v, o and the gated MLP's gate, up, down."""
    return 4 * hidden * hidden + 3 * hidden * intermediate


def ouro_lm_train_ops_per_token(hidden: int, intermediate: int, layers: int,
                                vocab: int, seq: int, passes: int,
                                recompute: bool = True) -> dict:
    """`total`: what the model requires per token, every pass counted.
    `visible_to_compiler`: what libtpu's cost analysis reports for the rolled
    step — one trip of each loop."""
    products = OPS_PER_MAC * TRAIN_PASSES
    a_pass = products * (layers * layer_macs_per_token(hidden, intermediate)
                         + hidden * vocab + hidden)
    attention = passes * TRAIN_PASSES * layers \
        * causal_attention_forward_ops_per_token(seq, hidden)
    again = (TRAIN_PASSES + 1) / TRAIN_PASSES if recompute else 1.0
    return {"total": passes * a_pass + attention,
            "visible_to_compiler": again * a_pass,
            "attention": attention,
            "head": passes * products * hidden * vocab,
            "layers": passes * products * layers
            * layer_macs_per_token(hidden, intermediate)}
