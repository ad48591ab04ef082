"""Operations and bytes of the Mellum2 decoder's training step (per-head-normed
grouped-query attention, rotated in every layer, under a sliding window or
none; softmax-routed gated sparse experts), from shapes alone, by
benchmark/ops_count.py's rules: a multiply-add is TWO operations, a training
step is three passes of every matmul, NOTHING RECOMPUTED IS COUNTED for
utilisation — a layer that computes its forward pass again in the backward pass
(`TransformerLM(recompute=True)`) has done the model no more work, so `total`
does not know of it and `mfu_pct` reads the model's arithmetic once.

One count does know: `visible_to_compiler`, which is compared with what libtpu
says the compiled step holds: a recomputing layer keeps the outputs of its
flash forward kernel, of its grouped expert products and of its router's
`top_k` (`models/transformer.py` `_kept_by_a_recomputing_layer`) and runs its
other products — the attention's four projections and the router's — a fourth
time; the experts' three and the head, outside every layer, run three passes.

What is counted is the work done HERE: the experts this chip holds (`shape`, as
benchmark/builders/mellum_lm.py fills it), a token's k choices putting `k / n`
rows on this chip's experts on average; attention and the router whole, as
every chip of the deployment computes them.
"""

from __future__ import annotations

from benchmark import ops_count_trinity
from benchmark.ops_count import OPS_PER_MAC, TRAIN_PASSES
# W_q and W_o at `heads * head_dim`, W_k and W_v at `kv_heads * head_dim`: the
# Qwen3-MoE body's projections, as SDAR's.
from benchmark.ops_count_sdar import (
    attention_macs_per_position as attention_macs_per_token)


# The flash kernels' work is Trinity's count at this cell's shapes — the band's
# exact pairs, `W (W + 1) / 2 + (S - W) W` a head, or the causal half's with its
# diagonal; the model's work, whatever kernel does it (the split pair's second
# pass over the probabilities is no work) — a call a layer and direction: a
# recomputing layer keeps its forward kernel's outputs and does not call it
# again.
flash_kernel = ops_count_trinity.flash_kernel


def mellum_lm_train_ops_per_token(shape: dict, seq: int,
                                  local_rows_per_token: float,
                                  buffer_rows_per_token: float,
                                  recompute: bool = False) -> dict:
    """`total`: what the model requires of this chip per token.
    `visible_to_compiler`: what libtpu's cost analysis reports for the same
    step: each `ragged_dot` a dense product over EVERY row of its buffer,
    nothing for the Pallas attention kernels (benchmark/ops_count_moe.py), and
    under `recompute` a layer's projections and its router a fourth time (the
    grouped products' outputs are kept)."""
    a, e, hidden = shape["attention"], shape["experts"], shape["hidden"]
    passes = OPS_PER_MAC * TRAIN_PASSES
    layers = shape["window_layers"] + shape["full_layers"]
    projections = layers * passes * attention_macs_per_token(
        hidden, a["heads"], a["kv_heads"], a["head_dim"])
    attention = TRAIN_PASSES * (
        shape["window_layers"]
        * ops_count_trinity.attention_forward_ops_per_token(
            seq, a["heads"], a["head_dim"], a["window"])
        + shape["full_layers"]
        * ops_count_trinity.attention_forward_ops_per_token(
            seq, a["heads"], a["head_dim"]))
    router = layers * passes * hidden * e["num_experts"]
    per_row = layers * passes * 3 * hidden * e["expert_width"]
    head = passes * hidden * shape["vocab"]
    experts = per_row * local_rows_per_token
    again = (TRAIN_PASSES + 1) / TRAIN_PASSES if recompute else 1.0
    return {"total": projections + router + head + experts + attention,
            "visible_to_compiler": head + per_row * buffer_rows_per_token
            + again * (projections + router),
            "attention": attention, "experts": experts,
            "attention_projections": projections, "router": router,
            "head": head}
