"""Operations and bytes of SDAR's block-diffusion training step (grouped-query
attention under the block mask over a clean and a noised copy of every
sequence, softmax-routed gated sparse experts), from shapes alone, by
benchmark/ops_count.py's rules: a multiply-add is TWO operations, a training
step is three passes of every matmul, nothing recomputed is counted for
utilisation.

A SAMPLE IS A DATA TOKEN: one of the L tokens of text a sequence trains on.
The step runs 2 L positions for them (the clean copy is the noised copy's
context), so what multiplies every position — the attention's projections,
the router, the experts' rows — counts twice a data token, and the head,
which runs on the noised copy alone, once.

What is counted is the work done HERE: the experts this chip holds, a
position's k choices putting `k / n` rows on this chip's experts on average;
attention and the router whole, as every chip of the deployment computes them.
"""

from __future__ import annotations

from benchmark.ops_count import (FLASH_BACKWARD_OVER_FORWARD, OPS_PER_MAC,
                                 TRAIN_PASSES)

COPIES = 2               # positions the step runs for one data token


def attention_macs_per_position(hidden: int, heads: int, kv_heads: int,
                                head_dim: int) -> int:
    """W_q and W_o at `heads * head_dim`, W_k and W_v at `kv_heads *
    head_dim`, of one layer."""
    return 2 * hidden * heads * head_dim + 2 * hidden * kv_heads * head_dim


def blockdiff_pairs(seq: int, block: int) -> int:
    """The (query, key) pairs of one head the block mask keeps over the two
    copies of `seq` positions, exactly, `seq` a multiple of `block`: a clean
    query of block b sees the `(b + 1) block` clean keys up to its block's
    end, a noised one the `b block` clean keys before its block and the
    `block` noised keys of its own — `seq^2 + seq * block` in all (16.79 M at
    4,096 and 4; a causal mask over the same 8,192 rows keeps 33.56 M)."""
    if seq % block:
        raise ValueError(f"{seq} positions are no whole blocks of {block}")
    blocks = seq // block
    clean = block * block * blocks * (blocks + 1) // 2
    noised_on_clean = block * block * blocks * (blocks - 1) // 2
    return clean + noised_on_clean + seq * block


def attention_forward_ops_per_token(seq: int, heads: int, head_dim: int,
                                    block: int) -> float:
    """QK^T and PV over the kept pairs: `4 e` operations a pair, a head; per
    data token."""
    return 2 * OPS_PER_MAC * head_dim * heads * blockdiff_pairs(seq, block) \
        / seq


def flash_kernel(seq: int, heads: int, head_dim: int, layers: int, block: int,
                 itemsize: int = 2) -> dict:
    """{"fwd": {"ops", "bytes"}, "bwd": {...}} per data token over `layers`
    layers of the block-diffusion flash kernels.  Forward: the two products
    over the mask's exact pairs; reads q, k, v and writes o, each once, at BOTH
    copies' rows (`heads` wide: a key/value head is repeated before the
    kernels).  Backward, as the algorithm needs it (Dao et al., algorithm 2):
    2.5 times the forward's operations; reads q, k, v, o, do and writes dq, dk,
    dv.  Blocks the mask cuts, and any re-reading of k and v, are the kernels'
    own business."""
    forward = layers * attention_forward_ops_per_token(seq, heads, head_dim,
                                                       block)
    row = COPIES * heads * head_dim * itemsize * layers
    return {"fwd": {"ops": forward, "bytes": 4 * row},
            "bwd": {"ops": forward * FLASH_BACKWARD_OVER_FORWARD,
                    "bytes": 8 * row}}


def sdar_lm_train_ops_per_token(shape: dict, seq: int,
                                local_rows_per_position: float,
                                buffer_rows_per_position: float) -> dict:
    """`total`: what the model requires of this chip per data token.
    `visible_to_compiler`: what libtpu's cost analysis reports for the same
    step: each `ragged_dot` a dense product over EVERY row of its buffer,
    nothing for the Pallas attention kernels (benchmark/ops_count_moe.py)."""
    a, e, hidden = shape["attention"], shape["experts"], shape["hidden"]
    passes, layers = OPS_PER_MAC * TRAIN_PASSES, shape["layers"]
    projections = COPIES * layers * passes * attention_macs_per_position(
        hidden, a["heads"], a["kv_heads"], a["head_dim"])
    attention = TRAIN_PASSES * layers * attention_forward_ops_per_token(
        seq, a["heads"], a["head_dim"], a["block_length"])
    router = COPIES * layers * passes * hidden * e["num_experts"]
    per_row = COPIES * layers * passes * 3 * hidden * e["expert_width"]
    head = passes * hidden * shape["vocab"]
    experts = per_row * local_rows_per_position
    seen = projections + router + head
    return {"total": seen + experts + attention,
            "visible_to_compiler": seen + per_row * buffer_rows_per_position,
            "attention": attention, "experts": experts,
            "attention_projections": projections, "router": router,
            "head": head}
