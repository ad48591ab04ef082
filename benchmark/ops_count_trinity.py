"""Operations and bytes of the Trinity-Mini decoder's training step (gated
grouped-query attention under a sliding window or none, a dense gated MLP,
gated sparse experts with a shared one), from shapes alone, by
benchmark/ops_count.py's rules: a multiply-add is TWO operations, a training
step is three passes of every matmul, nothing recomputed is counted for
utilisation.

What is counted is the work done HERE: the experts this chip holds (`shape`, as
benchmark/builders/trinity_lm.py fills it), a token's k choices putting `k / n`
rows on this chip's experts on average; attention, the router, the shared
expert and the dense MLP whole, as every chip of the deployment computes them.
"""

from __future__ import annotations

from benchmark.ops_count import (FLASH_BACKWARD_OVER_FORWARD, OPS_PER_MAC,
                                 TRAIN_PASSES)


def attention_macs_per_token(hidden: int, heads: int, kv_heads: int,
                             head_dim: int) -> int:
    """W_q, the output gate's W_g and W_o at `heads * head_dim`, and W_k, W_v
    at `kv_heads * head_dim`, of one layer."""
    return 3 * hidden * heads * head_dim + 2 * hidden * kv_heads * head_dim


def band_pairs(seq: int, window=None) -> int:
    """The (query, key) pairs of one head one sequence long that the mask
    keeps: `0 <= t - s < window`, exactly — `W (W + 1) / 2` in the first `W`
    rows and `W` in each of the others; without a window (or one no shorter
    than the sequence) the causal half with its diagonal."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_forward_ops_per_token(seq: int, heads: int, head_dim: int,
                                    window=None) -> float:
    """QK^T and PV over the kept pairs: `4 e` operations a pair, a head."""
    return 2 * OPS_PER_MAC * head_dim * heads * band_pairs(seq, window) / seq


def flash_kernel(seq: int, heads: int, head_dim: int, layers: int,
                 window=None, itemsize: int = 2) -> dict:
    """{"fwd": {"ops", "bytes"}, "bwd": {...}} per token over `layers` layers
    of the flash kernels under `window` (None: causal).  Forward: the two
    products over the band's exact pairs; reads q, k, v and writes o, each
    once (`heads` wide: a key/value head is repeated before the kernels).
    Backward, as the algorithm needs it (Dao et al., algorithm 2): 2.5 times
    the forward's operations (the probabilities again and four gradient
    products); reads q, k, v, o, do and writes dq, dk, dv.  Blocks the band's
    edges cut, and any re-reading of k and v, are the kernels' own business."""
    forward = layers * attention_forward_ops_per_token(seq, heads, head_dim,
                                                       window)
    row = heads * head_dim * itemsize * layers
    return {"fwd": {"ops": forward, "bytes": 4 * row},
            "bwd": {"ops": forward * FLASH_BACKWARD_OVER_FORWARD,
                    "bytes": 8 * row}}


def expert_layer_dense_macs_per_token(hidden: int, num_experts: int,
                                      shared: int) -> int:
    """What multiplies every token in one expert layer: the router and the
    gated shared expert."""
    return hidden * num_experts + 3 * hidden * shared


def trinity_lm_train_ops_per_token(shape: dict, seq: int,
                                   local_rows_per_token: float,
                                   buffer_rows_per_token: float) -> dict:
    """`total`: what the model requires of this chip per token.
    `visible_to_compiler`: what libtpu's cost analysis reports for the same
    step: each `ragged_dot` a dense product over EVERY row of its buffer,
    nothing for the Pallas attention kernels (benchmark/ops_count_moe.py)."""
    a, e, hidden = shape["attention"], shape["experts"], shape["hidden"]
    passes = OPS_PER_MAC * TRAIN_PASSES
    layers = shape["window_layers"] + shape["full_layers"]
    projections = layers * passes * attention_macs_per_token(
        hidden, a["heads"], a["kv_heads"], a["head_dim"])
    attention = TRAIN_PASSES * (
        shape["window_layers"] * attention_forward_ops_per_token(
            seq, a["heads"], a["head_dim"], a["window"])
        + shape["full_layers"] * attention_forward_ops_per_token(
            seq, a["heads"], a["head_dim"]))
    mlp = shape["mlp_layers"] * passes * 3 * hidden * shape["mlp_width"]
    dense = shape["expert_layers"] * passes \
        * expert_layer_dense_macs_per_token(hidden, e["num_experts"],
                                            e["shared"])
    per_row = shape["expert_layers"] * passes * 3 * hidden * e["expert_width"]
    head = passes * hidden * shape["vocab"]
    experts = per_row * local_rows_per_token
    seen = projections + mlp + dense + head
    return {"total": seen + experts + attention,
            "visible_to_compiler": seen + per_row * buffer_rows_per_token,
            "attention": attention, "experts": experts,
            "attention_projections": projections, "mlp": mlp, "head": head}
