"""Operations and bytes of Keye-VL-2.0-30B-A3B's language-model training step
(per-head-normed grouped-query attention behind a learned selection of keys,
the indexer that makes it, softmax-routed gated sparse experts), from shapes
alone, by benchmark/ops_count.py's rules: a multiply-add is TWO operations, a
training step is three passes of every matmul, nothing recomputed is counted
for utilisation.

A sample is a token.  What is counted is the work done HERE: the experts this
chip holds, a token's k choices putting `k / n` rows on this chip's experts on
average; attention, indexer and router whole, as every chip of the deployment
computes them.

Attention is counted over the SELECTED pairs, `sum_t min(t + 1, topk)` a head:
what the model requires.  The kernels run dense causal tiles under a mask of
data, so `mfu_pct` prices a tile's unselected pairs as waste, while a kernel's
own roofline (`flash_kernel`, `index_kernel`) counts what the kernel executes,
the causal pairs, and cannot read over 100 % for the selection's sake.  The
indexer scores EVERY causal pair (that is what selecting costs), forward, and
its backward — two gradient products a pair and a head — runs over them too.
"""

from __future__ import annotations

from benchmark.ops_count import (FLASH_BACKWARD_OVER_FORWARD, OPS_PER_MAC,
                                 TRAIN_PASSES)
from benchmark.ops_count_sdar import attention_macs_per_position


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def selected_pairs(seq: int, topk: int) -> int:
    """`sum_t min(t + 1, topk)` over the `seq` rows: 14.68 M of the 33.56 M
    causal pairs at 8,192 rows and 2,048 (43.8 %)."""
    full = min(seq, topk)
    return full * (full + 1) // 2 + (seq - full) * topk


def indexer_macs_per_position(hidden: int, heads: int, head_dim: int) -> int:
    """W_qI at `heads * head_dim`, W_kI at `head_dim`, W_w at `heads`."""
    return hidden * (heads * head_dim + head_dim + heads)


def attention_forward_ops_per_token(pairs: int, seq: int, heads: int,
                                    head_dim: int) -> float:
    """QK^T and PV over `pairs` (query, key) pairs a head: `4 e` operations a
    pair, a head; per token."""
    return 2 * OPS_PER_MAC * head_dim * heads * pairs / seq


def index_forward_ops_per_token(seq: int, heads: int, head_dim: int) -> float:
    """The score product over the causal pairs: `2 e` operations a pair and
    an indexer head (the ReLU and the weighted sum over heads are no matrix
    work); per token."""
    return OPS_PER_MAC * head_dim * heads * causal_pairs(seq) / seq


def flash_kernel(seq: int, heads: int, head_dim: int, layers: int,
                 itemsize: int = 2) -> dict:
    """{"fwd": {"ops", "bytes"}, "bwd": {...}} per token over `layers` layers
    of the `_selected` flash kernels, as the kernels execute them: the two
    products over the CAUSAL pairs (the mask is data; no tile is skipped for
    it); forward reads q, k, v and writes o once (`heads` wide: a key/value
    head is repeated before the kernels), backward reads q, k, v, o, do and
    writes dq, dk, dv, 2.5 times the forward's operations.  The selection's
    int8 tiles, read again by every head, are the kernels' own business."""
    forward = layers * attention_forward_ops_per_token(
        causal_pairs(seq), seq, heads, head_dim)
    row = heads * head_dim * itemsize * layers
    return {"fwd": {"ops": forward, "bytes": 4 * row},
            "bwd": {"ops": forward * FLASH_BACKWARD_OVER_FORWARD,
                    "bytes": 8 * row}}


def index_kernel(seq: int, heads: int, head_dim: int, layers: int,
                 itemsize: int = 2) -> dict:
    """{"ops", "bytes"} per token over `layers` layers of the score product's
    three kernels (`hvd_dsa_index`, `hvd_dsa_index_bwd_dq`,
    `hvd_dsa_index_bwd_dk`) as the algorithm needs them: forward one product
    a causal pair and head; each backward kernel recomputes it and adds its
    gradient product (dq's, dk's), four products' worth.  Bytes it cannot
    avoid: the float32 scores written once and their cotangent read by both
    backward kernels (a token's row of `(seq + 1) / 2` causal pairs each), and
    q_I, k_I read three times and their gradients written once."""
    product = layers * index_forward_ops_per_token(seq, heads, head_dim)
    narrow = (heads + 1) * head_dim * itemsize
    return {"ops": 5 * product,
            "bytes": layers * (3 * 4 * causal_pairs(seq) / seq + 4 * narrow)}


def keye_lm_train_ops_per_token(shape: dict, seq: int,
                                local_rows_per_token: float,
                                buffer_rows_per_token: float) -> dict:
    """`total`: what the model requires of this chip per token.
    `visible_to_compiler`: what libtpu's cost analysis reports for the same
    step: each `ragged_dot` a dense product over EVERY row of its buffer,
    nothing for the Pallas kernels (attention, the score product, the target
    pass; benchmark/ops_count_moe.py)."""
    a, i, e, hidden = (shape["attention"], shape["indexer"],
                       shape["experts"], shape["hidden"])
    passes, layers = OPS_PER_MAC * TRAIN_PASSES, shape["layers"]
    projections = layers * passes * attention_macs_per_position(
        hidden, a["heads"], a["kv_heads"], a["head_dim"])
    attention = TRAIN_PASSES * layers * attention_forward_ops_per_token(
        selected_pairs(seq, i["topk"]), seq, a["heads"], a["head_dim"])
    indexer_projections = layers * passes * indexer_macs_per_position(
        hidden, i["heads"], i["head_dim"])
    index = TRAIN_PASSES * layers * index_forward_ops_per_token(
        seq, i["heads"], i["head_dim"])
    router = layers * passes * hidden * e["num_experts"]
    per_row = layers * passes * 3 * hidden * e["expert_width"]
    head = passes * hidden * shape["vocab"]
    experts = per_row * local_rows_per_token
    seen = projections + indexer_projections + router + head
    return {"total": seen + experts + attention + index,
            "visible_to_compiler": seen + per_row * buffer_rows_per_token,
            "attention": attention, "indexer": index + indexer_projections,
            "experts": experts, "attention_projections": projections,
            "router": router, "head": head}
