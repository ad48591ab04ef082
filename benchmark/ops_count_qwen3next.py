"""Operations and bytes of the Qwen3-Next decoder's training step (Gated
DeltaNet mixers over grouped heads, gated grouped-query attention at a head of
256, softmax-routed sparse experts beside a gated shared one), from shapes
alone, by benchmark/ops_count.py's rules: a multiply-add is TWO operations, a
training step is three passes of every matmul, nothing recomputed is counted
for utilisation.

What is counted is the ALGORITHM's work done HERE, whatever implements it: the
experts this chip holds (`shape`, as benchmark/builders/qwen3next_lm.py fills
it), a token's k choices putting `k / n` rows on this chip's experts on
average; the products of q and k alone (K K^T, Q K^T) once a KEY head of the
delta rule, the k and v of attention once a key/value head.
"""

from __future__ import annotations

from benchmark.ops_count import OPS_PER_MAC, TRAIN_PASSES


def gated_delta_macs_per_token(hidden: int, key_heads: int, value_heads: int,
                               head_dim: int) -> int:
    """The two projections of one Gated DeltaNet layer: into q, k (key
    heads), v, z (value heads) and a value head's b and a, and out."""
    keys, values = key_heads * head_dim, value_heads * head_dim
    return hidden * (2 * keys + 2 * values + 2 * value_heads) \
        + values * hidden


def delta_rule_macs_per_token(key_heads: int, value_heads: int, d_k: int,
                              d_v: int, chunk: int) -> float:
    """The chunked delta rule's products for one token of one layer with a
    decay a head over grouped heads (`ops/delta_rule.py`).  A KEY head: K K^T
    and Q K^T against the chunk's `chunk` tokens (the masked half is
    multiplied like the rest and is counted: it is the form's work).  A VALUE
    head: the substitution for T (chunk^3 / 3 a chunk), T against beta K G and
    beta V, and in the recurrence W S, Q S, (Q K^T) U and K^T U.  The sums of
    log-decays are no product of the algorithm's and are not counted."""
    return key_heads * 2 * chunk * d_k + value_heads * (
        chunk * chunk / 3 + chunk * (d_k + d_v) + 3 * d_k * d_v + chunk * d_v)


def gated_attention_macs_per_token(hidden: int, heads: int, kv_heads: int,
                                   head_dim: int) -> int:
    """W_q and the gate beside it, W_k and W_v once a key/value head, W_o."""
    return hidden * heads * head_dim * 2 + hidden * 2 * kv_heads * head_dim \
        + heads * head_dim * hidden


def attention_forward_ops_per_token(seq: int, heads: int,
                                    head_dim: int) -> int:
    """QK^T and PV over the causal half: 2 ops x (seq / 2) keys x 2 head_dim,
    a query head."""
    return seq * heads * 2 * head_dim


def flash_kernel(seq: int, heads: int, kv_heads: int, head_dim: int,
                 layers: int, itemsize: int = 2) -> dict:
    """{"fwd": {"ops", "bytes"}, "bwd": {...}} per token over `layers` layers
    of the causal flash kernels.  Forward: the two products over the causal
    pairs; reads q and writes o a query head, reads k and v ONCE A KEY/VALUE
    HEAD (the repeat for the query heads that read them is the program's own
    business).  Backward, as the algorithm needs it (Dao et al., algorithm 2):
    the probabilities again and four gradient products, 2.5 times the
    forward's operations — the split pair's second recompute is the kernels'
    own business and is not credited, as `mla_flash_bwd_roofline` counts;
    reads q, o, do a query head and k, v a key/value head, writes dq a query
    head and dk, dv a key/value head."""
    forward = layers * attention_forward_ops_per_token(seq, heads, head_dim)
    wide = head_dim * itemsize * layers
    return {"fwd": {"ops": forward,
                    "bytes": wide * (2 * heads + 2 * kv_heads)},
            "bwd": {"ops": forward * 2.5,
                    "bytes": wide * (4 * heads + 4 * kv_heads)}}


def expert_layer_dense_macs_per_token(hidden: int, num_experts: int,
                                      shared: int) -> int:
    """What multiplies every token in one expert layer: the router, the gated
    shared expert and its one-wide output gate."""
    return hidden * num_experts + 3 * hidden * shared + hidden


def qwen3next_lm_train_ops_per_token(shape: dict, seq: int,
                                     local_rows_per_token: float,
                                     buffer_rows_per_token: float) -> dict:
    """`total`: what the model requires of this chip per token.
    `visible_to_compiler`: what libtpu's cost analysis reports for the same
    step: each `ragged_dot` a dense product over EVERY row of its buffer, the
    delta rule's products as the einsums they are, nothing for the Pallas
    attention kernels (benchmark/ops_count_moe.py)."""
    g, a, e = shape["gdn"], shape["attention"], shape["experts"]
    hidden = shape["hidden"]
    passes = OPS_PER_MAC * TRAIN_PASSES
    gdn = shape["gdn_layers"] * passes * (
        gated_delta_macs_per_token(hidden, g["key_heads"], g["value_heads"],
                                   g["head_dim"])
        + delta_rule_macs_per_token(g["key_heads"], g["value_heads"],
                                    g["head_dim"], g["head_dim"], g["chunk"]))
    projections = shape["attention_layers"] * passes \
        * gated_attention_macs_per_token(hidden, a["heads"], a["kv_heads"],
                                         a["head_dim"])
    attention = TRAIN_PASSES * shape["attention_layers"] \
        * attention_forward_ops_per_token(seq, a["heads"], a["head_dim"])
    dense = shape["expert_layers"] * passes \
        * expert_layer_dense_macs_per_token(hidden, e["num_experts"],
                                            e["shared"])
    per_row = shape["expert_layers"] * passes * 3 * hidden * e["expert_width"]
    head = passes * hidden * shape["vocab"]
    experts = per_row * local_rows_per_token
    seen = gdn + projections + dense + head
    return {"total": seen + experts + attention,
            "visible_to_compiler": seen + per_row * buffer_rows_per_token,
            "attention": attention, "experts": experts, "gdn": gdn,
            "head": head}


def delta_rule_kernel_ops(tokens: float, key_heads: int, value_heads: int,
                          d_k: int, d_v: int, chunk: int) -> float:
    """Operations of ONE layer's delta rule over `tokens` tokens, forward and
    both gradient passes."""
    return OPS_PER_MAC * TRAIN_PASSES * tokens * delta_rule_macs_per_token(
        key_heads, value_heads, d_k, d_v, chunk)


def delta_rule_kernel_bytes(tokens: float, key_heads: int, value_heads: int,
                            d_k: int, d_v: int, itemsize: int = 2) -> float:
    """HBM bytes ONE layer's delta rule cannot avoid: forward reads q, k (a
    key head) and v (a value head) in the compute dtype, a value head's
    log-decay and beta in float32, and writes o in float32; backward reads
    them and o's cotangent again and writes the five cotangents.  Every decay
    matrix, solve and state between is the form's own business."""
    row = itemsize * (2 * key_heads * d_k + value_heads * d_v) \
        + 8 * value_heads
    out = 4 * value_heads * d_v
    return tokens * ((row + out) + (row + out + row))
