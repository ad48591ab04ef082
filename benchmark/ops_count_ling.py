"""Operations and bytes of the Ling-3.0-flash decoder's training step
(Kimi-delta mixers, latent attention with two head widths, a dense gated MLP,
gated sparse experts with a shared one), from shapes alone, by
benchmark/ops_count.py's rules: a multiply-add is TWO operations, a training
step is three passes of every matmul, nothing recomputed is counted for
utilisation.

What is counted is the work done HERE: the heads and experts this chip holds
(`shape`, as benchmark/builders/ling_lm.py fills it), a token's k choices
putting `k / n` rows on this chip's experts on average.
"""

from __future__ import annotations

from benchmark.ops_count import OPS_PER_MAC, TRAIN_PASSES


def kda_macs_per_token(hidden: int, heads: int, head_dim: int) -> int:
    """The two projections of one Kimi-delta layer: into q, k, v, the decay's
    and the gate's pre-activations and beta, and out."""
    inner = heads * head_dim
    return hidden * (5 * inner + heads) + inner * hidden


def delta_rule_macs_per_token(heads: int, d_k: int, d_v: int,
                              chunk: int) -> float:
    """The chunked delta rule's products for one token of one layer
    (`ops/delta_rule.py`), a head: K K^T and Q K^T against the chunk's
    `chunk` tokens (the masked half is multiplied like the rest and is
    counted: it is the form's work), the substitution for T (chunk^3 / 3 a
    chunk), T against beta K G and beta V, and in the recurrence W S, Q S,
    (Q K^T) U and K^T U."""
    return heads * (2 * chunk * d_k + chunk * chunk / 3
                    + chunk * (d_k + d_v)
                    + 3 * d_k * d_v + chunk * d_v)


def latent_attention_macs_per_token(hidden: int, heads: int, kv_rank: int,
                                    nope_dim: int, rope_dim: int,
                                    v_dim: int) -> int:
    """W_q, W_kva, W_kvb, the head-wise gate and W_o of one layer with the
    heads held here; the latent's projection down is whole on every chip."""
    return hidden * heads * (nope_dim + rope_dim) \
        + hidden * (kv_rank + rope_dim) \
        + kv_rank * heads * (nope_dim + v_dim) \
        + hidden * heads + heads * v_dim * hidden


def two_width_attention_forward_ops_per_token(seq: int, heads: int, d_qk: int,
                                              d_v: int) -> int:
    """QK^T at `d_qk` and PV at `d_v` over the causal half: 2 ops x (seq / 2)
    keys x (d_qk + d_v), a head.  With one width it is
    ops_count.causal_attention_forward_ops_per_token."""
    return seq * heads * (d_qk + d_v)


def flash_two_width_kernel(seq: int, heads: int, d_qk: int, d_v: int,
                           layers: int, itemsize: int = 2) -> dict:
    """{"fwd": {"ops", "bytes"}, "bwd": {...}} per token over all layers, of
    the flash kernels with a query/key width and a value width.  Forward: the
    two products above; reads q, k (d_qk) and v, writes o (d_v).  Backward, as
    the algorithm needs it (Dao et al., algorithm 2): the probabilities again
    (one more QK^T) and four gradient products — dP = dO V^T and dV at d_v, dK
    and dQ at d_qk — so 3 d_qk + 2 d_v against the forward's d_qk + d_v; reads
    q, k, v, o, do and writes dq, dk, dv.  The split kernel pair's second
    recompute is the kernels' own business, like any re-reading of k and v."""
    forward = layers * two_width_attention_forward_ops_per_token(
        seq, heads, d_qk, d_v)
    row = heads * itemsize * layers
    return {"fwd": {"ops": forward, "bytes": row * (2 * d_qk + 2 * d_v)},
            "bwd": {"ops": forward * (3 * d_qk + 2 * d_v) / (d_qk + d_v),
                    "bytes": row * (4 * d_qk + 4 * d_v)}}


def expert_layer_dense_macs_per_token(hidden: int, num_experts: int,
                                      shared: int) -> int:
    """What multiplies every token in one expert layer: the router and the
    gated shared expert."""
    return hidden * num_experts + 3 * hidden * shared


def ling_lm_train_ops_per_token(shape: dict, seq: int,
                                local_rows_per_token: float,
                                buffer_rows_per_token: float) -> dict:
    """`total`: what the model requires of this chip per token.
    `visible_to_compiler`: what libtpu's cost analysis reports for the same
    step: each `ragged_dot` a dense product over EVERY row of its buffer, the
    delta rule's products as the einsums they are, nothing for the Pallas
    attention kernels (benchmark/ops_count_moe.py)."""
    k, a, e = shape["kda"], shape["latent_attention"], shape["experts"]
    hidden = shape["hidden"]
    passes = OPS_PER_MAC * TRAIN_PASSES
    kda = shape["kda_layers"] * passes * (
        kda_macs_per_token(hidden, k["heads"], k["head_dim"])
        + delta_rule_macs_per_token(k["heads"], k["head_dim"], k["head_dim"],
                                    k["chunk"]))
    projections = shape["latent_attention_layers"] * passes \
        * latent_attention_macs_per_token(
            hidden, a["heads"], a["kv_rank"], a["nope_dim"], a["rope_dim"],
            a["v_dim"])
    attention = TRAIN_PASSES * shape["latent_attention_layers"] \
        * two_width_attention_forward_ops_per_token(
            seq, a["heads"], a["nope_dim"] + a["rope_dim"], a["v_dim"])
    mlp = shape["mlp_layers"] * passes * 3 * hidden * shape["mlp_width"]
    dense = shape["expert_layers"] * passes \
        * expert_layer_dense_macs_per_token(hidden, e["num_experts"],
                                            e["shared"])
    per_row = shape["expert_layers"] * passes * 3 * hidden * e["expert_width"]
    head = passes * hidden * shape["vocab"]
    experts = per_row * local_rows_per_token
    seen = kda + projections + mlp + dense + head
    return {"total": seen + experts + attention,
            "visible_to_compiler": seen + per_row * buffer_rows_per_token,
            "attention": attention, "experts": experts, "kda": kda,
            "mlp": mlp, "head": head}


def delta_rule_kernel_ops(tokens: float, heads: int, d_k: int, d_v: int,
                          chunk: int) -> float:
    """Operations of ONE layer's delta rule over `tokens` tokens, forward and
    both gradient passes."""
    return OPS_PER_MAC * TRAIN_PASSES * tokens * delta_rule_macs_per_token(
        heads, d_k, d_v, chunk)


def delta_rule_kernel_bytes(tokens: float, heads: int, d_k: int, d_v: int,
                            itemsize: int = 2) -> float:
    """HBM bytes ONE layer's delta rule cannot avoid: forward reads q, k, v in
    the compute dtype, the log-decays (a channel) and beta in float32, and
    writes o in float32; backward reads them and o's cotangent again and
    writes the five cotangents.  Every decay, solve and state between is the
    form's own business."""
    row = heads * (itemsize * (2 * d_k + d_v) + 4 * d_k + 4)
    out = 4 * heads * d_v
    return tokens * ((row + out) + (row + out + row))
