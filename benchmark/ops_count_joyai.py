"""Operations of the JoyAI-LLM-Flash decoder's training step (latent attention
with a query latent and no gate in every published layer, one dense gated MLP,
sigmoid-routed gated experts with a shared one, and a multi-token-prediction
module: a projection, one more whole layer and a second application of the
head), from shapes alone, by benchmark/ops_count.py's rules: a multiply-add is
TWO operations, a training step is three passes of every matmul, nothing
recomputed is counted for utilisation.

What is counted is the work done HERE: the experts this chip holds (`shape`,
as benchmark/builders/joyai_lm.py fills it), a token's k choices putting
`k / n` rows on this chip's experts on average; the module's layer and its
head are counted with the main model's.  The two-width flash kernels'
operations and bytes are benchmark/ops_count_ling.py's
(`flash_two_width_kernel`), over the main layers' blocks and the module's.
"""

from __future__ import annotations

from benchmark.ops_count import OPS_PER_MAC, TRAIN_PASSES
from benchmark.ops_count_ling import (
    expert_layer_dense_macs_per_token,
    two_width_attention_forward_ops_per_token)


def latent_attention_macs_per_token(hidden: int, heads: int, q_rank: int,
                                    kv_rank: int, nope_dim: int,
                                    rope_dim: int, v_dim: int) -> int:
    """W_qa, W_qb, W_kva, W_kvb and W_o of one layer, every head held here;
    no gate."""
    return hidden * q_rank + q_rank * heads * (nope_dim + rope_dim) \
        + hidden * (kv_rank + rope_dim) \
        + kv_rank * heads * (nope_dim + v_dim) + heads * v_dim * hidden


def parameters(shape: dict) -> dict:
    """Parameters by part, as the model's tree holds them (norm scales
    included), and their sum."""
    a, e, hidden = shape["latent_attention"], shape["experts"], \
        shape["hidden"]
    attention = latent_attention_macs_per_token(
        hidden, a["heads"], a["q_rank"], a["kv_rank"], a["nope_dim"],
        a["rope_dim"], a["v_dim"]) + a["q_rank"] + a["kv_rank"] + hidden
    expert_entry = hidden + hidden * e["num_experts"] \
        + 3 * hidden * e["shared"] \
        + e["local_experts"] * 3 * hidden * e["expert_width"]
    dense_entry = hidden + 3 * hidden * shape["mlp_width"]
    parts = {
        "latent_attention_a_block": attention,
        "expert_entry": expert_entry, "dense_entry": dense_entry,
        "embedding_and_head": 2 * hidden * shape["vocab"],
        # W_eh, the module's three norms, its block; `final_norm` is the
        # main model's.
        "mtp_module": shape["mtp_modules"] * (
            2 * hidden * hidden + 3 * hidden + attention + expert_entry)}
    total = (shape["latent_attention_layers"] * attention
             + shape["expert_layers"] * expert_entry
             + shape["mlp_layers"] * dense_entry
             + parts["embedding_and_head"] + parts["mtp_module"] + hidden)
    return dict(parts, total=total)


def joyai_lm_train_ops_per_token(shape: dict, seq: int,
                                 local_rows_per_token: float,
                                 buffer_rows_per_token: float) -> dict:
    """`total`: what the model requires of this chip per token, the module
    counted.  `visible_to_compiler`: what libtpu's cost analysis reports for
    the same step: each `ragged_dot` a dense product over EVERY row of its
    buffer, nothing for the Pallas attention kernels
    (benchmark/ops_count_moe.py).  `mtp`: the module's part of `total` (its
    projection, its block, the second head)."""
    a, e, hidden = shape["latent_attention"], shape["experts"], \
        shape["hidden"]
    modules = shape["mtp_modules"]
    passes = OPS_PER_MAC * TRAIN_PASSES
    blocks = shape["latent_attention_layers"] + modules
    expert_entries = shape["expert_layers"] + modules
    one_projections = passes * latent_attention_macs_per_token(
        hidden, a["heads"], a["q_rank"], a["kv_rank"], a["nope_dim"],
        a["rope_dim"], a["v_dim"])
    one_attention = TRAIN_PASSES * two_width_attention_forward_ops_per_token(
        seq, a["heads"], a["nope_dim"] + a["rope_dim"], a["v_dim"])
    one_dense = passes * expert_layer_dense_macs_per_token(
        hidden, e["num_experts"], e["shared"])
    per_row = passes * 3 * hidden * e["expert_width"]
    one_head = passes * hidden * shape["vocab"]
    mlp = shape["mlp_layers"] * passes * 3 * hidden * shape["mlp_width"]
    mtp_proj = modules * passes * 2 * hidden * hidden
    seen = blocks * one_projections + mlp + expert_entries * one_dense \
        + (1 + modules) * one_head + mtp_proj
    experts = expert_entries * per_row * local_rows_per_token
    attention = blocks * one_attention
    return {"total": seen + experts + attention,
            "visible_to_compiler": seen + expert_entries * per_row
            * buffer_rows_per_token,
            "attention": attention, "experts": experts,
            "latent_projections": blocks * one_projections, "mlp": mlp,
            "head": (1 + modules) * one_head,
            "mtp": modules * (one_projections + one_attention + one_dense
                              + per_row * local_rows_per_token + one_head)
            + mtp_proj}
