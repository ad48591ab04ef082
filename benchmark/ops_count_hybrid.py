"""Operations and bytes of the hybrid decoder's training step (Mamba-2,
grouped-query attention, latent sparse experts), from shapes alone, by
benchmark/ops_count.py's rules: a multiply-add is TWO operations, a training
step is three passes of every matmul, nothing recomputed is counted for
utilisation.

What is counted is the work done HERE: the heads, groups and experts this chip
holds (`shape`, as benchmark/builders/hybrid_lm.py fills it), a token's k
choices putting `k / n` rows on this chip's experts on average.
"""

from __future__ import annotations

from benchmark.ops_count import (OPS_PER_MAC, TRAIN_PASSES,
                                 causal_attention_forward_ops_per_token)

# Up and down of a non-gated expert, each forward, gradient of the rows,
# gradient of the weights: six grouped matmuls a layer.
GROUPED_MATMULS = 2 * TRAIN_PASSES


def scan_macs_per_token(heads: int, head_dim: int, groups: int, state: int,
                        chunk: int) -> int:
    """The chunked scan's four products for one token of one layer
    (`ops/ssm.py`): C B^T against the chunk's `chunk` tokens, a group; the
    masked scores times x, a head; the chunk's end state B^T x and the
    entering state's C h, a head.  The masked half of a chunk's square is
    multiplied like the rest and is counted: it is the form's work, and the
    carry over `seq / chunk` chunks (a 32 x 32 matrix a head) is not."""
    return groups * chunk * state + heads * (chunk * head_dim
                                             + 2 * state * head_dim)


def mamba2_macs_per_token(hidden: int, heads: int, head_dim: int, groups: int,
                          state: int) -> int:
    """The two projections of one layer: into z, x, B, C, dt and out."""
    inner, bc = heads * head_dim, groups * state
    return hidden * (2 * inner + 2 * bc + heads) + inner * hidden


def attention_macs_per_token(hidden: int, heads: int, kv_heads: int,
                             head_dim: int) -> int:
    """q, k, v and o of one layer with the heads held here."""
    return hidden * head_dim * (2 * heads + 2 * kv_heads)


def expert_layer_dense_macs_per_token(hidden: int, num_experts: int,
                                      latent: int, shared: int) -> int:
    """What multiplies every token in one expert layer: the router, the two
    latent projections and the shared expert."""
    return hidden * num_experts + 2 * hidden * latent + 2 * hidden * shared


def expert_macs_per_row(latent: int, expert_width: int) -> int:
    """Up and down of one expert on one row."""
    return 2 * latent * expert_width


def hybrid_lm_train_ops_per_token(shape: dict, seq: int,
                                  local_rows_per_token: float,
                                  buffer_rows_per_token: float) -> dict:
    """`total`: what the model requires of this chip per token.
    `visible_to_compiler`: what libtpu's cost analysis reports for the same
    step: each `ragged_dot` a dense product over EVERY row of its buffer, the
    scan's products as the einsums they are, nothing for the Pallas attention
    kernels (benchmark/ops_count_moe.py)."""
    s, a, e = shape["ssm"], shape["attention"], shape["experts"]
    hidden = shape["hidden"]
    passes = OPS_PER_MAC * TRAIN_PASSES
    ssm = shape["ssm_layers"] * passes * (
        mamba2_macs_per_token(hidden, s["heads"], s["head_dim"], s["groups"],
                              s["state"])
        + scan_macs_per_token(s["heads"], s["head_dim"], s["groups"],
                              s["state"], s["chunk"]))
    projections = shape["attention_layers"] * passes \
        * attention_macs_per_token(hidden, a["heads"], a["kv_heads"],
                                   a["head_dim"])
    attention = TRAIN_PASSES * shape["attention_layers"] \
        * causal_attention_forward_ops_per_token(
            seq, a["heads"] * a["head_dim"])
    dense = shape["expert_layers"] * passes \
        * expert_layer_dense_macs_per_token(hidden, e["num_experts"],
                                            e["latent"], e["shared"])
    per_row = shape["expert_layers"] * passes * expert_macs_per_row(
        e["latent"], e["expert_width"])
    head = passes * hidden * shape["vocab"]
    experts = per_row * local_rows_per_token
    seen = ssm + projections + dense + head
    return {"total": seen + experts + attention,
            "visible_to_compiler": seen + per_row * buffer_rows_per_token,
            "attention": attention, "experts": experts, "ssm": ssm,
            "shared_expert": shape["expert_layers"] * passes * 2 * hidden
            * e["shared"]}


def scan_kernel_ops(tokens: float, heads: int, head_dim: int, groups: int,
                    state: int, chunk: int) -> float:
    """Operations of ONE layer's scan over `tokens` tokens, forward and both
    gradient passes."""
    return OPS_PER_MAC * TRAIN_PASSES * tokens * scan_macs_per_token(
        heads, head_dim, groups, state, chunk)


def scan_kernel_bytes(tokens: float, heads: int, head_dim: int, groups: int,
                      state: int, itemsize: int = 2) -> float:
    """HBM bytes ONE layer's scan cannot avoid: forward reads x, B, C in the
    compute dtype and dt in float32 and writes y; backward reads them and y's
    cotangent again and writes the four cotangents.  Every decay matrix and
    state between is the form's own business."""
    row = itemsize * (heads * head_dim + 2 * groups * state) + 4 * heads
    out = itemsize * heads * head_dim
    return tokens * ((row + out) + (row + out + row))


def expert_kernel_ops(rows: float, latent: int, expert_width: int) -> float:
    """Operations of the six grouped matmuls of ONE layer over `rows` rows
    really routed to this chip's experts."""
    return GROUPED_MATMULS * OPS_PER_MAC * rows * latent * expert_width


def expert_kernel_bytes(rows: float, local_experts: int, latent: int,
                        expert_width: int, itemsize: int = 2) -> float:
    """HBM bytes those six cannot avoid: each reads or writes the rows at
    both widths and the weights of every local expert once, in the compute
    dtype."""
    return GROUPED_MATMULS * itemsize * (
        rows * (latent + expert_width)
        + local_experts * latent * expert_width)
