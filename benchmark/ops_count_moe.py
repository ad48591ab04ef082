"""Operations and bytes of a sparse-expert decoder's training step, from
shapes alone, by benchmark/ops_count.py's rules: a multiply-add is TWO
operations, a training step is three passes of every matmul, nothing
recomputed is counted for utilisation.

What is counted is the work done HERE: with the experts of one shard of `n`
on this chip, a token's k choices put `k / n` rows on it on average
(`local_rows_per_token`), not k.
"""

from __future__ import annotations

from benchmark.ops_count import (OPS_PER_MAC, TRAIN_PASSES,
                                 causal_attention_forward_ops_per_token)

# Gate, up and down, each forward, gradient of the rows, gradient of the
# weights: nine grouped matmuls a layer, all of hidden x expert_width.
GROUPED_MATMULS = 3 * TRAIN_PASSES


def moe_lm_dense_macs_per_token(hidden: int, layers: int, vocab: int,
                                num_experts: int) -> int:
    """Weights that multiply every token: q, k, v, o and the router of each
    layer, and the output head.  The embedding is a lookup."""
    return layers * (4 * hidden * hidden + hidden * num_experts) \
        + hidden * vocab


def expert_macs_per_row(hidden: int, expert_width: int) -> int:
    """Gate, up and down of one expert on one row."""
    return 3 * hidden * expert_width


def moe_lm_train_ops_per_token(hidden: int, expert_width: int, layers: int,
                               vocab: int, seq: int, num_experts: int,
                               local_rows_per_token: float,
                               buffer_rows_per_token: float) -> dict:
    """`total`: what the model requires of this chip per token.
    `visible_to_compiler`: what libtpu's cost analysis reports for the same
    step (described v5e, PR 26): it counts each `ragged_dot` as a dense
    product over EVERY row of its buffer (the custom call's own
    `cost_estimate`: 2 * buffer rows * k * n, whatever the group sizes), and
    nothing for the Pallas attention kernels."""
    dense = OPS_PER_MAC * TRAIN_PASSES * moe_lm_dense_macs_per_token(
        hidden, layers, vocab, num_experts)
    per_row = OPS_PER_MAC * TRAIN_PASSES * layers * expert_macs_per_row(
        hidden, expert_width)
    attention = TRAIN_PASSES * layers * causal_attention_forward_ops_per_token(
        seq, hidden)
    experts = per_row * local_rows_per_token
    return {"total": dense + experts + attention,
            "visible_to_compiler": dense + per_row * buffer_rows_per_token,
            "attention": attention, "experts": experts}


def expert_kernel_ops(rows: float, hidden: int, expert_width: int) -> float:
    """Operations of the nine grouped matmuls of ONE layer over `rows` rows
    really routed to this chip's experts."""
    return GROUPED_MATMULS * OPS_PER_MAC * rows * hidden * expert_width


def expert_kernel_bytes(rows: float, local_experts: int, hidden: int,
                        expert_width: int, itemsize: int = 2) -> float:
    """HBM bytes those nine cannot avoid: each reads or writes the rows at
    both widths and the weights of every local expert once, in the compute
    dtype (the float32 master weights, their casts and the float32 the
    weight-gradient kernel writes are the program's own business)."""
    return GROUPED_MATMULS * itemsize * (
        rows * (hidden + expert_width)
        + local_experts * hidden * expert_width)
