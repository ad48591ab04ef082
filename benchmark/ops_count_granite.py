"""Operations of the Granite-4.0-H decoder's training step (Mamba-2 mixers on
ONE group, grouped-query attention without a position embedding, a dense gated
MLP behind every mixer, a head TIED to the embedding), from shapes alone, by
benchmark/ops_count.py's rules: a multiply-add is TWO operations, a training
step is three passes of every matmul, nothing recomputed is counted for
utilisation (a cell that recomputes every entry executes a third more than it
is credited with).

What is counted is the ALGORITHM's work done HERE (`shape`, as
benchmark/builders/granite_lm.py fills it): every layer whole.  The tied table
is ONE parameter with two uses and one of them multiplies: the lookup is a
gather (its gradient a scatter-add), the head is three products over the
vocabulary slice.  The four scalar multipliers are element-wise and are not
counted.  The scan's bytes and operations a layer are
benchmark/ops_count_hybrid.py's (`scan_kernel_ops`, `scan_kernel_bytes`),
which `layer_metrics/_hybrid.scan_roofline_pct` reads at this cell's sizes
from `Built.kernels["ssm_scan"]`.
"""

from __future__ import annotations

from benchmark.ops_count import (OPS_PER_MAC, TRAIN_PASSES,
                                 causal_attention_forward_ops_per_token)
from benchmark.ops_count_hybrid import (attention_macs_per_token,
                                        mamba2_macs_per_token,
                                        scan_macs_per_token)


def parameters(shape: dict) -> int:
    """The model's parameters as the builder's tree holds them, the tied
    table ONCE: what `deployment` in the configuration counts by hand."""
    s, a, hidden = shape["ssm"], shape["attention"], shape["hidden"]
    inner, bc = s["heads"] * s["head_dim"], s["groups"] * s["state"]
    mamba2 = mamba2_macs_per_token(hidden, s["heads"], s["head_dim"],
                                   s["groups"], s["state"]) \
        + (s["conv"] + 1) * (inner + 2 * bc) + 3 * s["heads"] + inner
    attention = attention_macs_per_token(hidden, a["heads"], a["kv_heads"],
                                         a["head_dim"])
    mlp = 3 * hidden * shape["intermediate"]
    entries = shape["ssm_layers"] + shape["attention_layers"] \
        + shape["mlp_layers"]
    return shape["ssm_layers"] * mamba2 \
        + shape["attention_layers"] * attention + shape["mlp_layers"] * mlp \
        + (entries + 1) * hidden + shape["vocab"] * hidden


def granite_lm_train_ops_per_token(shape: dict, seq: int) -> dict:
    """`total`: what the model requires of this chip per token.
    `visible_to_compiler`: what libtpu's cost analysis reports for the same
    step: the scan's products as the einsums they are, nothing for the Pallas
    attention kernels (benchmark/ops_count_hybrid.py)."""
    s, a, hidden = shape["ssm"], shape["attention"], shape["hidden"]
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
    mlp = shape["mlp_layers"] * passes * 3 * hidden * shape["intermediate"]
    head = passes * hidden * shape["vocab"]
    seen = ssm + projections + mlp + head
    return {"total": seen + attention, "visible_to_compiler": seen,
            "attention": attention, "ssm": ssm, "mlp": mlp, "head": head}
