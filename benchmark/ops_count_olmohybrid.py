"""Operations and bytes of the Olmo-Hybrid decoder's training step (Gated
DeltaNet mixers whose keys and values have widths of their own, full attention
with a q/k norm over the whole projection and no rotary, a dense gated MLP
behind every mixer), from shapes alone, by benchmark/ops_count.py's rules: a
multiply-add is TWO operations, a training step is three passes of every
matmul, nothing recomputed is counted for utilisation.

What is counted is the ALGORITHM's work done HERE, whatever implements it: the
heads this chip holds of each mixer (`shape`, as
benchmark/builders/olmohybrid_lm.py fills it), the MLP whole, and the delta
rule at its TRUE widths — a key of 96 and a value of 192 fill three quarters of
a 128-lane tile and of an MXU pass, and what a kernel pads to whole tiles is
the kernel's own business, not the model's work.
"""

from __future__ import annotations

from benchmark import ops_count
from benchmark.ops_count import OPS_PER_MAC, TRAIN_PASSES
from benchmark.ops_count_qwen3next import (attention_forward_ops_per_token,
                                           delta_rule_kernel_bytes,
                                           delta_rule_kernel_ops,
                                           delta_rule_macs_per_token)


def gated_delta_macs_per_token(hidden: int, heads: int, d_k: int,
                               d_v: int) -> int:
    """The two projections of one Gated DeltaNet layer: into q, k (heads of
    d_k), v, z (heads of d_v) and a head's b and a, and out."""
    keys, values = heads * d_k, heads * d_v
    return hidden * (2 * keys + 2 * values + 2 * heads) + values * hidden


def attention_macs_per_token(hidden: int, heads: int, head_dim: int) -> int:
    """W_q, W_k, W_v and W_o of the heads held."""
    return 4 * hidden * heads * head_dim


def olmohybrid_lm_train_ops_per_token(shape: dict, seq: int) -> dict:
    """`total`: what the model requires of this chip per token.
    `visible_to_compiler`: what libtpu's cost analysis reports for the same
    step: the delta rule's products as the einsums and the kernels' cost
    estimates they are, nothing for the Pallas attention kernels
    (benchmark/ops_count_qwen3next.py)."""
    g, a = shape["gdn"], shape["attention"]
    hidden = shape["hidden"]
    passes = OPS_PER_MAC * TRAIN_PASSES
    gdn = shape["gdn_layers"] * passes * (
        gated_delta_macs_per_token(hidden, g["heads"], g["d_k"], g["d_v"])
        + delta_rule_macs_per_token(g["heads"], g["heads"], g["d_k"],
                                    g["d_v"], g["chunk"]))
    projections = shape["attention_layers"] * passes \
        * attention_macs_per_token(hidden, a["heads"], a["head_dim"])
    attention = TRAIN_PASSES * shape["attention_layers"] \
        * attention_forward_ops_per_token(seq, a["heads"], a["head_dim"])
    mlp = shape["mlp_layers"] * passes * 3 * hidden * shape["intermediate"]
    head = passes * hidden * shape["vocab"]
    seen = gdn + projections + mlp + head
    return {"total": seen + attention, "visible_to_compiler": seen,
            "attention": attention, "gdn": gdn, "mlp": mlp, "head": head}


def flash_kernel(seq: int, heads: int, head_dim: int, layers: int,
                 itemsize: int = 2) -> dict:
    """{"ops", "bytes"} per token of the causal flash kernels at the heads
    held, forward and backward together, as `flash_roofline` splits them
    (benchmark/ops_count.py: `hidden` there is all the heads' channels)."""
    return {"ops": ops_count.flash_kernel_ops_per_token(
                seq, heads * head_dim, layers),
            "bytes": ops_count.flash_kernel_bytes_per_token(
                heads * head_dim, layers, itemsize)}


def scan_least_seconds(shape: dict, tokens: float, peak: dict) -> float:
    """The least time the chip could take for every Gated DeltaNet layer's
    delta rule over `tokens` tokens, forward and backward: the larger of its
    products' operations over peak FLOP/s and the bytes it cannot avoid over
    peak bytes/s, at the true widths `shape` holds (`heads`, `d_k`, `d_v`,
    `chunk`, `layers`, `itemsize`)."""
    sizes = (shape["heads"], shape["heads"], shape["d_k"], shape["d_v"])
    return shape["layers"] * max(
        delta_rule_kernel_ops(tokens, *sizes, shape["chunk"])
        / peak["bf16_flops_per_s"],
        delta_rule_kernel_bytes(tokens, *sizes, shape["itemsize"])
        / peak["hbm_bytes_per_s"])
