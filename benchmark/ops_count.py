"""Operations and bytes a training step requires, from shapes alone.

One multiply-add is TWO operations, as in the chips' published peaks
(benchmark/peaks.json).  A training step is counted as three forward passes
(forward, gradient with respect to the activations, gradient with respect to
the weights); recomputation is never counted for utilisation (`mfu_pct`).
Only the flash kernel's own roofline counts the recompute its backward pass
really does, because there the question is how well the kernel uses the chip
for the work it was given.

Every builder hands `run.py` the numbers of this file; `run.py` compares the
step's total with the compiler's own `cost_analysis()["flops"]` (the Pallas
calls are invisible to the compiler, so attention is left out of that
comparison) and prints the agreement.
"""

from __future__ import annotations

OPS_PER_MAC = 2
TRAIN_PASSES = 3  # forward + two gradient passes of every matmul/convolution


def _same_out(size: int, stride: int) -> int:
    return -(-size // stride)


def resnet_forward_macs(stage_sizes, num_filters: int, num_classes: int,
                        image_size: int, channels: int = 3,
                        expansion: int = 4) -> int:
    """Multiply-adds of one image's forward pass through a bottleneck ResNet
    (v1.5: the stride sits on the 3x3).  Convolutions and the dense head;
    batch norm, ReLU and pooling are not matrix work and are left out, as in
    the 3.8-4.1 G multiply-adds usually quoted for ResNet-50."""
    size = _same_out(image_size, 2)                       # 7x7 / 2 stem
    macs = size * size * 7 * 7 * channels * num_filters
    size = _same_out(size, 2)                             # 3x3 / 2 max pool
    width_in = num_filters
    for stage, blocks in enumerate(stage_sizes):
        mid = num_filters * 2 ** stage
        out = mid * expansion
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            macs += size * size * width_in * mid          # 1x1 at input size
            size_out = _same_out(size, stride)
            macs += size_out * size_out * 9 * mid * mid   # 3x3, strided
            macs += size_out * size_out * mid * out       # 1x1
            if width_in != out or stride != 1:            # projection
                macs += size_out * size_out * width_in * out
            size, width_in = size_out, out
    return macs + width_in * num_classes


def resnet_train_ops_per_image(**shape) -> dict:
    total = OPS_PER_MAC * TRAIN_PASSES * resnet_forward_macs(**shape)
    return {"total": total, "visible_to_compiler": total}


def dense_lm_matmul_params(hidden: int, intermediate: int, layers: int,
                           vocab: int) -> int:
    """Weights that multiply every token: q, k, v, o, up, down of each layer
    and the output head.  The embedding is a lookup and does no arithmetic."""
    return layers * (4 * hidden * hidden + 2 * hidden * intermediate) \
        + hidden * vocab


def causal_attention_forward_ops_per_token(seq: int, hidden: int) -> int:
    """QK^T and PV over the causal half: 2 products x 2 ops x (seq / 2) keys
    x `hidden` (all heads together) = 2 * seq * hidden, per layer."""
    return 2 * seq * hidden


def dense_lm_train_ops_per_token(hidden: int, intermediate: int, layers: int,
                                 vocab: int, seq: int) -> dict:
    matmul = OPS_PER_MAC * TRAIN_PASSES * dense_lm_matmul_params(
        hidden, intermediate, layers, vocab)
    attention = TRAIN_PASSES * layers * causal_attention_forward_ops_per_token(
        seq, hidden)
    return {"total": matmul + attention, "visible_to_compiler": matmul,
            "attention": attention}


# The flash kernels as ops/attention.py runs them: the backward pass recomputes
# the probabilities of every block (one more QK^T) beside its four gradient
# products, so it is 2.5 forward passes, and the kernel is asked for 3.5 in
# all.  Counted here and nowhere else.
FLASH_BACKWARD_OVER_FORWARD = 2.5


def flash_kernel_ops_per_token(seq: int, hidden: int, layers: int) -> float:
    return (1 + FLASH_BACKWARD_OVER_FORWARD) * layers \
        * causal_attention_forward_ops_per_token(seq, hidden)


def flash_kernel_bytes_per_token(hidden: int, layers: int,
                                 itemsize: int = 2) -> float:
    """HBM bytes the kernels cannot avoid, per token and over all layers:
    forward reads q, k, v and writes o (4 rows of `hidden`); backward reads
    q, k, v, o, do and writes dq, dk, dv (8 rows).  The logsumexp row and any
    re-reading of k/v per query block are the kernel's own business."""
    return 12 * hidden * itemsize * layers
